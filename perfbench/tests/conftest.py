import os
from pathlib import Path

# the tests run the harness on the CPU at tiny sizes, with a compile
# cache of their own inside the checkout
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    str(Path(__file__).resolve().parents[1] / ".out" / "jax_cache_tests"))
