"""Compile rehearsals: the main-path Pallas kernels at real widths,
compiled for a described (not attached) TPU v5e.

The TPU compiler refuses what interpret mode accepts: block shapes that
break the tiling rule, vector ops the chip lacks, VMEM overflows.  Each
test lowers and compiles one kernel for device 0 of a `v5e:2x2`
topology and checks that the Mosaic kernel made it into the program
(`tpu_custom_call`).  Nothing runs, so these say nothing about results
or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.engine.backends import pallas_gemm
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.grouped_gemm import grouped_matmul
from repro.kernels.paged_attention import paged_attention_tpu
from repro.kernels.quant_gemm import quant_gemm
from repro.kernels.sparse_gemm import sparse_gemm

QWEN = get_config("qwen2-1.5b")
MOE = get_config("granite-moe-1b-a400m")  # grouped GEMMs need experts
PREFILL_M = 2048  # tokens of one prefill call (8 slots x 256)
DECODE_M = 8      # one token per slot of an 8-slot pool
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on device 0, with the persistent compile cache off: a
    compile for a described chip can be written to the cache but never
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [PREFILL_M, DECODE_M])
def test_redas_gemm_compiles(one_chip, m):
    """The output-stationary schedule at a prefill and a decode width."""
    d, f = QWEN.d_model, QWEN.d_ff
    text = _compiled_text(
        one_chip,
        lambda a, b: pallas_gemm(a, b, dataflow="os", interpret=False),
        ((m, d), jnp.bfloat16), ((d, f), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_grouped_matmul_compiles(one_chip):
    e, d, f = MOE.moe.n_experts, MOE.d_model, MOE.d_ff
    text = _compiled_text(
        one_chip, lambda x, w: grouped_matmul(x, w, interpret=False),
        ((e, 256, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    b, h, s, d = 1, QWEN.n_heads, 1024, QWEN.head_dim_
    qkv = ((b, h, s, d), jnp.bfloat16)
    text = _compiled_text(
        one_chip, lambda q, k, v: flash_attention_tpu(q, k, v, interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_quant_gemm_compiles(one_chip):
    d, f = QWEN.d_model, QWEN.d_ff
    text = _compiled_text(
        one_chip, lambda a, b: quant_gemm(a, b, interpret=False),
        ((PREFILL_M, d), jnp.bfloat16), ((d, f), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_sparse_gemm_compiles(one_chip):
    """2:4 storage of the qwen2 up-projection: (K/2, N) kept values and
    their int8 in-group offsets."""
    d, f = QWEN.d_model, QWEN.d_ff
    text = _compiled_text(
        one_chip,
        lambda a, v, i: sparse_gemm(a, v, i, n_keep=2, m_group=4,
                                    interpret=False),
        ((DECODE_M, d), jnp.bfloat16), ((d // 2, f), jnp.bfloat16),
        ((d // 2, f), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("int8", [False, True])
def test_paged_attention_compiles(one_chip, int8):
    """Decode over a pool sized for 8 slots of 1,088 rows."""
    b, h, kv, d = DECODE_M, QWEN.n_heads, QWEN.n_kv, QWEN.head_dim_
    n_bt = 1088 // PAGE
    n_pool = b * n_bt + 2 * n_bt
    pool_dtype = jnp.int8 if int8 else jnp.bfloat16
    shapes = [((b, 1, h, d), jnp.bfloat16),
              ((n_pool, PAGE, kv, d), pool_dtype),
              ((n_pool, PAGE, kv, d), pool_dtype),
              ((b, n_bt), jnp.int32), ((b,), jnp.int32)]
    if int8:
        shapes += [((n_pool, PAGE, kv), jnp.float32)] * 2
    text = _compiled_text(
        one_chip,
        lambda q, k, v, bt, ln, *sc: paged_attention_tpu(q, k, v, bt, ln, *sc,
                                                         interpret=False),
        *shapes)
    assert "tpu_custom_call" in text
