"""End-to-end: training converges, checkpoints restart exactly, data is
deterministic, the launcher entry points run."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import Checkpointer, resume_or_init
from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_source
from repro.optim.adamw import AdamWConfig
from repro.optim.schedule import linear_warmup_cosine
from repro.train_lib import train as train_lib


def _setup(arch="qwen2-1.5b", lr=1e-2, micro=2):
    cfg = get_config(arch, smoke=True)
    tcfg = train_lib.TrainConfig(
        microbatches=micro, compute_dtype=jnp.float32,
        optimizer=AdamWConfig(lr=linear_warmup_cosine(lr, 5, 100)))
    state = train_lib.init_state(jax.random.PRNGKey(0), cfg, tcfg)
    src = make_source(cfg, DataConfig(batch=8, seq_len=32))
    step = jax.jit(train_lib.make_train_step(cfg, tcfg), donate_argnums=(0,))
    return cfg, tcfg, state, src, step


def test_loss_decreases():
    _, _, state, src, step = _setup()
    losses = []
    for s in range(20):
        state, m = step(state, jax.tree.map(jnp.asarray, src.batch(s)))
        losses.append(float(m["ce"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_microbatching_equivalent_to_full_batch():
    """Grad accumulation must not change the update (same data)."""
    cfg, _, s1, src, step1 = _setup(micro=1)
    *_, s4, _, step4 = _setup(micro=4)
    b = jax.tree.map(jnp.asarray, src.batch(0))
    n1, _ = step1(s1, b)
    n4, _ = step4(s4, b)
    for a, c in zip(jax.tree.leaves(n1["params"]), jax.tree.leaves(n4["params"]),
                    strict=True):
        # f32 GEMM reduction order differs between one batch-8 grad and
        # four accumulated batch-2 grads; observed worst case ~9e-5 abs.
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-4)


def test_checkpoint_restart_bitexact():
    """Train 6 steps straight == train 3, checkpoint, restore, train 3."""
    _, tcfg, state, src, step = _setup()
    batches = [jax.tree.map(jnp.asarray, src.batch(s)) for s in range(6)]
    ref = state
    for b in batches:
        ref, _ = step(ref, b)
    # restart path
    _, _, state2, _, step2 = _setup()
    for b in batches[:3]:
        state2, _ = step2(state2, b)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(3, state2, blocking=True)
        like = jax.eval_shape(lambda: train_lib.init_state(
            jax.random.PRNGKey(0), get_config("qwen2-1.5b", smoke=True), tcfg))
        restored = ck.restore(3, like)
    for b in batches[3:]:
        restored, _ = step2(restored, b)
    for a, c in zip(jax.tree.leaves(ref), jax.tree.leaves(restored), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-6)


def test_checkpointer_mechanics():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        state = {"w": jnp.arange(4.0)}
        for s in (1, 2, 3):
            ck.save(s, state, blocking=True)
        assert ck.all_steps() == [2, 3]  # gc keeps 2
        assert ck.latest_step() == 3
        # async save + wait
        ck.save(4, state)
        ck.wait()
        assert ck.latest_step() == 4
        assert not [f for f in os.listdir(d) if f.startswith("tmp")]
        step, got = resume_or_init(ck, lambda: {"w": jnp.zeros(4)})
        assert step == 4
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.arange(4.0))


def test_data_pipeline_deterministic():
    cfg = get_config("qwen2-1.5b", smoke=True)
    src = make_source(cfg, DataConfig(batch=4, seq_len=16, seed=7))
    b1, b2 = src.batch(5), src.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(src.batch(6)["tokens"], b1["tokens"])
    # memmap source
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "toks.bin")
        np.arange(10000, dtype=np.int32).tofile(path)
        m = make_source(cfg, DataConfig(batch=2, seq_len=8, seed=0), path)
        mb = m.batch(0)
        assert mb["tokens"].shape == (2, 9)
        np.testing.assert_array_equal(m.batch(0)["tokens"], mb["tokens"])


@pytest.fixture
def compile_cache_dir(tmp_path, monkeypatch):
    """Send the launchers' persistent compile cache to `tmp_path`, and
    restore jax's cache state afterwards so later tests compile as
    before."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(compile_cache_dir, monkeypatch, from_env):
    """The variable, when set, is the only cache directory; otherwise
    the cache sits at the one fixed, gitignored path of the checkout."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    if from_env:
        want = str(compile_cache_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(REPO_CACHE_DIR)
        root = REPO_CACHE_DIR.parent
        assert (root / "src" / "repro").is_dir()
        ignored = (root / ".gitignore").read_text().split()
        assert f"{REPO_CACHE_DIR.name}/" in ignored
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_train_launcher_end_to_end(compile_cache_dir):
    from repro.launch.train import main
    with tempfile.TemporaryDirectory() as d:
        out = main(["--arch", "qwen2-1.5b", "--smoke", "--steps", "12",
                    "--batch", "4", "--seq", "32", "--lr", "1e-2",
                    "--ckpt-dir", d, "--ckpt-every", "6"])
        assert out["final_ce"] < out["first_ce"]
        # resume picks up the saved step
        out2 = main(["--arch", "qwen2-1.5b", "--smoke", "--steps", "14",
                     "--batch", "4", "--seq", "32", "--lr", "1e-2",
                     "--ckpt-dir", d, "--resume", "auto"])
        assert out2["steps"] == 14


def test_serve_launcher_end_to_end(compile_cache_dir):
    from repro.launch.serve import main
    out = main(["--arch", "qwen2-1.5b", "--smoke", "--batch", "2",
                "--prompt-len", "8", "--gen", "4"])
    assert out["shape"] == (2, 4)


def test_encoder_arch_trains():
    """hubert (embed-input encoder) goes through the same train path."""
    _, _, state, src, step = _setup(arch="hubert-xlarge", lr=3e-3)
    for s in range(4):
        state, m = step(state, jax.tree.map(jnp.asarray, src.batch(s)))
        assert bool(jnp.isfinite(m["loss"]))
