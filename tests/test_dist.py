"""Distribution: sharding rules, multi-device train step, tiny dry-run.

Multi-device cases run in a subprocess with
xla_force_host_platform_device_count=8 so the main test process keeps its
single-device view (the brief's requirement that smoke tests see 1
device)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.launch.mesh import make_mesh, make_test_mesh, parse_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_spec_divisibility_safe():
    mesh = make_test_mesh()
    with shd.use_mesh(mesh):
        # dims that do not divide the axis degrade to replication
        s = shd.spec((7, 13), ("batch", "heads"), mesh)
        assert isinstance(s, P)


def test_auto_spec_rules():
    sizes = {"pod": 2, "data": 16, "model": 16}
    # expert weights: expert dim on model (EP)
    spec = shd._auto_spec("experts/wi", (32, 64, 128), sizes)
    assert spec[0] == "model"
    # embedding: vocab only (and only if divisible), never the gathered
    # feature dim — 50280 % 16 != 0 -> fully replicated
    spec_e = shd._auto_spec("mu/embed", (50280, 1536), sizes)
    assert all(p is None for p in spec_e)
    spec_e2 = shd._auto_spec("embed", (151936, 1536), sizes)
    assert spec_e2[0] == "model" and len(spec_e2) == 1
    # stacked params: leading axis never sharded; TP+FSDP on the rest
    spec_s = shd._auto_spec("stack/b0/attn/wq/w", (14, 64, 128), sizes)
    assert len(spec_s) == 0 or spec_s[0] is None
    assert "model" in spec_s and "data" in spec_s
    # dims that do not divide degrade gracefully
    spec_o = shd._auto_spec("w", (7, 13), sizes)
    assert all(p is None for p in spec_o)


def test_constrain_noop_without_mesh():
    x = jax.numpy.ones((4, 4))
    assert shd.constrain(x, "batch", None) is x


@pytest.mark.parametrize("spec,want", [
    ("1x1", (1, 1)), ("2x2", (2, 2)), ("4X2", (4, 2)),
    ("2", None), ("0x2", None), ("2x2x2", None), ("axb", None)])
def test_parse_mesh(spec, want):
    if want is None:
        with pytest.raises(ValueError):
            parse_mesh(spec)
    else:
        assert parse_mesh(spec) == want


def test_make_mesh_auto_axes_and_device_count():
    """Auto axes accept the model's logical-axis constraints; a mesh
    larger than the host raises instead of silently shrinking."""
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    with mesh, shd.use_mesh(mesh):
        y = jax.jit(lambda x: shd.constrain(x * 2, "batch", None))(
            jax.numpy.ones((4, 4)))
    assert float(y.sum()) == 32.0
    with pytest.raises(ValueError, match="devices"):
        make_mesh((len(jax.devices()) + 1, 1), ("data", "model"))


@pytest.mark.slow
def test_multidevice_train_and_dryrun():
    code = textwrap.dedent("""
        import json, os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.dist import sharding as shd
        from repro.data.pipeline import DataConfig, make_source
        from repro.train_lib import train as train_lib

        from repro.optim.adamw import AdamWConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.train import build_train, main
        assert len(jax.devices()) == 8
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("qwen2-1.5b", smoke=True)
        tcfg = train_lib.TrainConfig(microbatches=2,
                                     compute_dtype=jnp.float32,
                                     optimizer=AdamWConfig(lr=5e-3))
        with mesh, shd.use_mesh(mesh):
            init, _, step = build_train(cfg, tcfg, mesh, 0)
            state = init()
            src = make_source(cfg, DataConfig(batch=8, seq_len=32))
            losses = []
            for s in range(6):
                state, m = step(state, jax.tree.map(jnp.asarray,
                                                    src.batch(s)))
                losses.append(float(m["ce"]))
        # single-device reference: SPMD must not change the math
        cfg2 = get_config("qwen2-1.5b", smoke=True)
        state2 = train_lib.init_state(jax.random.PRNGKey(0), cfg2, tcfg)
        step2 = jax.jit(train_lib.make_train_step(cfg2, tcfg),
                        donate_argnums=(0,))
        src2 = make_source(cfg2, DataConfig(batch=8, seq_len=32))
        ref = []
        for s in range(6):
            state2, m2 = step2(state2, jax.tree.map(jnp.asarray,
                                                    src2.batch(s)))
            ref.append(float(m2["ce"]))
        err = max(abs(a - b) for a, b in zip(losses, ref, strict=True))
        # the launcher on a named mesh: same first step as above
        os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp()
        cli = main(["--arch", "qwen2-1.5b", "--smoke", "--steps", "2",
                    "--batch", "8", "--seq", "32", "--microbatches", "2",
                    "--mesh", "4x2"])
        print(json.dumps({"losses": losses, "ref": ref, "err": err,
                          "cli_first_ce": cli["first_ce"]}))
    """)
    out = _run_subprocess(code)
    assert out["losses"][-1] < out["losses"][0] - 0.1
    assert out["err"] < 5e-3, out  # SPMD == single-device math
    assert abs(out["cli_first_ce"] - out["ref"][0]) < 5e-3, out


@pytest.mark.slow
def test_elastic_reshard_restore():
    """Checkpoint on a (4,2) mesh, restore onto (2,4) — elastic scaling."""
    code = textwrap.dedent("""
        import json, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.dist import sharding as shd
        from repro.checkpoint.checkpoint import Checkpointer
        from repro.launch.mesh import make_mesh
        from repro.train_lib import train as train_lib

        cfg = get_config("qwen2-1.5b", smoke=True)
        tcfg = train_lib.TrainConfig(compute_dtype=jnp.float32)
        d = tempfile.mkdtemp()
        mesh1 = make_mesh((4, 2), ("data", "model"))
        with mesh1, shd.use_mesh(mesh1):
            state = train_lib.init_state(jax.random.PRNGKey(0), cfg, tcfg)
            sh1 = shd.params_shardings(state, mesh1)
            state = jax.tree.map(jax.device_put, state, sh1)
            ck = Checkpointer(d)
            ck.save(1, state, blocking=True)
        mesh2 = make_mesh((2, 4), ("data", "model"))
        with mesh2, shd.use_mesh(mesh2):
            like = jax.eval_shape(lambda: train_lib.init_state(
                jax.random.PRNGKey(0), cfg, tcfg))
            sh2 = shd.params_shardings(like, mesh2)
            restored = Checkpointer(d).restore(1, like, sh2)
        a = np.asarray(jax.tree.leaves(state)[3])
        b = np.asarray(jax.tree.leaves(restored)[3])
        print(json.dumps({"equal": bool(np.allclose(a, b))}))
    """)
    out = _run_subprocess(code)
    assert out["equal"]


@pytest.mark.slow
def test_tiny_dryrun_cell_multipod():
    """A 2x2x2 'multi-pod' mesh lowers+compiles a smoke train cell, and
    the roofline walker returns nonzero loop-multiplied terms."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.dist import sharding as shd
        from repro.launch import specs as S
        from repro.configs.shapes import ShapeSpec
        from repro.train_lib.train import TrainConfig, make_train_step
        from repro.roofline import hlo_costs
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("qwen2-1.5b", smoke=True)
        shape = ShapeSpec("tiny_train", 64, 8, "train")
        tcfg = TrainConfig(microbatches=2, compute_dtype=jnp.bfloat16)
        with mesh, shd.use_mesh(mesh):
            args, sh = S.input_specs(cfg, shape, mesh, tcfg)
            comp = jax.jit(make_train_step(cfg, tcfg), in_shardings=sh,
                           donate_argnums=(0,)).lower(*args).compile()
        cost = hlo_costs.module_costs(comp.as_text())
        print(json.dumps({"flops": cost.flops, "coll": cost.coll_bytes}))
    """)
    out = _run_subprocess(code)
    assert out["flops"] > 0
    assert out["coll"] > 0
