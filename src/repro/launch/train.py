"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir runs/ckpt --resume auto

`--mesh DATAxMODEL` (default 1x1) names the (data, model) mesh over the
devices present, e.g. `--mesh 2x2` on a four-chip host: the train state
is born sharded on it (TP + FSDP rule table, dist.sharding) and
checkpoints reshard on restore, so a run survives mesh-shape changes
(elastic).  Across hosts the same entry point runs under
`jax.distributed.initialize()`.  On a CPU host it trains the reduced
configs end-to-end (examples/train_tiny_lm.py drives it).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import Checkpointer
from repro.configs import ARCH_NAMES, get_config
from repro.data.pipeline import DataConfig, make_source
from repro.dist import reshard, sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, parse_mesh
from repro.optim.adamw import AdamWConfig
from repro.optim.schedule import linear_warmup_cosine
from repro.train_lib import train as train_lib


def build_train(cfg, tcfg: train_lib.TrainConfig, mesh, seed: int):
    """(init, state_shardings, step) for `cfg` on `mesh`.  `init()`
    makes the train state from `seed` directly in its shards (one jit
    with out_shardings), so no device ever holds the whole state;
    `step(state, batch)` is the donated, sharded train step.  Call both
    inside `with mesh, shd.use_mesh(mesh)` so the model's activation
    constraints trace against the mesh."""
    def init_fn():
        return train_lib.init_state(jax.random.PRNGKey(seed), cfg, tcfg)

    state_sh = shd.params_shardings(jax.eval_shape(init_fn), mesh)
    init = jax.jit(init_fn, out_shardings=state_sh)
    step = jax.jit(train_lib.make_train_step(cfg, tcfg),
                   in_shardings=(state_sh, None),
                   out_shardings=(state_sh, None), donate_argnums=(0,))
    return init, state_sh, step


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", choices=("auto", "none"), default="none")
    ap.add_argument("--data-path", default=None,
                    help="memmap token corpus; default synthetic")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default="1x1", metavar="DATAxMODEL",
                    help="(data, model) mesh over the devices present, "
                         "e.g. 2x2 on four chips")
    ap.add_argument("--kernel-backend", default=None,
                    choices=("pallas-tpu", "pallas-interpret", "xla-einsum",
                             "pallas-tpu-sparse", "xla-sparse"),
                    help="repro.engine backend for model matmuls "
                         "(default: XLA-native)")
    ap.add_argument("--sparsity", default=None, metavar="N:M",
                    help="sparse-QAT posture (e.g. '2:4'): upgrade the "
                         "kernel backend to its sparse sibling; pair with "
                         "repro.sparse.prune_params weights")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    tcfg = train_lib.TrainConfig(
        microbatches=args.microbatches,
        compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16,
        optimizer=AdamWConfig(
            lr=linear_warmup_cosine(args.lr, args.warmup, args.steps)),
        kernel_backend=args.kernel_backend,
        sparsity=args.sparsity,
    )
    mesh = make_mesh(parse_mesh(args.mesh), ("data", "model"))
    source = make_source(cfg, DataConfig(args.batch, args.seq, args.seed),
                         args.data_path)
    enable_compile_cache()

    with mesh, shd.use_mesh(mesh):
        init, state_sh, step_fn = build_train(cfg, tcfg, mesh, args.seed)
        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt and args.resume == "auto":
            # Elastic: the checkpoint may come from any mesh shape;
            # placement is re-derived for *this* mesh (DESIGN.md §4).
            start, state = reshard.resume_or_init(ckpt, init, mesh)
        else:
            start, state = 0, init()
        if start:
            print(f"resumed from step {start}")
        if start >= args.steps:
            print(f"checkpoint already at step {start} >= --steps "
                  f"{args.steps}; nothing to train")
            return {"final_ce": None, "first_ce": None, "steps": start}
        state = reshard.reshard(state, state_sh)

        losses = []
        t0 = time.time()
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, source.batch(step))
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["ce"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  ce {losses[-1]:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"{(time.time() - t0):.1f}s", flush=True)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state)
        if ckpt:
            ckpt.save(args.steps, state, blocking=True)
        return {"final_ce": losses[-1], "first_ce": losses[0],
                "steps": args.steps}


if __name__ == "__main__":
    out = main()
    print(out)
