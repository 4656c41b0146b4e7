#!/usr/bin/env python3
"""Chip smoke test: the system's main path at the published width of
qwen2-1.5b, on TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: serve + engine phases
    python chip_smoke.py --four-chips  # four chips: sharded train step only

One chip (default):
  serve   random bf16 params from a fixed seed, made directly in bf16
          (`launch.serve.init_serving_params`); a seeded trace of 16
          requests (prompts 64-1024 tokens, 32-64 new tokens) through the
          continuous-batching `Scheduler` with the default `ServeConfig`
          (contiguous cache, XLA-native matmuls, 8 slots), run twice:
          every request must finish with its token budget, every token
          must be in [0, vocab), and the two runs must emit the same
          tokens.
  engine  the same model's prefill traced under `repro.use_engine()`
          must resolve to the compiled Pallas backend, and its logits
          for one prompt must match the XLA-native prefill and a float32
          `transformer.forward` at highest matmul precision.
Four chips (`--four-chips`): a few steps of the sharded train step
(`launch.train.build_train`) on a (data=2, model=2) mesh; the step-0
loss must match an unsharded float32 forward of the same batch, and
each device must hold about a quarter of the train state.

Timings printed here are single-run wall clock, not benchmark metrics.
The script exits non-zero off-TPU, on any failed check, and when run
outside a checkout of the repository.  Its last line is the JSON result
`{"ok": true, "device": {...}}`.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
ARCH = "qwen2-1.5b"

# serve trace (seeded): 16 requests over an 8-slot pool
N_REQUESTS = 16
PROMPT_LEN = (64, 1024)
NEW_TOKENS = (32, 64)
SLOTS = 8
# admit widths round up to this multiple: four prefill shapes cover
# every prompt of the trace
PREFILL_BUCKET = 256

# Tolerances, as relative L2 error of last-token logits (||a-b||/||b||).
# bf16 vs float32: every matmul output and residual add rounds to 8
# mantissa bits (2^-9 relative); over 28 layers these zero-mean errors
# add up to a few percent of the logits' norm.
TOL_BF16_VS_F32 = 5e-2
# Pallas vs XLA-native: identical bf16 operands and f32 accumulation, so
# they differ only where summation order flips an output's bf16 rounding
# by one ulp; 28 layers carry those flips to the logits like any other
# bf16 rounding, so the two paths sit at most as far apart as the bf16
# noise floor, well under the float32 bound above.
TOL_PALLAS_VS_XLA = 2e-2

# four-chip train phase
TRAIN_BATCH = 8
TRAIN_SEQ = 256
TRAIN_STEPS = 3
# |step-0 CE (bf16 compute) - CE of the float32 forward| in nats: the
# per-token errors from bf16 rounding are zero-mean and average over
# 2,048 tokens, so a correct sharded step lands far inside 0.02.
TOL_CE = 2e-2


def _log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def require_tpu(n: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    _log(f"device: platform={d0.platform} kind={d0.device_kind} "
         f"count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {d0.platform!r}")
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: needs {n} TPU devices, found "
                         f"{len(devs)}")
    return d0


def make_trace(cfg):
    import numpy as np

    from repro.serve_lib.scheduler import Request

    rng = np.random.default_rng(SEED)
    reqs = []
    for uid in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
        gen = int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1))
        prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)
        reqs.append(Request(uid=uid, prompt=prompt, max_new_tokens=gen))
    return reqs


def serve_once(params, cfg, scfg, reqs):
    """One full drain of `reqs` through a fresh Scheduler, checking
    every completion; returns ({uid: tokens}, seconds, scheduler
    stats)."""
    from repro.serve_lib.scheduler import Scheduler

    sched = Scheduler(params, cfg, scfg, prefill_bucket=PREFILL_BUCKET)
    t0 = time.perf_counter()
    comps = sched.run(reqs)
    dt = time.perf_counter() - t0  # tokens are host-side: work is done
    check(len(comps) == len(reqs),
          f"served {len(comps)} of {len(reqs)} requests")
    out = {}
    for r in reqs:
        c = comps[r.uid]
        toks = c.tokens
        check(c.finish_reason == "length" and len(toks) == r.max_new_tokens,
              f"request {r.uid}: {len(toks)} tokens ({c.finish_reason}), "
              f"budget {r.max_new_tokens}")
        check(bool(((toks >= 0) & (toks < cfg.vocab)).all()),
              f"request {r.uid}: token outside [0, {cfg.vocab})")
        out[r.uid] = toks
    return out, dt, sched.stats


def serve_phase(params, cfg, reqs) -> None:
    import numpy as np

    from repro.serve_lib.serve import ServeConfig

    max_seq = max(r.prompt.size + r.max_new_tokens for r in reqs) + 1
    scfg = ServeConfig(max_seq=max_seq, batch=SLOTS)
    first, t_first, _ = serve_once(params, cfg, scfg, reqs)
    second, t_second, stats = serve_once(params, cfg, scfg, reqs)
    for uid, toks in first.items():
        check(np.array_equal(toks, second[uid]),
              f"request {uid}: greedy replay emitted different tokens")
    n_tok = sum(len(t) for t in first.values())
    prompt_tok = sum(r.prompt.size for r in reqs)
    _log(f"serve: {len(reqs)} requests, {prompt_tok} prompt tokens, "
         f"{n_tok} generated tokens, {SLOTS} slots, max_seq {max_seq}; "
         f"prefill widths {sorted(stats['prefill_widths'])}, "
         f"{stats['decode_steps']} decode steps")
    _log(f"serve timings (single-run wall clock, not benchmark metrics): "
         f"first run {t_first:.2f} s (compiles included), second run "
         f"{t_second:.2f} s, compile ~{t_first - t_second:.2f} s")


def engine_prefill(params, cfg, tokens):
    """Prefill `tokens` (1, S) twice: XLA-native, and traced under
    `repro.use_engine()` with no backend given.  Returns (engine,
    compiled engine-step text, xla logits, engine logits)."""
    import jax

    import repro
    from repro.serve_lib import serve as serve_lib

    scfg = serve_lib.ServeConfig(max_seq=tokens.shape[1] + 1, batch=1)
    cache = serve_lib.init_cache(cfg, scfg)
    xla_logits, _ = jax.jit(serve_lib.make_prefill_step(cfg, scfg))(
        params, tokens, cache)
    with repro.use_engine() as eng:
        step = jax.jit(serve_lib.make_prefill_step(cfg, scfg))
        compiled = step.lower(params, tokens, cache).compile()
        eng_logits, _ = compiled(params, tokens, cache)
    return eng, compiled.as_text(), xla_logits[0, -1], eng_logits[0, -1]


def f32_last_logits(params, cfg, tokens):
    """Last-token logits of a float32 `transformer.forward` of the same
    (bf16-valued) params at highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    def fwd(p, t):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        logits, _ = T.forward(p32, cfg, t, compute_dtype=jnp.float32)
        return logits[0, -1]

    with jax.default_matmul_precision("highest"):
        return jax.jit(fwd)(params, tokens)


def engine_phase(params, cfg, prompt) -> None:
    import jax.numpy as jnp

    from repro.engine.backends import auto_interpret

    tokens = jnp.asarray(prompt)[None]
    eng, text, xla, pallas = engine_prefill(params, cfg, tokens)
    check(eng.backend == "pallas-tpu",
          f"use_engine() resolved to {eng.backend!r}, not 'pallas-tpu'")
    check(auto_interpret(None) is False, "Pallas would run in interpret mode")
    check("tpu_custom_call" in text,
          "compiled engine prefill holds no Pallas (tpu_custom_call) kernel")
    ref = f32_last_logits(params, cfg, tokens)
    errs = {"pallas_vs_xla": (rel_l2(pallas, xla), TOL_PALLAS_VS_XLA),
            "pallas_vs_f32": (rel_l2(pallas, ref), TOL_BF16_VS_F32),
            "xla_vs_f32": (rel_l2(xla, ref), TOL_BF16_VS_F32)}
    _log(f"engine: backend {eng.backend}, {len(eng.plan)} planned shapes, "
         f"prompt {tokens.shape[1]} tokens; last-token logits rel-L2 "
         + ", ".join(f"{k} {v:.3e} (tol {t:.0e})"
                     for k, (v, t) in errs.items()))
    for name, (v, tol) in errs.items():
        check(v <= tol, f"{name}: rel-L2 {v:.3e} > {tol:.0e}")


def one_chip(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import init_serving_params
    from repro.models.transformer import param_count

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_serving_params(SEED, cfg, jnp.bfloat16))
    _log(f"model: {cfg.name}, {cfg.n_layers} layers, "
         f"d_model {cfg.d_model}, vocab {cfg.vocab}, "
         f"{param_count(params) / 1e9:.3f} B params in bf16 "
         f"(init {time.perf_counter() - t0:.2f} s)")
    reqs = make_trace(cfg)
    serve_phase(params, cfg, reqs)
    gc.collect()
    engine_phase(params, cfg, reqs[0].prompt)
    stats = jax.devices()[0].memory_stats() or {}
    _log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def per_device_bytes(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def f32_reference_ce(cfg, tcfg, batch) -> float:
    """CE of an unsharded float32 forward on device 0, from the same
    seed and the same bf16-valued params the train state starts from.
    Everything but the scalar is freed when the jit returns."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.train_lib import train as train_lib

    tcfg32 = dataclasses.replace(tcfg, compute_dtype=jnp.float32)
    loss_fn = train_lib.make_loss_fn(cfg, tcfg32)

    def ce(tokens):
        p = T.init_params(jax.random.PRNGKey(SEED), cfg)
        p = jax.tree.map(
            lambda x: x.astype(tcfg.compute_dtype).astype(jnp.float32), p)
        _, (ce_, _) = loss_fn(p, {"tokens": tokens[:, :-1]}, tokens[:, 1:])
        return ce_

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(ce)(jnp.asarray(batch["tokens"])))


def four_chips(cfg) -> None:
    import math

    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, make_source
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_train
    from repro.optim.adamw import AdamWConfig
    from repro.train_lib import train as train_lib

    tcfg = train_lib.TrainConfig(compute_dtype=jnp.bfloat16,
                                 optimizer=AdamWConfig(lr=1e-4))
    source = make_source(cfg, DataConfig(TRAIN_BATCH, TRAIN_SEQ, SEED))
    t0 = time.perf_counter()
    ref = f32_reference_ce(cfg, tcfg, source.batch(0))
    gc.collect()
    _log(f"reference: unsharded float32 forward CE {ref:.6f} "
         f"({time.perf_counter() - t0:.2f} s)")

    mesh = make_mesh((2, 2), ("data", "model"))
    with mesh, shd.use_mesh(mesh):
        init, _, step = build_train(cfg, tcfg, mesh, SEED)
        state = jax.block_until_ready(init())
        total = sum(x.nbytes for x in jax.tree.leaves(state))
        held = per_device_bytes(state)
        per_dev = ", ".join(f"device {d.id}: {b} ({b / total:.4f})"
                            for d, b in sorted(held.items(),
                                               key=lambda kv: kv[0].id))
        _log(f"train state: {total} bytes; per-device bytes (share) "
             f"{per_dev}")
        check(len(held) == 4, f"state spans {len(held)} devices, not 4")
        check(max(held.values()) <= 0.3 * total,
              f"one device holds {max(held.values()) / total:.1%} of the "
              f"train state")
        losses = []
        t0 = time.perf_counter()
        for s in range(TRAIN_STEPS):
            batch = jax.tree.map(jnp.asarray, source.batch(s))
            state, metrics = step(state, batch)
            losses.append(float(metrics["ce"]))
        dt = time.perf_counter() - t0
    _log(f"train: {TRAIN_STEPS} steps on a (data=2, model=2) mesh, batch "
         f"{TRAIN_BATCH}x{TRAIN_SEQ}, CE {losses} ({dt:.2f} s single-run "
         f"wall clock, compiles included)")
    check(all(map(math.isfinite, losses)), f"non-finite loss: {losses}")
    gap = abs(losses[0] - ref)
    _log(f"step-0 CE {losses[0]:.6f} vs float32 reference {ref:.6f}: "
         f"|diff| {gap:.3e} (tol {TOL_CE:.0e})")
    check(gap <= TOL_CE, f"step-0 CE off by {gap:.3e} > {TOL_CE:.0e}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:4]]
    _log(f"peak_bytes_in_use per device {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train phase on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: run from a checkout of the repository "
                         "(src/repro not found next to this script)")
    sys.path.insert(0, str(ROOT / "src"))
    d0 = require_tpu(4 if args.four_chips else 1)

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    if args.four_chips:
        four_chips(cfg)
    else:
        one_chip(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
