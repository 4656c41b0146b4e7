"""Serving launcher: batched prefill + decode with the arch's cache kind.

Static one-batch mode (every prompt the same length, one generate call):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --batch 4 --prompt-len 32 --gen 32

Request-trace mode (`--trace`): a mixed-length request list served by
the continuous-batching `serve_lib.scheduler.Scheduler` over a pool of
`--batch` slots.  Each item is PROMPTxGEN with an optional *COUNT
repeat, e.g.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --batch 4 --trace "24x32,8x8*6,16x48"
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.serve_lib import serve as serve_lib
from repro.serve_lib.scheduler import Request, Scheduler


def parse_trace(spec: str) -> list[tuple[int, int]]:
    """"24x32,8x8*6" -> [(24, 32), (8, 8) x 6] (prompt_len, gen_len)."""
    out: list[tuple[int, int]] = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        count = 1
        if "*" in item:
            item, n = item.split("*")
            count = int(n)
        p, g = item.split("x")
        out.extend([(int(p), int(g))] * count)
    if not out:
        raise ValueError(f"empty trace spec {spec!r}")
    return out


def init_serving_params(seed: int, cfg, dtype):
    """Random params from `seed`, made directly in `dtype` inside one
    jit, so no float32 copy of the whole model is ever live on the
    device (at qwen2-1.5b's widths that copy alone is ~7 GB)."""
    init = jax.jit(lambda key: jax.tree.map(
        lambda p: p.astype(dtype), T.init_params(key, cfg)))
    return init(jax.random.PRNGKey(seed))


def _run_trace(params, cfg, scfg, args, trace) -> dict:
    rng = np.random.default_rng(args.seed + 2)
    key = jax.random.PRNGKey(args.seed + 3)
    reqs = []
    for uid, (plen, gen) in enumerate(trace):
        key, sub = jax.random.split(key)
        reqs.append(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=gen, temperature=args.temperature,
            key=sub if args.temperature > 0 else None))
    sched = Scheduler(params, cfg, scfg, prefill_bucket=args.prefill_bucket)
    t0 = time.time()
    if args.async_ingest:
        with sched.serve_async(max_queue=max(len(reqs), 1)) as srv:
            futs = [srv.submit(r) for r in reqs]
            for f in futs:
                f.result()
        comps = sched.completions
    else:
        comps = sched.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(c.tokens) for c in comps.values())
    print(f"served {len(comps)} requests / {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s) over {scfg.batch} slots")
    print(f"scheduler: {sched.stats}")
    for uid in sorted(comps)[:8]:
        c = comps[uid]
        print(f"  req {uid}: prompt {c.prompt_len} -> {len(c.tokens)} tokens "
              f"({c.finish_reason}, steps {c.admit_step}..{c.finish_step}, "
              f"queue wait {(c.admitted_s - c.submitted_s) * 1e3:.1f} ms, "
              f"ttft {(c.first_token_s - c.submitted_s) * 1e3:.1f} ms)")
    out = {"tokens_per_s": n_tok / dt, "requests": len(comps),
           "decode_steps": sched.stats["decode_steps"]}
    if sched.engine is not None:
        print(f"engine plan: {sched.engine.plan.stats}")
        out["engine_plan"] = sched.engine.plan.stats
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch (static mode) / slot-pool size (--trace)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trace", default=None,
                    help="request trace 'PROMPTxGEN[*COUNT],...' served by "
                         "the continuous-batching scheduler")
    ap.add_argument("--prefill-bucket", type=int, default=8,
                    help="round admit widths up to this multiple "
                         "(bounds jit retraces; 1 = exact)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (trace mode only, DESIGN.md "
                         "§12): stream prompts longer than this into "
                         "their slot CHUNK tokens per tick, interleaved "
                         "with decode, instead of one blocking prefill; "
                         "must be a multiple of --prefill-bucket")
    ap.add_argument("--async-ingest", action="store_true",
                    help="drive the trace through Scheduler.serve_async "
                         "(worker thread + bounded request queue) instead "
                         "of the synchronous run loop")
    ap.add_argument("--kernel-backend", default=None,
                    choices=("pallas-tpu", "pallas-interpret", "xla-einsum",
                             "pallas-tpu-int8", "xla-int8",
                             "pallas-tpu-sparse", "xla-sparse"),
                    help="repro.engine backend for model matmuls")
    ap.add_argument("--quantize", action="store_true",
                    help="full int8 serving posture: quantize the dense "
                         "weights (repro.quant.quantize_params), store the "
                         "KV cache int8 (cache_dtype='int8'), and upgrade "
                         "the kernel backend to its int8 sibling")
    ap.add_argument("--sparsity", default=None, metavar="N:M",
                    help="structured-sparse serving posture (e.g. '2:4'): "
                         "magnitude-prune the dense weights "
                         "(repro.sparse.prune_params) and upgrade the "
                         "kernel backend to its sparse sibling; with "
                         "--quantize the kept values store as sparse×int8")
    ap.add_argument("--plan", default=None,
                    help="ExecutionPlan JSON to warm-start the decision "
                         "cache from (see repro.engine.plan_arch)")
    ap.add_argument("--cache-layout", default="contiguous",
                    choices=("contiguous", "paged"),
                    help="KV-cache layout; 'paged' (trace mode only) pools "
                         "fixed pages behind per-slot block tables and "
                         "shares prefilled prompt pages across requests")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page for --cache-layout paged")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding (trace mode only): draft K "
                         "tokens per tick and verify them in one fused "
                         "K+1-wide pass; greedy-only, outputs bitwise "
                         "identical to --speculate 0")
    ap.add_argument("--draft", default="self", choices=("self", "self-int8"),
                    help="draft model for --speculate: 'self' shares the "
                         "target params, 'self-int8' drafts with an int8-"
                         "quantized copy")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.kind == "encoder":
        raise SystemExit("encoder-only arch: no decode step (see DESIGN.md)")
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    trace = parse_trace(args.trace) if args.trace else None
    max_seq = (max(p + g for p, g in trace) + 1 if trace
               else args.prompt_len + args.gen + 1)
    if args.cache_layout == "paged" and trace is None:
        raise SystemExit("--cache-layout paged needs --trace (the block-table "
                         "plane lives in the continuous-batching scheduler)")
    if (args.prefill_chunk or args.async_ingest) and trace is None:
        raise SystemExit("--prefill-chunk / --async-ingest need --trace "
                         "(chunked ingestion lives in the continuous-"
                         "batching scheduler)")
    if args.speculate:
        if trace is None:
            raise SystemExit("--speculate needs --trace (the draft/verify "
                             "tick lives in the continuous-batching "
                             "scheduler)")
        if args.temperature > 0:
            raise SystemExit("--speculate is greedy-only (temperature 0)")
        max_seq += args.speculate  # verify writes k rows past the last token
    scfg = serve_lib.ServeConfig(
        max_seq=max_seq, batch=args.batch,
        compute_dtype=dtype,
        cache_dtype=jnp.int8 if args.quantize else dtype,
        kernel_backend=args.kernel_backend, plan_path=args.plan,
        quantize=args.quantize, sparsity=args.sparsity,
        cache_layout=args.cache_layout, page_size=args.page_size,
        speculate_k=args.speculate,
        draft=args.draft if args.speculate else None,
        prefill_chunk=args.prefill_chunk)
    mesh = make_test_mesh()
    enable_compile_cache()

    with mesh, shd.use_mesh(mesh):
        params = init_serving_params(args.seed, cfg, dtype)
        if args.sparsity:
            from repro.sparse import parse_sparsity, prune_params
            n, m = parse_sparsity(args.sparsity)
            # with --quantize the kept values store int8 inside the
            # SparseTensor (sparse×int8) — quantize_params must not run
            params = prune_params(params, n, m, quantize=args.quantize)
        elif args.quantize:
            from repro.quant import quantize_params
            params = quantize_params(params)
        if trace is not None:
            return _run_trace(params, cfg, scfg, args, trace)
        key = jax.random.PRNGKey(args.seed + 1)
        prompt = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab, jnp.int32)
        embeds = None
        if cfg.prefix_tokens:
            embeds = 0.02 * jax.random.normal(
                key, (args.batch, cfg.prefix_tokens, cfg.d_model), dtype)
        t0 = time.time()
        tokens = serve_lib.generate(
            params, cfg, scfg, prompt, args.gen,
            temperature=args.temperature, key=key, embeds=embeds)
        dt = time.time() - t0
    print(f"generated {tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(tokens[0][:16])
    return {"tokens_per_s": args.batch * args.gen / dt,
            "shape": tuple(tokens.shape)}


if __name__ == "__main__":
    main()
