import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes, prove memory/sharding coherence, and dump
cost/collective numbers for the roofline analysis.

    python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--jobs 2]
    python -m repro.launch.dryrun --summarize

Single-cell mode does the work in-process; --all orchestrates one
subprocess per cell (isolating XLA compile memory and letting a bad cell
fail alone) and writes runs/dryrun/<mesh>/<arch>__<shape>.json.

NOTE the two environment lines above run before any jax import: the
dry-run (and only the dry-run) needs 512 placeholder host devices so
launch.mesh can build the (2, 16, 16) production mesh, and it pins itself
and the per-cell children (which inherit the environment) to the CPU,
so that on a machine with a TPU none of them takes the chip.
"""

import argparse
import json
import subprocess
import sys
import time

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.dist import sharding as shd
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.roofline import analysis as roofline
from repro.train_lib.train import TrainConfig

RESULTS_DIR = os.environ.get(
    "REPRO_DRYRUN_DIR",
    os.path.join(os.path.dirname(__file__), "..", "..", "..",
                 "runs", "dryrun"))
SAVE_HLO = None

# Grad-accumulation microbatch counts per arch for train_4k (global batch
# 256): sized so saved activations fit HBM alongside FSDP state.
# mistral 16 -> 4 was §Perf iteration 1 (collective term ∝ accumulation
# trips); kept at 4 for the optimized sweep, 16 reproduces the baseline
# via --micro 16.
MICROBATCHES = {
    "mistral-large-123b": 4,
    "qwen3-14b": 8,
    "gemma3-12b": 8,
    "mixtral-8x7b": 8,
    "hubert-xlarge": 2,
    "recurrentgemma-2b": 4,
    "qwen2-1.5b": 2,
    "granite-moe-1b-a400m": 2,
    "mamba2-780m": 2,
    "internvl2-1b": 2,
}


def step_fn_for(cfg, shape, tcfg):
    if shape.step == "train":
        from repro.train_lib.train import make_train_step
        return make_train_step(cfg, tcfg), (0,)
    if shape.step == "prefill":
        if cfg.embed_inputs:
            def prefill_embeds(params, embeds, cache):
                return T.prefill(params, cfg, None, cache, embeds=embeds)
            return prefill_embeds, (2,)
        if cfg.prefix_tokens:
            def prefill_vlm(params, tokens, cache, embeds):
                return T.prefill(params, cfg, tokens, cache, embeds=embeds)
            return prefill_vlm, (2,)

        def prefill_step(params, tokens, cache):
            return T.prefill(params, cfg, tokens, cache)
        return prefill_step, (2,)

    def decode_step(params, cache, token):
        return T.decode_step(params, cfg, cache, token)
    return decode_step, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             micro: int | None = None,
             shard_grad_accum: bool = False,
             moe_impl: str | None = None,
             kernel_backend: str | None = None) -> dict:
    """shard_grad_accum=False reproduces the recorded §Roofline baseline;
    perf iterations re-run cells with overrides (see EXPERIMENTS.md §Perf).
    kernel_backend routes every model matmul through a repro.engine
    context ("xla-einsum" exercises the unified decision path with
    baseline numerics; Pallas backends need the matching host)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if moe_impl and cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, impl=moe_impl))
    shape = SHAPES[shape_name]
    runs, why = applicable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    base = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not runs:
        return {**base, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    tcfg = TrainConfig(microbatches=micro or MICROBATCHES.get(arch, 2),
                       shard_grad_accum=shard_grad_accum,
                       kernel_backend=kernel_backend)
    import contextlib

    from repro import engine as engine_mod

    # train cells route through TrainConfig.kernel_backend; prefill/decode
    # cells trace inside an engine context here.
    scope = (engine_mod.use_engine(backend=kernel_backend)
             if kernel_backend and shape.step != "train"
             else contextlib.nullcontext())
    t0 = time.time()
    with mesh, shd.use_mesh(mesh):
        args, shardings = S.input_specs(cfg, shape, mesh, tcfg)
        fn, donate = step_fn_for(cfg, shape, tcfg)
        with scope:
            lowered = jax.jit(fn, in_shardings=shardings,
                              donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        try:
            mem = compiled.memory_analysis()
            mem_report = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)
            } or {"repr": str(mem)}
        except Exception as e:  # CPU backend may not implement it
            mem_report = {"error": str(e)}

        hlo = compiled.as_text()
        if SAVE_HLO:
            with open(SAVE_HLO, "w") as f:
                f.write(hlo)
        from repro.roofline import hlo_costs
        walk = hlo_costs.ModuleCosts(hlo).total()
        mf = roofline.model_flops(cfg, shape)
        rl = roofline.from_compiled(compiled, model_flops_total=mf,
                                    n_devices=n_dev, hlo_text=hlo)
        top_coll = sorted(walk.coll_by_opname.items(),
                          key=lambda kv: -kv[1])[:12]

    return {
        **base,
        "status": "ok",
        "n_devices": n_dev,
        "microbatches": tcfg.microbatches if shape.step == "train" else None,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem_report,
        "collective_bytes": dict(walk.coll_by_kind),
        "top_collectives": top_coll,
        "raw_cost_analysis": roofline.raw_cost_analysis(compiled),
        "roofline": rl.as_dict(),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }


def _out_path(arch, shape_name, mesh_name):
    d = os.path.abspath(os.path.join(RESULTS_DIR, mesh_name))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def run_all(mesh_modes, jobs: int, only_missing: bool) -> None:
    cells = []
    for mesh_name in mesh_modes:
        for arch in ARCH_NAMES:
            for shape_name in SHAPES:
                path = _out_path(arch, shape_name, mesh_name)
                if only_missing and os.path.exists(path):
                    continue
                cells.append((arch, shape_name, mesh_name, path))
    procs: list[tuple[subprocess.Popen, tuple]] = []

    def drain(block_until_below: int):
        while len(procs) >= block_until_below:
            for i, (p, cell) in enumerate(procs):
                if p.poll() is not None:
                    ok = p.returncode == 0
                    print(f"[{'ok' if ok else 'FAIL'}] {cell[0]} {cell[1]} "
                          f"{cell[2]}", flush=True)
                    if not ok:
                        err = {"arch": cell[0], "shape": cell[1],
                               "mesh": cell[2], "status": "error",
                               "returncode": p.returncode}
                        with open(cell[3], "w") as f:
                            json.dump(err, f)
                    procs.pop(i)
                    break
            else:
                time.sleep(1.0)

    for arch, shape_name, mesh_name, path in cells:
        drain(jobs)
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape_name, "--mesh", mesh_name, "--out", path]
        procs.append((subprocess.Popen(cmd), (arch, shape_name, mesh_name, path)))
    drain(1)


def summarize() -> None:
    rows = []
    for mesh_name in ("single", "multi"):
        d = os.path.abspath(os.path.join(RESULTS_DIR, mesh_name))
        if not os.path.isdir(d):
            continue
        for f in sorted(os.listdir(d)):
            with open(os.path.join(d, f)) as fh:
                rows.append(json.load(fh))
    print(f"{'arch':24s} {'shape':12s} {'mesh':6s} {'status':8s} "
          f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} "
          f"{'bneck':>10s} {'useful':>7s} {'roofl%':>7s}")
    for r in rows:
        if r["status"] != "ok":
            print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} "
                  f"{r['status']:8s} {r.get('reason', '')}")
            continue
        rl = r["roofline"]
        print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} ok       "
              f"{rl['compute_s']:10.4g} {rl['memory_s']:10.4g} "
              f"{rl['collective_s']:10.4g} {rl['bottleneck']:>10s} "
              f"{rl['useful_flops_ratio']:7.3f} "
              f"{100 * rl['roofline_fraction']:6.1f}%")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--summarize", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--micro", type=int, default=None,
                    help="override grad-accumulation microbatches")
    ap.add_argument("--shard-grad-accum", action="store_true",
                    help="perf variant: FSDP-shard the grad accumulator")
    ap.add_argument("--save-hlo", default=None,
                    help="dump the partitioned HLO text to this path")
    ap.add_argument("--moe-impl", choices=("einsum", "sort"), default=None)
    ap.add_argument("--kernel-backend", default=None,
                    choices=("pallas-tpu", "pallas-interpret", "xla-einsum"),
                    help="trace model matmuls through a repro.engine "
                         "context instead of XLA-native contractions")
    args = ap.parse_args()
    global SAVE_HLO
    SAVE_HLO = args.save_hlo

    if args.summarize:
        summarize()
        return
    if args.all:
        modes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
        run_all(modes, args.jobs, args.only_missing)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    report = run_cell(args.arch, args.shape, multi_pod=(args.mesh == "multi"),
                      micro=args.micro,
                      shard_grad_accum=args.shard_grad_accum,
                      moe_impl=args.moe_impl,
                      kernel_backend=args.kernel_backend)
    out = args.out or _out_path(args.arch, args.shape, args.mesh)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps({k: v for k, v in report.items()
                      if k != "memory_analysis"}, indent=2))
    if report["status"] == "ok":
        print("memory_analysis:", report["memory_analysis"])


if __name__ == "__main__":
    main()
