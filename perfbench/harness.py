"""Cell lookup, the chip check and the result line.  Everything
particular to one configuration, traffic mix, runner or metric is found
by name under `perfbench/`; nothing here names a cell.

A traffic file names its runner, `perfbench/runners/<runner>.py`, whose
`Runner(context)` gives `setup()`, `run()`, `finish_trace()` (sets
`.trace`), `summary() -> (attempted, failed)`, `free()` and
`check() -> {name: {"value", "limit"}}`; the metric readers in
`perfbench/metrics/` read the finished runner."""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def load_runner(name: str):
    path = HERE / "runners" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"perfbench: no runner {name!r} ({path})")
    return _load_module(path)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json")


def require_chips(n: int) -> None:
    """Fail, printing no result, unless JAX sees at least `n` TPU chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"perfbench: needs a TPU, JAX found {d0.platform!r}")
    if len(devs) < n:
        raise SystemExit(f"perfbench: the cell needs {n} chips, JAX found "
                         f"{len(devs)}")


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(f"perfbench: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return table[device_kind]


@dataclasses.dataclass
class Context:
    """What a runner and the metric readers are given."""
    cell: dict
    conf: dict
    cfgmod: object
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    control: bool        # compare the precision control, not the program
    t_process: float
    out_dir: Path


def context(bench: dict, cell: dict, **kw) -> Context:
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf_path = HERE.parent / conf_entry["file"]
    conf = json.loads(conf_path.read_text())
    cfgmod = _load_module(conf_path.with_suffix(".py"))
    spec = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                      .read_text())
    limits = json.loads((HERE / "cells" / f"{cell['name']}.json").read_text())
    return Context(cell=cell, conf=conf, cfgmod=cfgmod, traffic=spec,
                   limits=limits, **kw)


def metric_names(bench: dict, cell: dict, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def read_metrics(bench: dict, cell: dict, trace: bool, run) -> dict:
    out = {}
    for m in metric_names(bench, cell, trace):
        reader = _load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(bench: dict, h: Context) -> dict:
    """Set up, run the window, read the metrics, check the outputs."""
    import jax

    cell, trace = h.cell, h.trace
    h.out_dir.mkdir(parents=True, exist_ok=True)
    devs = jax.devices()[:cell["chips"]]
    run = load_runner(h.traffic["runner"]).Runner(h)
    run.setup()
    run.setup_s = time.perf_counter() - h.t_process
    run.peaks = (peaks(devs[0].device_kind) if devs[0].platform == "tpu"
                 else None)
    run.run()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs) or None
    if trace:
        run.finish_trace()
    attempted, failed = run.summary()
    log(f"setup_s {run.setup_s:.3f}; memory: peak_bytes_in_use {peak_bytes} "
        f"on the fullest of {len(devs)} chips")
    metrics = read_metrics(bench, cell, trace, run)
    run.free()
    checks = run.check()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()) and failed == 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        import tracing
        device["busy_s"] = tracing.mean_busy_s(run.trace)
        device["window_s"] = tracing.window_s(run.trace)
        result["breakdown"] = {"device_ops": tracing.top_ops(run.trace),
                               "idle_gaps": tracing.idle_gaps(run.trace)}
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
