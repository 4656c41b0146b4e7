"""Reduction of a profiler trace to the numbers the metrics read.

`load` turns the `.xplane.pb` that `jax.profiler` writes into a small,
JSON-ready record:

    {"window": [t0, t1],                          # traced window, ns
     "devices": {"0": {"modules": [[program, start, end], ...],
                       "ops": [[program:op, start, end], ...]}, ...},
     "host": [[span, start, end], ...]}           # bench.* spans only

Every other function here works on that record, so the tests check them
on a small recorded excerpt (`tests/data/trace_small.json`).  A program
is named from its module: `jit_bench_decode(17)` -> `bench.decode`.
"""

from __future__ import annotations

import bisect
import glob
import os


def program_name(module: str) -> str:
    name = module.split("(")[0]
    if name.startswith("jit_"):
        name = name[4:]
    if name.startswith("bench_"):
        name = "bench." + name[6:]
    return name


def op_name(hlo: str) -> str:
    """`%fusion.87 = s32[...] fusion(...)` -> `fusion.87`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> dict:
    """The newest trace under `trace_dir`; its window runs from the
    first host `bench.*` span to the end of the last."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: dict[str, dict] = {}
    host: list[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.split(":")[-1]
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [[program_name(e.name), int(e.start_ns),
                             int(e.end_ns)] for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [[op_name(e.name), int(e.start_ns),
                            int(e.end_ns)] for e in line.events]
            mods.sort(key=lambda m: m[1])
            devices[dev] = {"modules": mods, "ops": _label_ops(mods, ops)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.end_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    host.sort(key=lambda h: h[1])
    if not host:
        raise ValueError(f"no bench.* host spans in the trace {paths[-1]}")
    return {"window": [host[0][1], max(h[2] for h in host)],
            "devices": devices, "host": host}


def _label_ops(mods: list, ops: list) -> list:
    """Prefix each op with the program whose execution holds it."""
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        prog = mods[k][0] if k >= 0 and s < mods[k][2] else "?"
        out.append([f"{prog}:{name}", s, e])
    return out


# -- interval arithmetic --------------------------------------------------


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [s, e) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[int, int]], lo: int, hi: int) -> int:
    """ns of [lo, hi) that the merged intervals cover."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


# -- per-device readings --------------------------------------------------


def busy_ns(trace: dict, dev: str = "0") -> int:
    """ns of the traced window in which some op ran on the device."""
    lo, hi = trace["window"]
    return covered(union(trace["devices"][dev]["ops"], lo, hi), lo, hi)


def mean_busy_s(trace: dict) -> float:
    devs = sorted(trace["devices"])
    return sum(busy_ns(trace, d) for d in devs) / len(devs) / 1e9


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def program_ns(trace: dict, program: str, dev: str = "0") -> int:
    """Device time of every execution of `program` inside the window."""
    lo, hi = trace["window"]
    return sum(min(e, hi) - max(s, lo)
               for name, s, e in trace["devices"][dev]["modules"]
               if name == program and e > lo and s < hi)


def span_idle_share(trace: dict, span: str, dev: str = "0") -> float | None:
    """Share of the time inside host spans named `span` in which no op
    ran on the device."""
    lo, hi = trace["window"]
    merged = union(trace["devices"][dev]["ops"], lo, hi)
    total = idle = 0
    for name, s, e in trace["host"]:
        s, e = max(s, lo), min(e, hi)
        if name != span or e <= s:
            continue
        total += e - s
        idle += (e - s) - covered(merged, s, e)
    return idle / total if total else None


def idle_gaps(trace: dict, top: int = 10, dev: str = "0") -> list[list]:
    """The longest gaps with no op on the device, each named by the
    innermost host span that holds its midpoint (`idle` if none)."""
    lo, hi = trace["window"]
    merged = union(trace["devices"][dev]["ops"], lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        holders = [h for h in trace["host"] if h[1] <= mid < h[2]]
        name = min(holders, key=lambda h: h[2] - h[1])[0] if holders \
            else "idle"
        out.append([name, (e - s) / 1e9])
    return out


def top_ops(trace: dict, top: int = 10, dev: str = "0") -> list[list]:
    """Device ops that took most time in the window, summed by name."""
    lo, hi = trace["window"]
    tot: dict[str, int] = {}
    for name, s, e in trace["devices"][dev]["ops"]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[name] = tot.get(name, 0) + d
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]
