"""Readings of the scheduler's own instrumentation: its `serve.*` spans
in the profiler trace, the counters in `Scheduler.stats` and the
timestamps on its `Completion`s.  A program without them (an older
checkout) gives None, never an error.

The reduced trace (`tracing.load`) keeps the benchmark's `bench.*` host
spans alone; the program's `serve.*` spans are read here from the same
trace file, once per run, and kept on the run as `serve_spans`.  The
span tree of one tick is `serve.step` > `serve.admit` / `serve.ingest` /
`serve.decode` / `serve.spec` > the leaves `serve.stage`, `serve.pull`
and `serve.sample`, which never nest in each other."""

from __future__ import annotations

import bisect
import glob
import os
import sys

import readings
import tracing

LEAVES = ("serve.stage", "serve.pull", "serve.sample")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spans(trace_dir: str) -> list[list]:
    """[[name, start, end], ...] of the host's `serve.*` events in the
    newest trace under `trace_dir` (the file `tracing.load` reads)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return []
    spans = [[e.name, int(e.start_ns), int(e.end_ns)]
             for plane in ProfileData.from_file(paths[-1]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    return sorted(spans, key=lambda s: s[1])


def serve_spans(run) -> list[list]:
    """The run's `serve.*` spans; [] when the run was not traced.  The
    first call reads them and logs where the device idles by them."""
    if run.trace is None:
        return []
    if getattr(run, "serve_spans", None) is None:
        run.serve_spans = load_spans(str(run.h.out_dir / "trace"))
        if run.serve_spans:
            log_idle(run.trace, run.serve_spans)
    return run.serve_spans


def idle_in_span(trace: dict, spans: list, name: str,
                 dev: str = "0") -> int | None:
    """ns of the traced window with no op on the device while the host
    is inside a span called `name`; None when the window holds none."""
    lo, hi = trace["window"]
    inside = tracing.union([s for s in spans if s[0] == name], lo, hi)
    if not inside:
        return None
    merged = tracing.union(trace["devices"][dev]["ops"], lo, hi)
    ends = [e for _, e in merged]
    idle = 0
    for s, e in inside:
        # only the merged ops that end after s can overlap [s, e)
        k = bisect.bisect_right(ends, s)
        busy = 0
        while k < len(merged) and merged[k][0] < e:
            busy += min(merged[k][1], e) - max(merged[k][0], s)
            k += 1
        idle += (e - s) - busy
    return idle


def idle_gaps(trace: dict, spans: list, top: int = 10) -> list[list]:
    """`tracing.idle_gaps`, each gap named by the innermost `serve.*`
    span that holds its midpoint (`idle` if none)."""
    return tracing.idle_gaps(dict(trace, host=spans), top)


def log_idle(trace: dict, spans: list) -> None:
    """One stderr line of the longest device-idle gaps by the program's
    span, one of the device idle inside `bench.step` the leaves hold."""
    log("trace: longest device-idle gaps by the scheduler's span: "
        + ", ".join(f"{name} {s * 1e3:.2f} ms"
                    for name, s in idle_gaps(trace, spans)))
    in_step = idle_in_span(trace, trace["host"], "bench.step") or 0
    leaves = sum(idle_in_span(trace, spans, n) or 0 for n in LEAVES)
    log(f"trace: device idle {in_step / 1e6:.3f} ms inside bench.step, "
        f"{leaves / 1e6:.3f} ms of it under serve.stage, serve.pull and "
        f"serve.sample")


def span_idle_pct(run, name: str) -> float | None:
    """Device-idle ns under the program's span `name` over the traced
    window (the window `device_idle` reads)."""
    ns = idle_in_span(run.trace, serve_spans(run), name) \
        if run.trace is not None else None
    if ns is None:
        return None
    lo, hi = run.trace["window"]
    return 100.0 * ns / (hi - lo)


def admit_wait_ms(run) -> list[float]:
    """Submission to admission of each finished window request, from
    the timestamps on the scheduler's `Completion`s."""
    out = []
    for r in readings.window_requests(run):
        c = run.sched.completions.get(r.idx)
        if c is not None and hasattr(c, "admitted_s"):
            out.append((c.admitted_s - c.submitted_s) * 1e3)
    return out


def stats_share_pct(run, num: str, den: str, scale: int = 1) -> float | None:
    """100 x stats[num] / (stats[den] x scale) over the whole run."""
    st = run.stats
    if num not in st or den not in st or not st[den]:
        return None
    return 100.0 * st[num] / (st[den] * scale)


def kv_live_pct(run) -> float | None:
    """Live context rows of the decoded slots over the rows a
    contiguous attention cache reserves for them (max_seq each)."""
    if run.scfg.cache_layout == "paged" or \
            "attn" not in run.cfg.layer_pattern:
        return None
    return stats_share_pct(run, "decode_kv_rows", "decode_slots",
                           run.scfg.max_seq)
