"""The arithmetic behind the metric readers in `metrics/`: percentiles
over the window's requests, and roofline and MFU shares from the
benchmark's own work counts (`work.py`) against the device's peaks
(`peaks.json`).  A reader gets the finished run (`runners/serve.py`'s
`Runner`)."""

from __future__ import annotations

import numpy as np

import tracing


def pct(values, q: float) -> float | None:
    """The q-th percentile (numpy's linear rule); None with no values."""
    return float(np.percentile(values, q)) if len(values) else None


def window_requests(run):
    return [r for r in run.requests if r.in_window]


def ttft_ms(run) -> list[float]:
    return [(r.times[0] - r.due) * 1e3 for r in window_requests(run)
            if r.times]


def itl_ms(run) -> list[float]:
    out: list[float] = []
    for r in window_requests(run):
        out.extend(np.diff(r.times) * 1e3)
    return out


def queue_wait_ms(run) -> list[float]:
    return [(r.admit - r.due) * 1e3 for r in window_requests(run)
            if r.admit == r.admit]


def window_ticks(run):
    return [t for t in run.ticks if t.start >= run.ws and t.end <= run.we]


def traced_ticks(run):
    t0, t1 = run.trace_window
    return [t for t in run.ticks if t.start >= t0 and t.end <= t1]


def prefill_work(run, tick) -> tuple[int, int]:
    """(FLOPs, bytes) of the tick's prefill call; (0, 0) if none."""
    if not tick.prefill_lens:
        return 0, 0
    s = run.shapes
    return (sum(s.prefill_flops(n) for n in tick.prefill_lens),
            s.prefill_bytes(tick.prefill_lens))


def decode_work(run, tick) -> tuple[int, int]:
    if not tick.decode_ctxs:
        return 0, 0
    s = run.shapes
    return (sum(s.decode_flops(c) for c in tick.decode_ctxs),
            s.decode_bytes(tick.decode_ctxs))


def _bound_s(run, flops: int, nbytes: int) -> float:
    pk = run.peaks
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def roofline_pct(run, program: str, work) -> float | None:
    """Least time the chip could take for the traced calls of `program`
    over their device time.  None when the window holds no such call."""
    if run.trace is None or run.peaks is None:
        return None
    dev_ns = tracing.program_ns(run.trace, program)
    least = sum(_bound_s(run, *work(run, t)) for t in traced_ticks(run))
    if dev_ns <= 0 or least <= 0:
        return None
    return 100.0 * least / (dev_ns / 1e9)


def tick_mfu_pct(run, pick, work) -> float | None:
    """Counted FLOPs of the picked window ticks over their wall time at
    the chip's peak."""
    if run.peaks is None:
        return None
    ticks = [t for t in window_ticks(run) if pick(t)]
    wall = sum(t.end - t.start for t in ticks)
    flops = sum(work(run, t)[0] for t in ticks)
    if wall <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])


def serve_mfu_pct(run) -> float | None:
    if run.peaks is None:
        return None
    flops = sum(prefill_work(run, t)[0] + decode_work(run, t)[0]
                for t in window_ticks(run))
    return 100.0 * flops / (run.h.seconds * run.peaks["bf16_flops_per_s"])


def tokens_in_window(run) -> int:
    return sum(run.ws <= t < run.we for r in run.requests for t in r.times)
