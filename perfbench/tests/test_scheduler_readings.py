"""The readings of the scheduler's own spans, counters and timestamps,
on the hand-made trace of `test_trace_reduce.py` with the program's
spans beside it, and on programs that lack them."""

from types import SimpleNamespace as NS

import pytest

import tiny  # puts perfbench/ on the path first
import harness
import scheduler_readings as sr
import tracing
from test_trace_reduce import HAND, MS, RECORDED

# one decode tick in each bench.step, and a sample that runs past the
# window's end; device ops cover 10-40, 45-55 and 70-80 ms
SPANS = [["serve.step", 7 * MS, 59 * MS],
         ["serve.decode", 8 * MS, 58 * MS],
         ["serve.stage", 8 * MS, 10 * MS],
         ["serve.pull", 10 * MS, 56 * MS],
         ["serve.sample", 56 * MS, 58 * MS],
         ["serve.step", 63 * MS, 94 * MS],
         ["serve.decode", 63 * MS, 93 * MS],
         ["serve.stage", 63 * MS, 66 * MS],
         ["serve.pull", 66 * MS, 82 * MS],
         ["serve.sample", 82 * MS, 92 * MS],
         ["serve.sample", 98 * MS, 104 * MS]]


def test_idle_in_span():
    assert sr.idle_in_span(HAND, SPANS, "serve.stage") == (2 + 3) * MS
    assert sr.idle_in_span(HAND, SPANS, "serve.pull") == (6 + 6) * MS
    # 82-92, and 98-100 of the span the window cuts
    assert sr.idle_in_span(HAND, SPANS, "serve.sample") == (2 + 10 + 2) * MS
    assert sr.idle_in_span(HAND, SPANS, "serve.nothing") is None
    assert sr.idle_in_span(HAND, [], "serve.stage") is None
    # the leaves never overlap, so their idle is part of the device's
    leaves = sum(sr.idle_in_span(HAND, SPANS, n) for n in sr.LEAVES)
    assert leaves <= HAND["window"][1] - tracing.busy_ns(HAND)
    # on the benchmark's own spans it agrees with tick_idle
    assert sr.idle_in_span(HAND, HAND["host"], "bench.step") == \
        pytest.approx(tracing.span_idle_share(HAND, "bench.step") * 87 * MS)


def test_idle_gaps_named_by_the_program():
    assert sr.idle_gaps(HAND, SPANS) == [
        ["serve.sample", pytest.approx(0.020)],
        ["idle", pytest.approx(0.015)],
        ["idle", pytest.approx(0.010)],
        ["serve.pull", pytest.approx(0.005)]]
    assert sr.idle_gaps(RECORDED, [], 2) == [
        ["idle", pytest.approx(g[1])] for g in tracing.idle_gaps(RECORDED, 2)]


def test_log_idle(capsys):
    sr.log_idle(HAND, SPANS)
    err = capsys.readouterr().err.splitlines()
    assert err[0].startswith("trace: longest device-idle gaps by the "
                             "scheduler's span: serve.sample 20.00 ms")
    # bench.step idle is 14 + 23 ms; the leaves hold 31 ms
    assert err[1] == ("trace: device idle 37.000 ms inside bench.step, "
                      "31.000 ms of it under serve.stage, serve.pull and "
                      "serve.sample")


def test_spans_leave_the_reduced_trace_alone(tmp_path):
    """`tracing.load` reads a trace with the program's serve.* spans as
    it read one without them: `host` and the window follow bench.*
    alone; `load_spans` reads the serve.* spans from the same file."""
    import jax
    from jax.profiler import TraceAnnotation

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("serve.step"):
            pass
        with TraceAnnotation("bench.step"):
            with TraceAnnotation("serve.step"):
                with TraceAnnotation("serve.stage", width=8):
                    pass
        with TraceAnnotation("other"):
            pass
    t = tracing.load(str(tmp_path))
    assert [h[0] for h in t["host"]] == ["bench.step"]
    assert t["window"] == t["host"][0][1:]
    spans = sr.load_spans(str(tmp_path))
    assert [s[0] for s in spans] == ["serve.step", "serve.step",
                                     "serve.stage"]
    assert spans[0][2] <= t["window"][0]
    assert sr.load_spans(str(tmp_path / "none")) == []


class _Served:
    """What a reader of the scheduler's counters and timestamps sees."""

    def __init__(self, stats, *, layout="contiguous", pattern=("attn",),
                 waits_ms=(), trace=None, spans=None):
        self.stats = stats
        self.scfg = NS(batch=8, max_seq=1000, cache_layout=layout)
        self.cfg = NS(layer_pattern=pattern)
        self.trace = trace
        self.serve_spans = spans
        self.h = NS(out_dir=tiny.HERE / "data" / "no-such-run")
        # window requests wait `waits_ms`; one outside the window waits 1 s
        self.requests = [NS(idx=i, in_window=True)
                         for i in range(len(waits_ms))]
        self.requests.append(NS(idx=99, in_window=False))
        comps = {i: NS(submitted_s=10.0, admitted_s=10.0 + w / 1e3)
                 for i, w in enumerate(waits_ms)}
        comps[99] = NS(submitted_s=10.0, admitted_s=11.0)
        self.sched = NS(completions=comps)


STATS = {"prefill_tokens": 300, "prefill_rows": 2048, "decode_slots": 60,
         "decode_steps": 10, "decode_kv_rows": 6000, "decode_tokens": 60}


def _read(name, run):
    return harness._load_module(tiny.PERFBENCH / "metrics" /
                                f"{name}.py").read(run)


@pytest.mark.parametrize("kind", ["latency", "throughput"])
@pytest.mark.parametrize("name,want", [
    ("stage_idle", 5.0), ("pull_idle", 12.0), ("sample_idle", 14.0),
    ("prefill_useful", 100 * 300 / 2048), ("decode_occupancy", 75.0),
    ("kv_live_share", 10.0)])
def test_scheduler_readers(name, kind, want):
    run = _Served(STATS, trace=HAND, spans=SPANS)
    assert _read(f"{name}.{kind}", run) == pytest.approx(want)


@pytest.mark.parametrize("kind", ["latency", "throughput"])
@pytest.mark.parametrize("name", ["stage_idle", "pull_idle", "sample_idle",
                                  "prefill_useful", "decode_occupancy",
                                  "kv_live_share"])
def test_scheduler_readers_without_the_instrumentation(name, kind):
    """An older program (no serve.* spans, no new counters) reads none;
    a run whose trace directory holds no trace reads no spans either."""
    old = {k: v for k, v in STATS.items()
           if k in ("prefill_tokens", "decode_steps", "decode_tokens")}
    assert _read(f"{name}.{kind}", _Served(old, trace=HAND, spans=[])) \
        is None
    assert _read(f"{name}.{kind}", _Served(old, trace=HAND)) is None
    assert _read(f"{name}.{kind}", _Served(old)) is None


def test_kv_live_share_reads_contiguous_attention_only():
    for kind in ("latency", "throughput"):
        name = f"kv_live_share.{kind}"
        assert _read(name, _Served(STATS, layout="paged")) is None
        assert _read(name, _Served(STATS, pattern=("ssm",))) is None


def test_admit_wait_readers():
    run = _Served(STATS, waits_ms=(4.0, 1.0, 2.0))
    assert _read("admit_wait_p50_ms", run) == pytest.approx(2.0)
    assert _read("admit_wait_p90_ms", run) == pytest.approx(3.6)
    # Completions without the timestamps (an older program): no reading
    run.sched = NS(completions={i: NS() for i in range(4)})
    assert _read("admit_wait_p50_ms", run) is None
    assert _read("admit_wait_p90_ms", run) is None
