"""The reduction from a trace to metrics, on a hand-made trace whose
answers are known, and on a small excerpt recorded on the chip."""

import json

import pytest

import tiny
import tracing

MS = 1_000_000

# two ticks; device ops of bench.prefill and bench.decode inside them
HAND = {
    "window": [0, 100 * MS],
    "devices": {"0": {
        "modules": [["bench.prefill", 10 * MS, 40 * MS],
                    ["bench.decode", 45 * MS, 55 * MS],
                    ["bench.decode", 70 * MS, 80 * MS]],
        "ops": [["bench.prefill:while.1", 10 * MS, 40 * MS],
                ["bench.prefill:fusion.2", 12 * MS, 20 * MS],
                ["bench.decode:fusion.3", 45 * MS, 55 * MS],
                ["bench.decode:fusion.3", 70 * MS, 80 * MS]]}},
    "host": [["bench.step", 6 * MS, 60 * MS],
             ["bench.submit", 60 * MS, 62 * MS],
             ["bench.step", 62 * MS, 95 * MS]],
}


def test_program_names():
    assert tracing.program_name("jit_bench_decode(123)") == "bench.decode"
    assert tracing.program_name("jit_bench_prefill") == "bench.prefill"
    assert tracing.program_name("jit__lambda_(7)") == "_lambda_"


def test_busy_is_the_union_of_ops():
    assert tracing.busy_ns(HAND) == 50 * MS
    assert tracing.mean_busy_s(HAND) == pytest.approx(0.05)
    assert tracing.window_s(HAND) == pytest.approx(0.1)


def test_program_time_groups_modules():
    assert tracing.program_ns(HAND, "bench.prefill") == 30 * MS
    assert tracing.program_ns(HAND, "bench.decode") == 20 * MS


def test_tick_idle_share():
    # steps cover 54 + 33 ms; ops cover 40 + 10 of them
    assert tracing.span_idle_share(HAND, "bench.step") == \
        pytest.approx((87 - 50) / 87)
    assert tracing.span_idle_share(HAND, "bench.nothing") is None


def test_idle_gaps_are_named_by_the_host():
    # gaps 80..100, 55..70, 0..10 (before the first span), 40..45
    assert tracing.idle_gaps(HAND) == [
        ["bench.step", pytest.approx(0.020)],
        ["bench.step", pytest.approx(0.015)],
        ["idle", pytest.approx(0.010)],
        ["bench.step", pytest.approx(0.005)]]
    assert len(tracing.idle_gaps(HAND, top=2)) == 2


def test_top_ops_sum_by_name():
    ops = dict(tracing.top_ops(HAND))
    assert ops["bench.decode:fusion.3"] == pytest.approx(0.02)
    assert ops["bench.prefill:while.1"] == pytest.approx(0.03)


def test_ops_are_labelled_by_their_module():
    mods = HAND["devices"]["0"]["modules"]
    ops = tracing._label_ops(mods, [["fusion", 46 * MS, 47 * MS],
                                    ["copy", 41 * MS, 42 * MS]])
    assert ops == [["bench.decode:fusion", 46 * MS, 47 * MS],
                   ["?:copy", 41 * MS, 42 * MS]]


# -- a recorded excerpt: two ticks of qwen2-1.5b.chat on one TPU v5 lite
# (a whole-pool prefill tick, then a decode tick), times rebased to 0

RECORDED = json.loads((tiny.HERE / "data" / "trace_small.json").read_text())


def test_recorded_trace_readings():
    t = RECORDED
    assert tracing.window_s(t) == pytest.approx(0.205332293)
    assert tracing.busy_ns(t) == 193954371
    assert tracing.program_ns(t, "bench.prefill") == 180187920
    assert tracing.program_ns(t, "bench.decode") == 13721496
    assert tracing.span_idle_share(t, "bench.step") == \
        pytest.approx(0.05542121304481677)
    assert tracing.top_ops(t, 1) == [["bench.prefill:while.45",
                                      pytest.approx(0.179364734)]]
    assert [g[0] for g in tracing.idle_gaps(t, 3)] == ["bench.step"] * 3


def test_recorded_trace_is_consistent():
    t = RECORDED
    busy = tracing.busy_ns(t)
    progs = sum(tracing.program_ns(t, p)
                for p in {m[0] for m in t["devices"]["0"]["modules"]})
    # every op lies inside its module's execution
    assert busy <= progs <= tracing.window_s(t) * 1e9
    assert all(o[0].split(":")[0] != "?" for o in t["devices"]["0"]["ops"])


class _Run:
    """What a metric reader sees, for one traced prefill tick."""

    def __init__(self, trace, prefill_lens, decode_ctxs):
        import harness
        import work
        conf = json.loads((tiny.PERFBENCH / "configs" /
                           "qwen2-1.5b.json").read_text())
        self.shapes = work.Shapes.from_config(conf)
        self.trace = trace
        self.trace_window = (0.0, 1.0)
        self.ticks = [harness.load_runner("serve").Tick(0.1, 0.3, prefill_lens,
                                                      decode_ctxs)]
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_roofline_of_the_recorded_prefill():
    import readings
    lens = [700, 300]  # the real prompt tokens of the call
    run = _Run(RECORDED, lens, [])
    s = run.shapes
    least = max(sum(s.prefill_flops(n) for n in lens) / 197e12,
                s.prefill_bytes(lens) / 819e9)
    got = readings.roofline_pct(run, "bench.prefill", readings.prefill_work)
    assert got == pytest.approx(100 * least / 0.18018792)
    assert 0 < got < 100
    # a program absent from the window has no reading, never 0
    assert readings.roofline_pct(run, "bench.other",
                                 readings.prefill_work) is None
