"""Least time of the traced prefill calls (real prompt tokens) over the device time of bench.prefill."""
import readings


def read(run):
    return readings.roofline_pct(run, "bench.prefill", readings.prefill_work)
