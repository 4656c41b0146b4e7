"""Least time of the traced decode steps (weights once, live context rows) over the device time of bench.decode."""
import readings


def read(run):
    return readings.roofline_pct(run, "bench.decode", readings.decode_work)
