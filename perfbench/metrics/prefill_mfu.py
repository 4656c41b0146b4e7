"""Counted prefill FLOPs over the wall time of the window's ticks that prefilled, at the chip's peak."""
import readings


def read(run):
    return readings.tick_mfu_pct(run, lambda t: bool(t.prefill_lens), readings.prefill_work)
