"""Counted FLOPs of every prompt and generated token of the window's ticks, over the window at the chip's peak."""
import readings


def read(run):
    return readings.serve_mfu_pct(run)
