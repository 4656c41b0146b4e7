"""Counted decode FLOPs over the wall time of the window's decode-only ticks, at the chip's peak."""
import readings


def read(run):
    return readings.tick_mfu_pct(
        run, lambda t: bool(t.decode_ctxs) and not t.prefill_lens, readings.decode_work)
