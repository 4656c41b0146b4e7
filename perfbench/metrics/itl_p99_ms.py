"""99th percentile of every gap between consecutive output tokens of the window's requests."""
import readings


def read(run):
    return readings.pct(readings.itl_ms(run), 99)
