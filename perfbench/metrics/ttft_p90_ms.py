"""90th percentile of time to first token of the window's requests, from when each was due."""
import readings


def read(run):
    return readings.pct(readings.ttft_ms(run), 90)
