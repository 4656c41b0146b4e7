"""90th percentile of due time to the start of the tick that admitted the request (left the scheduler's queue)."""
import readings


def read(run):
    return readings.pct(readings.queue_wait_ms(run), 90)
