"""90th percentile of submission to admission (the pick in Scheduler._admit) of the window's requests, from their Completion timestamps."""
import readings
import scheduler_readings


def read(run):
    return readings.pct(scheduler_readings.admit_wait_ms(run), 90)
