"""Share of the traced window with no op on the device while the scheduler is inside serve.stage (inputs built, moved to the device, program dispatched)."""
import scheduler_readings


def read(run):
    return scheduler_readings.span_idle_pct(run, "serve.stage")
