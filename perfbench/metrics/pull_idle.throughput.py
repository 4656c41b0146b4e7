"""Share of the traced window with no op on the device while the scheduler is inside serve.pull (the logits row or verify outputs brought to the host)."""
import scheduler_readings


def read(run):
    return scheduler_readings.span_idle_pct(run, "serve.pull")
