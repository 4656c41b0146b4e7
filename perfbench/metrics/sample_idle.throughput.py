"""Share of the traced window with no op on the device while the scheduler is inside serve.sample (host argmax or sampling and the emitted tokens' bookkeeping)."""
import scheduler_readings


def read(run):
    return scheduler_readings.span_idle_pct(run, "serve.sample")
