"""Share of the traced window with no op running on the device."""
import tracing


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - tracing.busy_ns(run.trace) / 1e9 / tracing.window_s(run.trace))
