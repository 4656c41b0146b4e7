"""Share of the time inside bench.step host spans with no op running on the device (traced window)."""
import tracing


def read(run):
    if run.trace is None:
        return None
    share = tracing.span_idle_share(run.trace, "bench.step")
    return None if share is None else 100.0 * share
