"""Generated tokens that reached the host inside the window, over the window."""
import readings


def read(run):
    return readings.tokens_in_window(run) / run.h.seconds
