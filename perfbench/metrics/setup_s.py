"""Process start to the start of traffic: weights, warm-up and any compilation."""


def read(run):
    return run.setup_s
