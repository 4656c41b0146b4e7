"""Live KV rows of the decoded slots over the rows the contiguous cache reserves, decode_kv_rows / (decode_slots x max_seq): Scheduler.stats totals over the whole run, lead-in and drain included; none on a paged or attention-free cache."""
import scheduler_readings


def read(run):
    return scheduler_readings.kv_live_pct(run)
