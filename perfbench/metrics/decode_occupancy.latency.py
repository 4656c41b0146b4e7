"""Active slots over pool slots of the decode calls, decode_slots / (decode_steps x batch): Scheduler.stats totals over the whole run, lead-in and drain included."""
import scheduler_readings


def read(run):
    return scheduler_readings.stats_share_pct(run, "decode_slots", "decode_steps", run.scfg.batch)
