"""Prompt tokens prefilled over the rows the prefill calls computed (batch x width): Scheduler.stats totals over the whole run, lead-in and drain included."""
import scheduler_readings


def read(run):
    return scheduler_readings.stats_share_pct(run, "prefill_tokens", "prefill_rows")
