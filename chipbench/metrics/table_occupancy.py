"""Share of the job table's fixed per-tick work spent on live jobs: slots
holding a submitted, unfinished job, summed over the window's full event
ticks (``TelemetrySummary.live_slot_ticks``), over the table's slots
times those ticks. Only a trace that streams through the table counts
live slots."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("live_slot_ticks") or not c["macro_steps"]:
        return None
    return c["live_slot_ticks"] / (c["slots"] * c["macro_steps"])
