"""Ticks simulated per full event tick of the macro engine, summed over
every replica and call of the window (``TelemetrySummary.n_steps`` over
``TelemetrySummary.macro_steps``)."""


def read(ctx):
    c = ctx["counters"]
    return c["replica_ticks"] / c["macro_steps"] if c["macro_steps"] else None
