"""Device-busy microseconds per replica-tick of the traced window: the
union of op intervals, summed over the chips used, over the replica-ticks
the window completed."""


def read(ctx):
    tr, c = ctx.get("trace"), ctx["traced"]
    if not tr or not c["replica_ticks"]:
        return None
    return sum(tr["busy_s_chips"]) * 1e6 / c["replica_ticks"]
