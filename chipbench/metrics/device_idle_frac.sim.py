"""Share of the traced window in which no operation ran on the device
(mean over the chips used), in cells whose window simulates the twin."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return sum(tr["idle_frac"]) / len(tr["idle_frac"])
