"""Share of the macro engine's lockstep iterations that a replica spends
waiting on the busiest replica of its vmap (one chip's block): 1 - the
sum over segments and blocks of the mean macro steps per replica over the
sum of the block maxima."""


def read(ctx):
    c = ctx["counters"]
    if not c["lane_max"]:
        return None
    return 1.0 - c["lane_mean"] / c["lane_max"]
