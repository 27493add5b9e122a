"""Engine decode: mean host time of a ``decode`` call (it ends in a host
read of the tokens), over every step of the window."""


def read(ctx):
    steps = ctx.get("decodes")
    if not steps:
        return None
    return sum(t1 - t0 for t0, t1 in steps) / len(steps) * 1e3
