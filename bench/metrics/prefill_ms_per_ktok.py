"""Engine prefill: host time of ``admit`` calls (each ends in a host read
of its first token) per thousand prompt tokens."""


def read(ctx):
    admits = ctx.get("admits")
    if not admits:
        return None
    seconds = sum(t1 - t0 for t0, t1, _ in admits)
    tokens = sum(n for _, _, n in admits)
    return seconds * 1e3 / tokens * 1e3
