"""Model step, prefill: operations the live prompt tokens need (no
padding, last-position logits only) over the host time of ``admit`` at
the chip's bf16 peak, in percent."""
from bench import flops, peaks


def read(ctx):
    admits = ctx.get("admits")
    if not admits:
        return None
    peak = peaks.for_kind(ctx["devices"][0].device_kind)["bf16_flops_per_s"]
    work = sum(flops.prefill_flops(ctx["model"], n) for _, _, n in admits)
    seconds = sum(t1 - t0 for t0, t1, _ in admits)
    return 100.0 * work / (seconds * peak * len(ctx["devices"]))
