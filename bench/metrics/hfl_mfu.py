"""FL client, whole round: the training operations of a round (GRU
forward and backward over every window of every client and epoch) over
the round's wall time at the chip's bf16 peak, in percent.  The GRU runs
in float32 at JAX's default matmul precision; the bf16 peak is the
chip's only matmul peak."""
from bench import peaks


def read(ctx):
    work, round_s = ctx.get("round_flops"), ctx.get("round_s")
    if not work or not round_s:
        return None
    peak = peaks.for_kind(ctx["devices"][0].device_kind)["bf16_flops_per_s"]
    return 100.0 * work / (round_s * peak * len(ctx["devices"]))
