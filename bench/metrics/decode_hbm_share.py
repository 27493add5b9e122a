"""Model step, decode: bytes each step needs (weights, one embedding row
per live sequence, the live keys and values) over the host time of
``decode`` at the chip's HBM peak, in percent."""
from bench import flops, peaks


def read(ctx):
    steps = ctx.get("decodes")
    if not steps:
        return None
    peak = peaks.for_kind(ctx["devices"][0].device_kind)["hbm_bytes_per_s"]
    need = sum(flops.decode_bytes(ctx["model"], rows, kv)
               for rows, kv in zip(ctx["decode_rows"],
                                   ctx["decode_kv_tokens"]))
    seconds = sum(t1 - t0 for t0, t1 in steps)
    return 100.0 * need / (seconds * peak * len(ctx["devices"]))
