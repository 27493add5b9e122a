"""FL data, HFL cells: share of the traced window in which the chip is
idle while the host is inside ``hfl.data`` or one of its children
(profiler trace, each idle gap labelled by the innermost host
annotation open at its midpoint), in percent.  A part of
``device_idle_share.hfl``."""


def read(ctx):
    summary = ctx.get("trace")
    if ctx.get("driver") != "hfl" or not summary \
            or summary["window_s"] <= 0:
        return None
    idle = [v for k, v in summary["idle_by_label_s"].items()
            if k == "hfl.data" or k.startswith("hfl.data.")]
    return 100.0 * sum(idle) / summary["window_s"] if idle else None
