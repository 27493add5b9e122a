"""Shared by the ``device_idle_share.*`` readers."""


def idle_share(ctx, driver: str):
    summary = ctx.get("trace")
    if ctx.get("driver") != driver or not summary \
            or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
