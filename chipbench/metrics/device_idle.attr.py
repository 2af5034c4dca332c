"""Share of the traced attribution window in which no operation ran on
the device (1 - busy union / window)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["device_planes"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
