"""Share of the time in which some request was queued or being served
that no operation ran on the device."""
import trace_reduce


def read(ctx):
    ev, iv = ctx.get("events"), ctx.get("request_intervals_ns")
    if not ev or not ev["devices"] or not iv:
        return None
    busy, length = trace_reduce.busy_in(ev, iv)
    return 100.0 * (1.0 - busy / length) if length > 0 else None
