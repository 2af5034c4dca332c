"""Decode's share of the chip's peak FLOP/s: the FLOPs of the active
slots' tokens only (two per weight, attention over each slot's cached
length, the LM head), counted from shapes, over the device's busy time
inside the engine's decode regions in the trace."""
import trace_reduce


def read(ctx):
    ev, iv = ctx.get("events"), ctx.get("decode_intervals_ns")
    if not ev or not ev["devices"] or not iv or not ctx.get("decode_flops"):
        return None
    busy, _ = trace_reduce.busy_in(ev, iv)
    if busy <= 0:
        return None
    return 100.0 * ctx["decode_flops"] / (busy * ctx["peaks"]["flops_per_s"])
