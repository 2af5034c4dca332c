"""Median host milliseconds of the engine's ``prefill`` regions (one
per admitted request, ending on the first token at the host)."""
import statistics


def read(ctx):
    d = ctx.get("prefill_s")
    return 1e3 * statistics.median(d) if d else None
