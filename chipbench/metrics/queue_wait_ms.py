"""Median milliseconds a request waits for a batch slot, from its
arrival to its admission: the serve engine's ``serve.queued`` spans, as
the profiler session of the traced window recorded them."""
import statistics


def read(ctx):
    from repro.core import tracing
    program = getattr(tracing, "PROGRAM", None)
    if program is None:
        return None
    waits = [e.t_end - e.t_start for e in program.events
             if e.name == "serve.queued"]
    return 1e3 * statistics.median(waits) if waits else None
