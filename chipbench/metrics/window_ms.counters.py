"""Host milliseconds per counter chunk, timed inside the program: its
``fleet.window`` spans (one ``FleetStream.update`` each) and its
``fleet.totals`` spans (the read of the totals, which waits for the
device), over the windows, as the profiler session of the traced window
recorded them.  The in-program twin of ``chunk_ms.counters``."""


def read(ctx):
    from repro.core import tracing
    program = getattr(tracing, "PROGRAM", None)
    if program is None:
        return None
    events = list(program.events)
    windows = sum(e.name == "fleet.window" for e in events)
    if not windows:
        return None
    return 1e3 * sum(e.t_end - e.t_start for e in events
                     if e.name in ("fleet.window", "fleet.totals")) / windows
