"""Host milliseconds per fleet job spent correcting and packing the raw
reads: the program's ``fleet.pack`` spans over its ``fleet.attribute``
roots, as the profiler session of the traced window recorded them."""


def read(ctx):
    from repro.core import tracing
    program = getattr(tracing, "PROGRAM", None)
    if program is None:
        return None
    events = list(program.events)
    roots = sum(e.name == "fleet.attribute" for e in events)
    if not roots:
        return None
    return 1e3 * sum(e.t_end - e.t_start for e in events
                     if e.name == "fleet.pack") / roots
