"""Host milliseconds per masked decode step: the engine's ``decode``
regions (each ends on a drain of the device token buffer) over the
steps they ran."""


def read(ctx):
    steps = ctx.get("decode_steps")
    return 1e3 * ctx["decode_s"] / steps if steps else None
