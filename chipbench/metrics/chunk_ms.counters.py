"""Host milliseconds per counter chunk inside ``FleetStream``: every
``update`` call and the final read of the totals (which waits for the
device's result), over the chunks streamed."""


def read(ctx):
    t = ctx.get("stream_walls")
    if not t or not t["update"]:
        return None
    return 1e3 * (sum(t["update"]) + sum(t["totals"])) / len(t["update"])
