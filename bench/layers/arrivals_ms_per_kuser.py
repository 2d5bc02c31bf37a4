"""Host time drawing arrivals, per 1000 users: the fleet's trace generation
and per-window arrival pulls (``fleet/generate_traces`` + ``fleet/arrivals``
in ``FleetResult.timings``).  Busy time: the pulls run on the producer
thread, so part of it may hide behind the device."""
SPANS = ("fleet/generate_traces", "fleet/arrivals")


def read(ctx):
    t = ctx.get("timings")
    if not t or not ctx.get("users"):
        return None
    return 1e3 * sum(d.get(s, 0.0) for d in t for s in SPANS) / (ctx["users"] / 1e3)
