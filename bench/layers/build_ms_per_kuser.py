"""Host time building the dense fleet's frame grids, per 1000 users
(``fleet/grid_build`` in ``FleetResult.timings``).  Busy time on the
producer thread."""


def read(ctx):
    t = ctx.get("timings")
    if not t or not ctx.get("users"):
        return None
    return 1e3 * sum(d.get("fleet/grid_build", 0.0) for d in t) / (ctx["users"] / 1e3)
