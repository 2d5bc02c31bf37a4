"""Host time of the dense fleet's per-window accounting, per 1000 users
(``fleet/window_metrics`` in ``FleetResult.timings``)."""


def read(ctx):
    t = ctx.get("timings")
    if not t or not ctx.get("users") or not any("fleet/window_metrics" in d for d in t):
        return None
    return 1e3 * sum(d.get("fleet/window_metrics", 0.0) for d in t) / (ctx["users"] / 1e3)
