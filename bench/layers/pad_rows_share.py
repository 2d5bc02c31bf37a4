"""Share of the request rows the dense fleet's scan schedules that are
padding, in percent: 1 - users / ``fleet/rows`` (replications x frames x
padded rows, a program counter in ``FleetResult.timings``)."""
from bench.spans import counter_total


def read(ctx):
    rows = counter_total(ctx, "fleet/rows")
    if not rows or not ctx.get("users"):
        return None
    return 100.0 * (1.0 - ctx["users"] / rows)
