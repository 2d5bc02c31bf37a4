"""Device idle under the fleet's grid build (``fleet/grid_build``), per
1000 users, in ms."""
from bench.spans import per_kuser_ms


def read(ctx):
    return per_kuser_ms(ctx, ("fleet/grid_build",))
