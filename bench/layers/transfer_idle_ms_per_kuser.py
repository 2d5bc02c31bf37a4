"""Device idle under the fleet's host-device transfers: placing each
group's window inputs (``fleet/put``) and draining its outputs to the
host (``fleet/drain``), per 1000 users, in ms."""
from bench.spans import per_kuser_ms


def read(ctx):
    return per_kuser_ms(ctx, ("fleet/put", "fleet/drain"))
