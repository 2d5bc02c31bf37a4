"""Device idle under the fleet's arrival draws (``fleet/generate_traces`` +
``fleet/arrivals``), per 1000 users, in ms: the part of the arrivals'
busy time (``arrivals_ms_per_kuser``) the device waited through."""
from bench.spans import per_kuser_ms


def read(ctx):
    return per_kuser_ms(ctx, ("fleet/generate_traces", "fleet/arrivals"))
