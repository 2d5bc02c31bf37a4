"""Host-to-device bytes of the dense fleet per 1000 users, in MB: the scan's
window inputs (``fleet/h2d_bytes``) and the accounting's upload
(``fleet/accounting_h2d_bytes``), program counters in
``FleetResult.timings``."""
from bench.spans import counter_total


def read(ctx):
    b = counter_total(ctx, "fleet/h2d_bytes", "fleet/accounting_h2d_bytes")
    if b is None or not ctx.get("users"):
        return None
    return b / 1e6 / (ctx["users"] / 1e3)
