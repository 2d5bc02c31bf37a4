"""Device idle share of the traced window in the fleet cells, in percent."""
from bench.trace_reduce import idle_share_pct as read  # noqa: F401
