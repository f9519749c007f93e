"""Device idle share of the traced window, in %: 1 - busy / window."""
from benchmarks.chip.trace import idle_pct as read  # noqa: F401
