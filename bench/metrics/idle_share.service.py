"""Percent of the traced window in which no operation ran on the chip
(averaged over the chips the cell uses), in the service cells."""
from bench.tracefile import idle_share_pct as read  # noqa: F401
