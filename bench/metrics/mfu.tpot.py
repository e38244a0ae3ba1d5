"""mfu.tpot: the whole step's share of the chip's peak, in the cells where
it moves tpot_p70_ms."""
from bench.readers import mfu as read  # noqa: F401
