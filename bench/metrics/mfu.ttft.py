"""mfu.ttft: the whole step's share of the chip's peak, in the cells where
it moves ttft_p50_ms."""
from bench.readers import mfu as read  # noqa: F401
