"""idle_share.code: share of the traced interval in which no operation ran on
the device, in the code cell (moves ttft_p50_ms)."""
from bench.readers import idle_share as read  # noqa: F401
