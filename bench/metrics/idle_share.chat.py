"""idle_share.chat: share of the traced interval in which no operation ran on
the device, in the chat cell (moves tpot_p70_ms)."""
from bench.readers import idle_share as read  # noqa: F401
