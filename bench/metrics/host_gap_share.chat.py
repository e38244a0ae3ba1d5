"""host_gap_share.chat: share of the traced interval in which the device runs
no step program while the host does scheduler work outside every sync span
and outside await_arrival, in the chat cell (moves tpot_p70_ms;
`bench/host_spans.py`)."""
from bench.host_spans import host_gap_share as read  # noqa: F401
