"""host_gap_share.code: share of the traced interval in which the device runs
no step program while the host does scheduler work outside every sync span
and outside await_arrival, in the code cell (moves ttft_p50_ms;
`bench/host_spans.py`)."""
from bench.host_spans import host_gap_share as read  # noqa: F401
