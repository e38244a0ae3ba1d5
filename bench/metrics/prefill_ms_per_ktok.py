"""prefill_ms_per_ktok: device time of the prefill programs per 1,000
prompt tokens they processed."""
from bench.readers import prefill_chunks, program_seconds


def read(run):
    secs, n = program_seconds(run, "prefill")
    tokens = sum(c for _, c in prefill_chunks(run))
    if not n or not tokens:
        return None
    return 1e3 * secs / (tokens / 1e3)
