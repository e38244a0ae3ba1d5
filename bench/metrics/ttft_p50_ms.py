"""ttft_p50_ms: median over the window's requests of first token minus
scheduled arrival."""
from bench.readers import percentile, ttft_ms


def read(run):
    return percentile(ttft_ms(run), 50)
