"""tpot_p70_ms: 70th percentile over the window's requests of
(last − first token) / (n − 1), for n >= 2."""
from bench.readers import percentile, tpot_ms


def read(run):
    return percentile(tpot_ms(run), 70)
