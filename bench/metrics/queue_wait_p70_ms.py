"""queue_wait_p70_ms: 70th percentile over the window's requests of
admission minus scheduled arrival (ingress, serving/ingress.py)."""
from bench.readers import percentile, queue_wait_ms


def read(run):
    return percentile(queue_wait_ms(run), 70)
