"""prefill_wait_ms: per first token, the time from the request's submit to
the batcher to its first prefill chunk: earlier prefills and the wait for
blocks (scheduler, `PagedBatcher`'s ``prefill_wait_s``)."""
from bench.stages import stage_ms


def read(run):
    return stage_ms(run, "prefill_wait_s")
