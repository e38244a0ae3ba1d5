"""first_token_hold_ms: per first token, the time from the first token on
the host to the end of the scheduler step that placed it, when ingress can
stream it (scheduler, `PagedBatcher`'s ``first_token_hold_s``)."""
from bench.stages import stage_ms


def read(run):
    return stage_ms(run, "first_token_hold_s")
