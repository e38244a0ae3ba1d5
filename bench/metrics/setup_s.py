"""setup_s: seconds from the process start to the first request of the
lead-in: imports, weights, the batcher and pool, and the warm-up that
compiles (or loads from the persistent cache) every program."""


def read(run):
    return run.setup_s
