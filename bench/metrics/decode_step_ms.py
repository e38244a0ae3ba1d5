"""decode_step_ms: device time of the decode-window programs over the decode
steps they ran (each window runs its full number of steps)."""
from bench.readers import program_seconds


def read(run):
    secs, n = program_seconds(run, "decode")
    if not n:
        return None
    return 1e3 * secs / (n * run.config["deployment"]["window"])
