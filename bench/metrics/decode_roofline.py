"""decode_roofline: the decode steps' least time on the roofline (weights
once per step, each lane's live keys and values, the new ones written) over
the device time of the decode-window programs."""
from bench.readers import decode_least_seconds, program_seconds, share


def read(run):
    return share(decode_least_seconds(run), program_seconds(run, "decode")[0])
