"""prefill_roofline: the prefill chunks' least time on the roofline
(weights once per chunk, causal attention, the resident keys and values read
and the chunk's written) over the device time of the prefill programs."""
from bench.readers import prefill_least_seconds, program_seconds, share


def read(run):
    return share(prefill_least_seconds(run),
                 program_seconds(run, "prefill")[0])
