#!/usr/bin/env python3
"""Read the numbers a cell's correctness limits are set from, many seeds in
one process, set up once.

    python bench/limits.py --workload <cell> --seconds <s> --seeds 1 2 3 \\
        [--program '{"weight_quant": "int8"}']

For each seed the cell's weights are made anew and put in the batcher
built for the first seed, and one lead-in and window of the cell's own
traffic is served. Once every seed has been served, the program's state is
freed and each seed's sample is compared with the reference, as a run of
`run.py` compares it. ``--program`` switches on a path of the program:
the control (int8 weights, the nearest precision below the bfloat16 the
configuration states) must read above the limit. One JSON line per seed.
Not run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench.model import make_weights  # noqa: E402
from bench.run import (NoChip, Session, cell_spec, check_outputs,  # noqa: E402
                       device_info, sample_requests)


def set_weights(session, seed: int, program: dict) -> None:
    """Put seed ``seed``'s weights in the session's batcher, quantized as
    the batcher quantized its own."""
    b = session.server.batcher
    params = make_weights(session.spec["config"], seed)
    if program.get("weight_quant"):
        from repro.models.quant import quantize_params
        params = quantize_params(params, b.cfg, program["weight_quant"])
    b.params = b.layout.place_params(params)
    session.seed = seed


def readings(spec: dict, seeds, seconds: float, program: dict) -> list:
    session = Session(spec, seed=seeds[0], trace=False,
                      program=program or None)
    served = []
    for seed in seeds:
        if seed != seeds[0]:
            set_weights(session, seed, program)
        data = session.serve(spec["mix"], seconds)
        served.append((seed, data))
    session.close()
    out = []
    for seed, data in served:
        streams = {r.rid: (r.prompt, data.served[r.rid])
                   for r in data.records if r.done}
        checks, max_gap = check_outputs(
            spec, seed, data.traffic, sample_requests(data.records, seed),
            streams)
        out.append({"seed": seed, "program": program,
                    "attempted": len(data.records),
                    "unfinished": sum(not r.done for r in data.records),
                    "max_logit_gap": max_gap,
                    **{k: v["value"] for k, v in checks.items()}})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", type=json.loads, default={})
    args = ap.parse_args(argv)
    spec = cell_spec(args.workload)
    try:
        device_info(spec["workload"]["chips"])
    except NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 2
    for row in readings(spec, args.seeds, args.seconds, args.program):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
