#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its mix at several fixed rates in
one process, set up once.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2 3 4 5

One line of JSON per rate: requests sent in the window and finished, the
output tokens/s, the latency percentiles, and how long the drain ran past
the window's close (a backlog that grew through the window drains late).
The knee is the highest rate whose backlog stays bounded; the cell's mix
then fixes its rate at about 0.8 of it. Not run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench.readers import percentile, queue_wait_ms, tpot_ms, ttft_ms  # noqa: E402
from bench.run import NoChip, Session, cell_spec, device_info  # noqa: E402


def summary(data, rate: float) -> dict:
    done = [r for r in data.records if r.done]
    out_tok = sum(1 for r in data.all_records for t in r.tokens
                  if 0.0 <= t < data.seconds)
    return {
        "rate_rps": rate, "sent": len(data.records), "finished": len(done),
        "output_tok_s": out_tok / data.seconds,
        "ttft_p50_ms": percentile(ttft_ms(data), 50),
        "ttft_p95_ms": percentile(ttft_ms(data), 95),
        "tpot_p95_ms": percentile(tpot_ms(data), 95),
        "queue_wait_p95_ms": percentile(queue_wait_ms(data), 95),
        "drain_past_close_s": max((r.finish for r in done), default=0.0)
        - data.seconds,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = cell_spec(args.workload)
    if spec["mix"]["loop"] != "open":
        print("sweep: the cell's mix is not an open loop", file=sys.stderr)
        return 2
    try:
        device_info(spec["workload"]["chips"])
    except NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    t = time.monotonic()
    session = Session(spec, seed=args.seed, trace=False)
    print(json.dumps({"setup_s": time.monotonic() - t}), flush=True)
    for rate in args.rates:
        mix = dict(spec["mix"], arrival=dict(spec["mix"]["arrival"],
                                             rate_rps=rate))
        print(json.dumps(summary(session.serve(mix, args.seconds), rate)),
              flush=True)
    session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
