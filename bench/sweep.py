#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate it
sustains.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates 2,4,6

Runs the cell's traffic at each rate in one process (a fresh engine per
rate, no reference check) and prints one JSON line per rate: offered and
completed requests per second, output tokens per second, the median and
95th percentile time to first token, and the requests still waiting for
their first token at the close.  A rate is sustained while completions
keep up with arrivals and that backlog stays near zero.  The cell's
traffic file takes about four fifths of the knee as its rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    base = run.load_cell(args.workload)
    if base["traffic"]["loop"] != "open":
        raise SystemExit("only an open-loop cell has a rate to sweep")
    run.use_compile_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["traffic"]["rate_req_s"] = rate
        w = run.serve_window(cell, args.seed, args.seconds, False,
                             time.perf_counter())
        reqs, t0, t1 = w["reqs"], w["t_open"], w["t_close"]
        e2e = stats.end_to_end(reqs, t0, t1)
        ft = stats.ttfts(reqs, t0, t1)
        print(json.dumps({
            "rate_req_s": rate,
            "offered_req_s": len(ft) / (t1 - t0),
            "completed_req_s": e2e["completed"] / (t1 - t0),
            "output_tok_s": e2e["output_tok_s"],
            "ttft_p50_ms": stats.percentile(ft, 50) * 1e3 if ft else None,
            "ttft_p95_ms": e2e["ttft_p95_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "backlog": w["backlog"], "compiles": w["compiles"],
            "setup_s": w["setup_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
