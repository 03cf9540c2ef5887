#!/usr/bin/env python3
"""Record a short traced run of a cell and keep its compact events, the
input of ``benchlib.trace.reduce``, as JSON (the recorded trace that
``bench/tests`` check the reduction on).

    python3 bench/tools/record_trace.py --workload serve-openb-grmu \\
        --seed 5 --seconds 2 --trace-seconds 0.05 --out events.json
"""
import argparse
import gzip
import json
import time

import _boot  # noqa: F401  (paths and caches)
from benchlib import harness


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    kept = {}
    res = harness.run_cell(
        args.workload, args.seed, args.seconds, True,
        process_start=time.perf_counter(),
        overrides={"settings": {"trace_seconds": args.trace_seconds}},
        keep=kept)
    with gzip.open(args.out, "wt") as fh:
        json.dump({"captured": kept["captured"], "device": res["device"],
                   "metrics": res["metrics"]}, fh)
    print(json.dumps(res["device"]))


if __name__ == "__main__":
    main()
