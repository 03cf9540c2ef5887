#!/usr/bin/env python3
"""Find the served cell's knee: the open loop at each of several rates.

    python3 bench/tools/knee.py --workload serve-openb-grmu \\
        --seeds 11,12,13 --seconds 10 --rates 1000,2000,3000

One process, one run per rate and seed (its own service), each checked
against the reference.  Prints one JSON line per run (p50, p95 and p99 in
ms, the requests still queued at the window's close, the time it took to
drain them), then one per rate with the medians over the seeds, then the
knee: the highest rate whose median p99 meets the service's own 50 ms
limit (``ServeConfig.slo_s``) with a median backlog at the close of at
most one micro-batch, every lower rate passing too.  The served cell
runs at 4/5 of it.
"""
import argparse
import collections
import json
import statistics
import time

import _boot  # noqa: F401  (paths and caches)
from benchlib import harness


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve-openb-grmu")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    runs = [(float(r), int(s)) for r in args.rates.split(",")
            for s in args.seeds.split(",")]
    by_rate = collections.defaultdict(list)
    for rate, seed in runs:
        kept = {}
        res = harness.run_cell(
            args.workload, seed, args.seconds, False,
            process_start=time.perf_counter(),
            overrides={"traffic": {"rate": rate}}, keep=kept)
        info = kept["info"]
        m = res["metrics"]
        line = {
            "rate": rate, "seed": seed, "seconds": args.seconds,
            "p50_ms": info["decision_p50_ms"],
            "p95_ms": info["decision_p95_ms"],
            "p99_ms": info["decision_p99_ms"],
            "backlog_at_close": info.get("backlog_at_close"),
            "drain_after_close_s": info.get("drain_after_close_s"),
            "submit_lateness_p99_ms": info.get(
                "submit_lateness_p99_ms"),
            "setup_s": m["setup_s"]["value"],
            "correct": res["correct"]}
        by_rate[rate].append(line)
        print(json.dumps(line), flush=True)
    knee = None
    batch = harness.spec.Cell(args.workload).traffic["micro_batch"]
    for rate in sorted(by_rate):
        p99 = statistics.median(r["p99_ms"] for r in by_rate[rate])
        backlog = statistics.median(r["backlog_at_close"]
                                    for r in by_rate[rate])
        print(json.dumps({"rate": rate, "median_p99_ms": p99,
                          "median_backlog": backlog}), flush=True)
        if p99 > 50.0 or backlog > batch:
            break
        knee = rate
    print(json.dumps({"knee": knee}), flush=True)


if __name__ == "__main__":
    main()
