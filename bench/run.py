#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the checkout root, on a machine whose accelerator JAX sees
(it exits non-zero, printing no result, when there is none or there are
fewer chips than the cell asks for).  ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` profiles the first seconds of the
window and prints the per-layer metrics, the device's busy time and a
breakdown.  ``--control`` runs the comparison against the control (the
reference with the guarantee the configuration names broken) in the
program's place; it has to come out not correct.

The cells, configurations and metrics are listed in ``BENCHMARK.json``;
``bench/benchlib/spec.py`` says where each one's files are.
"""
from __future__ import annotations

import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


PROCESS_START = time.perf_counter() - _process_age()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    # JAX's compile cache lives at a fixed path inside the checkout, so
    # only a cell's first run there compiles; libtpu keeps no log files.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        BENCH, ".cache", "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from benchlib import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace),
                                  process_start=PROCESS_START,
                                  control=args.control)
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
