"""Helpers for the benchmark's own tests: the harness on the CPU at a
size a test run holds, with the look for a chip skipped."""
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# A 60-host fleet, 400 set-up arrivals, 150 arrivals a second.
SMALL = {"config": {"fleet": {"hosts": 60}, "stream": {"vms": 400}},
         "traffic": {"setup_arrivals": 400, "rate": 150}}


def any_device(n):
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def run(cell, seed=20251017, seconds=0.6, control=False, overrides=None):
    from benchlib import harness, spec
    over = spec.merge(SMALL, overrides)
    return harness.run_cell(cell, seed, seconds, False,
                            process_start=time.perf_counter(),
                            control=control, overrides=over,
                            device_check=any_device)


def fresh_programs():
    """Drop the program's cached jitted functions, so a patched function
    is traced anew."""
    import jax
    from repro.core import compile_cache
    compile_cache.clear_cache()
    jax.clear_caches()
