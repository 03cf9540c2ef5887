"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--perf-env] [module ...]

``--perf-env`` applies the reproducible perf environment (the SNIPPETS
XLA tuning idioms) *before* jax is imported: virtual host devices for
the sharded replay path and tcmalloc when present.
``benchmarks/perf_env.sh`` exports the same settings for interactive
shells.  The persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``
(``repro.core.compile_cache``).

Modules: config_space (§5.1), basket_sweep (Fig. 6-8),
consolidation_sweep (Fig. 9), acceptance (Fig. 10-11),
active_hardware (Fig. 12 / Table 6), migrations (§8.3.3),
ilp_gap (§6 oracle vs all policies, homogeneous + mixed fleets),
adaptive (online basket-capacity control),
kernel_throughput + batched_engine + hetero_sweep (beyond-paper),
serve_latency (online placement-service SLO surface).
The roofline table is produced separately by repro.launch.roofline
(needs a fresh process for the 512-device XLA flag).
"""
from __future__ import annotations

import os
import sys
import traceback

MODULES = [
    "config_space",
    "basket_sweep",
    "consolidation_sweep",
    "acceptance",
    "active_hardware",
    "migrations",
    "ilp_gap",
    "adaptive",
    "kernel_throughput",
    "batched_engine",
    "hetero_sweep",
    "serve_latency",
]

# tcmalloc beats glibc malloc on XLA's allocation-heavy host paths
# (SNIPPETS idiom); only preloaded when actually installed.
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)


def apply_perf_env() -> None:
    """Set the reproducible-perf env vars.  MUST run before any jax
    import — XLA reads XLA_FLAGS at backend initialization, and
    LD_PRELOAD only matters for exec'd children (we re-exec if a
    tcmalloc is present but not yet preloaded)."""
    if "jax" in sys.modules:
        raise RuntimeError("--perf-env must be applied before jax "
                           "is imported")
    n_dev = os.environ.setdefault("REPRO_HOST_DEVICES", "4")
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_dev}"
        ).strip()
    tc = next((p for p in TCMALLOC_PATHS if os.path.exists(p)), None)
    if tc and tc not in os.environ.get("LD_PRELOAD", ""):
        # LD_PRELOAD can't retroactively affect a running interpreter:
        # re-exec ourselves once with it set.
        os.environ["LD_PRELOAD"] = (
            f"{os.environ.get('LD_PRELOAD', '')} {tc}".strip())
        os.environ["REPRO_PERF_ENV_REEXEC"] = "1"
        if os.environ.get("REPRO_PERF_ENV_REEXEC_DONE") != "1":
            os.environ["REPRO_PERF_ENV_REEXEC_DONE"] = "1"
            os.execv(sys.executable, [sys.executable, "-m",
                                      "benchmarks.run"] + sys.argv[1:])


def main() -> None:
    args = sys.argv[1:]
    if "--perf-env" in args:
        args = [a for a in args if a != "--perf-env"]
        apply_perf_env()
    requested = args or MODULES
    print("name,us_per_call,derived")
    failed = []
    for name in requested:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"FAILED modules: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
