#!/usr/bin/env bash
# Reproducible perf environment for the benchmark harness (the SNIPPETS
# XLA tuning idioms).  Source it, then run the ladder:
#
#   source benchmarks/perf_env.sh            # default: 4 virtual devices
#   REPRO_HOST_DEVICES=8 source benchmarks/perf_env.sh
#   PYTHONPATH=src python -m benchmarks.run batched_engine
#
# `python -m benchmarks.run --perf-env` applies the same settings
# in-process for users who skip this file.

# Virtual host devices: gives the sharded replay path (shard_map over
# fleet partitions) real XLA devices on a CPU-only machine.  Must be set
# before the first jax import.
: "${REPRO_HOST_DEVICES:=4}"
case "${XLA_FLAGS:-}" in
  *--xla_force_host_platform_device_count*) ;;
  *) export XLA_FLAGS="${XLA_FLAGS:+${XLA_FLAGS} }--xla_force_host_platform_device_count=${REPRO_HOST_DEVICES}" ;;
esac

# tcmalloc, when installed, removes glibc-malloc contention from XLA's
# host allocation paths.
for _tc in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
           /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
  if [ -e "${_tc}" ]; then
    case "${LD_PRELOAD:-}" in
      *"${_tc}"*) ;;
      *) export LD_PRELOAD="${LD_PRELOAD:+${LD_PRELOAD} }${_tc}" ;;
    esac
    break
  fi
done
unset _tc

echo "perf env: XLA_FLAGS=${XLA_FLAGS}"
echo "perf env: LD_PRELOAD=${LD_PRELOAD:-<none>}"
