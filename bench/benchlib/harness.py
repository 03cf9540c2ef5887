"""One run of one cell: set up, measure a window, check the answers
against the reference, print the result line.

The flow is the same for every cell; what differs lives in the cell's
driver (``benchlib/drivers/<driver>.py``, named by the mix's ``driver``
key):

  1. the device check: JAX must see an accelerator with at least the
     chips the cell asks for, or the run exits non-zero with no result;
  2. ``driver.setup``: the stream from the seed, the program's objects,
     and a warm-up of every shape the window uses (all of it ``setup_s``);
  3. ``driver.window``: the measured window.  With ``--trace 1`` the
     profiler records its first ``trace_seconds`` and the per-layer
     readers get the spans;
  4. the device's peak memory, then ``driver.check``: the program's state
     is dropped and its answers are compared with the plain reference
     (``--control`` puts the reference with a broken guarantee in the
     program's place, to show that the comparison fails it);
  5. the result line: ``correct``, ``attempted``, ``failed``, the metrics
     of the cell's kind of run, ``device``, the breakdown of a traced run,
     and last the compared numbers beside their limits (also printed as
     the last lines of standard error).
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

from . import spec, trace as trace_mod

OUT = os.path.join(spec.BENCH, "out")


class NoDevice(SystemExit):
    """Raised when JAX finds no accelerator or too few chips."""


def require_devices(n: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoDevice(f"bench: needs an accelerator, JAX found "
                       f"{devs[0].platform!r} devices")
    if len(devs) < n:
        raise NoDevice(f"bench: the cell asks for {n} chips, JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def peak_memory_bytes(n: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs handed to the compiler (a compile or a load from
    the persistent cache) and true compiles (persistent-cache misses)."""

    def __init__(self):
        from jax._src import monitoring
        self.backend = 0
        self.misses = 0

        def on_duration(event, *_a, **_k):
            if event == "/jax/core/compile/backend_compile_duration":
                self.backend += 1

        def on_event(event, *_a, **_k):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.backend, self.misses


class Run:
    """What a driver measured, and what the readers read from it."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 traced: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.traced = traced
        self.spans: List[tuple] = []         # (name, begin_s, end_s)
        self.program_spans: List[dict] = []  # the recorder's JSONL spans
        self.metrics: Dict[str, float] = {}  # end-to-end, by name
        self.reduced: Optional[dict] = None  # trace.reduce output
        self.info: Dict[str, object] = {}
        self.out_dir = os.path.join(OUT, f"{cell.name}-{seed}")
        self.attempted = 0
        self.failed = 0
        self._trace_open = None
        self.trace_closed: Optional[float] = None   # host clock, first stop
        self.captured: Optional[dict] = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark span: host clock, and a profiler annotation while
        tracing (free otherwise)."""
        t0 = time.perf_counter()
        if self._trace_open is not None:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    # -- the profiler's window --------------------------------------------
    def trace_start(self) -> None:
        if not self.traced:
            return
        import jax
        d = os.path.join(self.out_dir, "trace")
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d, exist_ok=True)
        # No Python tracer: it records every Python call, which would
        # slow the host path being measured and swell the trace.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(d, profiler_options=opts)
        self._trace_dir = d
        self._trace_ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._trace_ann.__enter__()
        self._trace_open = time.perf_counter()

    def trace_due(self) -> bool:
        """True once the traced window has lasted ``trace_seconds``."""
        return (self._trace_open is not None
                and time.perf_counter() - self._trace_open
                >= float(self.cell.settings.get("trace_seconds", 2.0)))

    def trace_stop(self) -> None:
        if self._trace_open is None:
            return
        import jax
        self._trace_ann.__exit__(None, None, None)
        self.trace_closed = time.perf_counter()
        jax.profiler.stop_trace()
        self._trace_open = None

    def reduce_trace(self) -> None:
        if not self.traced:
            return
        path = trace_mod.find_xplane(self._trace_dir)
        self.info["trace_bytes"] = os.path.getsize(path)
        self.captured = trace_mod.capture(path)
        self.reduced = trace_mod.reduce(self.captured)
        shutil.rmtree(self._trace_dir, ignore_errors=True)


def _driver(kind: str):
    """``benchlib/drivers/<kind>.py``: a new kind of run is a new file."""
    return importlib.import_module(f".drivers.{kind}", __package__)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             process_start: float, control: bool = False,
             overrides: Optional[dict] = None,
             device_check=require_devices,
             keep: Optional[dict] = None) -> dict:
    """Run one cell once and return the result line's object.  ``keep``,
    when given, receives the run's ``info`` and the captured trace."""
    cell = spec.Cell(name, overrides=overrides)
    device = device_check(cell.chips)
    import jax
    log(f"device platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} of {len(jax.devices())} "
        f"jax={jax.__version__}")
    driver = _driver(cell.traffic["driver"])
    run = Run(cell, seed, seconds, traced)
    counter = CompileCounter()

    state = driver.setup(run)
    gc.collect()
    gc.freeze()
    before = counter.snapshot()
    window_start = time.perf_counter()
    setup_s = window_start - process_start
    driver.window(run, state, window_start)
    gc.unfreeze()
    after = counter.snapshot()
    run.info["compiles_in_window"] = after[0] - before[0]
    run.info["compile_misses_in_window"] = after[1] - before[1]
    log(f"compiles inside the window: {after[0] - before[0]} programs "
        f"handed to the compiler, {after[1] - before[1]} not in the "
        "persistent cache (want 0 and 0)")
    device["memory_peak_bytes"] = peak_memory_bytes(cell.chips)
    checks = driver.check(run, state, control=control)
    del state
    run.reduce_trace()

    metrics = {}
    if traced:
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
    else:
        run.metrics["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] not in run.metrics:
                raise KeyError(f"cell {name} reports no {m['name']}")
            metrics[m["name"]] = {"value": run.metrics[m["name"]],
                                  "unit": m["unit"]}
    for k, v in sorted(run.info.items()):
        log(f"{k} = {v}")
    correct = (run.failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {
            "device_ops": run.reduced["device_ops"],
            "idle_gaps": run.reduced["idle_gaps"],
            "modules": run.reduced["modules"]}
    result["checks"] = checks
    if keep is not None:
        keep.update(info=dict(run.info), captured=run.captured)
    return result


def print_result(result: dict) -> None:
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


__all__ = ["run_cell", "print_result", "require_devices", "Run",
           "NoDevice", "log"]
