"""Host observability plane: spans, cache stats, schema-versioned JSONL.

The :class:`Recorder` is the flight recorder's host half.  It never runs
inside jit — engines check :func:`active` (None when recording is off,
the default) and only then emit spans, so the hot path stays a no-op
unless a recorder is installed with :func:`record`:

    from repro.obs import recorder as obs_recorder
    with obs_recorder.record("run.jsonl", meta={"policy": "GRMU"}) as rec:
        res = replay_chunked(events, GRMU)      # emits chunk.* spans
        rec.result(res)
        rec.cache_stats()

Every line in the JSONL file is one record with ``schema`` (the
``SCHEMA_VERSION`` of ``repro.obs.inscan``), ``kind`` and ``run_id``:

  ``meta``       run header (wall time, caller-provided metadata)
  ``span``       a named wall-clock span: ``name``; ``t0``/``t1``, its
                 start and end on ``time.perf_counter()``; ``dur_s``
                 (``t1 - t0``); ``id``, a sequence number of the
                 recorder; ``parent``, the ``id`` of the span open
                 around it (None at the top); and the caller's fields
                 (``index``/``nbytes`` for chunk steps, ``batch``/
                 ``rows``/... for served micro-batches).  Each span is
                 also a ``jax.profiler.TraceAnnotation``, so a profiler
                 capture holds the same interval on its own clock
  ``cache``      compile-cache hits/misses/evictions/entries snapshot
  ``result``     a SimResult summary + rejection-reason tally
  ``telemetry``  a full ``ReplayTelemetry`` payload (in-scan plane)
  ``service``    a placement-service control-plane event (admission
                 governor tier switches, checkpoint/restore) — emitted
                 by ``repro.serve.placement`` beside its ``serve.*``
                 spans

Records are kept in memory and the file is written once, by
:meth:`Recorder.close` (the :func:`record` block's exit), so a recorded
loop makes no file write.  Spans measure *dispatch* wall-clock: jax
executes asynchronously, so a chunk-step span is the host-side cost of
submitting (and, under donation back-pressure, partially waiting on)
that chunk — end-to-end device time comes from a profiler trace, which
whoever opens the window starts (``jax.profiler``); the recorder starts
none.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Dict, Iterator, List, Optional

import jax

from .inscan import SCHEMA_VERSION

_ACTIVE: Optional["Recorder"] = None


def active() -> Optional["Recorder"]:
    """The process-active recorder, or None (recording off — default)."""
    return _ACTIVE


class Recorder:
    """Keeps schema-versioned records in memory and writes them as JSONL
    at :meth:`close`; see the module docstring.  Prefer the
    :func:`record` context manager, which also installs the recorder as
    the process-active one so engine loops emit spans."""

    def __init__(self, path, *, run_id: Optional[str] = None,
                 meta: Optional[dict] = None):
        self.path = str(path)
        self.run_id = run_id or f"run-{os.getpid()}-{int(time.time())}"
        self.records: List[dict] = []
        self._next_id = 0
        self._open: List[int] = []   # ids of the open spans, outermost first
        self._closed = False
        self.emit("meta", time_unix=time.time(), **(meta or {}))

    def emit(self, kind: str, **fields) -> None:
        rec = {"schema": SCHEMA_VERSION, "kind": kind,
               "run_id": self.run_id}
        rec.update(fields)
        self.records.append(rec)

    @contextlib.contextmanager
    def span(self, name: str, **fields) -> Iterator[Dict[str, object]]:
        """Time a host-side region, nested in the span open around it.
        Yields the record's field dict: what the caller adds to it
        inside the block (counts known only at the end) is recorded."""
        sid = self._next_id
        self._next_id += 1
        parent = self._open[-1] if self._open else None
        self._open.append(sid)
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                yield fields
            t1 = time.perf_counter()
        finally:
            self._open.pop()
        self.emit("span", name=name, t0=t0, t1=t1, dur_s=t1 - t0, id=sid,
                  parent=parent, **fields)

    def cache_stats(self) -> None:
        """Snapshot the replay compile cache (hits/misses/evictions)."""
        from ..core import compile_cache
        self.emit("cache", **compile_cache.cache_stats())

    def result(self, res) -> None:
        """Record a ``SimResult``'s summary + rejection-reason tally."""
        self.emit("result", summary=res.summary(),
                  rejection_reasons=dict(res.rejection_reasons))

    def telemetry(self, tele) -> None:
        """Record a full in-scan ``ReplayTelemetry`` payload."""
        self.emit("telemetry", **tele.to_json_dict())

    def service(self, event: str, **fields) -> None:
        """Record a placement-service control-plane event (``kind=
        "service"``): governor tier switches, checkpoint/restore marks.
        ``event`` names the transition (e.g. ``degrade``/``recover``)."""
        self.emit("service", event=event, **fields)

    def close(self) -> None:
        """Append every record to the file, once."""
        if self._closed:
            return
        self._closed = True
        with open(self.path, "a") as fh:
            fh.writelines(json.dumps(r) + "\n" for r in self.records)


@contextlib.contextmanager
def record(path, *, run_id: Optional[str] = None,
           meta: Optional[dict] = None) -> Iterator[Recorder]:
    """Open a :class:`Recorder` on ``path`` and install it as the
    process-active recorder for the duration of the block."""
    global _ACTIVE
    rec = Recorder(path, run_id=run_id, meta=meta)
    prev, _ACTIVE = _ACTIVE, rec
    try:
        yield rec
    finally:
        _ACTIVE = prev
        rec.close()


__all__ = ["Recorder", "record", "active"]
