"""From a profiler trace to the device's busy time, its idle gaps and
where they fall.

``capture`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
compact event list: per device plane its ``XLA Ops`` and ``XLA Modules``
events, and the host spans the benchmark and the program annotate
(``jax.profiler.TraceAnnotation``; both put theirs on the profiler's
clock).  ``reduce`` turns that list into:

  * ``busy_s``   — the union of the intervals in which an op ran, inside
                   the traced window, averaged over the chips that ran;
  * ``window_s`` — the traced window (the ``bench.traced_window`` span);
  * ``device_ops`` — ops by self time (an op's time less the ops nested
                   in it), largest first, by ``op_label``;
  * ``modules``  — device time per compiled program (``XLA Modules``);
  * ``idle_gaps`` — idle time by the innermost host span open at each
                   gap's midpoint (``no_span`` where none was), largest
                   first.

Times are seconds.  The reduction is kept with the benchmark so that
every later change is measured by the same arithmetic.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

WINDOW_SPAN = "bench.traced_window"
HOST_PREFIXES = ("bench.", "serve.", "chunk.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def capture(path: str) -> dict:
    """The events ``reduce`` needs, read from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
            if lines.get(OPS_LINE):
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"devices": devices, "host": host}


def op_label(name: str) -> str:
    """An op's short name: the TPU trace names an op by its whole HLO
    line (``%copy.88 = s32[16384,3]{1,0:T(8,128)} copy(...)``); keep the
    instruction's name and, where it is one array, its shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    label = head.lstrip("%")
    shape = rest.split(" ", 1)[0]
    if not shape.startswith("("):
        label += " " + shape.split("{", 1)[0]
    return label


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint ones, in order."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds per op name, each op less the ops nested inside it
    (one line's events nest or follow each other)."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List] = []      # [name, end, child_ns, dur]

    def close(frame):
        out[frame[0]] += max(frame[3] - frame[2], 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
        stack.append([name, end, 0, dur])
    while stack:
        close(stack.pop())
    return out


def _label_segments(spans: Sequence[Event]
                    ) -> Tuple[List[int], List[str]]:
    """Piecewise-constant innermost open span: boundaries and labels."""
    bounds = sorted({t for _, s, d in spans for t in (s, s + d)})
    labels = []
    by_start = sorted(spans, key=lambda e: (e[1], -e[2]))
    for i in range(len(bounds) - 1):
        mid = (bounds[i] + bounds[i + 1]) / 2
        best: Optional[Event] = None
        for sp in by_start:
            if sp[1] > mid:
                break
            if sp[1] + sp[2] > mid and (best is None or sp[1] >= best[1]):
                best = sp
        labels.append(best[0] if best else "no_span")
    return bounds, labels


def reduce(captured: dict, top: int = 10) -> dict:
    host = captured["host"]
    win = [e for e in host if e[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, w0, wd = max(win, key=lambda e: e[2])
    w1 = w0 + wd
    spans = [e for e in host if e[0] != WINDOW_SPAN
             and e[1] < w1 and e[1] + e[2] > w0]
    bounds, labels = _label_segments(spans)

    busy_ns, ops, modules = [], defaultdict(int), defaultdict(int)
    gaps: Dict[str, int] = defaultdict(int)
    for lines in captured["devices"].values():
        evs = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
               for n, s, d in lines.get(OPS_LINE, [])
               if s < w1 and s + d > w0]
        if not evs:
            continue
        busy = union((s, s + d) for _, s, d in evs)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, ns in self_times(evs).items():
            ops[op_label(name)] += ns
        for name, s, d in lines.get(MODULES_LINE, []):
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                modules[name] += hi - lo
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            i = bisect.bisect_right(bounds, mid) - 1
            label = labels[i] if 0 <= i < len(labels) else "no_span"
            gaps[label] += b - a
    if not busy_ns:
        raise ValueError("no device op ran inside the traced window")
    chips = len(busy_ns)

    def ranked(d):
        return [[k, v / 1e9 / chips] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(busy_ns) / chips / 1e9, "window_s": wd / 1e9,
            "chips": chips, "device_ops": ranked(ops),
            "modules": ranked(modules), "idle_gaps": ranked(gaps),
            "host_spans": sorted({e[0] for e in spans})}


__all__ = ["capture", "reduce", "union", "self_times", "op_label",
           "find_xplane", "WINDOW_SPAN"]
