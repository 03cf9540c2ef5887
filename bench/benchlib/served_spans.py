"""The program's span tree of each served micro-batch, over the traced
part of the window.

With a recorder installed, ``PlacementService`` records every
micro-batch as a ``serve.drain_batch`` root whose descendants
(``serve.pop``, ``serve.batch`` with ``serve.ingest``, ``serve.step`` and
``serve.readback`` inside it, ``serve.emit``) name their parent by
``id``.  The k-th ``bench.drain`` holds the k-th root, so the traced
batches are the roots up to the count of drains that ended before the
profiler stopped (stopping it stalls the loop, and later batches run
behind a backlog).  A program that records no roots gives no batches.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

ROOT = "serve.drain_batch"


def traced_batches(run) -> List[Dict[str, dict]]:
    """Per traced micro-batch, its spans by name, the root included."""
    traced = sum(1 for n, b, e in run.spans
                 if n == "bench.drain" and e <= run.trace_closed)
    roots = [s for s in run.program_spans if s["name"] == ROOT][:traced]
    kids = defaultdict(list)
    for s in run.program_spans:
        if s.get("parent") is not None:
            kids[s["parent"]].append(s)
    out = []
    for r in roots:
        tree, todo = {}, [r]
        while todo:
            s = todo.pop()
            tree[s["name"]] = s
            todo.extend(kids[s["id"]])
        out.append(tree)
    return out


def mean_ms(run, name: str) -> Optional[float]:
    """Mean duration of span ``name`` over the traced batches holding it."""
    durs = [b[name]["dur_s"] for b in traced_batches(run) if name in b]
    return sum(durs) / len(durs) * 1e3 if durs else None


def pop_total(run, field: str) -> Optional[float]:
    """The sum of a ``serve.pop`` counter over the traced batches."""
    pops = [b["serve.pop"] for b in traced_batches(run) if "serve.pop" in b]
    return sum(p[field] for p in pops) if pops else None


__all__ = ["traced_batches", "mean_ms", "pop_total", "ROOT"]
