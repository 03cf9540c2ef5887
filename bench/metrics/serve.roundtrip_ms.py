"""Mean device round trip of a served micro-batch: the program's
``serve.batch`` span (ingest jit, decision step, blocking read-back),
from its flight recorder, over the batches of the traced part of the
window (stopping the profiler stalls the loop, so later batches run
behind a backlog)."""


def read(run):
    traced = sum(1 for n, b, e in run.spans
                 if n == "bench.drain" and e <= run.trace_closed)
    durs = [s["dur_s"] for s in run.program_spans
            if s["name"] == "serve.batch"][:traced]
    return sum(durs) / len(durs) * 1e3 if durs else None
