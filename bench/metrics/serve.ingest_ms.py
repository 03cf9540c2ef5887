"""Host time of the ingest jit per served micro-batch: the program's
``serve.ingest`` span (the new arrivals' table rows gathered and handed
to the ingest jit), summed and divided by the traced micro-batches; a
batch with no arrival runs no ingest and counts 0."""
from benchlib.served_spans import traced_batches


def read(run):
    batches = traced_batches(run)
    if not batches:
        return None
    return sum(b["serve.ingest"]["dur_s"] for b in batches
               if "serve.ingest" in b) / len(batches) * 1e3
