"""How full the served micro-batches are: 100 x the real rows over the
rows the decision step scans (``rows`` and ``batch_rows`` of the
program's ``serve.pop`` span), summed over the traced micro-batches."""
from benchlib.served_spans import pop_total


def read(run):
    scanned = pop_total(run, "batch_rows")
    if not scanned:
        return None
    return 100.0 * pop_total(run, "rows") / scanned
