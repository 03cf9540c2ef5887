"""Mean host time to hand a served micro-batch's answers out: the
program's ``serve.emit`` span (the ``Decision`` objects and the
admission governor), over the traced micro-batches."""
from benchlib.served_spans import mean_ms


def read(run):
    return mean_ms(run, "serve.emit")
