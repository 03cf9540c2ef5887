"""Mean host time to dispatch a served micro-batch's decision step: the
program's ``serve.step`` span (the event rows and the step's call, which
returns before the device is done), over the traced micro-batches."""
from benchlib.served_spans import mean_ms


def read(run):
    return mean_ms(run, "serve.step")
