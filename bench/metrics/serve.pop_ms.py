"""Mean host time of a served micro-batch's assembly: the program's
``serve.pop`` span (queue pops and the step's rows built in numpy), over
the traced micro-batches."""
from benchlib.served_spans import mean_ms


def read(run):
    return mean_ms(run, "serve.pop")
