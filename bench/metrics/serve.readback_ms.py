"""Mean read-back of a served micro-batch: the program's
``serve.readback`` span around ``jax.device_get`` of the step's rows,
which waits for the device to finish the step and then copies, over the
traced micro-batches."""
from benchlib.served_spans import mean_ms


def read(run):
    return mean_ms(run, "serve.readback")
