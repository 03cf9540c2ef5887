"""Mean host time per served micro-batch outside the device round trip,
over the traced part of the window: the benchmark's ``bench.drain``
span around ``drain(max_batches=1)`` less the program's ``serve.batch``
span inside it (batch assembly from the queue, and turning rows into
``Decision`` objects).  The k-th drain holds the k-th batch."""


def read(run):
    drains = [e - b for n, b, e in run.spans
              if n == "bench.drain" and e <= run.trace_closed]
    batches = [s["dur_s"] for s in run.program_spans
               if s["name"] == "serve.batch"][:len(drains)]
    if not drains or len(drains) != len(batches):
        return None
    return (sum(drains) - sum(batches)) / len(drains) * 1e3
