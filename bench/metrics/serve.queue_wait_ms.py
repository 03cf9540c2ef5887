"""Mean queue wait of a served arrival: from its ``submit`` to the start
of the pop that took it, summed by the program's ``serve.pop`` span
(``wait_sum_s``) and divided by the arrivals popped, over the traced
micro-batches.  The served driver submits each due request just before
it drains, so in ``serve-openb-grmu`` this is the wait inside one loop
turn; the wait for the batch in flight passes before ``submit`` and is
not in it."""
from benchlib.served_spans import pop_total


def read(run):
    arrivals = pop_total(run, "arrivals")
    if not arrivals:
        return None
    return pop_total(run, "wait_sum_s") / arrivals * 1e3
