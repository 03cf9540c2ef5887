"""The (GPU, slot) tests that one arrival's ``policy_core.fit_mask``
makes over the fleet: padded GPUs times the sum of each model's slot
count, the ``slot_tests`` counter of the program's ``replay.prepare``
span (``core/batched.py`` ``make_replay``).  Every replay of the window
runs the same trace, so the first span's counter is read.  A program
without the span gives nothing."""


def read(run):
    for s in run.program_spans:
        if s["name"] == "replay.prepare":
            return s["slot_tests"]
    return None
