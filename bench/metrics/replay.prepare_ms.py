"""Mean host time of the program's ``replay.prepare`` span over the
window's replays: ``make_replay``'s statics, compiled-function lookup and
trace upload (``core/batched.py``).  A program without the span gives
nothing."""


def read(run):
    durs = [s["dur_s"] for s in run.program_spans
            if s["name"] == "replay.prepare"]
    return sum(durs) / len(durs) * 1e3 if durs else None
