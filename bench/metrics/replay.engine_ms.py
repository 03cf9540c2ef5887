"""Mean time of one engine call, by the benchmark's span around it: the
jitted replay ended by ``block_until_ready`` (``bench.replay``), or one
whole vmapped sweep (``bench.sweep``, trace upload included)."""


def read(run):
    durs = [e - b for n, b, e in run.spans
            if n in ("bench.replay", "bench.sweep")]
    return sum(durs) / len(durs) * 1e3 if durs else None
