"""Share of the traced window in which no op ran on the device, in the
served cell: 100 x (1 - busy union / window), from the profiler trace."""


def read(run):
    r = run.reduced
    if not r or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
