"""Device busy seconds per traced simulation: the union of device-op
intervals in the profiler trace (``bench/trace_reduce.py``)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["n_sims"] == 0 or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] / tr["n_sims"]
