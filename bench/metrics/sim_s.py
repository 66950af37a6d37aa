"""Seconds per whole simulation: the window's elapsed host time over the
simulations completed in it (the window ends on a whole cycle of the
mix's load levels)."""


def read(ctx):
    return ctx["window_s"] / ctx["n_sims"] if ctx["n_sims"] else None
