"""Solver call (edge compression, transfer, jitted event loop, read-back,
finalize): mean seconds per simulation of the harness's host span around
``simulate_incidence``, over the window."""


def read(ctx):
    spans = [s["solve_s"] for s in ctx["sims"]]
    return sum(spans) / len(spans) if spans else None
