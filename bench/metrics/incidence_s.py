"""Routing-engine incidence extraction: mean seconds per simulation of
the harness's host span around ``flow_incidence``, over the window."""


def read(ctx):
    spans = [s["incidence_s"] for s in ctx["sims"]]
    return sum(spans) / len(spans) if spans else None
