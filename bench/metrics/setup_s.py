"""Set-up seconds: from the harness's first statement to the first timed
simulation (imports, TPU start, compile-cache load, router build, the
mix's input pool and its warm-up simulations)."""


def read(ctx):
    return ctx["setup_s"]
