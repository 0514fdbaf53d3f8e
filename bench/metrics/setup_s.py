"""Seconds from process start to the first timed call: JAX and chip start,
the deployment built from the seed, programs compiled or loaded, and the
cell's own shapes warmed (one admit, or one job)."""


def read(run):
    return run.setup_s
