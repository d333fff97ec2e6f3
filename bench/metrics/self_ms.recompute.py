"""Device self time per train step, in ms, of every op that recomputes the
forward for the backward (JAX's remat marker); each also counts in its layer
(bench/scopes.py). Layer: model step."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "recompute")
