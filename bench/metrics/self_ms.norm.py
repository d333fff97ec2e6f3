"""Device self time per train step, in ms, of the ops in scope ``norm``: the
pre-norms of each layer (bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "norm")
