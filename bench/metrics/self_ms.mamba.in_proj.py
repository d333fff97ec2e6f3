"""Device self time per train step, in ms, of the ops in scope
``mamba.in_proj``: the Mamba2 in-projection and its split (bench/scopes.py).
Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "mamba.in_proj")
