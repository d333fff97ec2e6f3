"""Device self time per train step, in ms, of the ops in scope ``moe.combine``:
the einsum from the experts back to the tokens (bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "moe.combine")
