"""Device self time per train step, in ms, of ops in no scope: the layer scan's
own bookkeeping, the residual adds, copies (bench/scopes.py). Layer: model
step."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "unscoped")
