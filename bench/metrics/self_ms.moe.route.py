"""Device self time per train step, in ms, of the ops in scope ``moe.route``:
the router matmul and the top-k choice with its nearest-victim
steals (bench/scopes.py). Layer: routing."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "moe.route")
