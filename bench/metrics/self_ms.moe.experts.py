"""Device self time per train step, in ms, of the ops in scope ``moe.experts``:
the expert GEMMs (bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "moe.experts")
