"""Device self time per train step, in ms, of the ops in scope ``optimizer``:
AdamW with clipping (bench/scopes.py). Layer: model step."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "optimizer")
