"""Device self time per train step, in ms, of the ops in scope ``attention``:
the attention layer: q/k/v projections, RoPE, the scores and the
out-projection (bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "attention")
