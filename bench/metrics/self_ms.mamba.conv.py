"""Device self time per train step, in ms, of the ops in scope ``mamba.conv``:
the causal depthwise conv (bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "mamba.conv")
