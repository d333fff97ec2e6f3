"""Device self time per train step, in ms, of the ops in scope ``mamba.out``:
the gate, the gated norm and the out-projection (bench/scopes.py). Layer:
layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "mamba.out")
