"""Device self time per train step, in ms, of the ops in scope ``moe.dispatch``:
the one-hots, the dispatch and combine weights and the
einsum to the experts' input (bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "moe.dispatch")
