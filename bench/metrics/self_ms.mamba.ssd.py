"""Device self time per train step, in ms, of the ops in scope ``mamba.ssd``:
the SSD scan: dt and a, the chunked scan (or its kernel) and the D skip term
(bench/scopes.py). Layer: layers."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "mamba.ssd")
