"""Device self time per train step, in ms, of the ops in scopes ``head`` and
``embed``: both ends of the tied embedding table: the token lookup, and the
final norm, head matmul and loss (bench/scopes.py). Layer: model step."""

from bench.scopes import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "head", "embed")
