"""Model operations of the train steps executed in the traced window
(forward and backward, no recompute; bench/flops.py) over the window
times the chip's peak, in percent. Layer: model step."""

from bench.metrics._common import module_seconds


def read(ctx):
    work = ctx["work"].get("train_step")
    if work is None:
        return None
    red = ctx["trace"]
    steps = len(module_seconds(red, "step_fn"))
    if not steps:
        return None
    return 100.0 * steps * work.flops / (red.window_s
                                         * ctx["peaks"]["flops_per_s"])
