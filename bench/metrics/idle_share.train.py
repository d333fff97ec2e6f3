"""Percent of the traced train window in which no operation ran on the
device (mean over chips). Layer: drivers (the loop of launch/train.py)."""


def read(ctx):
    if "train_step" not in ctx["work"]:
        return None
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
