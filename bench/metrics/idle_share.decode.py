"""Percent of the decode loops' time (host span "decode") in which no
operation ran on the device. Layer: drivers (the decode loop)."""

from bench.metrics._common import idle_share_in


def read(ctx):
    if "decode" not in ctx["work"]:
        return None
    return idle_share_in(ctx["trace"], "decode")
