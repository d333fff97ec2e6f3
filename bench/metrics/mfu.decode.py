"""Roofline share of the decode steps, in percent: the least time the chip
could take for each step (the larger of its operations over peak FLOP/s
and the bytes it must move, weights and live cache or state, over peak
bandwidth; bench/flops.py), summed, over the steps' device time. A
roofline share, not a FLOP share: decode is bound by bytes. Layer: model
step (model.decode_step)."""

from bench.metrics._common import matched


def read(ctx):
    got = matched(ctx["trace"], "bench_decode", ctx["work"].get("decode"))
    if got is None:
        return None
    secs, works = got
    pk = ctx["peaks"]
    least = sum(max(w.flops / pk["flops_per_s"],
                    w.bytes / pk["hbm_bytes_per_s"]) for w in works)
    return 100.0 * least / secs.sum()
