"""Model operations of the prefill calls over their device time times the
chip's peak, in percent. Layer: model step (model.prefill)."""

from bench.metrics._common import matched


def read(ctx):
    got = matched(ctx["trace"], "bench_prefill", ctx["work"].get("prefill"))
    if got is None:
        return None
    secs, works = got
    return 100.0 * sum(w.flops for w in works) / (
        secs.sum() * ctx["peaks"]["flops_per_s"])
