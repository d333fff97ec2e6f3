"""Serve driver: ``model.prefill`` and ``model.decode_step`` compiled ahead
of time as ``launch/serve.py`` compiles them, greedy argmax inside each
program, closed loop: one batch in flight, the next submitted when it
finishes. Every step's tokens come to the host, as a streaming server's
must, and each arrival is timed.

No steal table is passed, so the router walks its ring fallback, as in
``launch/serve.py``. After the window the program's state is freed, and
the reference recomputes the logits of a sample of finished batches
drawn from the seed, the longest prompts among them.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import check, flops, harness, kinds, weights
from bench.harness import span
from bench.reference import run as ref_run
from bench.traffic import gen


def programs(cfg, params, batch: int, prompt_len: int, gen_len: int):
    """(prefill, decode) compiled for one prompt length (mirrors
    launch/serve.py:67-83, with the first token's argmax in prefill)."""
    from repro.models import model as model_lib

    max_len = prompt_len + gen_len

    def bench_prefill(params, tokens):
        logits, caches = model_lib.prefill(params, cfg, tokens=tokens,
                                           max_len=max_len)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return tok[:, None], caches

    def bench_decode(params, caches, tok):
        logits, caches = model_lib.decode_step(params, cfg, caches, tok)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], caches

    tok_sds = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    lo = jax.jit(bench_prefill).lower(params, tok_sds)
    one = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    dec = jax.jit(bench_decode).lower(params, lo.out_info[1], one)
    return lo.compile(), dec.compile()


def serve_batch(prefill, decode, params, prompts, gen_len: int):
    """One request batch; returns tokens (B, gen) and host arrival times."""
    times = []
    with span("prefill"):
        tok, caches = prefill(params, prompts)
        host = [np.asarray(tok)]
    times.append(time.perf_counter())
    with span("decode"):
        for _ in range(gen_len - 1):
            with span("dispatch"):
                tok, caches = decode(params, caches, tok)
            with span("token_fetch"):
                host.append(np.asarray(tok))
            times.append(time.perf_counter())
    return np.concatenate(host, 1), np.asarray(times)


def run(cfg, fcfg: dict, mix: dict, seed: int, seconds: float, traced: bool,
        limits: dict, cell: dict, t_start: float) -> dict:
    B, gen_len = mix["batch"], mix["gen"]
    kind = kinds.kind(fcfg)
    key = weights.seed_key(seed)
    init = weights.make_init(weights.layout(cfg), kind)
    params = init(key)
    progs = {P: programs(cfg, params, B, P, gen_len)
             for P in mix["prompt_lens"]}
    zipf = gen.Zipf(seed, cfg.vocab_size, mix["zipf_s"])
    for P, (pre, dec) in progs.items():          # first executions
        warm = zipf.draw(gen.rng(seed, 9, P), (B, P))
        serve_batch(pre, dec, params, warm, 2)

    setup_s = time.perf_counter() - t_start
    done = []                   # (P, prompts, tokens, t_submit, times)
    with harness.Window(seconds, traced, cell["name"]) as win:
        with span("bench.window"):
            i = 0
            while win.open():
                with span("next_batch"):
                    prompts = gen.serve_batch(mix, cfg.vocab_size, seed, i,
                                              zipf)
                pre, dec = progs[prompts.shape[1]]
                t_submit = time.perf_counter()
                tokens, times = serve_batch(pre, dec, params, prompts,
                                            gen_len)
                done.append((prompts.shape[1], prompts, tokens, t_submit,
                             times))
                i += 1
    memory_peak = harness.memory_peak_bytes()
    del params

    # the reference over a sample of finished batches, the longest in it
    spec = kind.spec_from_file(fcfg)
    table = kind.serve_table(spec)
    ref_params = init(key)
    gaps = []
    for j in sample(done, seed, mix["checked_batches"]):
        P, prompts, tokens = done[j][:3]
        seq = np.concatenate([prompts, tokens[:, :-1]], 1)
        ref = ref_run.serve_logits(ref_params, kind, spec, seq, P, table)
        gaps.append(check.token_gaps(ref, tokens).ravel())
    numbers = check.serve_numbers(np.concatenate(gaps))
    harness.write_json(f"{cell['name']}.{seed}.{int(traced)}.requests.json", [
        {"prompt_len": P, "ttft_s": t[0] - s, "tpot_s": (t[-1] - t[0]) / (len(t) - 1),
         "t_submit": s - win.t0, "tokens_in_window": int(np.sum(t <= win.deadline))}
        for P, _, _, s, t in done])
    ok, checks = check.judge(numbers, limits)
    if win.counter.events:
        ok = False
        checks["compiles_in_window"] = (len(win.counter.events), 0)

    result = {"correct": bool(ok), "attempted": B * len(done), "failed": 0,
              "setup_s": setup_s, "memory_peak": memory_peak,
              "checks": checks, "numbers": numbers}
    if traced:
        result["ctx"] = {"trace": win.reduced_trace(), "peaks": None,
                         "work": executions(kind, cfg, done, B, gen_len)}
        return result
    end = win.deadline
    ttft = [t[0] - s for _, _, _, s, t in done for _ in range(B)]
    tpot = [(t[-1] - t[0]) / (len(t) - 1) for _, _, _, _, t in done
            for _ in range(B)]
    in_window = sum(B * int(np.sum(t <= end)) for *_, t in done)
    result["metrics"] = {
        "ttft_p95_ms": {"value": 1e3 * float(np.percentile(ttft, 95)),
                        "unit": "ms"},
        "tpot_p95_ms": {"value": 1e3 * float(np.percentile(tpot, 95)),
                        "unit": "ms"},
        "serve_tokens_per_s": {"value": in_window / seconds,
                               "unit": "tokens/s"}}
    return result


def sample(done: list, seed: int, n: int) -> list[int]:
    """n finished batches drawn from the seed: one with the longest prompt
    first, then others, distinct prompt lengths before repeats."""
    g = gen.rng(seed, 4)
    order = list(g.permutation(len(done)))
    longest = max(P for P, *_ in done)
    first = next(j for j in order if done[j][0] == longest)
    picked, lens = [first], {longest}
    for j in order:
        if len(picked) == n:
            break
        if j not in picked and done[j][0] not in lens:
            picked.append(j)
            lens.add(done[j][0])
    for j in order:
        if len(picked) == n:
            break
        if j not in picked:
            picked.append(j)
    return picked


def executions(kind, cfg, done: list, B: int, gen_len: int) -> dict:
    """Model work of every prefill and decode call the window made."""
    pre, dec = [], []
    for P, *_ in done:
        pre.append(flops.prefill(kind, cfg, B, P))
        dec.extend(flops.decode_step(kind, cfg, B, P + j)
                   for j in range(gen_len - 1))
    return {"prefill": pre, "decode": dec}
