"""Batched serving driver: prefill a prompt batch, decode with KV/SSM
caches, report latency/throughput.

The decode loop is the production shape (jit'd single-token step over a
static-capacity cache); batch composition is static per run (continuous
batching would swap finished rows — the cache layout already supports
per-row lengths via the shared ``length`` counter). Both programs are
compiled ahead of time, so the reported prefill and decode times hold no
compilation.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
        --reduced --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.jax_cache import use_persistent_compile_cache
from repro.models import model as model_lib


@dataclasses.dataclass(frozen=True)
class ServeResult:
    tokens: np.ndarray          # (B, gen) generated ids
    prefill_logits: np.ndarray  # (B, 1, V) f32 logits at the last prompt token
    compile_s: float            # prefill + decode compilation
    prefill_s: float            # host clock, to the first token
    decode_s_per_token: float   # host clock, mean over gen - 1 steps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")

    key = jax.random.PRNGKey(args.seed)
    params = model_lib.init_params(cfg, key)
    B, P = args.batch, args.prompt_len
    prompts = jax.random.randint(key, (B, P), 1, cfg.vocab_size)
    media = None
    if cfg.num_media_tokens:
        media = jax.random.normal(
            key, (B, cfg.num_media_tokens, cfg.d_model), cfg.param_dtype)

    max_len = P + args.gen

    @jax.jit
    def prefill_fn(params, tokens, media):
        return model_lib.prefill(params, cfg, tokens=tokens, media=media,
                                 max_len=max_len)

    @jax.jit
    def decode_fn(params, caches, tok):
        logits, caches = model_lib.decode_step(params, cfg, caches, tok)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], caches

    t0 = time.time()
    prefill_lo = prefill_fn.lower(params, prompts, media)
    prefill_c = prefill_lo.compile()
    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    decode_c = decode_fn.lower(params, prefill_lo.out_info[1],
                               tok_sds).compile()
    t_compile = time.time() - t0

    t0 = time.time()
    logits, caches = prefill_c(params, prompts, media)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        tok, caches = decode_c(params, caches, tok)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    per_tok = t_decode / max(args.gen - 1, 1)
    print(f"[serve] {cfg.name}: batch={B} prompt={P} gen={args.gen}")
    print(f"[serve] compile {t_compile:8.1f} s (prefill + decode)")
    print(f"[serve] prefill {t_prefill*1e3:8.1f} ms "
          f"({B*P/t_prefill:9.0f} tok/s)")
    print(f"[serve] decode  {per_tok*1e3:8.2f} ms/tok "
          f"({B/max(per_tok,1e-9):9.0f} tok/s)")
    print(f"[serve] sample row 0: {gen[0][:16].tolist()}")
    return ServeResult(gen, np.asarray(logits), t_compile, t_prefill, per_tok)


if __name__ == "__main__":
    use_persistent_compile_cache()
    main()
