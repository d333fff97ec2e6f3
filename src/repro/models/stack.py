"""Pattern-scanned layer stack.

Every assigned architecture is a repeated *period* of heterogeneous layer
slots (dense: ``[attn]``; jamba: ``[attn, mamba×7]`` with MoE on odd
slots; vision: ``[self×4, cross]``). Per-slot parameters are stacked with
a leading ``repeats`` axis and the whole stack runs under ``jax.lax.scan``
— one traced period regardless of depth (fast compiles, small HLO) and a
natural remat boundary.

Caches (KV / SSM / media-KV) are threaded through the same scan as
``xs``/``ys`` so train, prefill and decode share one code path.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import layers

Params = dict[str, Any]


def init_stack(key, cfg) -> Params:
    """Stacked per-slot params: each leaf has leading dim R = repeats."""
    R = cfg.repeats

    def init_one_repeat(k):
        slot_params = []
        for si, (kind, ffn) in enumerate(cfg.pattern):
            k, k1, k2, k3, k4 = jax.random.split(k, 5)
            p: Params = {"ln1": layers.rms_weight(cfg.d_model, cfg.param_dtype)}
            if kind == "attn":
                p["mix"] = layers.init_attention(k1, cfg)
            elif kind == "mamba":
                p["mix"] = layers.init_mamba(k1, cfg)
            elif kind == "cross":
                p["mix"] = layers.init_cross_attention(k1, cfg)
            else:
                raise ValueError(f"unknown slot kind {kind!r}")
            if ffn == "moe":
                p["ln2"] = layers.rms_weight(cfg.d_model, cfg.param_dtype)
                p["ffn"] = layers.init_moe(k2, cfg)
            elif ffn == "mlp":
                p["ln2"] = layers.rms_weight(cfg.d_model, cfg.param_dtype)
                p["ffn"] = layers.init_mlp(k3, cfg)
            elif ffn != "none":
                raise ValueError(f"unknown ffn kind {ffn!r}")
            slot_params.append(p)
        return slot_params

    keys = jax.random.split(key, R)
    return jax.vmap(init_one_repeat)(keys)


def init_caches(cfg, batch: int, max_len: int, dtype):
    """Stacked caches per slot (leading dim R); None for stateless slots."""
    R = cfg.repeats
    slots = []
    for kind, _ in cfg.pattern:
        if kind == "attn":
            c = layers.attn_cache_init(cfg, batch, max_len, dtype)
            c.pop("length")
            slots.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (R,) + x.shape), c))
        elif kind == "mamba":
            c = layers.mamba_cache_init(cfg, batch, dtype)
            slots.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (R,) + x.shape), c))
        elif kind == "cross":
            stored = cfg.num_kv_heads * cfg.kv_repeat
            c = dict(
                k=jnp.zeros((batch, cfg.num_media_tokens, stored,
                             cfg.head_dim), dtype),
                v=jnp.zeros((batch, cfg.num_media_tokens, stored,
                             cfg.head_dim), dtype),
            )
            slots.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (R,) + x.shape), c))
        else:
            slots.append(None)
    return dict(length=jnp.zeros((), jnp.int32), slots=slots)


def apply_stack(params, cfg, x, *, positions, media=None, caches=None,
                steal_table=None, mode: str = "train"):
    """Run the stack. mode: 'train' (no caches) | 'prefill' (fill caches)
    | 'decode' (read + update caches). Returns (x, new_caches, aux)."""
    if mode == "train":
        caches = None
    length = caches["length"] if caches is not None else None

    def make_slot_fn(si, kind, ffn):
        def slot_fn(h, p, c):
            with jax.named_scope("norm"):
                hin = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
            if kind == "attn":
                cc = dict(c, length=length) if c is not None else None
                y, nc = layers.attention(hin, p["mix"], cfg,
                                         positions=positions, cache=cc,
                                         causal=not cfg.is_encoder)
                if nc is not None:
                    nc.pop("length")
            elif kind == "mamba":
                y, nc = layers.mamba(hin, p["mix"], cfg, cache=c)
            elif kind == "cross":
                # prefill projects media into the cache; decode reuses it.
                y, nc = layers.cross_attention(
                    hin, p["mix"], cfg, media=media,
                    cache=c if mode == "decode" else None)
                if caches is None:
                    nc = None
            else:
                raise ValueError(kind)
            h = h + y
            aux = jnp.zeros((), jnp.float32)
            if ffn != "none":
                with jax.named_scope("norm"):
                    hin = layers.rmsnorm(h, p["ln2"], cfg.norm_eps)
                if ffn == "moe":
                    y, aux = layers.moe(hin, p["ffn"], cfg, steal_table)
                else:
                    y = layers.mlp(hin, p["ffn"])
                h = h + y
            return h, nc, aux
        if cfg.remat != "none" and mode == "train" and len(cfg.pattern) > 1:
            # nested remat (multi-slot periods only): the period checkpoint
            # bounds what the scan saves; the per-slot checkpoint bounds
            # the *backward* live set to one slot's internals at a time.
            policy = (jax.checkpoint_policies.nothing_saveable
                      if cfg.remat == "full" else
                      jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            return jax.checkpoint(slot_fn, policy=policy, prevent_cse=False)
        return slot_fn

    slot_fns = [make_slot_fn(si, kind, ffn)
                for si, (kind, ffn) in enumerate(cfg.pattern)]

    def period_body(carry, xs):
        h, aux = carry
        slot_params, slot_caches = xs
        new_slot_caches = []
        for si in range(len(cfg.pattern)):
            p = slot_params[si]
            if cfg.serialize_slot_gathers and si > 0:
                # gate this slot's weight reads on the running activation:
                # FSDP gathers then happen at use, not all at period top.
                p = jax.tree.map(
                    lambda w: jax.lax.optimization_barrier((w, h))[0], p)
            c = slot_caches[si] if slot_caches is not None else None
            h, nc, a = slot_fns[si](h, p, c)
            aux = aux + a
            new_slot_caches.append(nc)
        return (h, aux), new_slot_caches

    body = period_body
    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat == "full"
                  else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        body = jax.checkpoint(period_body, policy=policy,
                              prevent_cse=False)

    slot_caches_xs = caches["slots"] if caches is not None else \
        [None for _ in cfg.pattern]
    (x, aux), new_slots = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params, slot_caches_xs))
    new_caches = None
    if caches is not None:
        new_caches = dict(length=length + x.shape[1], slots=new_slots)
    return x, new_caches, aux
