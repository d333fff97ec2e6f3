"""Model building blocks: norms, RoPE, GQA/cross attention, SwiGLU MLP,
MoE with locality-aware routing, Mamba2 (SSD) mixer.

Conventions:
  * pure functions over param dicts (no module framework);
  * activations (B, S, D); attention BSHD; params created by init_* fns;
  * every mixer returns ``(y, new_cache)`` where cache is ``None`` for
    stateless training, so the same code path serves train / prefill /
    decode;
  * f32 for softmax/normalizer math, params/activations in cfg dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.routing import RoutingConfig, route, slot_maps
from repro.kernels import ref as kref

Params = dict[str, Any]


def _constrain(x, spec):
    """Apply a sharding constraint from a config-carried spec tuple.

    ``spec`` is a tuple of (axis-name | tuple | None) per dim, set by the
    launcher per mesh (None config field = no constraint). Requires an
    ambient mesh (jit under ``with mesh:``); no-op otherwise.
    """
    if spec is None:
        return x
    from jax.sharding import PartitionSpec as P
    import jax as _jax
    try:
        return _jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x  # no ambient mesh (single-device smoke paths)


# ----------------------------------------------------------------------
# init helpers
# ----------------------------------------------------------------------

def _dense_init(key, d_in, d_out, dtype, scale=None):
    scale = (1.0 / np.sqrt(d_in)) if scale is None else scale
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def rms_weight(d, dtype):
    return jnp.ones((d,), dtype)


# ----------------------------------------------------------------------
# norms / rope
# ----------------------------------------------------------------------

def rmsnorm(x, w, eps=1e-6):
    return kref.rmsnorm_ref(x, w, eps)


def rope(x, positions, theta):
    """x: (B, S, H, D); positions: (B, S). Rotates pairs (d, d + D/2)."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# attention (self, GQA, optional qk-norm / bias; cross variant)
# ----------------------------------------------------------------------

def init_attention(key, cfg) -> Params:
    ks = jax.random.split(key, 6)
    D, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    p = {
        "wq": _dense_init(ks[0], D, H * Dh, dt),
        "wk": _dense_init(ks[1], D, Hkv * Dh, dt),
        "wv": _dense_init(ks[2], D, Hkv * Dh, dt),
        "wo": _dense_init(ks[3], H * Dh, D, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), dt)
        p["bk"] = jnp.zeros((Hkv * Dh,), dt)
        p["bv"] = jnp.zeros((Hkv * Dh,), dt)
    if cfg.qk_norm:
        p["q_norm"] = rms_weight(Dh, dt)
        p["k_norm"] = rms_weight(Dh, dt)
    return p


@jax.named_scope("attention")
def attention(x, p, cfg, *, positions, cache=None, causal=True):
    """Self attention. cache: None | dict(k, v, length: scalar int32).

    Training/prefill: full-sequence q over full k/v (cache written if
    provided). Decode: S == 1 query against cache (k/v updated in place).
    """
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if cfg.kv_repeat > 1:
        # pre-replicate kv heads so stored heads divide the TP axis
        k = jnp.repeat(k, cfg.kv_repeat, axis=2)
        v = jnp.repeat(v, cfg.kv_repeat, axis=2)
    q = _constrain(q, cfg.attn_q_spec)

    new_cache = None
    if cache is None:
        kk, vv, kv_off = k, v, 0
        kk = _constrain(kk, cfg.attn_kv_spec)
        vv = _constrain(vv, cfg.attn_kv_spec)
    else:
        length = cache["length"]                      # scalar int32
        kk = jax.lax.dynamic_update_slice(cache["k"], k, (0, length, 0, 0))
        vv = jax.lax.dynamic_update_slice(cache["v"], v, (0, length, 0, 0))
        kk = _constrain(kk, cfg.attn_kv_spec)
        vv = _constrain(vv, cfg.attn_kv_spec)
        new_cache = dict(k=kk, v=vv, length=length + S)
        kv_off = length

    if S >= cfg.attn_chunk_threshold:
        # long prefill/training: bound the score slab to (chunk × Skv)
        out = kref.attention_chunked_ref(
            q, kk, vv, causal=causal or cache is not None,
            window=cfg.attn_window, kv_offset=kv_off, chunk=cfg.attn_chunk)
    else:
        # decode path masks positions ≥ length + S via the causal mask on
        # absolute positions (cache tail is zeros but masked out); with a
        # cache, q's absolute position is the previous length.
        out = kref.attention_ref(q, kk, vv, causal=causal or cache is not None,
                                 window=cfg.attn_window, kv_offset=kv_off)
    out = out.reshape(B, S, H * Dh) @ p["wo"]
    return out, new_cache


def init_cross_attention(key, cfg) -> Params:
    ks = jax.random.split(key, 5)
    D, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    return {
        "wq": _dense_init(ks[0], D, H * Dh, dt),
        "wk": _dense_init(ks[1], D, Hkv * Dh, dt),
        "wv": _dense_init(ks[2], D, Hkv * Dh, dt),
        "wo": _dense_init(ks[3], H * Dh, D, dt),
        "q_norm": rms_weight(Dh, dt),
        "k_norm": rms_weight(Dh, dt),
        "gate": jnp.zeros((1,), dt),     # llama3.2-vision gated cross-attn
    }


@jax.named_scope("attention")
def cross_attention(x, p, cfg, *, media, cache=None):
    """Cross attention onto media embeddings (B, M, D_model).

    cache: None | dict(k, v) of projected media (decode reuses them).
    """
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    q = _constrain(q, cfg.attn_q_spec)
    if cache is None:
        M = media.shape[1]
        k = (media @ p["wk"]).reshape(B, M, Hkv, Dh)
        v = (media @ p["wv"]).reshape(B, M, Hkv, Dh)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if cfg.kv_repeat > 1:
            k = jnp.repeat(k, cfg.kv_repeat, axis=2)
            v = jnp.repeat(v, cfg.kv_repeat, axis=2)
        new_cache = dict(k=k, v=v)
    else:
        k, v = cache["k"], cache["v"]
        new_cache = cache
    out = kref.attention_ref(q, k, v, causal=False)
    out = out.reshape(B, S, H * Dh) @ p["wo"]
    return jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out, \
        new_cache


# ----------------------------------------------------------------------
# MLP / MoE
# ----------------------------------------------------------------------

def init_mlp(key, cfg, d_ff=None) -> Params:
    ks = jax.random.split(key, 3)
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    dt = cfg.param_dtype
    return {
        "wg": _dense_init(ks[0], D, F, dt),
        "wu": _dense_init(ks[1], D, F, dt),
        "wd": _dense_init(ks[2], F, D, dt),
    }


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


@jax.named_scope("mlp")
def mlp(x, p):
    return _swiglu(x, p)


def init_moe(key, cfg) -> Params:
    ks = jax.random.split(key, 5)
    D, E = cfg.d_model, cfg.moe_num_experts
    F = cfg.moe_d_ff or cfg.d_ff
    dt = cfg.param_dtype
    p = {
        "router": _dense_init(ks[0], D, E, jnp.float32),
        "wg": (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(dt),
        "wu": (jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D)).astype(dt),
        "wd": (jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)).astype(dt),
    }
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.d_ff)
    return p


# Rows move between the token layout (g, T, D) and the slot layout, held
# expert-major as (E, g, C, D): the layout the batched expert GEMMs read
# and write, so no copy sits between them and the gathers. Each gather
# batches over the group axis g; an index past the end reads zeros.
_FILL = jax.lax.GatherScatterMode.FILL_OR_DROP


def _to_slots(x, pair):
    """out[e, g, c] = x[g, pair[e, g, c]]: x (g, N, ...), pair (E, g, C)."""
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(3, x.ndim + 1)), collapsed_slice_dims=(1,),
        start_index_map=(1,), operand_batching_dims=(0,),
        start_indices_batching_dims=(1,))
    return jax.lax.gather(x, pair[..., None], dn, (1, 1) + x.shape[2:],
                          mode=_FILL, fill_value=0)


def _to_pairs(y, slot):
    """out[g, t, k] = y[e, g, c] for slot[g, t, k] = e·C + c: y (E, g, C,
    ...), slot (g, T, K)."""
    C = y.shape[2]
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(3, y.ndim)), collapsed_slice_dims=(0, 2),
        start_index_map=(0, 2), operand_batching_dims=(1,),
        start_indices_batching_dims=(0,))
    idx = jnp.stack([slot // C, slot % C], axis=-1)
    return jax.lax.gather(y, idx, dn, (1, 1, 1) + y.shape[3:], mode=_FILL,
                          fill_value=0)


@jax.custom_vjp
def _dispatch(xg, pair, slot):
    """The experts' input (E, g, C, D): each slot's token row of ``xg``
    (g, T, D), zeros in an empty slot. ``pair`` (E, g, C) and ``slot``
    (g, T, K) are ``routing.slot_maps``' maps, expert-major."""
    return _to_slots(xg, pair // slot.shape[-1])


def _dispatch_fwd(xg, pair, slot):
    return _dispatch(xg, pair, slot), slot


def _dispatch_bwd(slot, dxin):
    # a slot holds at most one pair: a token's gradient gathers its slots
    with jax.named_scope("moe.dispatch"):
        rows = _to_pairs(dxin, slot)                          # (g,T,K,D)
        dxg = rows.astype(jnp.float32).sum(2).astype(dxin.dtype)
    return dxg, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(eout, weight, pair, slot):
    """y[g, t] = Σ_k weight[g, t, k] · eout[e, g, c], slot[g, t, k] =
    e·C + c, in f32. eout: (E, g, C, D); weight: (g, T, K) f32, 0 for a
    drop."""
    rows = _to_pairs(eout, slot).astype(jnp.float32)          # (g,T,K,D)
    return (rows * weight[..., None]).sum(2).astype(eout.dtype)


def _combine_fwd(eout, weight, pair, slot):
    return _combine(eout, weight, pair, slot), (eout, weight, pair, slot)


def _combine_bwd(res, dy):
    eout, weight, pair, slot = res
    g, T, K = weight.shape
    with jax.named_scope("moe.combine"):
        w_slot = _to_slots(weight.reshape(g, T * K), pair)    # (E,g,C)
        dy_slot = _to_slots(dy, pair // K).astype(jnp.float32)
        deout = (dy_slot * w_slot[..., None]).astype(eout.dtype)
        # the router's gradient: ⟨dy, eout⟩ per slot, read back per pair
        dw_slot = (dy_slot * eout.astype(jnp.float32)).sum(-1)
        dweight = _to_pairs(dw_slot, slot)
    return deout, dweight, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe(x, p, cfg, steal_table=None):
    """Mixture of experts over (B, S, D) with locality-aware overflow.

    Tokens are routed in groups of ``cfg.moe_group`` (GShard-style) so the
    expert slots stay bounded; the router's overflow re-routing walks
    the topology steal table (the paper's scheduler, see core/routing.py),
    or routing's own order when given none. Returns (y, aux_loss).
    """
    B, S, D = x.shape
    E = cfg.moe_num_experts
    T = B * S
    xf = x.reshape(T, D)
    G = min(cfg.moe_group, T)
    ngroups = T // G
    xg = xf.reshape(ngroups, G, D)
    xg = _constrain(xg, cfg.moe_group_spec)
    capacity = int(np.ceil(G * cfg.moe_top_k * cfg.capacity_factor / E))
    capacity = max(capacity, cfg.moe_top_k)
    rcfg = RoutingConfig(num_experts=E, top_k=cfg.moe_top_k,
                         capacity=capacity,
                         steal_attempts=cfg.moe_steal_attempts)

    def route_group(xg1):
        logits = xg1.astype(jnp.float32) @ p["router"]
        r = route(logits, rcfg, steal_table)
        return r["expert"], r["slot"], r["weight"], r["aux_loss"]

    # routing per group (small tensors) …
    with jax.named_scope("moe.route"):
        expert, slot, weight, aux = jax.vmap(route_group)(xg)
    # … but rows move between tokens and expert slots by index gathers
    # that keep the group dim explicit, so the sharding constraints apply
    # at the jit level (groups ride the DP axes, experts the model axis —
    # constraints under vmap are not reliably honored by GSPMD).
    with jax.named_scope("moe.dispatch"):
        pair_of_slot, slot_of_pair = slot_maps(expert, slot, E, capacity)
        pair = pair_of_slot.reshape(ngroups, E, capacity).swapaxes(0, 1)
        xin = _dispatch(xg, pair, slot_of_pair).swapaxes(0, 1)
        xin = _constrain(xin, cfg.moe_xin_spec)                # (g,E,C,D)
    with jax.named_scope("moe.experts"):
        h = jnp.einsum("gecd,edf->gecf", xin, p["wg"])
        u = jnp.einsum("gecd,edf->gecf", xin, p["wu"])
        h = jax.nn.silu(h) * u
        h = _constrain(h, cfg.moe_h_spec)
        eout = jnp.einsum("gecf,efd->gecd", h, p["wd"])
        eout = _constrain(eout, cfg.moe_xin_spec)
    with jax.named_scope("moe.combine"):
        y = _combine(eout.swapaxes(0, 1), weight, pair,
                     slot_of_pair).reshape(B, S, D)
        if cfg.moe_shared_expert:
            y = y + _swiglu(x, p["shared"])
    return y, jnp.mean(aux)


# ----------------------------------------------------------------------
# Mamba2 (SSD) mixer
# ----------------------------------------------------------------------

def init_mamba(key, cfg) -> Params:
    ks = jax.random.split(key, 6)
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    H = d_inner // cfg.ssm_head_dim
    G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    dt = cfg.param_dtype
    conv_dim = d_inner + 2 * G * N
    return {
        "in_proj": _dense_init(ks[0], D, 2 * d_inner + 2 * G * N + H, dt),
        "conv_w": (jax.random.normal(ks[1], (K, conv_dim)) * 0.1).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "out_norm": rms_weight(d_inner, dt),
        "out_proj": _dense_init(ks[2], d_inner, D, dt),
    }


def _mamba_split(cfg):
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    G, N = cfg.ssm_groups, cfg.ssm_state
    H = d_inner // cfg.ssm_head_dim
    return d_inner, G, N, H


def _causal_conv(xbc, w, b, conv_state=None):
    """Depthwise causal conv1d. xbc: (B, S, C); w: (K, C).

    conv_state: (B, K-1, C) previous inputs for decode; returns new state.
    """
    K = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((xbc.shape[0], K - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = conv_state
    full = jnp.concatenate([pad, xbc], axis=1)          # (B, S+K-1, C)
    out = sum(full[:, i:i + xbc.shape[1]] * w[i] for i in range(K)) + b
    new_state = full[:, -(K - 1):] if K > 1 else pad
    return jax.nn.silu(out), new_state


def mamba(x, p, cfg, cache=None):
    """Mamba2 block. cache: None | dict(conv, ssm) for decode.

    Training/prefill: chunked SSD. Decode (S == 1): single-step
    recurrence.
    """
    B, S, D = x.shape
    d_inner, G, N, H = _mamba_split(cfg)
    P = cfg.ssm_head_dim
    with jax.named_scope("mamba.in_proj"):
        proj = x @ p["in_proj"]
        z, xbc, dtp = jnp.split(
            proj, [d_inner, 2 * d_inner + 2 * G * N], axis=-1)
    with jax.named_scope("mamba.conv"):
        conv_state = cache["conv"] if cache is not None else None
        xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                     conv_state)
    with jax.named_scope("mamba.ssd"):
        xs, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + G * N], axis=-1)
        xs = xs.reshape(B, S, H, P)
        bmat = bmat.reshape(B, S, G, N)
        cmat = cmat.reshape(B, S, G, N)
        dt = jax.nn.softplus(dtp.astype(jnp.float32) + p["dt_bias"])
        a = -jnp.exp(p["A_log"])[None, None, :] * dt              # (B,S,H)
        x_dt = xs * dt[..., None].astype(xs.dtype)
        x_dt = _constrain(x_dt, cfg.ssm_act_spec)

        if cache is None:
            y = kref.ssd_chunked_ref(x_dt, a, bmat, cmat, chunk=cfg.ssm_chunk)
            new_cache = None
        elif S > 1:
            # chunked prefill with carried state
            h0 = cache["ssm"]                             # (B,H,N,P) f32
            y, hT = kref.ssd_chunked_ref(x_dt, a, bmat, cmat, h0=h0,
                                         chunk=cfg.ssm_chunk,
                                         return_state=True)
            new_cache = dict(conv=new_conv, ssm=hT)
        else:
            h0 = cache["ssm"]
            y, hT = kref.ssd_ref(x_dt, a, bmat, cmat, h0=h0,
                                 return_state=True)
            new_cache = dict(conv=new_conv, ssm=hT)
        y = y + xs * p["D_skip"][None, None, :, None].astype(xs.dtype)
    with jax.named_scope("mamba.out"):
        y = y.reshape(B, S, d_inner)
        y = rmsnorm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
        y = y @ p["out_proj"]
    return y, new_cache


def mamba_cache_init(cfg, batch, dtype):
    d_inner, G, N, H = _mamba_split(cfg)
    conv_dim = d_inner + 2 * G * N
    return dict(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        ssm=jnp.zeros((batch, H, N, cfg.ssm_head_dim), jnp.float32),
    )


def attn_cache_init(cfg, batch, max_len, dtype):
    stored = cfg.num_kv_heads * cfg.kv_repeat
    return dict(
        k=jnp.zeros((batch, max_len, stored, cfg.head_dim), dtype),
        v=jnp.zeros((batch, max_len, stored, cfg.head_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )
