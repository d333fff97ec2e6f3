"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: kernels must match them (see
tests/test_kernels.py sweeps), and training uses them for backward passes
(ops.py wires kernels forward + ref-VJP backward).

Layouts:
  attention  — BSHD: q (B, S, Hq, D), k/v (B, S, Hkv, D), GQA via repeat.
  ssd        — x (B, S, H, P), a (B, S, H) log-decay, B/C (B, S, G, N).
  moe_gmm    — x (E, C, D), w (E, D, F).
  rmsnorm    — x (..., D), w (D,).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["rmsnorm_ref", "attention_ref", "attention_chunked_ref",
           "ssd_ref", "ssd_chunked_ref", "moe_gmm_ref"]


def rmsnorm_ref(x: jnp.ndarray, w: jnp.ndarray,
                eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True,
                  scale: float | None = None,
                  window: int | None = None,
                  kv_offset: int = 0) -> jnp.ndarray:
    """Multi-head attention with GQA, causal/bidirectional, sliding window.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) with Hq % Hkv == 0.
    kv_offset: absolute position of q[0] minus that of k[0] (decode: the
    query sits at position ``kv_offset`` within the cache).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None] + kv_offset
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_chunked_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          causal: bool = True,
                          scale: float | None = None,
                          window: int | None = None,
                          kv_offset: int = 0,
                          chunk: int = 1024) -> jnp.ndarray:
    """Memory-bounded attention: scan over query chunks.

    Same semantics as :func:`attention_ref`, but the (Sq × Skv) score
    matrix never materializes beyond one (chunk × Skv) f32 slab — the
    long-sequence prefill path (32k/500k cells) on any backend.
    """
    B, S, H, D = q.shape
    if S % chunk:
        return attention_ref(q, k, v, causal=causal, scale=scale,
                             window=window, kv_offset=kv_offset)
    nc = S // chunk
    qs = jnp.moveaxis(q.reshape(B, nc, chunk, H, D), 1, 0)

    def f(_, inp):
        i, qc = inp
        o = attention_ref(qc, k, v, causal=causal, scale=scale,
                          window=window, kv_offset=kv_offset + i * chunk)
        return None, o

    _, outs = jax.lax.scan(f, None, (jnp.arange(nc), qs))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, D)


def ssd_ref(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
            h0: jnp.ndarray | None = None,
            return_state: bool = False):
    """Mamba2 SSD (state-space dual) semantics via the sequential scan.

    x: (B, S, H, P) inputs (already multiplied by dt).
    a: (B, S, H) per-head log decay (a = -exp(A_log)·dt, ≤ 0).
    b, c: (B, S, G, N) input/output projections, G groups (H % G == 0).
    h0: optional initial state (B, H, N, P).

    h_t = exp(a_t)·h_{t-1} + B_t ⊗ x_t ;  y_t = C_t · h_t
    """
    B, S, H, P = x.shape
    _, _, G, N = b.shape
    if H % G:
        raise ValueError(f"H={H} not a multiple of G={G}")
    rep = H // G
    bb = jnp.repeat(b, rep, axis=2).astype(jnp.float32)   # (B,S,H,N)
    cc = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    af = a.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    def step(h, inp):
        xt, at, bt, ct = inp          # (B,H,P), (B,H), (B,H,N), (B,H,N)
        h = jnp.exp(at)[..., None, None] * h + bt[..., None] * xt[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", ct, h)
        return h, y

    hT, ys = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (xf.transpose(1, 0, 2, 3), af.transpose(1, 0, 2),
         bb.transpose(1, 0, 2, 3), cc.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3).astype(x.dtype)          # (B,S,H,P)
    if return_state:
        return y, hT
    return y


def moe_gmm_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Grouped expert GEMM: x (E, C, D) @ w (E, D, F) -> (E, C, F)."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def ssd_chunked_ref(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                    c: jnp.ndarray,
                    h0: jnp.ndarray | None = None,
                    chunk: int = 128,
                    return_state: bool = False):
    """Chunked (dual-form) SSD — same semantics as :func:`ssd_ref`, but
    MXU-shaped, with every chunk computed at once (the chunk-parallel form
    of arXiv:2405.21060 §6). This is the training/prefill path of the
    Mamba2 layers (the sequential scan would put S serialized steps in the
    HLO). With L = chunk, nc = S / L and R = H / G heads a group, each group
    joins the batch axis (B·G), so B and C are never repeated to the heads:

      diagonal blocks  C·Bᵀ once per group (B·G, nc, L, L), times the
                       masked exp(segsum) decay of each head, laid out
                       heads-major (B·G, nc, R, L, L), contracted with x;
      chunk states     Bᵀ·(exp(a_tot − acum) ⊙ x), (B·G, nc, R, N, P);
      inter-chunk      the nc + 1 states (h0 first) mixed by the masked
                       exp(segsum) of the chunk totals: the state entering
                       each chunk, and hT;
      off-diagonal     exp(acum) ⊙ C·h_in.

    Every tensor ``ssd_ref`` keeps in f32 stays f32; the inter-chunk mix
    runs at full f32 matmul precision, as the recurrence it replaces. (A
    separate group axis would have size 1 in the common G = 1 case, and
    XLA's CPU compiler drops the named scope of a dot with a batch axis of
    size 1.)"""
    B, S, H, P = x.shape
    _, _, G, N = b.shape
    if S % chunk or S == 0:
        return ssd_ref(x, a, b, c, h0=h0, return_state=return_state)
    if H % G:
        raise ValueError(f"H={H} not a multiple of G={G}")
    R, L, nc = H // G, chunk, S // chunk
    f32 = jnp.float32

    def by_group(t):
        """(B, S, G·k…) -> (B·G, nc, L, k…): each group joins the batch."""
        t = jnp.moveaxis(t.reshape(B, S, G, -1), 2, 1)
        return t.reshape((B * G, nc, L) + t.shape[3:]).astype(f32)

    xf = by_group(x.reshape(B, S, H * P)).reshape(B * G, nc, L, R, P)
    bf, cf = by_group(b), by_group(c)          # (BG,nc,L,N)
    acum = jnp.cumsum(jnp.moveaxis(by_group(a), 2, -1), axis=-1)
    a_tot = acum[..., -1]                      # (BG,nc,R); acum (BG,nc,R,L)

    def masked_exp_segsum(cum):
        """exp(cum[i] − cum[j]) for i ≥ j, else 0, over the last axis.

        Mask BEFORE exp: the upper triangle holds positive values whose
        exp overflows; inf·0 in the backward would produce NaN grads."""
        n = cum.shape[-1]
        tri = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        return jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :],
                                 -jnp.inf))

    # diagonal blocks: C·Bᵀ per group, decay per head (BG,nc,R,L,L)
    cb = jnp.einsum("bcln,bcmn->bclm", cf, bf)
    scores = cb[:, :, None] * masked_exp_segsum(acum)
    y = jnp.moveaxis(jnp.einsum("bcmrp,bcrlm->bcrpl", xf, scores), -1, 2)

    # each chunk's own state, from zero
    decay_in = jnp.moveaxis(jnp.exp(a_tot[..., None] - acum), -1, 2)
    states = jnp.einsum("bcln,bclrp->bcrnp", bf, decay_in[..., None] * xf)

    # inter-chunk: h_all[z] is the state entering chunk z; h_all[nc] is hT
    h_init = (jnp.zeros((B, H, N, P), f32) if h0 is None
              else h0.astype(f32)).reshape(B * G, 1, R, N, P)
    states = jnp.concatenate([h_init, states], axis=1)
    cum_tot = jnp.cumsum(jnp.pad(jnp.moveaxis(a_tot, 1, -1),
                                 [(0, 0), (0, 0), (1, 0)]), axis=-1)
    h_all = jnp.einsum("brzc,bcrnp->bzrnp", masked_exp_segsum(cum_tot),
                       states, precision=jax.lax.Precision.HIGHEST)

    # off-diagonal: what the entering state contributes inside each chunk
    y = y + jnp.moveaxis(jnp.exp(acum), -1, 2)[..., None] * jnp.moveaxis(
        jnp.einsum("bcrnp,bcln->bcrpl", h_all[:, :nc], cf), -1, 2)
    y = jnp.moveaxis(y.reshape(B, G, S, R, P), 1, 2)
    y = y.reshape(B, S, H, P).astype(x.dtype)
    if return_state:
        return y, h_all[:, nc].reshape(B, H, N, P)
    return y
