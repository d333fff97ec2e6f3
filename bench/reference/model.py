"""Plain float32 reference of the benchmark's models: the primitives the
model kinds (``bench/kinds/``) build their layers from.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, written from the
published descriptions and this benchmark's configuration files. It
imports nothing of the program. It reads the weight tree that
``bench/weights.py`` draws from the seed, by the leaves' names.

- GQA attention with RoPE (pairs d, d + Dh/2), causal, scale Dh**-0.5.
- RMSNorm.
- MoE: softmax router, top-k, capacity fill in token order (all tokens'
  first choices, then their second, ...) and, for a pair that finds its
  expert full, up to ``steal_attempts`` further tries along the steal
  table (taken as data), then renormalised weights over what was placed;
  SwiGLU experts. Tokens are routed in the groups the served or trained
  call forms: a call's B*S tokens in groups of min(group, B*S), and each
  decode step's B tokens as one group.
- Mamba2: in projection, causal depthwise conv with SiLU, the SSD
  recurrence h_t = exp(a_t) h_{t-1} + B_t x_t dt_t, y_t = C_t h_t + D x_t,
  gated RMSNorm, out projection. The recurrence runs step by step.
- The head: the tied embedding, or an untied ``lm_head``.

``Numerics`` decides how matrix products are computed: float32 at the
highest precision, or (the control) with both operands rounded to fp8
e4m3 with a per-tensor scale.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Numerics:
    fp8: bool = False

    def q(self, a):
        a = a.astype(jnp.float32)
        if not self.fp8:
            return a
        scale = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX)
        r = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return a + jax.lax.stop_gradient(r - a)

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HIGHEST)


@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes of one model, read from a configuration file by its kind's
    ``spec_from_file``; a kind may subclass it with sizes of its own."""
    layers: int
    D: int
    V: int
    eps: float
    H: int = 0
    Hkv: int = 0
    Dh: int = 0
    theta: float = 0.0
    E: int = 0
    K: int = 0
    F: int = 0
    capacity_factor: float = 0.0
    group: int = 0
    steal_attempts: int = 0
    d_inner: int = 0
    N: int = 0
    P: int = 0
    G: int = 0
    conv: int = 0
    aux_weight: float = 0.0
    z_weight: float = 0.0


def ring_table(E: int) -> np.ndarray:
    """Steal order (e+1, e+2, ...) mod E: what a router given no table
    walks."""
    return np.stack([(e + np.arange(1, E)) % E for e in range(E)])


def torus_table(E: int) -> np.ndarray:
    """Steal order when expert e lives on chip e of a ring of E chips:
    the others by hop distance min(|i-j|, E-|i-j|), ties by lower id."""
    rows = []
    for e in range(E):
        others = [x for x in range(E) if x != e]
        rows.append(sorted(others, key=lambda x: (min(abs(x - e),
                                                      E - abs(x - e)), x)))
    return np.asarray(rows)


def capacity(spec: Spec, group: int) -> int:
    c = int(np.ceil(group * spec.K * spec.capacity_factor / spec.E))
    return max(c, spec.K)


# ----------------------------------------------------------------------

def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, theta):
    """x: (L, H, Dh) at positions 0..L-1."""
    L, _, Dh = x.shape
    half = Dh // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(L, dtype=np.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, p, spec: Spec, num: Numerics):
    """Causal GQA over each row of h (B, L, D), one row at a time."""
    H, Hkv, Dh = spec.H, spec.Hkv, spec.Dh

    def row(x):
        L = x.shape[0]
        q = num.ein("ld,de->le", x, p["wq"]).reshape(L, H, Dh)
        k = num.ein("ld,de->le", x, p["wk"]).reshape(L, Hkv, Dh)
        v = num.ein("ld,de->le", x, p["wv"]).reshape(L, Hkv, Dh)
        q, k = rope(q, spec.theta), rope(k, spec.theta)
        k = jnp.repeat(k, H // Hkv, axis=1)   # q head i reads kv head i // (H/Hkv)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = num.ein("qhd,khd->hqk", q, k) * Dh ** -0.5
        mask = np.tril(np.ones((L, L), bool))
        s = jnp.where(mask, s, -jnp.inf)
        o = num.ein("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return num.ein("le,ed->ld", o.reshape(L, H * Dh), p["wo"])

    return jax.lax.map(row, h)


def route(logits, spec: Spec, cap: int, table):
    """One group. logits (G, E) f32 -> combine weights (G, E), aux loss."""
    G, E, K = logits.shape[0], spec.E, spec.K
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, K)
    aux = E * jnp.sum(jnp.mean(jax.nn.one_hot(top_e[:, 0], E), 0)
                      * jnp.mean(probs, 0))
    table = jnp.asarray(table, jnp.int32)
    choice = top_e.T.reshape(-1)              # pair j = k * G + t
    active = jnp.ones(K * G, bool)
    expert = jnp.full(K * G, -1, jnp.int32)
    used = jnp.zeros(E, jnp.int32)
    for attempt in range(spec.steal_attempts + 1):
        want = jax.nn.one_hot(choice, E, dtype=jnp.int32) * active[:, None]
        earlier = jnp.cumsum(want, 0) - want
        pos = earlier[jnp.arange(K * G), choice] + used[choice]
        placed = active & (pos < cap)
        expert = jnp.where(placed, choice, expert)
        used = jnp.minimum(used + want.sum(0), cap)
        active = active & ~placed
        if attempt < spec.steal_attempts:
            choice = table[choice, attempt]
    expert = expert.reshape(K, G).T
    w = top_p * (expert >= 0)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    comb = jnp.einsum("tk,tke->te", w, jax.nn.one_hot(expert, E),
                      precision=HIGHEST)
    return comb, aux


def moe(h, p, spec: Spec, num: Numerics, prompt_len: int, table):
    """h (B, L, D). The first ``prompt_len`` positions were one call (routed
    in groups of min(group, B*prompt_len)); each later position was one
    decode step (its B tokens one group)."""
    B, L, D = h.shape
    router = lambda x: num.ein("...d,de->...e", x, p["router"])
    T0 = B * prompt_len
    G = min(spec.group, T0)
    xp = h[:, :prompt_len].reshape(T0 // G, G, D)
    comb_p, aux = jax.vmap(lambda x: route(router(x), spec,
                                           capacity(spec, G), table))(xp)
    comb = comb_p.reshape(B, prompt_len, spec.E)
    if L > prompt_len:
        xt = jnp.swapaxes(h[:, prompt_len:], 0, 1)       # (L - P, B, D)
        comb_t, _ = jax.vmap(lambda x: route(router(x), spec,
                                             capacity(spec, B), table))(xt)
        comb = jnp.concatenate([comb, jnp.swapaxes(comb_t, 0, 1)], 1)

    def experts(args):
        x, c = args                                       # (n, D), (n, E)
        g = num.ein("nd,edf->nef", x, p["wg"])
        u = num.ein("nd,edf->nef", x, p["wu"])
        y = num.ein("nef,efd->ned", jax.nn.silu(g) * u, p["wd"])
        return jnp.einsum("ne,ned->nd", c, y, precision=HIGHEST)

    T, n = B * L, 512
    pad = (-T) % n
    xs = jnp.pad(h.reshape(T, D), [(0, pad), (0, 0)])
    cs = jnp.pad(comb.reshape(T, spec.E), [(0, pad), (0, 0)])
    y = jax.lax.map(experts, (xs.reshape(-1, n, D),
                              cs.reshape(-1, n, spec.E)))
    return y.reshape(-1, D)[:T].reshape(B, L, D), jnp.mean(aux)


def ssd(xdt, a, b, c, block: int = 64):
    """Sequential SSD recurrence. xdt (B,L,H,P), a (B,L,H), b, c (B,L,H,N)
    -> y (B,L,H,P). Blocks of steps are recomputed in the backward pass."""
    Bn, L, H, P = xdt.shape
    N = b.shape[-1]
    pad = (-L) % block
    padf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
    # zero padding leaves the state unchanged: exp(0) = 1 and b x = 0
    seq = [jnp.moveaxis(padf(t), 1, 0) for t in (xdt, a, b, c)]
    seq = [t.reshape((-1, block) + t.shape[1:]) for t in seq]

    def step(h, inp):
        xt, at, bt, ct = inp
        h = jnp.exp(at)[..., None, None] * h + bt[..., :, None] * xt[..., None, :]
        return h, jnp.einsum("bhn,bhnp->bhp", ct, h, precision=HIGHEST)

    @jax.checkpoint
    def run_block(h, blk):
        return jax.lax.scan(step, h, blk)

    h0 = jnp.zeros((Bn, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(run_block, h0, tuple(seq))
    ys = ys.reshape((-1,) + ys.shape[2:])[:L]
    return jnp.moveaxis(ys, 0, 1)


def mamba(h, p, spec: Spec, num: Numerics):
    Bn, L, _ = h.shape
    di, G, N, P, K = spec.d_inner, spec.G, spec.N, spec.P, spec.conv
    H = di // P                    # SSD heads; spec.H counts attention heads
    proj = num.ein("bld,de->ble", h, p["in_proj"])
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * G * N], -1)
    w = p["conv_w"].astype(jnp.float32)
    xpad = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
    xbc = sum(xpad[:, i:i + L] * w[i] for i in range(K)) \
        + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    xs, bm, cm = jnp.split(xbc, [di, di + G * N], -1)
    xs = xs.reshape(Bn, L, H, P)
    rep = lambda t: jnp.repeat(t.reshape(Bn, L, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"]) * dt
    y = ssd(xs * dt[..., None], a, rep(bm), rep(cm))
    y = y + xs * p["D_skip"][:, None]
    y = rmsnorm(y.reshape(Bn, L, di) * jax.nn.silu(z), p["out_norm"], spec.eps)
    return num.ein("ble,ed->bld", y, p["out_proj"])


def tied_head(params):
    """The (V, D) output projection of a model whose head is its
    embedding."""
    return params["embed"]


def untied_head(params):
    """The (V, D) output projection of a model with its own ``lm_head``
    (stored (D, V))."""
    return params["lm_head"].T


def logits(x, params, spec: Spec, num: Numerics, head):
    h = rmsnorm(x, params["final_norm"], spec.eps)
    return num.ein("bld,vd->blv", h, head(params))


def slot_params(params, slot: int, i: int):
    """Slot ``slot`` of the layer period, in its ``i``-th repeat."""
    return jax.tree.map(lambda t: t[i], params["blocks"][slot])
