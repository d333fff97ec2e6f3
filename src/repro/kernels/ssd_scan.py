"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel for TPU.

The SSD insight: the attention-free recurrence
    h_t = exp(a_t)·h_{t-1} + B_t ⊗ x_t ;   y_t = C_t·h_t
splits into (i) dense intra-chunk matmuls that run on the MXU and
(ii) a tiny inter-chunk state recurrence. TPU-native mapping:

  * grid = (B, H, S/L) with the chunk axis innermost — the sequential
    TPU grid carries the (N × P) chunk state in VMEM scratch, so the
    inter-chunk recurrence costs one multiply-add per chunk with no
    HBM traffic (the GPU version ping-pongs states through a separate
    kernel launch).
  * intra-chunk work is three MXU matmuls per chunk:
    (C·Bᵀ ⊙ decay) (L×L), its product with X (L×P), and the chunk-state
    update Bᵀ·(decay ⊙ X) (N×P). L defaults to 128 for MXU alignment.
  * the decay matrix uses the log-cumsum-exp trick in f32; per-head
    scalar decays (Mamba2) keep it rank-1 — exp(Acum_i − Acum_j). The
    in-chunk cumsum is a lower-triangular ones matmul (Mosaic has no
    cumsum lowering).
  * heads-major layouts — x (B, H, S, P), a (B, H, S, 1), b/c
    (B, G, S, N) — so every block's last two dims meet the TPU's (8, 128)
    tiling rule; ``ops.ssd_scan`` transposes from the public layout.

Oracle: :func:`repro.kernels.ref.ssd_ref` (sequential scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_kernel_call"]


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, state_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)          # (L, P)
    a = a_ref[...].astype(jnp.float32)          # (L, 1)
    b = b_ref[...].astype(jnp.float32)          # (L, N)
    c = c_ref[...].astype(jnp.float32)          # (L, N)

    # inclusive cumsum A_t = Σ_{s<=t} a_s, as a column and as a row
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    exact = jax.lax.Precision.HIGHEST
    acum = jax.lax.dot(tri.astype(jnp.float32), a,
                       precision=exact)                          # (L, 1)
    acum_row = jax.lax.dot_general(
        a, (row <= col).astype(jnp.float32),
        (((0,), (0,)), ((), ())), precision=exact)               # (1, L)
    a_tot = jnp.sum(a, axis=0, keepdims=True)                    # (1, 1)

    # --- carried-state contribution: y_inter[t] = exp(A_t)·C_t·h0
    h0 = state_ref[...]                         # (N, P)
    y_inter = jnp.exp(acum) * jax.lax.dot(c, h0)                 # (L, P)

    # --- intra-chunk (dual/attention-like) term, causal within the chunk:
    # scores[t, s] = (C_t·B_s)·exp(A_t − A_s) for s ≤ t
    logdecay = acum - acum_row                                   # (L, L)
    # mask before exp: upper-triangle logdecay is positive (overflow risk)
    decay = jnp.exp(jnp.where(tri, logdecay, -jnp.inf))
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ()))) * decay
    y = y_inter + jax.lax.dot(scores, x)
    y_ref[...] = y.astype(y_ref.dtype)

    # --- state update: h' = exp(A_tot)·h0 + Σ_s exp(A_tot − A_s)·B_s ⊗ x_s
    w = jnp.exp(a_tot - acum) * b                                # (L, N)
    state_ref[...] = jnp.exp(a_tot) * h0 + \
        jax.lax.dot_general(w, x, (((0,), (0,)), ((), ())))      # (N, P)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _emit_state():
        hout_ref[...] = state_ref[...]


def ssd_scan_kernel_call(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                         c: jnp.ndarray,
                         chunk: int = 128,
                         interpret: bool = False):
    """x: (B, H, S, P); a: (B, H, S, 1); b, c: (B, G, S, N).

    Returns (y, final_state): (B, H, S, P), (B, H, N, P) — with h0 = 0.
    """
    B, H, S, P = x.shape
    _, G, _, N = b.shape
    if H % G:
        raise ValueError(f"H={H} % G={G} != 0")
    rep = H // G
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")

    grid = (B, H, S // chunk)
    y, hT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        name="ssd_scan",
        out_shape=(jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
                   jax.ShapeDtypeStruct((B, H, N, P), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, P),
                         lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((None, None, chunk, 1),
                         lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((None, None, chunk, N),
                         lambda bb, h, ci: (bb, h // rep, ci, 0)),
            pl.BlockSpec((None, None, chunk, N),
                         lambda bb, h, ci: (bb, h // rep, ci, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, None, chunk, P),
                         lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((None, None, N, P),
                         lambda bb, h, ci: (bb, h, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, a, b, c)
    return y, hT
