"""Public kernel entry points: standalone Pallas ops.

The model layers do not call these; they run the jnp code of ``ref.py``.
``tests/test_kernels.py`` holds each op to its ``ref.py`` oracle,
``tests/test_tpu_compile.py`` compiles them for a described v5e, and
``chip_smoke.py`` runs them on the chip.

Each op pairs a Pallas forward kernel with a backward pass derived from
the pure-jnp oracle (``jax.vjp`` of ref.py) via ``jax.custom_vjp`` — the
kernels stay usable under ``jax.grad`` everywhere. A dedicated attention
backward kernel would be an optimization, not a semantics change.

``interpret`` resolution: ``None`` → interpret unless running on TPU, so
the same code runs kernels natively on TPU and in interpret mode in CPU
CI.

Shapes that do not tile into a kernel's blocks run the oracle off the
chip; on a TPU they raise ``ValueError`` instead, so a chip run never
measures the oracle in place of the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_kernel_call
from .moe_gmm import moe_gmm_kernel_call
from .rmsnorm import rmsnorm_kernel_call
from .ssd_scan import ssd_scan_kernel_call

__all__ = ["rmsnorm", "flash_attention", "ssd_scan", "moe_gmm"]


def _resolve_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _heads_major(t):
    """(B, S, H, ...) <-> (B, H, S, ...): the kernels' block layout."""
    return jnp.swapaxes(t, 1, 2)


def _check_oracle_fallback(op: str, shape_desc: str):
    """The oracle may stand in for an untiled shape off the chip only."""
    if jax.default_backend() == "tpu":
        raise ValueError(f"{op}: {shape_desc} does not tile into the "
                         f"kernel's blocks; no oracle fallback on TPU")


# ----------------------------------------------------------------------
# rmsnorm
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x2d, w, eps, interpret):
    return rmsnorm_kernel_call(x2d, w, eps=eps, interpret=interpret)


def _rmsnorm_fwd(x2d, w, eps, interpret):
    return _rmsnorm(x2d, w, eps, interpret), (x2d, w)


def _rmsnorm_bwd(eps, interpret, res, g):
    x2d, w = res
    _, vjp = jax.vjp(lambda xx, ww: ref.rmsnorm_ref(xx, ww, eps), x2d, w)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6,
            interpret: bool | None = None) -> jnp.ndarray:
    """RMSNorm over the last axis; any leading shape. The kernel raises
    ``ValueError`` for a row count it cannot tile, on every backend."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = _rmsnorm(x2d, w, eps, _resolve_interpret(interpret))
    return out.reshape(shape)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, window, kv_offset, bq, bk, interpret):
    return flash_attention_kernel_call(
        q, k, v, causal=causal, scale=scale, window=window,
        kv_offset=kv_offset, block_q=bq, block_k=bk, interpret=interpret)


def _flash_fwd(q, k, v, causal, scale, window, kv_offset, bq, bk, interpret):
    out = _flash(q, k, v, causal, scale, window, kv_offset, bq, bk, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, scale, window, kv_offset, bq, bk, interpret, res, g):
    q, k, v = res
    hm = _heads_major
    _, vjp = jax.vjp(
        lambda qq, kk, vv: hm(ref.attention_ref(
            hm(qq), hm(kk), hm(vv), causal=causal, scale=scale,
            window=window, kv_offset=kv_offset)), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None,
                    window: int | None = None, kv_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """GQA attention, BSHD layout. See flash_attention.py for the design.

    The kernel runs heads-major; the transposes live here.
    """
    Sq, Skv = q.shape[1], k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    if Sq % bq or Skv % bk:
        _check_oracle_fallback("flash_attention", f"q {q.shape}, k {k.shape}"
                               f" vs blocks ({bq}, {bk})")
        return ref.attention_ref(q, k, v, causal=causal, scale=scale,
                                 window=window, kv_offset=kv_offset)
    hm = _heads_major
    out = _flash(hm(q), hm(k), hm(v), causal, scale, window, kv_offset,
                 bq, bk, _resolve_interpret(interpret))
    return hm(out)


# ----------------------------------------------------------------------
# ssd scan
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(x, a, b, c, chunk, interpret):
    return ssd_scan_kernel_call(x, a, b, c, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, a, b, c, chunk, interpret):
    return _ssd(x, a, b, c, chunk, interpret), (x, a, b, c)


def _ssd_bwd(chunk, interpret, res, g):
    x, a, b, c = res
    _, vjp = jax.vjp(
        lambda xx, aa, bb, cc: _ssd_ref_heads_major(xx, aa, bb, cc),
        x, a, b, c)
    return vjp(g)


def _ssd_ref_heads_major(x, a, b, c):
    """ref.ssd_ref on the kernel's layouts (see ssd_scan.py)."""
    hm = _heads_major
    y, hT = ref.ssd_ref(hm(x), hm(a[..., 0]), hm(b), hm(c),
                        return_state=True)
    return hm(y), hT


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
             chunk: int = 128, interpret: bool | None = None):
    """Mamba2 SSD over a sequence. Returns (y, final_state).

    Public layouts as in ref.ssd_ref; the kernel runs heads-major.
    """
    S = x.shape[1]
    ch = min(chunk, S)
    if S % ch:
        _check_oracle_fallback("ssd_scan", f"S={S} (x {x.shape}) vs chunk "
                               f"{ch}")
        return ref.ssd_ref(x, a, b, c, return_state=True)
    hm = _heads_major
    y, hT = _ssd(hm(x), hm(a)[..., None], hm(b), hm(c), ch,
                 _resolve_interpret(interpret))
    return hm(y), hT


# ----------------------------------------------------------------------
# grouped expert GEMM
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _gmm(x, w, bc, bf, bd, interpret):
    return moe_gmm_kernel_call(x, w, block_c=bc, block_f=bf, block_d=bd,
                               interpret=interpret)


def _gmm_fwd(x, w, bc, bf, bd, interpret):
    return _gmm(x, w, bc, bf, bd, interpret), (x, w)


def _gmm_bwd(bc, bf, bd, interpret, res, g):
    x, w = res
    _, vjp = jax.vjp(ref.moe_gmm_ref, x, w)
    return vjp(g)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def moe_gmm(x: jnp.ndarray, w: jnp.ndarray,
            block_c: int = 128, block_f: int = 128, block_d: int = 128,
            interpret: bool | None = None) -> jnp.ndarray:
    """Per-expert GEMM: (E, C, D) @ (E, D, F) → (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[-1]
    bc, bf, bd = (min(block_c, C), min(block_f, F), min(block_d, D))
    if C % bc or F % bf or D % bd:
        _check_oracle_fallback("moe_gmm", f"x {x.shape}, w {w.shape} vs "
                               f"blocks ({bc}, {bd}, {bf})")
        return ref.moe_gmm_ref(x, w)
    return _gmm(x, w, bc, bf, bd, _resolve_interpret(interpret))
