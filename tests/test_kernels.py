"""Per-kernel sweeps vs the pure-jnp oracles (interpret=True on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(shape, dtype, k, scale=1.0):
    return (jax.random.normal(k, shape) * scale).astype(dtype)


# ----------------------------------------------------------------------
# rmsnorm
# ----------------------------------------------------------------------

@pytest.mark.parametrize("rows,d", [(64, 128), (256, 512), (31, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_shapes(rows, d, dtype):
    k1, k2 = jax.random.split(KEY)
    x = _rand((rows, d), dtype, k1)
    w = _rand((d,), dtype, k2)
    got = ops.rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_grad_matches_ref():
    x = _rand((128, 64), jnp.float32, KEY)
    w = jnp.ones((64,))
    g1 = jax.grad(lambda x: ops.rmsnorm(x, w).sum())(x)
    g2 = jax.grad(lambda x: ref.rmsnorm_ref(x, w).sum())(x)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@pytest.mark.parametrize("S,Hq,Hkv,D,causal", [
    (128, 4, 4, 32, True),       # MHA causal
    (256, 8, 2, 64, True),       # GQA causal
    (256, 8, 2, 64, False),      # bidirectional (encoder)
    (128, 6, 3, 48, True),       # non-pow2 heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(S, Hq, Hkv, D, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand((2, S, Hq, D), dtype, ks[0])
    k = _rand((2, S, Hkv, D), dtype, ks[1])
    v = _rand((2, S, Hkv, D), dtype, ks[2])
    got = ops.flash_attention(q, k, v, causal=causal,
                              block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_window():
    ks = jax.random.split(KEY, 3)
    q = _rand((1, 256, 4, 32), jnp.float32, ks[0])
    k = _rand((1, 256, 4, 32), jnp.float32, ks[1])
    v = _rand((1, 256, 4, 32), jnp.float32, ks[2])
    got = ops.flash_attention(q, k, v, causal=True, window=64)
    want = ref.attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(off=st.integers(0, 192))
def test_flash_decode_offsets(off):
    """Property: decode (Sq=1) matches ref at any cache offset."""
    ks = jax.random.split(jax.random.PRNGKey(off), 3)
    q = _rand((2, 1, 4, 32), jnp.float32, ks[0])
    k = _rand((2, 256, 2, 32), jnp.float32, ks[1])
    v = _rand((2, 256, 2, 32), jnp.float32, ks[2])
    got = ops.flash_attention(q, k, v, causal=True, kv_offset=off,
                              block_k=64)
    want = ref.attention_ref(q, k, v, causal=True, kv_offset=off)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


def test_chunked_ref_matches_ref():
    ks = jax.random.split(KEY, 3)
    q = _rand((1, 512, 4, 32), jnp.float32, ks[0])
    k = _rand((1, 512, 2, 32), jnp.float32, ks[1])
    v = _rand((1, 512, 2, 32), jnp.float32, ks[2])
    got = ref.attention_chunked_ref(q, k, v, causal=True, chunk=128)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------
# ssd scan
# ----------------------------------------------------------------------

@pytest.mark.parametrize("S,H,P,G,N,chunk", [
    (128, 2, 16, 1, 8, 32),
    (256, 4, 32, 2, 16, 64),
    (64, 2, 16, 2, 8, 64),       # chunk == S
])
def test_ssd_kernel_vs_ref(S, H, P, G, N, chunk):
    ks = jax.random.split(KEY, 4)
    x = _rand((2, S, H, P), jnp.float32, ks[0], 0.5)
    a = -jnp.abs(_rand((2, S, H), jnp.float32, ks[1], 0.3))
    b = _rand((2, S, G, N), jnp.float32, ks[2], 0.3)
    c = _rand((2, S, G, N), jnp.float32, ks[3], 0.3)
    y1, h1 = ops.ssd_scan(x, a, b, c, chunk=chunk)
    y2, h2 = ref.ssd_ref(x, a, b, c, return_state=True)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-3, atol=2e-4)


def _ssd_inputs(S, H, G, dtype=jnp.float32, batch=2, P=16, N=8):
    ks = jax.random.split(KEY, 5)
    x = _rand((batch, S, H, P), dtype, ks[0], 0.5)
    a = -jnp.abs(_rand((batch, S, H), jnp.float32, ks[1], 0.3))
    b = _rand((batch, S, G, N), dtype, ks[2], 0.3)
    c = _rand((batch, S, G, N), dtype, ks[3], 0.3)
    h0 = _rand((batch, H, N, P), jnp.float32, ks[4], 0.2)
    return x, a, b, c, h0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,chunk", [(128, 32), (64, 64)])  # 4 chunks, 1
@pytest.mark.parametrize("batch,H,G", [(2, 2, 1), (2, 4, 2), (1, 2, 1)])
@pytest.mark.parametrize("with_h0", [True, False])
def test_ssd_chunked_ref_with_state(with_h0, batch, H, G, S, chunk, dtype):
    """Chunked dual form == sequential scan: outputs and final state."""
    x, a, b, c, h0 = _ssd_inputs(S, H, G, dtype, batch)
    h0 = h0 if with_h0 else None
    y1, h1 = ref.ssd_chunked_ref(x, a, b, c, h0=h0, chunk=chunk,
                                 return_state=True)
    y2, h2 = ref.ssd_ref(x, a, b, c, h0=h0, return_state=True)
    assert y1.dtype == x.dtype and h1.dtype == jnp.float32
    tol = 2e-4 if dtype == jnp.float32 else 1e-2     # y rounded to bf16
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-5)


def _ssd_grads(fn, args, **kw):
    def loss(x, a, b, c, h0):
        y, hT = fn(x, a, b, c, h0=h0, return_state=True, **kw)
        return (y * y).sum() + (hT * hT).sum()
    return jax.grad(loss, argnums=range(5))(*args)


def test_ssd_chunked_ref_grad_matches_ref():
    """Backward of the chunked form == backward of the sequential scan,
    for x, a, B, C and h0."""
    args = _ssd_inputs(128, 4, 2)
    got = _ssd_grads(ref.ssd_chunked_ref, args, chunk=32)
    want = _ssd_grads(ref.ssd_ref, args)
    for name, g1, g2 in zip(("x", "a", "b", "c", "h0"), got, want):
        np.testing.assert_allclose(g1, g2, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def test_ssd_chunked_ref_grad_finite_under_strong_decay():
    """a = -30 a step: exp of the unmasked upper triangle would overflow,
    and inf·0 would turn the gradients to NaN."""
    x, _, b, c, h0 = _ssd_inputs(128, 4, 2)
    a = jnp.full((2, 128, 4), -30.0)
    got = _ssd_grads(ref.ssd_chunked_ref, (x, a, b, c, h0), chunk=32)
    want = _ssd_grads(ref.ssd_ref, (x, a, b, c, h0))
    for name, g1, g2 in zip(("x", "a", "b", "c", "h0"), got, want):
        assert np.isfinite(np.asarray(g1)).all(), name
        np.testing.assert_allclose(g1, g2, rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def test_ssd_decode_continuity():
    """State from prefill + single-step decode == full-sequence run."""
    ks = jax.random.split(KEY, 4)
    S = 96
    x = _rand((1, S, 2, 16), jnp.float32, ks[0], 0.5)
    a = -jnp.abs(_rand((1, S, 2), jnp.float32, ks[1], 0.3))
    b = _rand((1, S, 1, 8), jnp.float32, ks[2], 0.3)
    c = _rand((1, S, 1, 8), jnp.float32, ks[3], 0.3)
    y_full = ref.ssd_ref(x, a, b, c)
    _, h = ref.ssd_ref(x[:, :-1], a[:, :-1], b[:, :-1], c[:, :-1],
                       return_state=True)
    y_last = ref.ssd_ref(x[:, -1:], a[:, -1:], b[:, -1:], c[:, -1:], h0=h)
    np.testing.assert_allclose(y_last[:, 0], y_full[:, -1],
                               rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------
# moe gmm
# ----------------------------------------------------------------------

@pytest.mark.parametrize("E,C,D,F", [(4, 128, 64, 128), (8, 64, 128, 64),
                                     (2, 100, 48, 72)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_vs_ref(E, C, D, F, dtype):
    k1, k2 = jax.random.split(KEY)
    x = _rand((E, C, D), dtype, k1)
    w = _rand((E, D, F), dtype, k2)
    got = ops.moe_gmm(x, w, block_c=64, block_f=64, block_d=32)
    want = ref.moe_gmm_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_moe_gmm_grads():
    k1, k2 = jax.random.split(KEY)
    x = _rand((2, 64, 32), jnp.float32, k1)
    w = _rand((2, 32, 64), jnp.float32, k2)
    g1 = jax.grad(lambda w: ops.moe_gmm(x, w).sum())(w)
    g2 = jax.grad(lambda w: ref.moe_gmm_ref(x, w).sum())(w)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------
# untiled shapes: oracle off the chip, ValueError on a TPU
# ----------------------------------------------------------------------

_UNTILED = {
    "flash_attention": lambda: ops.flash_attention(
        *[_rand((1, 100, 2, 16), jnp.float32, KEY)] * 3, block_q=64),
    "ssd_scan": lambda: ops.ssd_scan(
        _rand((1, 100, 2, 16), jnp.float32, KEY, 0.5),
        -jnp.abs(_rand((1, 100, 2), jnp.float32, KEY, 0.3)),
        _rand((1, 100, 1, 8), jnp.float32, KEY, 0.3),
        _rand((1, 100, 1, 8), jnp.float32, KEY, 0.3), chunk=64),
    "moe_gmm": lambda: ops.moe_gmm(
        _rand((2, 100, 48), jnp.float32, KEY),
        _rand((2, 48, 72), jnp.float32, KEY), block_c=64),
}


@pytest.mark.parametrize("op", sorted(_UNTILED))
def test_untiled_shape_raises_on_tpu(monkeypatch, op):
    _UNTILED[op]()                                   # CPU: the oracle runs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=op):
        _UNTILED[op]()
