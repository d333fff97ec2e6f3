"""Model operations and bytes, computed from shapes.

Each function counts what the mathematics needs, not what an
implementation does: attention counts only the causal triangle, experts
count the top-k active experts (not capacity slots, not the one-hot
dispatch), the SSD scan counts its recurrence, and nothing recomputed by
remat counts. So a roofline read against these numbers reads the same
work whatever implements it. A multiply-add is two operations. Bytes are
what a step must move at least: weights once, caches or state read (and
written where they change), activations in and out.
"""

from __future__ import annotations

import dataclasses

W = 2   # bytes of a bf16 weight or activation
F32 = 4


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def attention(B, S, start, D, H, Hkv, Dh) -> Work:
    """GQA self-attention for S queries per row at positions start..start+S-1
    (causal: query at position p sees p + 1 keys). Projections included;
    the cache holds keys and values of every position so far."""
    proj = D * (2 * H * Dh + 2 * Hkv * Dh)
    keys = S * start + S * (S + 1) // 2          # sum over queries of p + 1
    flops = 2 * B * S * proj + 4 * B * H * Dh * keys
    kv = 2 * B * Hkv * Dh * W * (start + S)      # read the live cache
    return Work(flops, proj * W + kv + 2 * B * S * D * W)


def moe_experts(T, D, F, E, K) -> Work:
    """Top-k SwiGLU experts for T tokens, with the f32 router. Bytes count
    the experts T tokens can touch at most, min(E, T * K)."""
    flops = 2 * T * D * E + 2 * T * K * 3 * D * F
    touched = min(E, T * K)
    return Work(flops, touched * 3 * D * F * W + D * E * F32
                + 2 * T * D * W)


def ssd_scan(B, S, H, N, P, carried: bool) -> Work:
    """Mamba2 SSD recurrence h = exp(a) h + B x, y = C h: 3 operations per
    state element to update, 2 to read out. ``carried``: the f32 state is
    read and written (decode)."""
    flops = B * S * H * N * P * 5
    state = 2 * B * H * N * P * F32 if carried else 0
    return Work(flops, state + B * S * H * (2 * N + 2 * P) * W)


def mamba_mixer(B, S, D, d_inner, H, N, P, G, K, carried: bool) -> Work:
    """Mamba2 mixer: in projection, causal conv, SSD, gated norm, out
    projection."""
    conv_dim = d_inner + 2 * G * N
    w_in = D * (2 * d_inner + 2 * G * N + H)
    w_out = d_inner * D
    T = B * S
    flops = 2 * T * (w_in + w_out) + 2 * T * K * conv_dim + 6 * T * d_inner
    conv_state = 2 * B * (K - 1) * conv_dim * W if carried else 0
    proj = Work(flops, (w_in + w_out + K * conv_dim) * W + conv_state
                + 2 * T * D * W)
    return proj + ssd_scan(B, S, H, N, P, carried)


def rmsnorm(T, D) -> Work:
    return Work(4 * T * D, 2 * T * D * W + D * W)


def head(T, D, V) -> Work:
    """Final projection to the vocabulary (f32 logits out)."""
    return Work(2 * T * D * V, D * V * W + T * V * F32)


def cross_entropy(T, V) -> Work:
    """log-softmax, label pick and z-loss over f32 logits."""
    return Work(6 * T * V, T * V * F32)


def forward(kind, cfg, B, S, start=0, head_tokens=None, carried=False) -> Work:
    """One forward pass over B rows of S tokens after ``start`` earlier
    positions; ``head_tokens`` tokens reach the head (default all). The
    layers are counted by the model's kind (``bench/kinds/``)."""
    ht = B * S if head_tokens is None else head_tokens
    return kind.work(cfg, B, S, start, carried) + rmsnorm(
        ht, cfg.d_model) + head(ht, cfg.d_model, cfg.vocab_size)


def train_step(kind, cfg, B, S) -> Work:
    """Forward, loss and backward (twice the forward's operations)."""
    fwd = forward(kind, cfg, B, S) + cross_entropy(B * S, cfg.vocab_size)
    return Work(3 * fwd.flops, 3 * fwd.bytes)


def prefill(kind, cfg, B, S) -> Work:
    return forward(kind, cfg, B, S, head_tokens=B)


def decode_step(kind, cfg, B, length) -> Work:
    """One token per row after ``length`` cached positions."""
    return forward(kind, cfg, B, 1, start=length, carried=True)
