"""Drive the reference: logits of served tokens, and the first training
steps with AdamW. Every slot of the kind's layer period runs in order, in
each repeat; layer by layer or under remat, so that it fits one chip after
the program's state is freed."""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from bench.check import leaf_norms
from bench.reference.model import Numerics, Spec, logits, rmsnorm


def serve_logits(params, kind, spec: Spec, tokens: np.ndarray,
                 prompt_len: int, table,
                 num: Numerics = Numerics()) -> np.ndarray:
    """f32 logits (B, L - prompt_len + 1, V) at positions prompt_len-1 ..
    L-1 of tokens (B, L): the positions that produced the served tokens."""
    slots = kind.slots(spec)
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        steps = [jax.jit(lambda x, blocks, i, slot=slot: slot(
            x, jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                t, i, keepdims=False), blocks), spec, num, prompt_len,
            table)[0]) for slot in slots]
        head = jax.jit(lambda x, p: logits(
            x[:, prompt_len - 1:], p, spec, num, kind.head))
        x = embed(params["embed"], jnp.asarray(tokens))
        for i in range(spec.layers // len(slots)):
            for step, blocks in zip(steps, params["blocks"]):
                x = step(x, blocks, i)
        return np.asarray(head(x, {k: v for k, v in params.items()
                                   if k != "blocks"}))


def loss_fn(params, kind, spec: Spec, batch, table, num: Numerics):
    """Cross-entropy over unmasked labels + aux + z-loss, f32."""
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32)
    slots = kind.slots(spec)

    @jax.checkpoint
    def body(carry, ps):
        x, aux = carry
        for slot, p in zip(slots, ps):
            x, a = slot(x, p, spec, num, S, table)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros(())), params["blocks"])
    w = kind.head(params)

    @jax.checkpoint
    def row(args):
        xr, lr = args
        h = rmsnorm(xr, params["final_norm"], spec.eps)
        lg = num.ein("sd,vd->sv", h, w)
        valid = lr >= 0
        logp = jax.nn.log_softmax(lg, -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lr, 0)[:, None],
                                 -1)[:, 0]
        z = jnp.square(jax.nn.logsumexp(lg, -1))
        return jnp.stack([-(ll * valid).sum(), (z * valid).sum(),
                          valid.sum().astype(jnp.float32)])

    sums = jax.lax.map(row, (x, labels)).sum(0)
    denom = jnp.maximum(sums[2], 1.0)
    return sums[0] / denom + spec.aux_weight * aux \
        + spec.z_weight * sums[1] / denom


def lr_at(opt: dict, count: int) -> float:
    """Linear warm-up, then cosine decay to lr_min_ratio of the peak."""
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    frac = opt["lr_min_ratio"] + (1 - opt["lr_min_ratio"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * frac


def adamw(params, grads, m, v, count: int, opt: dict):
    """One AdamW step: clip by global norm, bias-corrected moments,
    decoupled weight decay on stored leaves of two or more axes;
    parameters kept in their stored dtype."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    lr = lr_at(opt, count)
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / (1 - b1 ** count)) / (
            jnp.sqrt(v / (1 - b2 ** count)) + opt["eps"])
        pf = p.astype(jnp.float32)
        if p.ndim >= 2:
            step = step + opt["weight_decay"] * pf
        return (pf - lr * step).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def train_steps(params, kind, spec: Spec, batches: list[dict], opt: dict,
                table, num: Numerics = Numerics()) -> dict:
    """The reference's first len(batches) steps from ``params`` (stored
    dtype). Returns losses, the first step's gradient (and its norms by
    leaf) and the parameters' change norms by leaf after the last step."""
    with jax.default_matmul_precision("highest"):
        vg = jax.value_and_grad(lambda p, b: loss_fn(p, kind, spec, b, table,
                                                      num))
        # differentiate at f32 copies, so the gradients are f32
        grad_fn = jax.jit(lambda p, b: vg(
            jax.tree.map(lambda t: t.astype(jnp.float32), p), b))
        m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        v = m
        cur = params
        losses = []
        for i, batch in enumerate(batches, start=1):
            loss, grads = grad_fn(cur, batch)
            losses.append(float(loss))
            if i == 1:
                first_grad = leaf_norms(grads)
            update = jax.jit(lambda p, g, m, v, i=i: adamw(p, g, m, v, i, opt))
            cur, m, v = update(cur, grads, m, v)
        change = leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            cur, params))
    return {"losses": losses, "grad": first_grad, "change": change}
