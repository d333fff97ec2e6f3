"""Locality-aware MoE routing — DFWSPT/DFWSRPT inside the XLA program.

The paper's schedulers let an idle thread steal queued tasks from the
*nearest* victim (ties deterministic for DFWSPT, random for DFWSRPT). The
SPMD analogue implemented here: experts are task queues with bounded
capacity; tokens that overflow an expert's capacity are re-routed ("stolen")
to the expert whose owning device is *fewest ICI hops away* from the
overloaded one, in a precomputed steal order. This keeps the rescue
traffic on short links instead of letting overflow drop (quality loss) or
re-shuffle across the whole mesh (bandwidth loss).

Because XLA programs are static, the steal order is baked in ahead of
time from the topology: ``expert_steal_table`` is
``core/stealing.steal_order_matrix`` with the experts as threads, the
same victim order the simulator's engines sweep; the DFWSRPT variant
bakes the random tie-breaks at trace time from a seed, which is exactly
the paper's "randomly choose its victim" decision frozen per program.
``launch/mesh`` builds the tables the launchers pass; ``models/layers.moe``
calls ``route`` and ``slot_maps``.

All shapes are static; everything lowers under pjit/shard_map.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .stealing import steal_order_matrix
from .topology import Topology

__all__ = ["RoutingConfig", "expert_steal_table", "route", "slot_maps"]


@dataclasses.dataclass(frozen=True)
class RoutingConfig:
    num_experts: int
    top_k: int
    capacity: int            # per-expert token slots (per routed batch)
    steal_attempts: int = 2  # 0 = vanilla GShard-style drop-on-overflow
    policy: str = "dfwspt"   # unread: the steal table carries the order


def expert_steal_table(topo: Topology,
                       expert_device: np.ndarray,
                       policy: str = "dfwspt",
                       seed: int = 0) -> np.ndarray:
    """(E, E-1) steal order: row e = other experts by hop distance from
    the device owning e (paper's priority list, expert-granular).

    expert_device: (E,) physical device (== core in the topology) owning
    each expert shard.
    """
    return steal_order_matrix(topo, expert_device, policy, seed)


def _fill_positions(pick: jnp.ndarray, active: jnp.ndarray,
                    used: jnp.ndarray, capacity: int):
    """Greedy in-order capacity fill for one routing attempt.

    pick: (N, E) bool, each pair's chosen expert; active: (N,) pairs still
    waiting. used: (E,) slots already taken. Returns (placed, position,
    new_used).

    A pair's position in its expert's queue is the count of earlier active
    pairs with the same expert. ``jnp.cumsum`` along N lowers to a
    reduce-window over the whole axis; here the count is a lower-triangular
    0/1 matmul within blocks of b pairs (exact: bf16 operands, f32 sums of
    at most 256 ones) plus the running total of the blocks before. Lookups
    into (E,) tables are sums over the pair's one-hot row.
    """
    n, e = pick.shape
    b = min(256, -(-n // 8) * 8)   # a decode group of a few tokens: one block
    nb = -(-n // b)
    onehot = pick & active[:, None]
    blk = jnp.pad(onehot, ((0, nb * b - n), (0, 0))).reshape(nb, b, e)
    upto = jnp.einsum("ij,kje->kie", jnp.tri(b, dtype=jnp.bfloat16),
                      blk.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).astype(jnp.int32)
    totals = upto[:, -1]                                  # (nb, E)
    before = jnp.cumsum(totals, axis=0) - totals
    count = (upto + before[:, None]).reshape(nb * b, e)[:n] - onehot
    # position of each pair within its chosen expert's queue
    pos = jnp.sum(jnp.where(pick, count + used, 0), axis=1)
    placed = active & (pos < capacity)
    new_used = used + jnp.minimum(totals.sum(axis=0), capacity - used)
    return placed, pos, new_used


def route(gate_logits: jnp.ndarray,
          cfg: RoutingConfig,
          steal_table: np.ndarray | None = None):
    """Top-k routing with locality-aware overflow stealing.

    Args:
      gate_logits: (T, E) router scores for a routed group.
      steal_table: (E, E-1) from :func:`expert_steal_table`; None steals
        from e+1, e+2, ... mod E. Unused when ``cfg.steal_attempts == 0``.

    Returns dict with:
      expert:   (T, K) int32 — final expert of each (token, slot); -1 drop.
      slot:     (T, K) int32 — capacity slot within that expert; -1 drop.
      weight:   (T, K) f32   — combine weights (renormalized gate probs).
      aux_loss: scalar load-balancing auxiliary (Switch-style).
      drop_fraction: scalar — fraction of (token, slot) pairs dropped.
    """
    T, E = gate_logits.shape
    if E != cfg.num_experts:
        raise ValueError(f"gate width {E} != num_experts {cfg.num_experts}")
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)          # (T, K)

    # Switch-Transformer auxiliary load-balance loss.
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), 0)
    router_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(density * router_prob)

    if cfg.steal_attempts > 0:
        if steal_table is None:
            # no topology given: expert e steals from e+1, e+2, ... mod E
            steal_table = (np.arange(E)[:, None] + np.arange(1, E)) % E
        table = jnp.asarray(steal_table, jnp.int32)         # (E, E-1)

    # Flatten (token, k-slot) pairs; earlier k-slots get priority, matching
    # the paper's depth-first "own queue first" preference.
    flat_e = top_e.T.reshape(-1)                            # (K*T,)
    flat_active = jnp.ones((cfg.top_k * T,), bool)
    flat_expert = jnp.full((cfg.top_k * T,), -1, jnp.int32)
    flat_slot = jnp.full((cfg.top_k * T,), -1, jnp.int32)
    used = jnp.zeros((E,), jnp.int32)

    choice = flat_e
    for attempt in range(cfg.steal_attempts + 1):
        pick = choice[:, None] == jnp.arange(E)             # (K*T, E)
        placed, pos, used = _fill_positions(pick, flat_active, used,
                                            cfg.capacity)
        flat_expert = jnp.where(placed, choice, flat_expert)
        flat_slot = jnp.where(placed, pos, flat_slot)
        flat_active = flat_active & ~placed
        if attempt < cfg.steal_attempts:
            # overflow tokens walk the victim list of their *current*
            # expert: nearest device first (DFWSPT/DFWSRPT).
            choice = jnp.sum(jnp.where(pick, table[:, attempt], 0), axis=1)
    expert = flat_expert.reshape(cfg.top_k, T).T            # (T, K)
    slot = flat_slot.reshape(cfg.top_k, T).T
    keep = expert >= 0
    w = top_p * keep
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return dict(expert=expert, slot=slot, weight=w, aux_loss=aux_loss,
                drop_fraction=1.0 - jnp.mean(keep.astype(jnp.float32)))


def slot_maps(expert: jnp.ndarray, slot: jnp.ndarray, num_experts: int,
              capacity: int):
    """Index maps between the (token, k) pairs and the (expert, slot) slots
    of routed groups: what the MoE layer moves rows by.

    Args:
      expert, slot: (..., T, K) int32 from :func:`route`, one routed group
        per leading index; -1 for a drop.

    Returns:
      pair_of_slot: (..., E·C) int32 — the flat pair t·K + k that fills
        slot e·C + c; T·K, one past the last pair, for an empty slot.
      slot_of_pair: (..., T, K) int32 — e·C + c of each pair; E·C, one
        past the last slot, for a drop.

    ``route`` gives each kept pair its own slot, so the two maps are
    inverse on the kept pairs.
    """
    *lead, T, K = expert.shape
    n_slots = num_experts * capacity
    kept = expert >= 0
    slot_of_pair = jnp.where(kept, expert * capacity + slot,
                             n_slots).astype(jnp.int32)
    # one scatter for all groups, each group's slots after the previous
    # group's (a scatter batched by vmap loses its op_name in XLA's
    # rewrite); a drop indexes past the last slot and writes nothing
    n_groups = int(np.prod(lead))
    group = jnp.arange(n_groups, dtype=jnp.int32).reshape(*lead, 1, 1)
    at = jnp.where(kept, group * n_slots + slot_of_pair, n_groups * n_slots)
    pair = jnp.broadcast_to(
        jnp.arange(T * K, dtype=jnp.int32).reshape(T, K), expert.shape)
    pair_of_slot = jnp.full((n_groups * n_slots,), T * K, jnp.int32).at[
        at.reshape(-1)].set(pair.reshape(-1), mode="drop")
    return pair_of_slot.reshape(*lead, n_slots), slot_of_pair
