"""NUMA-aware work-stealing victim orders (paper §VI).

Both of the paper's schedulers steal from victims ranked by hop distance
from the idle thread's core; they differ only in tie-breaking at equal
distance:

  * DFWSPT  — ties broken by ascending thread id ("threads with smaller
    id are placed first").
  * DFWSRPT — ties broken by a fresh random permutation each time the
    thread goes stealing ("victim thread is picked randomly" among the
    equally-close), which avoids convoys on the lowest-id victim.
  * DFWSHIER — the policy layer's hierarchical variant: equal-distance
    ties are randomized at *node* granularity — a sweep probes all of
    one NUMA node's threads (id asc) before moving to the next node,
    so consecutive probes share victim-node memory.

``victim_groups`` is the one place the order is written. The simulator
compiles it into its engines' victim plans (``core/sim/policy.py``);
``victim_order`` draws one sweep of it the way an engine's sweep does,
and ``priority_list`` / ``steal_order_matrix`` are built on that sweep.
``core/routing.expert_steal_table`` is ``steal_order_matrix`` with the
experts as threads: the TPU adaptation, where "threads" are
expert-owning devices.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from .topology import Topology

__all__ = ["victim_groups", "priority_list", "victim_order",
           "steal_order_matrix"]

# the paper's scheduler names → the policy layer's victim sweeps
_POLICY_VICTIM = {"dfwspt": "dist_id", "dfwsrpt": "dist_random",
                 "dfwshier": "node_hier"}


def victim_groups(victim: str, topo: Topology,
                  cores: Sequence[int]) -> list[list[list[int]]]:
    """Build the raw group/unit/victim nesting for one policy."""
    T = len(cores)
    dist = topo.core_distance_matrix()
    core_node = topo.core_node
    out: list[list[list[int]]] = []
    for th in range(T):
        others = [v for v in range(T) if v != th]
        if victim == "none" or not others:
            out.append([])
        elif victim == "random":
            # one group of singleton units, ascending id — a sweep is one
            # shuffle of T-1 elements, exactly the stock cilk/wf draw.
            out.append([[[v] for v in others]])
        elif victim == "dist_id":
            order = sorted(others,
                           key=lambda v: (dist[cores[th], cores[v]], v))
            out.append([[order]])  # one group, one unit: fully static
        elif victim == "dist_random":
            by_d: dict[int, list[int]] = {}
            for v in others:
                by_d.setdefault(int(dist[cores[th], cores[v]]), []).append(v)
            # one group per distance tier (asc), singleton units — one
            # shuffle per tier of tier-size elements, the DFWSRPT draws.
            out.append([[[v] for v in by_d[d]] for d in sorted(by_d)])
        elif victim == "node_hier":
            by_d = {}
            for v in others:
                by_d.setdefault(int(dist[cores[th], cores[v]]), []).append(v)
            per_thread = []
            for d in sorted(by_d):
                by_node: dict[int, list[int]] = {}
                for v in by_d[d]:
                    by_node.setdefault(int(core_node[cores[v]]), []).append(v)
                per_thread.append(list(by_node.values()))
            out.append(per_thread)
        else:  # pragma: no cover - guarded by SchedulerSpec validation
            raise ValueError(f"unknown victim policy {victim!r}")
    return out


def _groups(topo: Topology, thread_cores: Sequence[int],
            policy: str) -> list[list[list[int]]]:
    if policy not in _POLICY_VICTIM:
        raise ValueError(f"unknown stealing policy {policy!r}")
    return victim_groups(_POLICY_VICTIM[policy], topo, thread_cores)


def _sweep(groups: list[list[int]], rng) -> list[int]:
    """One sweep: the groups in order, each multi-unit group's units in a
    fresh ``rng.shuffle`` order — the engines' draws."""
    order: list[int] = []
    for units in groups:
        if len(units) > 1:
            units = list(units)
            rng.shuffle(units)
        for u in units:
            order.extend(u)
    return order


def victim_order(topo: Topology, thread_cores: Sequence[int], thread: int,
                 policy: str, rng: np.random.RandomState) -> list[int]:
    """Victim id order for one stealing sweep.

    policy: 'dfwspt' (deterministic ties), 'dfwsrpt' (random ties), or
    'dfwshier' (node-granular random ties, node members contiguous). From
    a fresh ``RandomState(seed)`` it equals the engine's first sweep.
    """
    return _sweep(_groups(topo, thread_cores, policy)[thread], rng)


def priority_list(topo: Topology, thread_cores: Sequence[int],
                  thread: int) -> list[int]:
    """DFWSPT static priority list for ``thread``.

    Returns other threads' ids ordered by (hop distance from this thread's
    core asc, thread id asc). This is computed once at startup, exactly as
    the paper prescribes. DFWSPT draws nothing, so no rng is needed.
    """
    return victim_order(topo, thread_cores, thread, "dfwspt", None)


def steal_order_matrix(topo: Topology, thread_cores: Sequence[int],
                       policy: str = "dfwspt",
                       seed: int = 0) -> np.ndarray:
    """(T, T-1) matrix: row t = victim order for thread t.

    One ``RandomState(seed)`` draws the rows' sweeps in thread order —
    the *ahead-of-time* form used by the TPU routing adaptation, where
    the steal order must be baked into the compiled program.
    """
    rng = np.random.RandomState(seed)
    rows = [_sweep(g, rng) for g in _groups(topo, thread_cores, policy)]
    return np.asarray(rows, np.int64)
