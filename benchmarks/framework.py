"""Framework-level benchmarks: mesh layout quality, MoE locality routing,
kernel micro-latencies (CPU fallback path — numbers are relative)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import placement, topology
from repro.core.routing import RoutingConfig, expert_steal_table, route
from repro.kernels import ref


def _timeit(fn, *args, reps=5):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # µs


def sim_engine(report, quick=False):
    """Simulator-engine throughput rows (full sweep: bench_sim.py)."""
    from benchmarks import bench_sim
    for row in bench_sim.bench(quick=quick, reps=3):
        report(f"sim-engine/{row['workload']}-{row['scale']}/"
               f"{row['scheduler']}/{row['engine']}",
               us=row["warm_s"] * 1e6,
               derived=f"tasks={row['tasks']} "
                       f"tps={row['tasks_per_s']:.0f} "
                       f"cold={row['cold_s']*1e3:.1f}ms "
                       f"speedup={row['speedup']:.2f}x")
    return True


def mesh_layout(report, quick=False):
    """Hop-weighted collective cost: enumeration order vs priority walk.

    On a *healthy* torus the enumeration order is already ring-optimal —
    the paper's walk matters on irregular topologies: after failures
    (the elastic-remesh case, §IV "cores already allocated") the naive
    order of survivors breaks rings, while the priority walk re-packs
    them (this is exactly `plan_elastic_remesh`'s layout step)."""
    cases = [("single-pod", topology.tpu_pod_2d(16, 16), (16, 16)),
             ("multi-pod", topology.multi_pod(2, 16, 16), (2, 16, 16))]
    for name, topo, shape in cases:
        t0 = time.perf_counter()
        perm = placement.device_order_priority(topo, shape)
        t_order = (time.perf_counter() - t0) * 1e6
        base = placement.layout_cost(
            topo, placement.device_order_baseline(topo), shape)
        pri = placement.layout_cost(topo, perm, shape)
        report(f"mesh-layout/{name}", us=t_order,
               derived=f"hops base={base:.3f} priority={pri:.3f} "
                       f"(healthy torus: enumeration already optimal)")

    # degraded topology: random failures, shrink to the largest square
    rng = np.random.RandomState(0)
    topo = topology.tpu_pod_2d(16, 16)
    for frac in (0.05, 0.15):
        failed = set(rng.choice(256, int(256 * frac), replace=False)
                     .tolist())
        survivors = [d for d in range(256) if d not in failed]
        keep = 12 * 12 if len(survivors) >= 144 else 8 * 8
        shape = (12, 12) if keep == 144 else (8, 8)
        sub = topo.restrict(survivors[:])
        # naive: first-k survivors in enumeration order
        base = placement.layout_cost(sub.restrict(list(range(keep))),
                                     np.arange(keep), shape)
        # paper walk, two-stage: compact blob → ring-aware order within it
        blob = placement.device_order_priority(
            sub, (sub.num_cores,))[:keep]
        sub2 = sub.restrict([int(b) for b in blob])
        perm = placement.device_order_priority(sub2, shape)
        pri = placement.layout_cost(sub2, perm, shape)
        report(f"mesh-layout/degraded-{int(frac*100)}pct",
               derived=f"hops naive={base:.3f} priority={pri:.3f} "
                       f"({(1 - pri / base) * 100:+.1f}%)")
    return True


def moe_locality(report, quick=False):
    """Drop fraction + steal distance: vanilla vs DFWSPT vs DFWSRPT."""
    topo = topology.tpu_pod_2d(4, 4)
    E, T = 16, 2048
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (T, E))
    logits = logits.at[:, :4].add(2.5)          # hot experts
    d = topo.core_distance_matrix()
    orig = np.asarray(jnp.argmax(logits, 1))
    for policy, attempts in (("none", 0), ("dfwspt", 3), ("dfwsrpt", 3)):
        tbl = (expert_steal_table(topo, np.arange(E), policy)
               if policy != "none" else None)
        cfg = RoutingConfig(E, top_k=1, capacity=T // E,
                            steal_attempts=attempts)
        fn = jax.jit(lambda lg: route(lg, cfg, tbl))
        us = _timeit(fn, logits)
        r = fn(logits)
        e = np.asarray(r["expert"][:, 0])
        moved = (e >= 0) & (e != orig)
        hops = d[orig[moved], e[moved]] if moved.any() else np.array([0])
        report(f"moe-locality/{policy}", us=us,
               derived=f"drop={float(r['drop_fraction']):.3f} "
                       f"steal_hops_mean={hops.mean():.2f}")
    return True


def kernels(report, quick=False):
    """Reference-path kernel latencies (CPU). Pallas kernels execute in
    interpret mode on CPU (correctness harness) — production latencies
    come from the TPU roofline, not from here."""
    key = jax.random.PRNGKey(1)
    S = 512 if quick else 1024

    q = jax.random.normal(key, (1, S, 8, 64), jnp.float32)
    k = jax.random.normal(key, (1, S, 2, 64), jnp.float32)
    v = jax.random.normal(key, (1, S, 2, 64), jnp.float32)
    report("kernel-ref/attention",
           us=_timeit(jax.jit(lambda q, k, v: ref.attention_ref(q, k, v)),
                      q, k, v),
           derived=f"S={S} GQA8/2")
    report("kernel-ref/attention_chunked",
           us=_timeit(jax.jit(lambda q, k, v:
                              ref.attention_chunked_ref(q, k, v, chunk=256)),
                      q, k, v),
           derived=f"S={S} chunk=256")

    x = jax.random.normal(key, (1, S, 8, 32)) * 0.5
    a = -jnp.abs(jax.random.normal(key, (1, S, 8))) * 0.3
    b = jax.random.normal(key, (1, S, 1, 16)) * 0.3
    c = jax.random.normal(key, (1, S, 1, 16)) * 0.3
    report("kernel-ref/ssd_sequential",
           us=_timeit(jax.jit(lambda *t: ref.ssd_ref(*t)), x, a, b, c),
           derived=f"S={S}")
    report("kernel-ref/ssd_chunked",
           us=_timeit(jax.jit(lambda *t: ref.ssd_chunked_ref(*t, chunk=128)),
                      x, a, b, c),
           derived=f"S={S} chunk=128 (dual form)")

    xg = jax.random.normal(key, (8, 256, 256))
    wg = jax.random.normal(key, (8, 256, 512))
    report("kernel-ref/moe_gmm",
           us=_timeit(jax.jit(ref.moe_gmm_ref), xg, wg),
           derived="E8 C256 D256 F512")

    xr = jax.random.normal(key, (4096, 1024))
    wr = jnp.ones((1024,))
    report("kernel-ref/rmsnorm",
           us=_timeit(jax.jit(ref.rmsnorm_ref), xr, wr),
           derived="4096x1024")
    return True
