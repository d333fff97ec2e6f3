"""§Perf hillclimbs: hypothesis → change → re-lower → measure.

Three roofline cells (see EXPERIMENTS.md §Perf for selection rationale)
plus a simulator *strategy* hillclimb (``--cell 4``): the paper's own
progression — baseline Nanos → +priority binding → +master-node spill →
+NUMA-aware stealing — expressed as one-context-knob-at-a-time
:class:`~repro.core.sim.Machine` variants, so each step isolates one
declarative change exactly like the roofline cells isolate one config
override. Results land in artifacts/hillclimb/ and the comparison table
is printed for the §Perf log.

    PYTHONPATH=src python -m benchmarks.hillclimb [--cell N]
"""

from __future__ import annotations

import argparse
import json
import os

from benchmarks.roofline import cell_roofline

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                   "hillclimb")


def _run(arch, shape, mesh, variant=None, **kw):
    from repro.launch.dryrun import run_cell
    rec = run_cell(arch, shape, mesh, skip_existing=True, variant=variant,
                   out_dir=ART, **kw)
    r = cell_roofline(rec)
    r["variant"] = variant or "baseline"
    return r


def _show(rows):
    print(f"{'variant':28s} {'compute_s':>10} {'memory_s':>10} "
          f"{'coll_s':>10} {'dominant':>12} {'mem GiB':>8} {'frac':>7}")
    for r in rows:
        print(f"{r['variant']:28s} {r['compute_s']:10.4f} "
              f"{r['memory_s']:10.4f} {r['collective_s']:10.4f} "
              f"{r['dominant'][:-2]:>12} {r['memory_gib']:8.2f} "
              f"{r['roofline_fraction']:7.3f}")
    return rows


def cell_granite():
    """granite-moe train_4k multi — the paper-technique cell.

    Baseline = paper-faithful (DFWSPT stealing on). Variants probe the
    dominant term with the technique held fixed, plus the
    paper-ablation (stealing off) for the §Repro delta.
    """
    a, s, m = "granite-moe-1b-a400m", "train_4k", "multi"
    rows = [_run(a, s, m)]
    # paper-ablation: vanilla GShard drops instead of locality stealing
    rows.append(_run(a, s, m, "nosteal",
                     cfg_overrides=dict(moe_steal_attempts=0)))
    # H1: grad sync dominates collectives → bf16 accumulation halves it
    rows.append(_run(a, s, m, "bf16grads",
                     opt_overrides=dict(factored=True,
                                        m_dtype="bfloat16")))
    # H2: smaller routing groups shrink routing's (K·G, E) fill at the
    # cost of more, smaller expert matmuls
    rows.append(_run(a, s, m, "group1024",
                     cfg_overrides=dict(moe_group=1024)))
    # H3: fewer microbatches → less recompute per step (compute term)
    rows.append(_run(a, s, m, "micro2", micro_override=2))
    # H4 (beyond-paper): d_model=1024 over 16-way TP is slivers — drop TP
    # entirely, keep EP on "model" + FSDP over both axes. Kills the
    # Megatron all-reduces that dominate this cell.
    rows.append(_run(a, s, m, "ep-only",
                     cfg_overrides=dict(sharding_profile="ep_only")))
    return _show(rows)


def cell_commandr():
    """command-r-35b decode_32k single — memory-bound decode.

    Baseline doubles the KV cache via kv_repeat (TP>kv). Variant:
    sequence-sharded cache (flash-decoding layout) — no replication.
    """
    a, s, m = "command-r-35b", "decode_32k", "single"
    rows = [_run(a, s, m)]
    rows.append(_run(a, s, m, "seqshard",
                     cfg_overrides=dict(
                         kv_repeat=1,
                         attn_kv_spec=(("data",), "model", None, None))))
    rows.append(_run(a, s, m, "seqshard-f32stats",
                     cfg_overrides=dict(
                         kv_repeat=1,
                         attn_chunk_threshold=1 << 30,
                         attn_kv_spec=(("data",), "model", None, None))))
    return _show(rows)


def cell_jamba():
    """jamba-398B train_4k single — biggest model, smaller mesh."""
    a, s, m = "jamba-1.5-large-398b", "train_4k", "single"
    rows = [_run(a, s, m)]
    # H1: selective remat (keep matmul outputs) trades memory for flops
    rows.append(_run(a, s, m, "remat-dots",
                     cfg_overrides=dict(remat="dots")))
    # H2: fewer microbatches → fewer recompute passes, more activation mem
    rows.append(_run(a, s, m, "micro8", micro_override=8))
    # H3: larger SSD chunks → bigger MXU matmuls, fewer scan steps
    rows.append(_run(a, s, m, "ssdchunk256",
                     cfg_overrides=dict(ssm_chunk=256)))
    # H4: keep shrinking the regather traffic (micro8 confirmed H2)
    rows.append(_run(a, s, m, "micro4", micro_override=4))
    return _show(rows)


def cell_sim():
    """NUMA-strategy hillclimb on the NANOS simulator (fft medium @ 16).

    Each variant flips exactly one execution-context knob relative to
    the previous row — the paper's §IV→§V→§VI progression, plus the
    policy layer's hierarchical-stealing step beyond it.

    Every cell is evaluated through the persistent result store
    (artifacts/hillclimb/sim_cells.jsonl): repeated searches over the
    same (topology, workload) replay already-scored variants from the
    journal instead of re-simulating them — the substrate the ROADMAP
    auto-tuner's search loop builds on.
    """
    from repro.core import topology
    from repro.core.sim import Machine, ResultStore, bots

    m = Machine(topology.sunfire_x4600())
    wl = bots.fft(n=1 << 15, cutoff=4)
    serial = m.serial_time(wl, placement="spill:2@0")
    os.makedirs(ART, exist_ok=True)
    store = ResultStore(os.path.join(ART, "sim_cells.jsonl"))
    base = dict(placement="spill:2@0", runtime_data=0, migration_rate=0.15)
    variants = [
        ("baseline-nanos", "wf", dict(binding="linear", **base)),
        ("+priority-binding", "wf", dict(binding="paper", **base)),
        ("+pin-threads", "wf",
         dict(binding="paper", placement="spill:2@0", runtime_data=0)),
        ("+local-runtime", "wf",
         dict(binding="paper", placement="spill:2@0")),
        ("+master-spill", "wf", dict(binding="paper", placement="spill:2")),
        ("+dfwsrpt-stealing", "dfwsrpt",
         dict(binding="paper", placement="spill:2")),
        ("hier-stealing", "dfwshier",
         dict(binding="paper", placement="spill:2")),
    ]
    rows = []
    print(f"{'variant':22s} {'sched':10s} {'speedup':>8} {'remote%':>8} "
          f"{'steals':>8} {'queue_wait':>10}")
    for label, sched, ctx_kw in variants:
        r = m.run(wl, sched, seed=0, threads=16, serial_reference=serial,
                  store=store, **ctx_kw)
        rows.append(dict(variant=label, scheduler=sched,
                         speedup=round(r.speedup, 4),
                         remote_work_fraction=round(r.remote_work_fraction,
                                                    4),
                         steals=r.steals,
                         queue_wait=round(r.queue_wait, 2)))
        print(f"{label:22s} {sched:10s} {r.speedup:8.2f} "
              f"{r.remote_work_fraction * 100:8.2f} {r.steals:8d} "
              f"{r.queue_wait:10.1f}")
    print(f"[store] {store!r}")
    if m.compile_cache is not None:
        print(f"[compile-cache] {m.compile_cache!r}")
    store.close()
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", type=int, default=0,
                    help="1=granite 2=command-r 3=jamba 4=sim-strategy; "
                         "0=all")
    args = ap.parse_args()
    os.makedirs(ART, exist_ok=True)
    out = {}
    if args.cell in (0, 1):
        print("== granite-moe-1b-a400m × train_4k × multi ==")
        out["granite"] = cell_granite()
    if args.cell in (0, 2):
        print("== command-r-35b × decode_32k × single ==")
        out["commandr"] = cell_commandr()
    if args.cell in (0, 3):
        print("== jamba-1.5-large-398b × train_4k × single ==")
        out["jamba"] = cell_jamba()
    if args.cell in (0, 4):
        print("== NANOS sim × fft-medium × NUMA strategy ==")
        out["sim"] = cell_sim()
    with open(os.path.join(ART, "summary.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
