#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        [--fault half_batch] [--base-seed N]

For each seed the program runs what a benchmark run checks (train: the
first steps; serve: one finished batch of each prompt length) and is
compared with the reference: the lower readings. On the first
``--control`` seeds the control, the reference computed with fp8
operands in its matrix products, is compared with the reference too: the
upper readings. ``--fault half_batch`` (train) also runs the program
with its loss taken over half of each batch. Each reading is judged
against the cell's limits (``bench/limits/<cell>.json``) as a run is:
``correct`` must come out true for the program and false for the control
and the fault. Prints one JSON line per reading and writes them all to
``bench/out/<cell>.calibration.json``; the readings a cell's limits were
set from are kept in ``bench/limits/readings/<cell>.json``. Needs the
chip, as a benchmark run does.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def half_batch():
    """Patch the program's loss to drop the second half of every batch."""
    from repro.models import model as model_lib

    loss = model_lib.train_loss

    def half(params, cfg, batch, steal_table=None):
        n = batch["labels"].shape[0] // 2
        return loss(params, cfg, {k: v[:n] for k, v in batch.items()},
                    steal_table)

    model_lib.train_loss = half
    return lambda: setattr(model_lib, "train_loss", loss)


def train_readings(cfg, fcfg, mix, seed, control, fault):
    from bench import check, kinds
    from bench.drivers import train
    from bench.reference.model import Numerics

    kind = kinds.kind(fcfg)
    st = train.State(cfg, kind, mix, seed)
    prog = st.checked(mix["checked_steps"])
    st.close()
    ref = train.reference(fcfg, mix, st.init, st.key, st.pool)
    out = [("program", check.train_numbers(prog, ref))]
    if control:
        ctl = train.reference(fcfg, mix, st.init, st.key, st.pool,
                              Numerics(fp8=True))
        out.append(("control", check.train_numbers(ctl, ref)))
    if fault == "half_batch":
        undo = half_batch()
        try:
            fs = train.State(cfg, kind, mix, seed)
            bad = fs.checked(mix["checked_steps"])
            fs.close()
        finally:
            undo()
        out.append(("half_batch", check.train_numbers(bad, ref)))
    return out


def serve_readings(cfg, fcfg, mix, seed, control, fault):
    import numpy as np

    from bench import check, kinds, weights
    from bench.drivers import serve
    from bench.reference import run as ref_run
    from bench.reference.model import Numerics
    from bench.traffic import gen

    kind = kinds.kind(fcfg)
    key = weights.seed_key(seed)
    init = weights.make_init(weights.layout(cfg), kind)
    params = init(key)
    zipf = gen.Zipf(seed, cfg.vocab_size, mix["zipf_s"])
    done = {}
    i = 0
    while len(done) < len(mix["prompt_lens"]):
        P = gen.serve_prompt_len(mix, i)
        if P not in done:
            prompts = gen.serve_batch(mix, cfg.vocab_size, seed, i, zipf)
            pre, dec = serve.programs(cfg, params, mix["batch"], P,
                                      mix["gen"])
            done[P] = (prompts, serve.serve_batch(pre, dec, params, prompts,
                                                  mix["gen"])[0])
        i += 1
    del params
    spec = kind.spec_from_file(fcfg)
    table = kind.serve_table(spec)
    params = init(key)
    gaps = {"program": [], "control": []}
    for P, (prompts, tokens) in done.items():
        seq = np.concatenate([prompts, tokens[:, :-1]], 1)
        ref = ref_run.serve_logits(params, kind, spec, seq, P, table)
        gaps["program"].append(check.token_gaps(ref, tokens).ravel())
        if control:
            low = ref_run.serve_logits(params, kind, spec, seq, P, table,
                                       Numerics(fp8=True))
            gaps["control"].append(
                check.token_gaps(ref, low.argmax(-1)).ravel())
    return [(k, check.serve_numbers(np.concatenate(v)))
            for k, v in gaps.items() if v]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", default=None, choices=(None, "half_batch"))
    ap.add_argument("--base-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)

    from bench import check, harness, spec
    from bench.run import compile_cache, preflight

    cell = spec.cell(args.workload)
    preflight(cell["chips"])
    compile_cache()
    cfg = spec.arch_config(cell["config"])
    fcfg = spec.config_file(cell["config"])
    mix = spec.traffic(cell["traffic"])
    fn = train_readings if mix["driver"] == "train" else serve_readings
    limits = spec.limits(args.workload)
    rows = []
    for k in range(args.seeds):
        seed = args.base_seed + 7919 * k
        for kind, numbers in fn(cfg, fcfg, mix, seed, k < args.control,
                                args.fault if k < args.control else None):
            row = {"cell": args.workload, "seed": seed, "kind": kind,
                   "correct": check.judge(numbers, limits)[0], **numbers}
            rows.append(row)
            print(json.dumps(row), flush=True)
    harness.write_json(f"{args.workload}.calibration.json", rows)


if __name__ == "__main__":
    main()
