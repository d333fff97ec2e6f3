"""Train driver: the jitted step of ``launch/train.build_train_step`` in the
loop ``launch/train.train`` runs, fed from the benchmark's own traffic.

Set-up builds the step and its state once, runs the mix's first
``checked_steps`` steps through the same call and feed as the window (on
distinct rows), and records what the check compares; the same objects
then run the window. After the window the program's state is freed and
the reference repeats those first steps from the same seed.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import check, flops, harness, kinds, weights
from bench.harness import span
from bench.reference import model as ref_model
from bench.reference import run as ref_run
from bench.traffic import gen


def opt_config(mix: dict):
    from repro.optim import AdamWConfig

    return AdamWConfig(lr_peak=mix["lr"], warmup_steps=mix["warmup_steps"],
                       total_steps=mix["total_steps"],
                       lr_min_ratio=mix["lr_min_ratio"], b1=mix["b1"],
                       b2=mix["b2"], eps=mix["eps"],
                       weight_decay=mix["weight_decay"],
                       clip_norm=mix["clip_norm"])


class State:
    """The compiled step with its state and feed, built once in set-up."""

    def __init__(self, cfg, kind, mix: dict, seed: int):
        from repro.data import Prefetcher
        from repro.launch.mesh import line_steal_table
        from repro.launch.train import build_train_step
        from repro.optim import adamw_init

        self.opt_cfg = opt_config(mix)
        self.key = weights.seed_key(seed)
        self.init = weights.make_init(weights.layout(cfg), kind)
        self.params = self.init(self.key)
        self.opt_state = jax.jit(lambda p: adamw_init(p, self.opt_cfg))(
            self.params)
        self.comp_state = None
        # the steal table train() builds
        table = (line_steal_table(cfg.moe_num_experts, cfg.moe_steal_policy)
                 if cfg.moe_num_experts else None)
        self.step_fn = jax.jit(build_train_step(cfg, self.opt_cfg, 1, table))
        self.pool = gen.train_pool(mix, cfg.vocab_size, seed)
        self.it = Prefetcher(itertools.cycle(self.pool))

    def step(self):
        """The loop body of train(): next batch, step, wait for the loss."""
        with span("next_batch"):
            batch = next(self.it)
        with span("dispatch"):
            (self.params, self.opt_state, self.comp_state, loss,
             gnorm) = self.step_fn(self.params, self.opt_state,
                                   self.comp_state, batch)
        with span("wait"):
            return float(jax.block_until_ready(loss)), gnorm

    def checked(self, n: int) -> dict:
        """Run the first n steps; the readings the check compares."""
        losses = []
        for i in range(n):
            loss, gnorm = self.step()
            losses.append(loss)
            if i == 0:
                clip = max(float(gnorm) / self.opt_cfg.clip_norm, 1.0)
                # the first gradient as the optimizer got it, unclipped,
                # from its first moment after one step
                grad = check.leaf_norms(jax.tree.map(
                    lambda m: m * (clip / (1 - self.opt_cfg.b1)),
                    self.opt_state["m"]))
        change = check.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            self.params, self.init(self.key)))
        return {"losses": losses, "change": change, "grad": grad}

    def close(self):
        self.it.close()
        del self.params, self.opt_state, self.comp_state


def reference(fcfg: dict, mix: dict, init, key, pool,
              num=ref_model.Numerics()) -> dict:
    """The reference's first steps from the same seed and rows."""
    kind = kinds.kind(fcfg)
    spec = kind.spec_from_file(fcfg)
    opt = {k: mix[k] for k in ("lr", "warmup_steps", "total_steps",
                               "lr_min_ratio", "b1", "b2", "eps",
                               "weight_decay", "clip_norm")}
    return ref_run.train_steps(init(key), kind, spec,
                               pool[:mix["checked_steps"]], opt,
                               kind.train_table(spec), num)


def run(cfg, fcfg: dict, mix: dict, seed: int, seconds: float, traced: bool,
        limits: dict, cell: dict, t_start: float) -> dict:
    kind = kinds.kind(fcfg)
    st = State(cfg, kind, mix, seed)
    prog = st.checked(mix["checked_steps"])
    setup_s = time.perf_counter() - t_start
    steps, loss = 0, float("nan")
    with harness.Window(seconds, traced, cell["name"]) as win:
        with span("bench.window"):
            while win.open():
                loss, _ = st.step()
                steps += 1
    elapsed = win.t_end - win.t0
    memory_peak = harness.memory_peak_bytes()
    st.close()

    numbers = check.train_numbers(
        prog, reference(fcfg, mix, st.init, st.key, st.pool))
    ok, checks = check.judge(numbers, limits)
    if win.counter.events:
        ok = False
        checks["compiles_in_window"] = (len(win.counter.events), 0)
    result = {"correct": bool(ok and np.isfinite(loss)), "attempted": steps,
              "failed": 0, "setup_s": setup_s, "memory_peak": memory_peak,
              "checks": checks, "numbers": numbers}
    if traced:
        result["ctx"] = {"trace": win.reduced_trace(), "peaks": None,
                         "work": {"train_step": flops.train_step(
                             kind, cfg, mix["batch"], mix["seq_len"])}}
    else:
        tokens = steps * mix["batch"] * mix["seq_len"]
        result["metrics"] = {"train_tokens_per_s": {
            "value": tokens / elapsed, "unit": "tokens/s"}}
    return result
