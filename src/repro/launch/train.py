"""End-to-end training driver.

Production-shaped loop: grad accumulation, checkpoint-every-k with async
writes + exact resume (stateless data pipeline), optional int8-compressed
cross-pod gradients, and the paper's MoE steal tables. The step is jitted
for one device; the mesh path is not wired in yet.

``main`` parses arguments and builds the config; :func:`train` runs the
loop for any ``ArchConfig`` (``chip_smoke.py`` passes a depth-cut one).
``--reduced`` gives the small same-family config the CPU tests use; the
published configs are sized for a TPU. Example:

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --reduced --steps 50 --global-batch 8 --seq-len 128
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import PipelineConfig, Prefetcher, TokenPipeline
from repro.launch.jax_cache import use_persistent_compile_cache
from repro.launch.mesh import line_steal_table
from repro.models import model as model_lib
from repro.optim import (AdamWConfig, accumulate_gradients, adamw_init,
                         adamw_update, compressed_gradients)


def build_train_step(cfg, opt_cfg, n_micro, steal_table, compress=False):
    def step_fn(params, opt_state, comp_state, batch):
        loss, grads, metrics = accumulate_gradients(
            lambda p, b: model_lib.train_loss(p, cfg, b,
                                              steal_table=steal_table),
            params, batch, n_micro)
        if compress:
            grads, comp_state = compressed_gradients(grads, comp_state)
        params, opt_state, om = adamw_update(grads, opt_state, params,
                                             opt_cfg)
        return params, opt_state, comp_state, loss, om["grad_norm"]
    return step_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="same-family small config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 + error feedback (cross-pod wire format)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, remat="none" if args.reduced else "full")
    history = train(
        cfg, steps=args.steps, global_batch=args.global_batch,
        seq_len=args.seq_len, microbatches=args.microbatches, lr=args.lr,
        warmup=args.warmup, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        compress_grads=args.compress_grads, seed=args.seed,
        log_every=args.log_every)
    return history[-1].loss if history else float("nan")


@dataclasses.dataclass(frozen=True)
class StepLog:
    step: int
    loss: float
    grad_norm: float
    seconds: float          # host clock around the step, to its loss


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          microbatches: int = 1, lr: float = 3e-4, warmup: int = 20,
          checkpoint_dir: str | None = None, checkpoint_every: int = 50,
          compress_grads: bool = False, seed: int = 0,
          log_every: int = 10) -> list[StepLog]:
    """Run the training loop for ``cfg``; one :class:`StepLog` per step."""
    # paper technique: steal table from the (modeled) topology
    steal = (line_steal_table(cfg.moe_num_experts, cfg.moe_steal_policy)
             if cfg.moe_num_experts else None)

    key = jax.random.PRNGKey(seed)
    params = model_lib.init_params(cfg, key)
    opt_cfg = AdamWConfig(lr_peak=lr, warmup_steps=warmup,
                          total_steps=steps)
    opt_state = adamw_init(params, opt_cfg)

    pipe = TokenPipeline(PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        global_batch=global_batch, seed=seed,
        embeds_dim=cfg.d_model if cfg.embeds_input else 0,
        media_tokens=cfg.num_media_tokens, d_model=cfg.d_model))

    start_step = 0
    mgr = None
    if checkpoint_dir:
        mgr = CheckpointManager(checkpoint_dir, keep_last=3)
        got = mgr.restore_latest({"params": params, "opt": opt_state})
        if got[0] is not None:
            start_step, tree = got
            params, opt_state = tree["params"], tree["opt"]
            print(f"[train] resumed from step {start_step}")

    step_fn = jax.jit(build_train_step(cfg, opt_cfg, microbatches,
                                       steal, compress_grads))
    comp_state = None
    it = Prefetcher(pipe.iter_from(start_step))

    t_start = time.time()
    tokens_done = 0
    history: list[StepLog] = []
    for step in range(start_step, steps):
        batch = next(it)
        t0 = time.time()
        params, opt_state, comp_state, loss, gnorm = step_fn(
            params, opt_state, comp_state, batch)
        loss = float(jax.block_until_ready(loss))
        dt = time.time() - t0
        gnorm = float(gnorm)
        history.append(StepLog(step, loss, gnorm, dt))
        tokens_done += global_batch * seq_len
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"gnorm {gnorm:7.3f} {dt*1e3:7.1f} ms/step "
                  f"{tokens_done/(time.time()-t_start):9.0f} tok/s")
        if mgr and (step + 1) % checkpoint_every == 0:
            mgr.save_async(step + 1, {"params": params, "opt": opt_state})
    if mgr:
        mgr.save_sync(steps, {"params": params, "opt": opt_state})
        mgr.wait()
    it.close()
    if history:
        print(f"[train] done: final loss {history[-1].loss:.4f}")
    return history


if __name__ == "__main__":
    use_persistent_compile_cache()
    main()
