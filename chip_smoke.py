#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip.

    python chip_smoke.py

Phases, in order, all in this one process (a chip belongs to one process
at a time, so nothing here starts another):

  preflight  JAX's default backend must be a TPU; there is no CPU fallback.
  serve      ``repro.launch.serve.main`` on granite-moe-1b-a400m as
             published (24 layers, bf16): batch 8, prompt 512, 32 new
             tokens. Every id is in the vocabulary and the prefill logits
             are finite.
  train      3 steps of ``repro.launch.train.train`` at granite's published
             widths, depth cut to 6 of 24 layers (the period is one layer):
             batch 8 x 1024 tokens, remat full. Loss and grad norm finite.
  kernels    each Pallas kernel of ``repro.kernels.ops`` once, bf16 inputs,
             against its ``kernels/ref.py`` oracle in f32 at highest matmul
             precision. Its compiled program must hold a ``tpu_custom_call``,
             so an oracle or interpret-mode fallback fails the phase.

Any failure raises and the script exits non-zero. Times printed are smoke
timings on the named device, not benchmark numbers. The last line of
stdout is one JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "granite-moe-1b-a400m"
TRAIN_LAYERS = 6


def check(ok, what: str):
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def preflight() -> dict:
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's default backend "
                         f"is {backend!r}); this script runs on a TPU only")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] preflight: platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']}")
    return device


def phase_serve(kind: str):
    from repro import configs
    from repro.launch import serve

    cfg = configs.get(ARCH)
    check(cfg.num_layers == 24 and cfg.dtype == "bfloat16",
          f"{ARCH} is not the published config")
    res = serve.main(["--arch", ARCH, "--batch", "8", "--prompt-len", "512",
                      "--gen", "32"])
    check(res.tokens.shape == (8, 32), f"tokens shape {res.tokens.shape}")
    check(((res.tokens >= 0) & (res.tokens < cfg.vocab_size)).all(),
          "generated id outside [0, vocab_size)")
    check(np.isfinite(res.prefill_logits).all(), "non-finite prefill logits")
    print(f"[smoke] serve ok on {kind} (smoke timings, not a benchmark): "
          f"compile {res.compile_s:.1f} s, prefill {res.prefill_s*1e3:.1f} "
          f"ms, decode {res.decode_s_per_token*1e3:.2f} ms/token")


def phase_train(kind: str):
    from repro import configs
    from repro.launch import train

    # ArchConfig refuses a depth that cuts a period
    cfg = dataclasses.replace(configs.get(ARCH), num_layers=TRAIN_LAYERS,
                              remat="full")
    hist = train.train(cfg, steps=3, global_batch=8, seq_len=1024,
                       log_every=1)
    check(len(hist) == 3, f"{len(hist)} steps ran, expected 3")
    for h in hist:
        check(np.isfinite(h.loss) and np.isfinite(h.grad_norm),
              f"step {h.step}: loss {h.loss}, grad norm {h.grad_norm}")
    print(f"[smoke] train ok on {kind} (smoke timings, not a benchmark; "
          f"step 0 includes compilation): "
          + ", ".join(f"step {h.step} {h.seconds*1e3:.1f} ms loss "
                      f"{h.loss:.4f}" for h in hist))


def _kernel_cases():
    """(name, kernel fn, oracle fn, bf16 inputs, tolerance, reason).

    The error is max |kernel - oracle| over max |oracle|.
    """
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def rand(shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale
                ).astype(jnp.bfloat16)

    D = 1024
    return [
        ("flash_attention",
         lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                             interpret=False),
         lambda q, k, v: ref.attention_ref(q, k, v, causal=True),
         [rand((2, 1024, 16, 64)), rand((2, 1024, 8, 64)),
          rand((2, 1024, 8, 64))],
         2e-2, "the MXU may round the f32 softmax weights to bf16 before "
               "p.v (2^-8 relative), and the output is bf16"),
        ("moe_gmm",
         lambda x, w: ops.moe_gmm(x, w, interpret=False),
         ref.moe_gmm_ref,
         [rand((32, 1280, D)), rand((32, D, 512), D ** -0.5)],
         1e-2, "products of bf16 inputs are exact in f32; only the f32 "
               "summation order and the bf16 output rounding (2^-8 "
               "relative) remain"),
        ("rmsnorm",
         lambda x, w: ops.rmsnorm(x, w, interpret=False),
         ref.rmsnorm_ref,
         [rand((8192, D)), rand((D,))],
         1e-2, "f32 math on both sides; the bf16 output rounding is "
               "2^-8 relative"),
        ("ssd_scan",
         lambda x, a, b, c: ops.ssd_scan(x, a, b, c, interpret=False),
         lambda x, a, b, c: ref.ssd_ref(x, a, b, c, return_state=True),
         [rand((1, 2048, 64, 64), 0.5),
          -jnp.abs(rand((1, 2048, 64), 0.1)),
          rand((1, 2048, 1, 128), 0.3), rand((1, 2048, 1, 128), 0.3)],
         3e-2, "the MXU may round the f32 decay-weighted scores and the "
               "carried state to bf16, and that error carries across 16 "
               "chunks; the output is bf16"),
    ]


def phase_kernels(kind: str):
    for name, fn, oracle, args, tol, reason in _kernel_cases():
        compiled = jax.jit(fn).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        got = jax.tree.leaves(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(oracle(*[a.astype(jnp.float32)
                                            for a in args]))
        for i, (g, w) in enumerate(zip(got, want)):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            check(g.shape == w.shape, f"{name}[{i}]: shape {g.shape} != "
                  f"{w.shape}")
            check(np.isfinite(g).all(), f"{name}[{i}]: non-finite output")
            err = float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-30))
            print(f"[smoke] kernel {name}[{i}] on {kind}: error {err:.3e} "
                  f"(tolerance {tol:g}: {reason})")
            check(err <= tol, f"{name}[{i}]: error {err:.3e} > {tol:g}")


def main():
    device = preflight()
    from repro.launch.jax_cache import use_persistent_compile_cache
    print(f"[smoke] compile cache: {use_persistent_compile_cache()}")
    phase_serve(device["kind"])
    phase_train(device["kind"])
    phase_kernels(device["kind"])
    check("repro.core.sim" not in sys.modules,
          "the simulator was imported; its engine forks")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
