"""Record the device trace that test_scope_reduction.py pins, on a TPU.

    python3 bench/tests/record_scoped.py <out dir>

A tiny train step, jitted as ``step_fn``: ``jax.grad`` of a loss over a
``lax.scan`` of four layers, each layer under ``jax.checkpoint`` (as the
model's stack is) and made of two scoped matmuls (``mamba.in_proj``,
``mamba.out``); the loss and the update lie in no scope. Six steps run
inside the harness's ``bench.window`` span with its host spans. Writes
``scoped.xplane.pb`` and ``scoped.hlo.txt`` (the compiled step) into the
out dir, and prints what bench/scopes.py reads from the trace.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import scopes, trace  # noqa: E402
from bench.harness import span  # noqa: E402

LAYERS, WIDTH, STEPS = 4, 1024, 6


def step_fn(w, x):
    def layer(h, wi):
        with jax.named_scope("mamba.in_proj"):
            h = jnp.tanh(h @ wi)
        with jax.named_scope("mamba.out"):
            h = h * jax.nn.sigmoid(h @ wi.T)
        return h, None

    def loss(w):
        h, _ = jax.lax.scan(jax.checkpoint(layer), x, w)
        return jnp.sum(jnp.square(h.astype(jnp.float32)))

    return w - 1e-3 * jax.grad(loss)(w).astype(w.dtype)


def main(out: Path):
    if jax.default_backend() != "tpu":
        raise SystemExit("record_scoped: no TPU; nothing recorded")
    kw, kx = jax.random.split(jax.random.PRNGKey(0))
    w = (jax.random.normal(kw, (LAYERS, WIDTH, WIDTH)) / WIDTH ** 0.5
         ).astype(jnp.bfloat16)
    x = jax.random.normal(kx, (WIDTH, WIDTH)).astype(jnp.bfloat16)
    step = jax.jit(step_fn)
    hlo = step.lower(w, x).compile().as_text()
    w = jax.block_until_ready(step(w, x))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with span("bench.window"):
            for _ in range(STEPS):
                with span("dispatch"):
                    w = step(w, x)
                with span("wait"):
                    jax.block_until_ready(w)
        jax.profiler.stop_trace()
        src = max(Path(tmp).rglob("*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out / "scoped.xplane.pb")
    (out / "scoped.hlo.txt").write_text(hlo)
    tr = trace.load(out / "scoped.xplane.pb")
    secs, steps = scopes.charge(tr, scopes.op_names(out / "scoped.xplane.pb"))
    print(json.dumps({"device_kind": jax.devices()[0].device_kind,
                      "busy_s": trace.reduce(tr).busy_s, "steps": steps,
                      "seconds": secs}, indent=1))


if __name__ == "__main__":
    main(Path(sys.argv[1]))
