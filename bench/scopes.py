"""Charge the device time of the train step to the program's layer scopes.

The program wraps each layer of its step in a ``jax.named_scope``; the
names are ``SCOPES`` below, as ``repro.models.scopes`` lists them (a
test checks that the two agree). The name lands in the HLO ``op_name`` of
every op of the layer, forward, backward and recomputed, and a TPU trace
carries it: the event metadata of each op on the "XLA Ops" line holds a
``tf_op`` stat, ``<op_name>:<op type>``. ``jax.profiler.ProfileData``
does not expose event metadata, so ``op_names`` reads it from the
``.xplane.pb`` itself.

``charge`` gives each op's self time in the window (``trace.self_times``)
to the innermost scope on its op_name, else to ``unscoped``, and also to
``recompute`` where the op_name holds JAX's remat marker. The scopes and
``unscoped`` add up to the device's busy time. An op with no metadata in
the trace, or a program other than the step in the window, is an error.
"""

from __future__ import annotations

import re

import numpy as np

from bench import harness, trace as trace_mod

SCOPES = ("embed", "norm", "mamba.in_proj", "mamba.conv", "mamba.ssd",
          "mamba.out", "attention", "mlp", "moe.route", "moe.dispatch",
          "moe.experts", "moe.combine", "head", "optimizer")
UNSCOPED, RECOMPUTE = "unscoped", "recompute"
REMAT = "rematted_computation"   # JAX's name for a recomputed forward
STEP = "step_fn"
# a transformed component, as in "transpose(jvp(head))"
_WRAP = re.compile(r"^(?:\w+\()*(.*?)\)*$")


def _fields(buf: bytes):
    """(field number, value) of a protobuf message: an int for a varint,
    the bytes for a length-delimited field, raw bytes for fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in the trace")
        yield key >> 3, v


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def op_names(path) -> dict[str, str]:
    """The op_name of each op of the TPU planes, by its event name ("" for
    an op the compiler made with no op_name, such as a copy).

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and
    stat_metadata = 5 (maps: key 1, value 2); XEventMetadata: name = 2,
    stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
    str_value = 5, ref_value = 7 (tsl/profiler/protobuf/xplane.proto).
    """
    with open(path, "rb") as f:
        space = f.read()
    out: dict[str, str] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((v.decode() for k, v in fields if k == 2), "")
        if not trace_mod._DEVICE.match(name):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        for k, entry in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            ev = next((v.decode() for f, v in meta if f == 2), "")
            op = ""
            for f, stat in meta:
                if f != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == "tf_op":
                    op = (st[5].decode() if 5 in st
                          else stat_names.get(st.get(7), ""))
                    op = op.rsplit(":", 1)[0]
            if out.setdefault(ev, op) != op:
                raise ValueError(f"two op_names for the op {ev[:80]!r}: "
                                 f"{out[ev]!r}, {op!r}")
    return out


def innermost(op_name: str) -> str | None:
    """The innermost scope on an op_name path, or None."""
    found = [n for n in (_WRAP.match(c).group(1) for c in op_name.split("/"))
             if n in SCOPES]
    return found[-1] if found else None


def charge(tr: "trace_mod.Trace", names: dict[str, str]
           ) -> tuple[dict[str, float], int]:
    """(device self seconds in the window by scope, with UNSCOPED and
    RECOMPUTE; executions of the step) on the first chip."""
    (w0, w1), = [(a, b) for n, a, b in tr.spans if n == trace_mod.WINDOW]
    chip = tr.chips[0]
    other = sorted({m for m in chip.module_names if STEP not in m})
    if other:
        raise ValueError(f"programs other than {STEP} in the window: "
                         f"{other[:5]}")
    lost = sorted({n for n in chip.op_names if n not in names})
    if lost:
        raise ValueError(f"{len(lost)} ops in the trace have no metadata, "
                         f"so no op_name: {[n[:80] for n in lost[:3]]}")
    own = trace_mod.self_times(np.clip(chip.ops, w0, w1)) * 1e-9
    out: dict[str, float] = {UNSCOPED: 0.0, RECOMPUTE: 0.0}
    for name, t in zip(chip.op_names, own):
        op = names[name]
        key = innermost(op) or UNSCOPED
        out[key] = out.get(key, 0.0) + t
        if REMAT in op.split("/"):
            out[RECOMPUTE] += t
    return out, len(chip.module_names)


def traced_file(red: "trace_mod.Reduced"):
    """The newest trace the harness wrote, checked to be the one ``red``
    was reduced from."""
    path = max((harness.OUT_DIR / "traces").rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    tr = trace_mod.load(path)
    win = [(a, b) for n, a, b in tr.spans if n == trace_mod.WINDOW]
    if win[:1] != [tuple(red.window)]:
        raise ValueError(f"{path} is not the trace of this run's window")
    return path, tr


def per_step_ms(ctx: dict, *keys: str) -> float | None:
    """Device self ms per train step of the ops charged to ``keys``; None
    if the run trained nothing or the program names no scope."""
    if "train_step" not in ctx["work"]:
        return None
    if "scope_ms" not in ctx:
        path, tr = traced_file(ctx["trace"])
        secs, steps = charge(tr, op_names(path))
        named = any(s in secs for s in SCOPES)
        ctx["scope_ms"] = ({k: 1e3 * v / steps for k, v in secs.items()}
                           if named and steps else None)
    ms = ctx["scope_ms"]
    return None if ms is None else sum(ms.get(k, 0.0) for k in keys)
