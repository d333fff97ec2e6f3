"""CPU tests of bench/scopes.py: the layer-scope reduction of a device
trace recorded on the chip (``bench/testdata/scoped.xplane.pb``, made by
``record_scoped.py``), and its failures.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -n 6 --dist loadfile
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import scopes, trace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
SCOPED = TESTDATA / "scoped.xplane.pb"


@pytest.fixture(scope="module")
def scoped():
    """(trace, op names, pinned numbers) of the recorded scoped step."""
    return (trace.load(SCOPED), scopes.op_names(SCOPED),
            json.loads((TESTDATA / "scoped.pinned.json").read_text()))


def test_the_benchmark_reads_the_programs_scopes():
    from repro.models.scopes import SCOPES

    assert scopes.SCOPES == SCOPES


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step_fn)/jvp()/while/body/closed_call/mamba.conv/mul",
     "mamba.conv"),
    ("jit(step_fn)/transpose(jvp(head))/dot_general", "head"),
    ("jit(step_fn)/jvp()/while/body/closed_call/moe.combine/mlp/dot_general",
     "mlp"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mamba.ssd/while/body/dynamic_update_slice",
     "mamba.ssd"),
    ("jit(step_fn)/transpose(jvp())/while/body/dynamic_update_slice", None),
    ("", None),
])
def test_the_innermost_scope_names_the_layer(op_name, scope):
    assert scopes.innermost(op_name) == scope


def test_scope_reduction_of_a_recorded_chip_trace(scoped):
    tr, names, pinned = scoped
    secs, steps = scopes.charge(tr, names)
    assert steps == pinned["steps"]
    assert set(secs) == set(pinned["seconds"])
    for key, want in pinned["seconds"].items():
        assert secs[key] == pytest.approx(want, rel=1e-9), key
    busy = trace.reduce(tr).busy_s
    assert busy == pytest.approx(pinned["busy_s"], rel=1e-9)
    layers = sum(v for k, v in secs.items() if k != scopes.RECOMPUTE)
    assert layers == pytest.approx(busy, rel=1e-6)
    # the recomputed forward is a part of its layers' time
    assert 0 < secs[scopes.RECOMPUTE] < secs["mamba.in_proj"] + secs[
        "mamba.out"]


def test_the_trace_carries_each_ops_op_name():
    names = scopes.op_names(TESTDATA / "small.xplane.pb")
    fusion = [n for n in names if n.startswith("%fusion = ")]
    assert [names[n] for n in fusion] == ["jit(<lambda>)/dot_general"]
    # a copy the compiler made has no op_name
    assert names[next(n for n in names if n.startswith("%copy-done"))] == ""


def test_an_op_with_no_op_name_in_the_trace_fails(scoped):
    tr, names, _ = scoped
    some = next(n for n in tr.chips[0].op_names)
    with pytest.raises(ValueError, match="no metadata"):
        scopes.charge(tr, {k: v for k, v in names.items() if k != some})


def test_another_program_in_the_window_fails():
    tr = trace.load(TESTDATA / "small.xplane.pb")
    with pytest.raises(ValueError, match="programs other than step_fn"):
        scopes.charge(tr, scopes.op_names(TESTDATA / "small.xplane.pb"))


def _ctx(scoped, monkeypatch, names):
    tr, _, _ = scoped
    monkeypatch.setattr(scopes, "traced_file", lambda red: (SCOPED, tr))
    monkeypatch.setattr(scopes, "op_names", lambda path: names)
    return {"trace": trace.reduce(tr), "work": {"train_step": object()}}


def test_per_step_ms_divides_by_the_steps_in_the_window(scoped,
                                                        monkeypatch):
    tr, names, pinned = scoped
    ctx = _ctx(scoped, monkeypatch, names)
    want = 1e3 * pinned["seconds"]["mamba.out"] / pinned["steps"]
    assert scopes.per_step_ms(ctx, "mamba.out") == pytest.approx(want)
    both = scopes.per_step_ms(ctx, "mamba.out", "mamba.in_proj")
    assert both > want
    assert scopes.per_step_ms(ctx, "optimizer") == 0.0


def test_a_program_without_scopes_reads_nothing(scoped, monkeypatch):
    _, names, _ = scoped
    ctx = _ctx(scoped, monkeypatch, {k: "" for k in names})
    assert scopes.per_step_ms(ctx, "unscoped") is None
    assert scopes.per_step_ms({"work": {}}, "unscoped") is None
