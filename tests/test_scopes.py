"""Every matrix op of the compiled model step lies in one named layer scope
(``repro.models.scopes``), forward, backward and recomputed, in train,
prefill and decode; the scopes seen are the documented ones, and each
layer a configuration runs shows up under its name."""

import ast
import dataclasses
import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch.train import build_train_step
from repro.models import model
from repro.models.scopes import SCOPES
from repro.optim import AdamWConfig, adamw_init

B, S = 2, 256
# the layers each configuration runs, by mode
EXPECTED = {
    "mamba2-1.3b": {"mamba.in_proj", "mamba.conv", "mamba.ssd", "mamba.out",
                    "norm", "head", "embed"},
    "granite-moe-1b-a400m": {"attention", "moe.route", "moe.dispatch",
                             "moe.experts", "moe.combine", "norm", "head",
                             "embed"},
}
MODES = ("train-remat-full", "train-remat-none", "prefill", "decode")
SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
_WRAP = re.compile(r"^(?:\w+\()*(.*?)\)*$")
_MATRIX = re.compile(r" (dot|convolution|custom-call)\(")


def innermost(op_name: str):
    """The innermost documented scope on an op_name path; a transformed
    component such as ``transpose(jvp(head))`` names ``head``."""
    found = [n for n in (_WRAP.match(c).group(1)
                         for c in op_name.split("/")) if n in SCOPES]
    return found[-1] if found else None


@functools.lru_cache(maxsize=None)
def compiled_text(arch: str, mode: str) -> str:
    cfg = configs.get(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: model.init_params(cfg, k), key)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if mode.startswith("train"):
        cfg = dataclasses.replace(cfg, remat=mode.rsplit("-", 1)[1])
        opt = AdamWConfig()
        state = jax.eval_shape(lambda p: adamw_init(p, opt), params)
        fn = jax.jit(build_train_step(cfg, opt, 1, None))
        lowered = fn.lower(params, state, None,
                           {"tokens": tokens, "labels": tokens})
    elif mode == "prefill":
        lowered = jax.jit(lambda p, t: model.prefill(p, cfg, t, max_len=S + 4)
                          ).lower(params, tokens)
    else:
        caches = jax.eval_shape(
            lambda p, t: model.prefill(p, cfg, t, max_len=S + 4)[1],
            params, tokens)
        lowered = jax.jit(lambda p, c, t: model.decode_step(p, cfg, c, t)
                          ).lower(params, caches,
                                  jax.ShapeDtypeStruct((B, 1), jnp.int32))
    return lowered.compile().as_text()


def parse(text: str):
    """(instructions of computations that are not fused, the fused
    computations that hold a matrix op). An instruction is (line,
    op_name)."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            m = re.search(r'op_name="([^"]*)"', line)
            cur.append((line, m.group(1) if m else ""))
    fused = {c for ins in comps.values() for line, _ in ins
             for c in re.findall(r" fusion\(.*calls=%([\w.-]+)", line)}

    @functools.lru_cache(maxsize=None)
    def holds_matrix(name):
        for line, _ in comps.get(name, []):
            if _MATRIX.search(line):
                return True
            if any(holds_matrix(c) for c in
                   re.findall(r"calls=%([\w.-]+)", line)):
                return True
        return False

    top = [i for name, ins in comps.items() if name not in fused
           for i in ins]
    return top, {c for c in fused if holds_matrix(c)}


def matrix_ops(text: str):
    top, with_matrix = parse(text)
    out = []
    for line, op_name in top:
        called = re.findall(r" fusion\(.*calls=%([\w.-]+)", line)
        if _MATRIX.search(line) or any(c in with_matrix for c in called):
            out.append((line, op_name))
    return out


CASES = [(a, m) for a in EXPECTED for m in MODES]
# A MoE decode step of a small batch routes one token group. XLA's
# batch-dot simplification may then rebuild the expert dots, whose group
# axis is 1, without metadata: such dots carry no op_name at all.
ONE_GROUP = [("granite-moe-1b-a400m", "decode")]


def _unscoped(ops):
    return [(op_name, line.split(" = ")[0].strip()) for line, op_name in ops
            if innermost(op_name) is None]


@pytest.mark.parametrize("arch,mode",
                         [c for c in CASES if c not in ONE_GROUP])
def test_every_matrix_op_lies_in_one_layer_scope(arch, mode):
    ops = matrix_ops(compiled_text(arch, mode))
    assert ops, "the parser found no matrix op"
    assert not _unscoped(ops), _unscoped(ops)[:10]
    if mode == "train-remat-full":
        assert any("rematted_computation" in n and innermost(n)
                   for _, n in ops), "no recomputed op carries its scope"


@pytest.mark.parametrize("arch,mode", ONE_GROUP)
def test_with_one_token_group_only_ops_without_metadata_lose_a_scope(
        arch, mode):
    ops = matrix_ops(compiled_text(arch, mode))
    assert ops, "the parser found no matrix op"
    named = [(line, n) for line, n in ops if n]
    assert named and not _unscoped(named), _unscoped(named)[:10]


def test_the_program_opens_only_the_documented_scopes():
    """Every ``jax.named_scope`` in the program names a documented scope
    as a string literal, and each documented scope is opened."""
    names = []
    for path in SRC.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope"):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant), (path, node.lineno)
                names.append(arg.value)
    assert set(names) == set(SCOPES), set(names) ^ set(SCOPES)


@pytest.mark.parametrize("arch,mode", CASES)
def test_each_layer_the_config_runs_appears(arch, mode):
    names = re.findall(r'op_name="([^"]*)"', compiled_text(arch, mode))
    seen = {innermost(n) for n in names} - {None}
    want = EXPECTED[arch] | ({"optimizer"} if mode.startswith("train")
                             else set())
    assert want <= seen, want - seen
    assert not seen - want, seen - want


def test_scope_names_are_unique_and_dotted_by_layer():
    assert len(set(SCOPES)) == len(SCOPES)
    assert all(re.fullmatch(r"[a-z_]+(\.[a-z_]+)?", s) for s in SCOPES)
