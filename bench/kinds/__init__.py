"""Model kinds: everything of the benchmark that differs by ``model_type``.

A configuration file names its kind by ``model_type`` (none means
``mamba2``), and :func:`kind` imports the module of that name from this
package's search path, ``__path__``. A new kind is a new module here; the
rest of the harness calls the module and never branches on a kind.

A kind module holds:

- ``WIDTHS``: (file key, ``ArchConfig`` field) pairs that must agree with
  the program's registered config, and ``overrides(f)``: the ``ArchConfig``
  fields the file sets (``bench/spec.py``);
- ``spec_from_file(f)``: the reference's sizes (a ``Spec``, or a subclass
  with fields of the kind's own); ``slots(spec)``: one reference function
  per slot of the layer period, in order, each
  ``(x, p, spec, num, prompt_len, table) -> (x, aux)``; ``head(params)``:
  the (V, D) output projection, tied or untied; ``train_table(spec)`` and
  ``serve_table(spec)``: the steal tables the reference routes with;
- ``work(cfg, B, S, start, carried)``: the operations and bytes of the
  layers of one forward pass, from ``bench/flops.py``'s primitives;
- optionally ``WEIGHTS``: rules to draw leaf names that
  ``bench/weights.py`` has none for, ``{name: (key, shape) -> f32 array}``;
- ``small(f, base)``: the program config and file at a small size for CPU
  tests, from the program's ``reduced()`` config ``base``; ``f`` is a copy
  and may be changed.

The reference parts import nothing of the program.
"""

from __future__ import annotations

import importlib


def kind(f: dict):
    """The kind module of configuration file ``f``."""
    name = f.get("model_type", "mamba2")
    return importlib.import_module(f"{__name__}.{name}")
