"""Architectures, one module each, found by the name a configuration
file gives under ``"architecture"``.

The harness reaches a model's reference and its work counts only
through the module of its architecture, ``archs/<name>.py``, which
gives:

* ``make_weights(model, dtype, seed, mesh)``: the weights of the recipe
  the configuration names (``"weights"``), drawn on the device from the
  seed in one jitted call, placed over ``mesh``;
* ``hidden(model, params, tokens, *, control)``: the final-norm hidden
  states [B, S, d] of token ids [B, S], in float32 (the caller sets the
  matmul precision), or one precision lower with ``control``;
* ``logits(params, x, *, control)``: float32 logits [M, V] of hidden
  rows ``x`` [M, d];
* ``Shapes.of(model, dtype)``, whose ``param_bytes()`` and
  ``kv_bytes_per_token()`` count the model, and ``step_flops(step)``
  and ``step_bytes(step)`` the work one ``records.StepRecord`` needs.

``model`` is the configuration's ``"model"`` block.  A new architecture
is a new file here and a configuration that names it.  A test derives
the list of architectures from this directory and never spells it out.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def known(*dirs: Path) -> list[str]:
    """The architectures in this directory and in ``dirs``."""
    return sorted(p.stem for d in (HERE, *dirs) for p in d.glob("*.py")
                  if not p.name.startswith("_"))


def load(name: str, *dirs: Path):
    """The module of architecture ``name``, from this directory or the
    first of ``dirs`` that has it.  Exits, naming the known ones, where
    none has."""
    found = [d / f"{name}.py" for d in (HERE, *dirs)
             if name.isidentifier() and not name.startswith("_")
             and (d / f"{name}.py").is_file()]
    if not found:
        raise SystemExit(f"unknown architecture {name!r}; known: "
                         f"{known(*dirs)}")
    key = f"{__name__}.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, found[0])
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]
