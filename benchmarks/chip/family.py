"""Model families: one file each, ``<paths[0]>/families/<family>.py``.

A configuration file names its family; the harness loads that file by path,
as it loads a metric reader, so a family in a new file needs no edit
anywhere else.  A family module provides:

* ``Shape``: a frozen dataclass that extends :class:`chip.shapes.Shape`;
* ``shape(config) -> Shape``: the sizes of a configuration file, refusing
  what its reference cannot compute;
* ``program_sizes(shape) -> dict``: the program's ``ArchConfig`` attributes
  (dotted paths) that must equal these sizes;
* ``leaves(shape)``: the served parameter leaves, in a fixed order
  (``chip.weights.Leaf``);
* ``hidden(params, tokens, shape, quant=False)``: the float32 reference's
  final normed hidden states (B, T, d);
* ``head(params, shape)``: the LM-head rows over the real vocabulary,
  (vocab, d) in float32;
* the counts ``non_embedding_params``, ``head_params``,
  ``weight_read_bytes``, ``decode_token_flops(s, ctx)``,
  ``prefill_flops(s, tokens)`` and ``decode_step_bytes(s, step)``, where
  ``step`` is the harness's ``chip.layer.Step`` record.

A family with routed layers (a top-k router over experts, some of them
held on this chip) may also provide

* ``hidden_ties(params, tokens, shape, margin) -> (hidden, ties)``: the
  states ``hidden`` gives and, from the same float32 pass (not a second
  forward), a (B, T) boolean: True where, in any routed layer, the
  reference's own choice of an expert held on this chip lies within
  ``margin`` of changing, at the group boundary of a grouped router and at
  the top-k boundary (``chip.reference.topk_near`` finds both).  A score
  that sums n selection keys (a group's two best) is compared at n times
  the margin.  Where the router normalises the weights over the chosen
  experts, a held expert's weight also jumps when a group near its
  boundary changes the chosen set while a held expert is chosen: mark
  that too.

Its configuration turns the marks on with four keys under ``check``:
``route_margin`` (delta, the margin for two selection keys), sized on the
chip from the spread of the router's selection keys, the served program's
against the float32 reference's over the check's sample: a choice flips
only where two keys lie closer than their two errors together, so delta is
at least twice the widest error, with room; ``max_route_tie_share`` (the
largest share of served positions that may be marked, above what sound
runs read); and ``route_margin_why`` and ``max_route_tie_share_why`` (the
readings each was set from).  The check then compares every unmarked
position at the unchanged ``max_logit_gap``, and the marked share at its
own limit (``chip.check``).  A family without routed layers provides
nothing more, and its configuration sets none of these keys.

A family may reuse another's pieces by loading its file:
``family.load(Path(__file__).with_name("decoder.py"))``.
"""
from __future__ import annotations

import hashlib
import importlib.util
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict

HERE = Path(__file__).resolve().parent      # the benchmark's own directory

_LOADED: Dict[Path, ModuleType] = {}


def load(path: Path) -> ModuleType:
    """The family module at ``path``, loaded once: its ``Shape`` class is
    the same object every time."""
    path = Path(path).resolve()
    mod = _LOADED.get(path)
    if mod is None:
        # a name of its own per path; dataclasses look the module up by name
        tag = hashlib.sha1(str(path).encode()).hexdigest()[:12]
        name = f"chip_family_{path.stem.replace('-', '_')}_{tag}"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except Exception:
            del sys.modules[name]
            raise
        _LOADED[path] = mod
    return mod


def find(name: str, chip_dir: Path = HERE) -> ModuleType:
    """The family ``name`` of the benchmark directory ``chip_dir``."""
    path = Path(chip_dir) / "families" / f"{name}.py"
    if not path.is_file():
        found = sorted(p.stem for p in path.parent.glob("*.py"))
        raise ValueError(f"no model family {name!r} in {path.parent} "
                         f"(families found: {found})")
    return load(path)


def of(shape) -> ModuleType:
    """The family module whose ``Shape`` made ``shape``."""
    return sys.modules[type(shape).__module__]
