"""Where a configuration's shape meets the program: one layout module per
plain reference, ``bench/layouts/<reference>.py``, found by the
``"reference"`` key of the configuration file.

A layout gives

- ``KEYS``: the architectural keys of a configuration file it maps (the
  metadata keys in ``META`` are allowed in every file);
- ``model_config(m)``: the program's ``ModelConfig`` from those keys;
- ``stacks(m)``: the program's layer stacks (``Stack``), in the order
  the program runs them;
- ``leaves(m)``: every parameter (``Leaf``): its program path, one
  layer's shape, its init scale and the id folded into its key;
- ``active_params_per_token(m)`` and ``attention_flops_per_token(m, S)``
  for ``bench/flops.py``.

Only the layouts import the program (its ``ModelConfig``); the reference
maths in ``bench/configs/`` imports nothing of it.
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass
from typing import Dict, Tuple

# keys every configuration file may hold, whatever its reference
META = frozenset({"name", "source", "reference", "reduced", "assumed",
                  "deployment", "why", "torch_dtype"})


class ConfigError(ValueError):
    """A configuration file its layout cannot run as written."""


@dataclass(frozen=True)
class Stack:
    """One of the program's stacks, ``params["stages"][si]["pos{j}"]``,
    its leaves stacked ``[rows, ...]``; ``block`` names the reference's
    function for one of its layers."""
    si: int
    pos: int
    rows: int
    block: str

    @property
    def sid(self) -> str:
        """The program's stack id (``meta["stack_idx"]`` keys)."""
        return f"s{self.si}/pos{self.pos}"

    @property
    def path(self) -> str:
        return f"stages/{self.si}/pos{self.pos}"


@dataclass(frozen=True)
class Leaf:
    """One parameter: ``name`` is the reference's name for it (inside its
    layer for a stacked leaf); ``shape`` is one layer's shape (the whole
    leaf for a top leaf); its values are a standard normal times
    ``scale`` from the key ``fold_in(fold_in(base, id), row)``, the row
    left out for a top leaf.  ``stack`` is the ``Stack.sid`` it is
    stacked in, "" for a top leaf."""
    name: str
    path: str
    shape: Tuple[int, ...]
    scale: float
    id: int
    stack: str = ""

    @property
    def size(self) -> int:
        return math.prod(self.shape)


def of(m: dict):
    """The layout module of configuration ``m``."""
    return importlib.import_module(f"bench.layouts.{m['reference']}")


def check(m: dict) -> None:
    """Refuse a configuration with a key that neither its layout maps nor
    ``META`` allows, naming the key."""
    extra = sorted(set(m) - META - of(m).KEYS)
    if extra:
        raise ConfigError(
            f"configuration {m.get('name')!r}: key(s) {', '.join(extra)} "
            f"not mapped by bench/layouts/{m['reference']}.py")


def table(m: dict) -> Dict[str, Leaf]:
    """Every leaf of ``m`` by program path."""
    return {l.path: l for l in of(m).leaves(m)}


def stack_rows(m: dict) -> Dict[str, int]:
    """Rows of every stack of ``m`` by stack id."""
    return {s.sid: s.rows for s in of(m).stacks(m)}


def numel(m: dict, leaf: Leaf) -> int:
    """Elements of ``leaf`` over all of its rows."""
    return leaf.size * (stack_rows(m)[leaf.stack] if leaf.stack else 1)
