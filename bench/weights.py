"""Seeded weights, made by the benchmark and not by the program: the
reference can then rebuild any layer from the seed alone.

Which leaves there are, where the program keeps them, their shapes and
scales come from the configuration's layout (``bench/layouts``).  Row
``l`` of a stacked leaf comes from its own key, ``fold_in(fold_in(base,
id), l)`` with the leaf's id, so one layer can be made without the
others.  ``program_params`` makes the whole tree in the layout the
program serves and trains (float32), in one jitted call on the device.
``start`` makes the same values, any rows of one leaf, for the checks.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench import layouts


def _leaf(key, leaf: layouts.Leaf, l=None):
    k = jax.random.fold_in(key, leaf.id)
    if l is not None:
        k = jax.random.fold_in(k, l)
    return jax.random.normal(k, leaf.shape, jnp.float32) * leaf.scale


def split_seed(seed: int):
    """(hi, lo) int32 words of a seed, as the jitted makers take them."""
    seed = int(seed)
    return jnp.int32((seed >> 31) & 0x7FFFFFFF), jnp.int32(seed & 0x7FFFFFFF)


def traced_key(seed_hi, seed_lo):
    """``base_key`` of a seed passed as traced words."""
    return jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)


def items(m: dict) -> tuple:
    """The scalar keys of a configuration, hashable for ``jit``."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str))))


def _start(seed_hi, seed_lo, rows, m, path):
    leaf = layouts.table(m)[path]
    key = traced_key(seed_hi, seed_lo)
    if not leaf.stack:
        return _leaf(key, leaf)
    return jax.vmap(lambda l: _leaf(key, leaf, l))(rows)


@functools.lru_cache(maxsize=None)
def _start_jit(path, m_items):
    m = dict(m_items)
    return jax.jit(lambda hi, lo, rows: _start(hi, lo, rows, m, path))


def start(seed: int, m: dict, path: str, rows: Sequence[int] = ()) -> jax.Array:
    """Rows ``rows`` of the stacked leaf at program path ``path``, or the
    whole top leaf there."""
    hi, lo = split_seed(seed)
    return _start_jit(path, items(m))(hi, lo, jnp.asarray(rows, jnp.int32))


@jax.jit
def _distance(now, then):
    return jnp.linalg.norm(now - then)


def change_norms(seed: int, m: dict, rows: Dict[str, Sequence[int]],
                 now: Dict[str, jax.Array]) -> Dict[str, float]:
    """Each leaf's distance from its seeded start, one leaf at a time (so
    no more than one leaf's start is on the device); ``now`` holds, by
    program path, rows ``rows[stack id]`` of stacked leaves and whole top
    leaves."""
    tab = layouts.table(m)
    return {p: float(_distance(v, start(seed, m, p, rows.get(tab[p].stack, ()))))
            for p, v in now.items()}


def _program_params(seed_hi, seed_lo, m_items):
    m = dict(m_items)
    key = traced_key(seed_hi, seed_lo)
    rows = layouts.stack_rows(m)
    tree: dict = {}
    for leaf in layouts.of(m).leaves(m):
        value = (jax.vmap(lambda l: _leaf(key, leaf, l))(jnp.arange(rows[leaf.stack]))
                 if leaf.stack else _leaf(key, leaf))
        *outer, last = leaf.path.split("/")
        node = tree
        for k in outer:
            node = node.setdefault(k, {})
        node[last] = value
    if "stages" in tree:
        tree["stages"] = [tree["stages"][str(i)] for i in range(len(tree["stages"]))]
    return tree


@functools.lru_cache(maxsize=None)
def _program_params_jit():
    return jax.jit(_program_params, static_argnums=(2,))


def program_params(seed: int, m: dict):
    """The whole tree in the program's layout, made on the device."""
    return _program_params_jit()(*split_seed(seed), items(m))


def by_path(tree) -> Dict[str, jax.Array]:
    """The leaves of a program tree by path (``stages/0/pos0/attn/wq``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p): v
            for p, v in flat}
