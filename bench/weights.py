"""Seeded weights of a dense decoder, made by the benchmark and not by the
program: the reference can then rebuild any layer from the seed alone.

Every leaf of layer ``l`` comes from its own key, ``key(seed, leaf, l)``,
so one layer can be made without the others.  ``program_params`` makes the
whole tree in the layout the program serves and trains (``stages[0].pos0``
stacked ``[L, ...]``, float32), in one jitted call on the device.
``start`` makes the same values, any layer rows of one leaf, for the
checks.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

# (group in the program's block, leaf) -> id folded into the key
LAYER_LEAVES = (("", "ln1"), ("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
                ("attn", "wo"), ("", "ln2"), ("mlp", "w_gate"),
                ("mlp", "w_up"), ("mlp", "w_down"))
TOP_LEAVES = ("embed", "final_norm", "head")
_IDS = {name: i for i, (_, name) in enumerate(LAYER_LEAVES)}
_IDS.update({name: 100 + i for i, name in enumerate(TOP_LEAVES)})


def shapes(m: dict) -> Dict[str, tuple]:
    d, f = m["hidden_size"], m["intermediate_size"]
    H, KV, V = (m["num_attention_heads"], m["num_key_value_heads"],
                m["vocab_size"])
    hd = m.get("head_dim") or d // H
    return {"ln1": (d,), "wq": (d, H * hd), "wk": (d, KV * hd),
            "wv": (d, KV * hd), "wo": (H * hd, d), "ln2": (d,),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
            "embed": (V, d), "final_norm": (d,), "head": (d, V)}


def scale(m: dict, name: str) -> float:
    """Init scales: fan-in for projections, residual-scaled outputs,
    0.02 for embedding and head, 0.1 for the norms' (1 + s) gains."""
    d, f, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    hd = m.get("head_dim") or d // m["num_attention_heads"]
    if name in ("ln1", "ln2", "final_norm"):
        return 0.1
    if name in ("embed", "head"):
        return 0.02
    if name == "wo":
        return 1.0 / math.sqrt(m["num_attention_heads"] * hd) / math.sqrt(2 * L)
    if name == "w_down":
        return 1.0 / math.sqrt(f) / math.sqrt(2 * L)
    return 1.0 / math.sqrt(d)


def _leaf(key, m, name, l=None):
    k = jax.random.fold_in(key, _IDS[name])
    if l is not None:
        k = jax.random.fold_in(k, l)
    return jax.random.normal(k, shapes(m)[name], jnp.float32) * scale(m, name)


def split_seed(seed: int):
    """(hi, lo) int32 words of a seed, as the jitted makers take them."""
    seed = int(seed)
    return jnp.int32((seed >> 31) & 0x7FFFFFFF), jnp.int32(seed & 0x7FFFFFFF)


def traced_key(seed_hi, seed_lo):
    """``base_key`` of a seed passed as traced words."""
    return jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)


def items(m: dict) -> tuple:
    """The numeric keys of a configuration, hashable for ``jit``."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


def _start(seed_hi, seed_lo, rows, m, name):
    key = traced_key(seed_hi, seed_lo)
    if name in TOP_LEAVES:
        return _leaf(key, m, name)
    return jax.vmap(lambda l: _leaf(key, m, name, l))(rows)


@functools.lru_cache(maxsize=None)
def _start_jit(name, m_items):
    m = dict(m_items)
    return jax.jit(lambda hi, lo, rows: _start(hi, lo, rows, m, name))


def start(seed: int, m: dict, name: str, layer_rows) -> jax.Array:
    """Layer rows ``layer_rows`` of stacked leaf ``name``, or the whole
    top leaf ``name``."""
    hi, lo = split_seed(seed)
    return _start_jit(name, items(m))(hi, lo, jnp.asarray(layer_rows, jnp.int32))


@jax.jit
def _distance(now, then):
    return jnp.linalg.norm(now - then)


def change_norms(seed: int, m: dict, layer_rows, now: Dict[str, jax.Array]):
    """Each leaf's distance from its seeded start, one leaf at a time (so
    no more than one leaf's start is on the device); ``now`` holds layer
    rows ``layer_rows`` of stacked leaves and whole top leaves."""
    return {n: float(_distance(v, start(seed, m, n, layer_rows)))
            for n, v in now.items()}


def _program_params(seed_hi, seed_lo, m_items):
    m = dict(m_items)
    key = traced_key(seed_hi, seed_lo)
    L = m["num_hidden_layers"]
    block = {"attn": {}, "mlp": {}}
    for group, name in LAYER_LEAVES:
        stacked = jax.vmap(lambda l: _leaf(key, m, name, l))(jnp.arange(L))
        if group:
            block[group][name] = stacked
        else:
            block[name] = {"scale": stacked}
    return {"embed": _leaf(key, m, "embed"),
            "final_norm": {"scale": _leaf(key, m, "final_norm")},
            "head": _leaf(key, m, "head"),
            "stages": [{"pos0": block}]}


@functools.lru_cache(maxsize=None)
def _program_params_jit():
    return jax.jit(_program_params, static_argnums=(2,))


def program_params(seed: int, m: dict):
    """The whole tree in the program's layout, made on the device."""
    return _program_params_jit()(*split_seed(seed), items(m))


# program leaf path of each leaf name
PATHS = {name: ("stages/0/pos0/" + (f"{group}/{name}" if group
                                    else f"{name}/scale"))
         for group, name in LAYER_LEAVES}
PATHS.update({"embed": "embed", "head": "head",
              "final_norm": "final_norm/scale"})
