"""Layout of ``bench/configs/moe_decoder.py``: the dense decoder's
attention and norms with a mixture of experts in place of the MLP, as
the program holds it: one stack ``stages[0].pos0``, the experts of a
layer stacked ``[E, ...]`` under ``moe/``, the shared experts as one
SwiGLU MLP under ``moe/shared/``.

Published keys as DeepSeek's configurations name them.  The program
drops a token over an expert's capacity, ``max(8, ceil(k T c / E))`` of
``T`` tokens a chunk, and the reference drops none: a configuration
states the factor ``c`` under ``assumed.capacity_factor``, at ``E / k``
or more where nothing may be dropped.
"""
from __future__ import annotations

import math

from bench.layouts import ConfigError, Leaf, dense_decoder, numel

KEYS = (dense_decoder.KEYS - {"intermediate_size"}) | {
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "aux_loss_alpha", "scoring_func", "norm_topk_prob"}

# the reference's name, its path inside the program's block, its key id
EXPERTS = (("router", "moe/router", 9), ("e_gate", "moe/w_gate", 10),
           ("e_up", "moe/w_up", 11), ("e_down", "moe/w_down", 12))
SHARED = (("s_gate", "moe/shared/w_gate", 13), ("s_up", "moe/shared/w_up", 14),
          ("s_down", "moe/shared/w_down", 15))

stacks = dense_decoder.stacks
attention_flops_per_token = dense_decoder.attention_flops_per_token


def _checked(m: dict) -> dict:
    if m.get("scoring_func", "softmax") != "softmax":
        raise ConfigError(f"scoring_func {m['scoring_func']!r}: the "
                          "program's router is a softmax")
    if not m.get("norm_topk_prob", True):
        raise ConfigError("norm_topk_prob false: the program renormalises "
                          "the top-k gates")
    return m


def model_config(m: dict):
    m = _checked(m)
    return dense_decoder.model_config(
        m, num_experts=int(m["n_routed_experts"]),
        num_experts_per_tok=int(m["num_experts_per_tok"]),
        moe_d_ff=int(m["moe_intermediate_size"]),
        shared_expert_d_ff=int(m["n_shared_experts"])
        * int(m["moe_intermediate_size"]),
        capacity_factor=float(m["assumed"]["capacity_factor"]),
        router_aux_coef=float(m["aux_loss_alpha"]))


def leaves(m: dict):
    _checked(m)
    (stack,) = stacks(m)
    d, L = m["hidden_size"], m["num_hidden_layers"]
    E, f = m["n_routed_experts"], m["moe_intermediate_size"]
    fs = m["n_shared_experts"] * f
    shape = {"router": (d, E), "e_gate": (E, d, f), "e_up": (E, d, f),
             "e_down": (E, f, d), "s_gate": (d, fs), "s_up": (d, fs),
             "s_down": (fs, d)}
    scale = {"e_down": 1.0 / math.sqrt(f) / math.sqrt(2 * L),
             "s_down": 1.0 / math.sqrt(max(fs, 1)) / math.sqrt(2 * L)}
    moe = tuple(Leaf(n, f"{stack.path}/{p}", shape[n],
                     scale.get(n, 1.0 / math.sqrt(d)), i, stack.sid)
                for n, p, i in EXPERTS + (SHARED if fs else ()))
    return dense_decoder.attention_leaves(m, stack) + moe \
        + dense_decoder.top_leaves(m)


def active_params_per_token(m: dict) -> int:
    """The parameters a token goes through: every leaf but the embedding
    table, the routed experts' at ``num_experts_per_tok`` of
    ``n_routed_experts``.  Capacity padding and dropped tokens are not
    counted."""
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    routed = {n for n, _, _ in EXPERTS[1:]}
    return sum(numel(m, l) // E * k if l.name in routed else numel(m, l)
               for l in leaves(m) if l.path != "embed")
