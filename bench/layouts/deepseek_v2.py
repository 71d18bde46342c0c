"""Layout of ``bench/configs/deepseek_v2.py``: DeepSeek-V2 as the program
holds it, two stacks: the leading dense layers ``stages[0].pos0`` and the
MoE layers ``stages[1].pos0``, each with latent attention under
``attn/`` (``wq``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``), a dense
MLP under ``mlp/`` or the held experts stacked ``[held, ...]`` under
``moe/`` with the shared experts as one SwiGLU MLP under ``moe/shared/``.

Published keys as DeepSeek-V2's ``config.json`` names them;
``n_routed_experts`` is the count held here and ``router_experts`` the
published count the router scores (``expert_offset``, 0 by default, the
first held).  ``aux_loss_alpha`` is read from ``assumed``.  A value the
program or the reference does not implement is refused.
"""
from __future__ import annotations

import math

from repro.configs.base import ModelConfig, Yarn

from bench.layouts import ConfigError, Leaf, Stack, dense_decoder, numel

KEYS = frozenset({
    "num_hidden_layers", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "vocab_size", "rope_theta",
    "rms_norm_eps", "tie_word_embeddings", "hidden_act", "attention_bias",
    "max_position_embeddings", "model_type", "rope_scaling",
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "moe_intermediate_size", "n_routed_experts",
    "router_experts", "expert_offset", "n_shared_experts",
    "num_experts_per_tok", "first_k_dense_replace", "moe_layer_freq",
    "scoring_func", "topk_method", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor", "seq_aux"})

# the reference's name, its path inside the program's block, and its key
# id in the dense stack and in the MoE stack (ids 0-15 are the other
# layouts'; each stack has its own, so row 0 of one is not row 0 of the
# other)
ATTN = (("ln1", "ln1/scale", 16, 32), ("wq", "attn/wq", 17, 33),
        ("wkv_a", "attn/wkv_a", 18, 34), ("kv_norm", "attn/kv_norm/scale", 19, 35),
        ("wkv_b", "attn/wkv_b", 20, 36), ("wo", "attn/wo", 21, 37),
        ("ln2", "ln2/scale", 22, 38))
MLP = (("w_gate", "mlp/w_gate", 23), ("w_up", "mlp/w_up", 24),
       ("w_down", "mlp/w_down", 25))
EXPERTS = (("router", "moe/router", 39), ("e_gate", "moe/w_gate", 40),
           ("e_up", "moe/w_up", 41), ("e_down", "moe/w_down", 42),
           ("s_gate", "moe/shared/w_gate", 43), ("s_up", "moe/shared/w_up", 44),
           ("s_down", "moe/shared/w_down", 45))
ROUTED = ("e_gate", "e_up", "e_down")


def _checked(m: dict) -> dict:
    """Refuse what the program or the reference does not implement."""
    need = {"q_lora_rank": None, "topk_method": "greedy",
            "scoring_func": "softmax", "moe_layer_freq": 1,
            "attention_bias": False, "hidden_act": "silu",
            "tie_word_embeddings": False, "seq_aux": True}
    for key, want in need.items():
        if key in m and m[key] != want:
            raise ConfigError(f"{key} {m[key]!r}: only {want!r} is implemented")
    if m.get("n_group", 1) > 1 or m.get("topk_group", 1) > 1:
        raise ConfigError("n_group/topk_group above 1: group-limited routing "
                          "is not implemented")
    if m["num_attention_heads"] != m["num_key_value_heads"]:
        raise ConfigError("latent attention has one kv head per query head")
    if not m["n_shared_experts"]:
        raise ConfigError("n_shared_experts 0: the reference adds shared experts")
    rs = m.get("rope_scaling")
    if rs and rs.get("type") != "yarn":
        raise ConfigError(f"rope_scaling {rs.get('type')!r}: only yarn")
    if m.get("expert_offset", 0) + m["n_routed_experts"] > m["router_experts"]:
        raise ConfigError("held experts past the router's count")
    return m


def model_config(m: dict) -> ModelConfig:
    m = _checked(m)
    rs = m.get("rope_scaling")
    yarn = rs and Yarn(
        factor=float(rs["factor"]),
        original_max_position=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    f = int(m["moe_intermediate_size"])
    return ModelConfig(
        name=m["name"], family="moe",
        num_layers=int(m["num_hidden_layers"]),
        d_model=int(m["hidden_size"]),
        num_heads=int(m["num_attention_heads"]),
        num_kv_heads=int(m["num_key_value_heads"]),
        d_ff=int(m["intermediate_size"]),
        vocab_size=int(m["vocab_size"]),
        rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=False, dtype=m["torch_dtype"],
        num_experts=int(m["n_routed_experts"]),
        router_experts=int(m["router_experts"]),
        expert_offset=int(m.get("expert_offset", 0)),
        num_experts_per_tok=int(m["num_experts_per_tok"]),
        moe_d_ff=f, shared_expert_d_ff=int(m["n_shared_experts"]) * f,
        router_aux_coef=float(m["assumed"]["aux_loss_alpha"]),
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        seq_aux=True,
        first_dense_layers=int(m["first_k_dense_replace"]),
        kv_lora_rank=int(m["kv_lora_rank"]),
        qk_nope_head_dim=int(m["qk_nope_head_dim"]),
        qk_rope_head_dim=int(m["qk_rope_head_dim"]),
        v_head_dim=int(m["v_head_dim"]),
        rope_yarn=yarn or None)


def stacks(m: dict):
    n = m["first_k_dense_replace"]
    return (Stack(0, 0, n, "dense_block"),
            Stack(1, 0, m["num_hidden_layers"] - n, "moe_block"))


def _attention(m: dict, stack: Stack, col: int):
    """Norms and latent attention of a layer: fan-in scales, the output
    also residual-scaled, 0.1 for the (1 + s) gains."""
    d, H, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    L = m["num_hidden_layers"]
    shape = {"ln1": (d,), "wq": (d, H * (dn + dr)), "wkv_a": (d, r + dr),
             "kv_norm": (r,), "wkv_b": (r, H * (dn + dv)), "wo": (H * dv, d),
             "ln2": (d,)}
    scale = {"ln1": 0.1, "kv_norm": 0.1, "ln2": 0.1, "wkv_b": 1 / math.sqrt(r),
             "wo": 1 / math.sqrt(H * dv) / math.sqrt(2 * L)}
    return tuple(Leaf(n, f"{stack.path}/{p}", shape[n],
                      scale.get(n, 1 / math.sqrt(d)), ids[col], stack.sid)
                 for n, p, *ids in ATTN)


def leaves(m: dict):
    _checked(m)
    dense, moe = stacks(m)
    d, L = m["hidden_size"], m["num_hidden_layers"]
    f, fd = m["moe_intermediate_size"], m["intermediate_size"]
    E, fs = m["n_routed_experts"], m["n_shared_experts"] * f
    shape = {"w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d),
             "router": (d, m["router_experts"]), "e_gate": (E, d, f),
             "e_up": (E, d, f), "e_down": (E, f, d), "s_gate": (d, fs),
             "s_up": (d, fs), "s_down": (fs, d)}
    down = {"w_down": fd, "e_down": f, "s_down": fs}
    scale = {n: 1 / math.sqrt(w) / math.sqrt(2 * L) for n, w in down.items()}

    def block(stack, table):
        return tuple(Leaf(n, f"{stack.path}/{p}", shape[n],
                          scale.get(n, 1 / math.sqrt(d)), i, stack.sid)
                     for n, p, i in table)

    return (_attention(m, dense, 0) + block(dense, MLP)
            + _attention(m, moe, 1) + block(moe, EXPERTS)
            + dense_decoder.top_leaves(m))


def active_params_per_token(m: dict) -> int:
    """The parameters a token goes through, the work this chip does: every
    leaf but the embedding table, the held experts' at ``k x held /
    router_experts`` experts a token (0.75 for 8 of 64 at top 6)."""
    k, E = m["num_experts_per_tok"], m["router_experts"]
    return sum(numel(m, l) * k // E if l.name in ROUTED else numel(m, l)
               for l in leaves(m) if l.path != "embed")


def attention_flops_per_token(m: dict, seq: int) -> int:
    """Scores and their product with the values, forward and backward:
    ``6 L H (qk + v) S`` with qk = nope + rope (``12 L (H hd) S`` where
    both are ``hd``), causal masking not discounted."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 6 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * (qk + m["v_head_dim"]) * seq
