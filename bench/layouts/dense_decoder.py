"""Layout of ``bench/configs/dense_decoder.py``: a Llama-style dense
decoder as the program holds it, one stack ``stages[0].pos0`` of
``num_hidden_layers`` rows."""
from __future__ import annotations

import math

from repro.configs.base import ModelConfig

from bench.layouts import ConfigError, Leaf, Stack, numel

KEYS = frozenset({"num_hidden_layers", "hidden_size", "num_attention_heads",
                  "num_key_value_heads", "intermediate_size", "vocab_size",
                  "head_dim", "rope_theta", "rms_norm_eps",
                  "tie_word_embeddings", "hidden_act"})

# the reference's name, its path inside the program's block, its key id
ATTN = (("ln1", "ln1/scale", 0), ("wq", "attn/wq", 1), ("wk", "attn/wk", 2),
        ("wv", "attn/wv", 3), ("wo", "attn/wo", 4), ("ln2", "ln2/scale", 5))
MLP = (("w_gate", "mlp/w_gate", 6), ("w_up", "mlp/w_up", 7),
       ("w_down", "mlp/w_down", 8))


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def _checked(m: dict) -> dict:
    if m.get("hidden_act", "silu") != "silu":
        raise ConfigError(f"hidden_act {m['hidden_act']!r}: the program's "
                          "gated MLP is SwiGLU (silu) only")
    if m["tie_word_embeddings"]:
        raise ConfigError("tie_word_embeddings true: the reference and the "
                          "leaf table have an untied head")
    return m


def model_config(m: dict, **moe) -> ModelConfig:
    """The program's ``ModelConfig``; ``moe`` adds the MoE fields."""
    m = _checked(m)
    return ModelConfig(
        name=m["name"], family="moe" if moe else "dense",
        num_layers=int(m["num_hidden_layers"]),
        d_model=int(m["hidden_size"]),
        num_heads=int(m["num_attention_heads"]),
        num_kv_heads=int(m["num_key_value_heads"]),
        d_ff=int(m.get("intermediate_size", 0)),
        vocab_size=int(m["vocab_size"]),
        head_dim=int(m.get("head_dim", 0)),
        rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        dtype=m["torch_dtype"], **moe)


def stacks(m: dict):
    return (Stack(0, 0, m["num_hidden_layers"], "block"),)


def attention_leaves(m: dict, stack: Stack):
    """Norms and attention of a layer: fan-in scales, the output also
    residual-scaled, 0.1 for the norms' (1 + s) gains."""
    d, H, KV = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    hd, L = head_dim(m), m["num_hidden_layers"]
    shape = {"ln1": (d,), "wq": (d, H * hd), "wk": (d, KV * hd),
             "wv": (d, KV * hd), "wo": (H * hd, d), "ln2": (d,)}
    scale = {"ln1": 0.1, "ln2": 0.1,
             "wo": 1.0 / math.sqrt(H * hd) / math.sqrt(2 * L)}
    return tuple(Leaf(n, f"{stack.path}/{p}", shape[n],
                      scale.get(n, 1.0 / math.sqrt(d)), i, stack.sid)
                 for n, p, i in ATTN)


def top_leaves(m: dict):
    """Embedding and head at 0.02, the final norm's gain at 0.1."""
    d, V = m["hidden_size"], m["vocab_size"]
    return (Leaf("embed", "embed", (V, d), 0.02, 100),
            Leaf("final_norm", "final_norm/scale", (d,), 0.1, 101),
            Leaf("head", "head", (d, V), 0.02, 102))


def leaves(m: dict):
    _checked(m)
    (stack,) = stacks(m)
    d, f, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    shape = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    scale = {"w_gate": 1.0 / math.sqrt(d), "w_up": 1.0 / math.sqrt(d),
             "w_down": 1.0 / math.sqrt(f) / math.sqrt(2 * L)}
    mlp = tuple(Leaf(n, f"{stack.path}/{p}", shape[n], scale[n], i, stack.sid)
                for n, p, i in MLP)
    return attention_leaves(m, stack) + mlp + top_leaves(m)


def active_params_per_token(m: dict) -> int:
    """Every parameter but the embedding table (the untied head is a
    matmul and counts)."""
    return sum(numel(m, l) for l in leaves(m) if l.path != "embed")


def attention_flops_per_token(m: dict, seq: int) -> int:
    """Scores and their product with the values, forward and backward:
    ``12 L (H hd) S`` (PaLM's count, causal masking not discounted)."""
    return 12 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * head_dim(m) * seq
