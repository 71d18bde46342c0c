"""Operations the algorithm needs, from the configuration's
shapes alone (published keys of ``bench/configs/<name>.json``).

- A train step: PaLM's count, ``6 N + 12 L d S`` FLOPs per token, with
  ``N`` the parameters outside the embedding table (the untied head is a
  matmul and counts).  BlockLLM prunes the weight gradients of frozen
  rows; they are counted as if done, so the share compares with full
  fine-tuning.  Recomputation (remat) is not counted.
"""
from __future__ import annotations

import numpy as np

from bench import weights


def _sizes(m: dict) -> dict:
    return {k: int(np.prod(v)) for k, v in weights.shapes(m).items()}


def non_embedding_params(m: dict) -> int:
    s = _sizes(m)
    layer = sum(s[n] for _, n in weights.LAYER_LEAVES)
    return m["num_hidden_layers"] * layer + s["final_norm"] + s["head"]


def _attn_width(m: dict) -> int:
    H = m["num_attention_heads"]
    return H * (m.get("head_dim") or m["hidden_size"] // H)


def train_flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * non_embedding_params(m) + 12.0 * m["num_hidden_layers"] * \
        _attn_width(m) * seq

