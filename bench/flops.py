"""Operations the algorithm needs, from the configuration's
shapes alone (published keys of ``bench/configs/<name>.json``, read
through its layout, ``bench/layouts/<reference>.py``).

- A train step: ``6 N + A`` FLOPs per token (PaLM's count), with ``N``
  the parameters a token goes through outside the embedding table (the
  untied head is a matmul and counts; of a mixture of experts the
  router, the shared experts and ``num_experts_per_tok`` routed experts,
  with no capacity padding and no dropped token counted) and ``A`` the
  attention's scores and weighted sums, forward and backward.  For a
  dense decoder ``A = 12 L (H hd) S``.  BlockLLM prunes the weight
  gradients of frozen rows; they are counted as if done, so the share
  compares with full fine-tuning.  Recomputation (remat) is not counted.
"""
from __future__ import annotations

from bench import layouts


def non_embedding_params(m: dict) -> int:
    """Every parameter outside the embedding table."""
    return sum(layouts.numel(m, l) for l in layouts.of(m).leaves(m)
               if l.path != "embed")


def train_flops_per_token(m: dict, seq: int) -> int:
    lay = layouts.of(m)
    return 6 * lay.active_params_per_token(m) \
        + lay.attention_flops_per_token(m, seq)
