"""BAdam baseline as a ``TrainerCore`` (Luo et al., 2024).

Block-coordinate Adam: cycles through parameter blocks (one transformer
layer at a time) in a FIXED order, switching every K steps — no gradient
scoring, no masks, no probes.  Configured as a policy of the same block
machinery BlockLLM uses (``BlockLLMCore`` with the ``cyclic`` selector),
which is exactly the relationship the paper draws: BlockLLM = BAdam +
informed selection + masks + adaptive trigger.
"""
from __future__ import annotations

from repro.core.selection import SelectorConfig
from repro.optim.adam import Adam
from repro.trainers.blockllm import BlockLLMCore
from repro.trainers.registry import register


def badam_config(switch_every: int = 100, block_rows: int = 1,
                 train_embeddings: bool = False):
    from repro.core.blockllm import BlockLLMConfig
    leaves = ("embed", "head") if train_embeddings else ()
    return BlockLLMConfig(
        selector=SelectorConfig(
            policy="cyclic",
            cyclic_block_rows=block_rows,
            reselect_every=switch_every,
            probe_rows_per_stack=0,
            use_visit_frequency=False,
            mask_updates=False,
            always_active_leaves=("final_norm",) + leaves,
            selectable_leaves=(),
        ),
        mask_refresh="never",
    )


class BAdamCore(BlockLLMCore):
    name = "badam"

    def __init__(self, cfg, *, switch_every=100, block_rows=1,
                 train_embeddings=False, adam=None, loss_fn=None,
                 attn_impl="auto", bcfg=None, quantize_state=False):
        super().__init__(
            cfg,
            bcfg=bcfg or badam_config(switch_every, block_rows,
                                      train_embeddings),
            adam=adam or Adam(lr=1e-3), loss_fn=loss_fn,
            attn_impl=attn_impl, quantize_state=quantize_state)


@register("badam")
def make_badam(cfg, *, switch_every=100, block_rows=1,
               train_embeddings=False, adam=None, loss_fn=None,
               attn_impl="auto", quantize_state=False, **_) -> BAdamCore:
    return BAdamCore(cfg, switch_every=switch_every, block_rows=block_rows,
                     train_embeddings=train_embeddings, adam=adam,
                     loss_fn=loss_fn, attn_impl=attn_impl,
                     quantize_state=quantize_state)


@register("badam+q8")
def make_badam_q8(cfg, **kw) -> BAdamCore:
    """BAdam with Q8State moments (int8 + block scales)."""
    kw["quantize_state"] = True
    return make_badam(cfg, **kw)
