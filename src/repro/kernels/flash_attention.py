"""Flash attention, forward and backward, on the splash kernels that ship
with JAX (``jax.experimental.pallas.ops.tpu.splash_attention``).

The op takes the model's layout, q [B, S, H, hd] and k/v [B, S, KV, hd],
and runs three Pallas kernels: the forward (online softmax, f32 running
max and denominator, the log-sum-exp kept for the backward), and the
backward's dq and dk/dv kernels, which recompute each block's
probabilities from it.  No [S, S] score, probability or dS array reaches
HBM, and blocks the mask leaves empty (above the causal diagonal, outside
a window) are skipped by the kernels' block-sparse schedule.  The score
dot takes the inputs' dtype on the MXU and accumulates in f32; splash's
forward multiplies the f32 probabilities by v in f32.

GQA: splash's MHA kernel maps q head h to kv head h // (H / KV) in its
index maps, the grouping ``jnp.repeat`` of ``layers._repeat_kv`` gives,
so repeated k/v are never materialised; dk/dv sum over each group.
Splash does not scale the logits: q is scaled here, by 1/sqrt(hd) or
the given ``scale``.  v may have a head size of its own (MLA: qk 192,
v 128); splash sizes its forward, dq and dk/dv blocks by each apart.
The batch axis is a ``vmap``, which Pallas turns into one more grid axis.

Masks are ``layers._mask_bias``'s: causal keeps ``q - k >= 0``; a window
keeps ``q - k < window`` on the causal side (and, without causality,
everything with ``q - k < window``).

Block sizes are not a knob: ``block_sizes`` picks them from the sequence
length (measured on one TPU v5e, PERF.md §6).  A sequence that is not a
multiple of 128 is not covered (``supports``).
"""
from __future__ import annotations

import functools
import math

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as splash

LANES = 128
# Block sizes, forward and backward apart: the largest of each list that
# divides S.  At S 2048, hd 128, 16 heads over 8 kv heads on one TPU v5e
# (PERF.md §6) the forward was fastest at 512 x 512 (0.54 ms, 1024
# kv 0.58) and the dq + dk/dv kernels at 1024 x 1024 (1.21 ms, 512 x 512
# 1.27); the fused backward kernel was faster but holds an f32 dq per kv
# block (128 MiB more at that shape).
_FWD_BLOCKS = (512, 256, 128)
_BWD_BLOCKS = (1024, 512, 256, 128)


def supports(seq_len: int) -> bool:
    """Whether the op covers a self-attention of ``seq_len`` tokens."""
    return seq_len % LANES == 0


def block_sizes(seq_len: int) -> splash.BlockSizes:
    fwd = next(b for b in _FWD_BLOCKS if seq_len % b == 0)
    bwd = next(b for b in _BWD_BLOCKS if seq_len % b == 0)
    return splash.BlockSizes(
        block_q=fwd, block_kv=fwd, block_kv_compute=fwd,
        block_q_dkv=bwd, block_kv_dkv=bwd, block_kv_dkv_compute=bwd,
        block_q_dq=bwd, block_kv_dq=bwd)


def _mask(seq_len: int, causal: bool, window: int):
    shape = (seq_len, seq_len)
    if window:
        # LocalMask keeps q - left <= k <= q + right
        return splash.LocalMask(shape, (window - 1, 0 if causal else None),
                                offset=0)
    return splash.CausalMask(shape) if causal else splash.FullMask(shape)


@functools.lru_cache(maxsize=32)
def _kernel(heads: int, seq_len: int, causal: bool, window: int,
            interpret: bool):
    mask = splash.MultiHeadMask([_mask(seq_len, causal, window)] * heads)
    # the block-sparse schedule is made once, as concrete arrays, and
    # enters every program that uses it as constants (built under a
    # trace they would be that trace's tracers)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            mask, block_sizes=block_sizes(seq_len), head_shards=1,
            q_seq_shards=1, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=0, interpret=False,
                    scale=None):
    """q [B, S, H, hd]; k [B, S, KV, hd]; v [B, S, KV, hd_v] -> o [B, S,
    H, hd_v] in q's dtype; scores scaled by ``scale`` (default
    1/sqrt(hd))."""
    B, S, H, hd = q.shape
    if not supports(S) or k.shape[1] != S:
        raise ValueError(f"flash attention needs a self-attention over a "
                         f"multiple of {LANES} tokens; got q {q.shape}, "
                         f"k {k.shape}")
    kern = _kernel(H, S, bool(causal), int(window), bool(interpret))
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qt = (q * scale).astype(q.dtype).swapaxes(1, 2)
    o = jax.vmap(kern)(qt, k.swapaxes(1, 2), v.swapaxes(1, 2))
    return o.swapaxes(1, 2)
