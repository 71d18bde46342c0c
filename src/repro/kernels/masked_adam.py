"""Fused masked-Adam update — the BlockLLM optimizer hot-spot (Pallas/TPU).

Unfused, the masked update is ~6 elementwise HLO ops over 5 tensors
(p, g, m, v, mask), each streamed HBM->VMEM->HBM: ~12 full-tensor HBM
round-trips.  The fused kernel streams every tile through VMEM exactly
once: 5 reads + 3 writes, a 2.4x cut on the memory-bound optimizer step
(the update is strictly memory-bound: ~10 FLOPs/element vs 16 bytes moved).

Two masking modes:
- ``mask``  : stored binary mask (the paper's Algorithm 1 semantics —
              mask fixed between re-selections);
- ``tau``   : threshold recomputed on the fly from |u| >= tau (the
              dynamic-mask variant; saves the mask's HBM entirely).

Grid: 2-D tiles over a [R, C] view of each tensor (ops.py flattens /
pads arbitrary leaves).  Tiles are (block_r, block_c) with block_c a
multiple of 128 (lane width) and block_r a multiple of 8 (f32 sublane).
Scalars (lr, betas, bias corrections, eps, wd, tau) ride in SMEM.

``masked_adam_q8_2d`` is the Q8State variant: moments arrive as int8
value blocks + per-block f32 scales (``runtime/compression.py`` codec,
one 256-element block per row of the [NB, 256] view) and leave the same
way — dequant -> masked Adam -> requant fused in one VMEM pass, so the
quantized optimizer never materializes fp32 moment tensors in HBM
(9 bytes/element moved vs 16 unquantized, on an already memory-bound op).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

# scalar layout: [lr, b1, b2, eps, wd, bc1, bc2, tau]
N_SCALARS = 8


def _kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, mask_ref,
            p_out, m_out, v_out, *, use_tau: bool):
    lr, b1, b2, eps = (scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3])
    wd, bc1, bc2, tau = (scal_ref[4], scal_ref[5], scal_ref[6], scal_ref[7])
    g = g_ref[...].astype(jnp.float32)
    m2 = b1 * m_ref[...] + (1.0 - b1) * g
    v2 = b2 * v_ref[...] + (1.0 - b2) * g * g
    u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
    if use_tau:
        gate = (jnp.abs(u) >= tau).astype(jnp.float32)
    else:
        gate = mask_ref[...].astype(jnp.float32)
    p32 = p_ref[...].astype(jnp.float32)
    u = u * gate + wd * p32
    p_out[...] = (p32 - lr * u).astype(p_out.dtype)
    m_out[...] = m2
    v_out[...] = v2


def _q8_kernel(scal_ref, p_ref, g_ref, mq_ref, ms_ref, vq_ref, vs_ref,
               mask_ref, p_out, mq_out, ms_out, vq_out, vs_out,
               *, use_tau: bool):
    lr, b1, b2, eps = (scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3])
    wd, bc1, bc2, tau = (scal_ref[4], scal_ref[5], scal_ref[6], scal_ref[7])
    g = g_ref[...].astype(jnp.float32)
    # dequant: one 256-element codec block per row, scale broadcast [br, 1]
    m = mq_ref[...].astype(jnp.float32) * ms_ref[...]
    v = vq_ref[...].astype(jnp.float32) * vs_ref[...]
    m2 = b1 * m + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * g * g
    u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
    if use_tau:
        gate = (jnp.abs(u) >= tau).astype(jnp.float32)
    else:
        gate = mask_ref[...].astype(jnp.float32)
    p32 = p_ref[...].astype(jnp.float32)
    u = u * gate + wd * p32
    p_out[...] = (p32 - lr * u).astype(p_out.dtype)
    # requant with the exact runtime/compression.py formula so fused and
    # host codec paths store bit-identical moments
    ms2 = jnp.maximum(jnp.max(jnp.abs(m2), axis=1, keepdims=True) / 127.0,
                      1e-12)
    vs2 = jnp.maximum(jnp.max(jnp.abs(v2), axis=1, keepdims=True) / 127.0,
                      1e-12)
    mq_out[...] = jnp.clip(jnp.round(m2 / ms2), -127, 127).astype(jnp.int8)
    vq_out[...] = jnp.clip(jnp.round(v2 / vs2), -127, 127).astype(jnp.int8)
    ms_out[...] = ms2
    vs_out[...] = vs2


@functools.partial(jax.jit, static_argnames=("use_tau", "block_r",
                                             "interpret"))
def masked_adam_q8_2d(p, g, mq, ms, vq, vs, mask, scalars, *, use_tau=False,
                      block_r=256, interpret=False):
    """One fused dequant->masked-Adam->requant step on codec views.

    ``p``/``g``/``mask`` are [NB, 256] views (one quantization block per
    row); ``mq``/``vq`` int8 [NB, 256]; ``ms``/``vs`` f32 [NB, 1]
    (``runtime/compression.py`` block scales).  Returns
    ``(p2, mq2, ms2, vq2, vs2)`` — the persistent optimizer state stays
    int8+scale end to end.
    """
    NB, C = p.shape
    block_r = min(block_r, NB)
    grid = (pl.cdiv(NB, block_r),)

    tile = lambda: pl.BlockSpec((block_r, C), lambda i: (i, 0))
    srow = lambda: pl.BlockSpec((block_r, 1), lambda i: (i, 0))
    scal_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_q8_kernel, use_tau=use_tau)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scal_spec, tile(), tile(), tile(), srow(), tile(),
                  srow(), tile()],
        out_specs=[tile(), tile(), srow(), tile(), srow()],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(mq.shape, jnp.int8),
            jax.ShapeDtypeStruct((NB, 1), jnp.float32),
            jax.ShapeDtypeStruct(vq.shape, jnp.int8),
            jax.ShapeDtypeStruct((NB, 1), jnp.float32),
        ],
        interpret=interpret,
    )(scalars, p, g, mq, ms, vq, vs, mask)


@functools.partial(jax.jit, static_argnames=("use_tau", "block_r", "block_c",
                                             "interpret"))
def masked_adam_2d(p, g, m, v, mask, scalars, *, use_tau=False,
                   block_r=256, block_c=512, interpret=False):
    """One fused update on 2-D views.  All of p/g/m/v/mask are [R, C]
    (m, v f32; mask any dtype; scalars f32[8]).  Returns (p2, m2, v2)."""
    R, C = p.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    grid = (pl.cdiv(R, block_r), pl.cdiv(C, block_c))

    def idx(i, j):
        return (i, j)

    tile = lambda: pl.BlockSpec((block_r, block_c), idx)
    scal_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, use_tau=use_tau)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[scal_spec, tile(), tile(), tile(), tile(), tile()],
        out_specs=[tile(), tile(), tile()],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        interpret=interpret,
    )(scalars, p, g, m, v, mask)
