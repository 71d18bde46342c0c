"""Flash attention (forward) — Pallas/TPU, online-softmax blockwise.

Grid (B, H, nq, nk) with the kv dimension innermost ("arbitrary"
semantics): the f32 accumulator/max/denominator live in VMEM scratch and
persist across the nk sweep for one (b, h, q-block).  Causal + sliding
window masks come from block offsets; fully-masked blocks are skipped via
``pl.when`` (no MXU work issued).  GQA is handled in the k/v index_map
(h -> h // group) — the repeated heads are never materialized.

Block sizes default to (512 q x 512 k) x head_dim tiles: q/k/v/o tiles at
hd=128 are 512*128*2B = 128 KiB each, accumulator 256 KiB — comfortably
inside the ~16 MiB VMEM with double buffering.

The backward pass intentionally reuses the XLA chunked-attention path
(``models.layers.attention_chunked``): it is already flash-structured
(O(S) memory, recomputes probabilities per block) — see ops.py
``flash_attention`` custom_vjp.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr,
            *, scale, causal, window, block_q, block_k, nk, sq, sk):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip blocks that are entirely masked out
    diag_ok = (not causal) or (k_start <= q_start + block_q - 1)
    win_ok = (not window) or (q_start - (k_start + block_k - 1) < window)

    @pl.when(jnp.logical_and(diag_ok, win_ok))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (qp < sq) & (kp < sk)
        if causal:
            ok &= qp >= kp
        if window:
            ok &= qp - kp < window
        s = jnp.where(ok, s, -jnp.inf)

        m_prev = m_scr[...]                          # [bq, 1]
        m_new = jnp.maximum(m_prev[:, 0], s.max(-1))[:, None]
        m_safe = jnp.maximum(m_new, -1e30)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(jnp.maximum(m_prev, -1e30) - m_safe)
        l_scr[...] = l_scr[...] * corr + p.sum(-1)[:, None]
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "block_q",
                              "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, window=0, scale=None,
                        block_q=512, block_k=512, interpret=False):
    """q [B, Sq, H, hd]; k/v [B, Sk, KV, hd] -> o [B, Sq, H, hd]."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    # layout [B, H, S, hd] for clean tiling
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, sq=Sq, sk=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.swapaxes(1, 2)
