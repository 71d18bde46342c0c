"""RG-LRU linear recurrence h_t = a_t * h_{t-1} + b_t — Pallas/TPU.

The recurrence is elementwise over the width dim, sequential over time.
Grid (B, nw, ns): width tiles are "parallel" (independent channels), the
time dimension innermost/"arbitrary" with the hidden state in VMEM
scratch.  Inside a time block the kernel runs a fori_loop over rows —
time stays HBM-tiled ([block_t, block_w] tiles stream through VMEM once)
while the state tile never leaves VMEM.

The XLA alternative (jax.lax.associative_scan, used in the model when the
kernel is off) is log-depth but moves ~2x the data and materializes
O(log S) intermediates; the kernel is single-pass — the right trade on a
bandwidth-bound op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, y_ref, hN_ref, h_scr, *, ns, block_t):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)   # [block_t, block_w]
    b = b_ref[0].astype(jnp.float32)

    def body(t, h):
        h2 = a[t] * h + b[t]
        y_ref[0, t] = h2.astype(y_ref.dtype)
        return h2

    h = jax.lax.fori_loop(0, block_t, body, h_scr[...])
    h_scr[...] = h

    @pl.when(ti == ns - 1)
    def _fin():
        hN_ref[0] = h.astype(hN_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_w",
                                             "interpret"))
def rglru_scan_kernel(a, b, h0, *, block_t=128, block_w=512,
                      interpret=False):
    """a, b [B, S, W] f32; h0 [B, W] f32 -> (y [B,S,W] f32, h_last [B,W])."""
    B, S, W = a.shape
    block_t = min(block_t, S)
    block_w = min(block_w, W)
    # time is sequential: pad to a block multiple with IDENTITY steps
    # (a=1, b=0) so the carried state is untouched by padding rows.
    pad_t = (-S) % block_t
    if pad_t:
        a = jnp.concatenate(
            [a, jnp.ones((B, pad_t, W), a.dtype)], axis=1)
        b = jnp.concatenate(
            [b, jnp.zeros((B, pad_t, W), b.dtype)], axis=1)
    ns = pl.cdiv(S + pad_t, block_t)
    nw = pl.cdiv(W, block_w)

    kernel = functools.partial(_kernel, ns=ns, block_t=block_t)
    y, hN = pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b_, w, t: (b_, t, w)),
            pl.BlockSpec((1, block_t, block_w), lambda b_, w, t: (b_, t, w)),
            pl.BlockSpec((1, block_w), lambda b_, w, t: (b_, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b_, w, t: (b_, t, w)),
            pl.BlockSpec((1, block_w), lambda b_, w, t: (b_, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S + pad_t, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
    return y[:, :S], hN
