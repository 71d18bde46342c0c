"""Fused row scatter-swap — the adapter hot-swap kernel (Pallas/TPU).

Applying a BlockDelta adapter touches only the K delta rows of each
[G, ...] parameter stack.  Unfused, a hot swap is a gather (save the
displaced base rows for revert) plus a scatter (write the adapter rows):
XLA materializes a full-tensor copy for the scatter (`.at[idx].set`
without donation) — O(G*C) bytes moved for an O(K*C) update.

This kernel fuses both into one pass over ONLY the tiles that hold
delta rows:

    full_out           = full;  full_out[idx[k]] = rows[k]
    saved_out[k]       = full[idx[k]]

- a [G, C] array sits in HBM as (8, 128) tiles, so the unit of traffic
  is a ``TILE_ROWS``-row band of a row, never a lone row: the full
  blocks are ``(TILE_ROWS, block_c)`` at band ``idx[k] // TILE_ROWS``,
  and the kernel reads/writes row ``idx[k] % TILE_ROWS`` inside the
  VMEM tile.  Bands holding no delta row are never streamed;
- the grid is (C/block_c, K) with k innermost and the indices sorted,
  so delta rows that share a band are consecutive grid steps that map
  to the same block: Pallas neither re-fetches nor writes it back in
  between, and each step edits the running output tile (the first step
  of a band seeds it from the input tile);
- ``input_output_aliases`` aliases ``full`` to ``full_out``: the update
  is in place, so HBM traffic is one band read + one band write per
  touched band, plus the K rows themselves — nothing proportional to G;
- the row indices ride in scalar-prefetch SMEM
  (``PrefetchScalarGridSpec``): the block index_map computes each
  tile's HBM offset from ``idx`` before the body runs, so the DMA
  pipeline stays ahead of compute.

The row inside a tile is addressed with a dynamic sublane slice, which
the TPU supports for 4-byte dtypes: the kernel takes f32/int32/uint32
leaves (the model zoo's params are f32) and refuses anything else.

The swap is an involution: calling it again with ``saved_out`` restores
``full`` bit-exactly (replacement semantics — see adapters/delta.py for
why BlockDelta stores replacement rows rather than additive deltas).

Interpret mode runs the same kernel on CPU for tests; ``kernels/ref.py:
scatter_swap_ref`` is the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 8   # sublanes of a 4-byte HBM tile


def _kernel(idx_ref, full_ref, rows_ref, full_out, saved_out):
    k = pl.program_id(1)
    band = idx_ref[k] // TILE_ROWS
    prev = idx_ref[jnp.maximum(k - 1, 0)] // TILE_ROWS

    @pl.when(jnp.logical_or(k == 0, band != prev))
    def _seed():
        full_out[...] = full_ref[...]

    r = pl.ds(idx_ref[k] % TILE_ROWS, 1)
    # order matters within one step: read the displaced row first
    saved_out[...] = full_out[r, :]
    full_out[r, :] = rows_ref[...]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"),
                   donate_argnums=(0,))
def scatter_swap_2d(full, idx, rows, *, block_c=2048, interpret=False):
    """Swap rows ``idx`` (unique) of ``full`` [G, C] with ``rows`` [K, C].

    Returns ``(new_full, displaced)`` where ``new_full[idx] == rows`` and
    ``displaced == old full[idx]``.  ``full`` is donated (in-place on
    device).  Exact involution: ``scatter_swap_2d(new_full, idx,
    displaced)`` restores the original bit-for-bit.
    """
    if full.dtype.itemsize != 4:
        raise ValueError(
            f"scatter_swap_2d takes 4-byte dtypes, got {full.dtype} "
            "(use kernels.ops.scatter_swap mode='xla')")
    G, C = full.shape
    K = idx.shape[0]
    bc = min(block_c, C)
    order = jnp.argsort(idx)
    # [K, 1, C]: a (1, bc) block over the last two dims is legal tiling
    srows = rows[order].astype(full.dtype).reshape(K, 1, C)
    band = lambda j, k, i: (i[k] // TILE_ROWS, j)
    row = lambda j, k, i: (k, 0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(C, bc), K),
        in_specs=[pl.BlockSpec((TILE_ROWS, bc), band),
                  pl.BlockSpec((None, 1, bc), row)],
        out_specs=[pl.BlockSpec((TILE_ROWS, bc), band),
                   pl.BlockSpec((None, 1, bc), row)],
    )
    new_full, saved = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(full.shape, full.dtype),
                   jax.ShapeDtypeStruct((K, 1, C), full.dtype)],
        input_output_aliases={1: 0},  # full aliases full_out (in-place)
        # each step edits the tile the previous one left: keep the order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx[order], full, srows)
    return new_full, saved.reshape(K, C)[jnp.argsort(order)]
