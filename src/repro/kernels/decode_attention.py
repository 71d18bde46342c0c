"""Fused decode attention — Pallas/TPU, one query token vs a KV cache.

The serving hot path calls this once per decode step per layer: q is a
single token per slot ([B, 1, H, hd]), k/v are the slot-batched cache
([B, C, KV, hd]) and ``pos`` is the per-slot index of the token just
written.  The XLA fallback (``models.layers.attention_decode``) scores
the FULL ``C = max_seq`` cache every step regardless of ``pos``; this
kernel makes the HBM traffic scale with the actual context instead:

- grid (B, KV, nk) with the k dimension innermost ("arbitrary"): the
  f32 accumulator / running max / denominator live in VMEM scratch and
  persist across the k sweep for one (slot, kv-head);
- GQA in the q layout: the ``H // KV`` query heads of one kv group form
  the rows of a single [G, hd] tile — repeated k/v heads are never
  materialized (the same trick as flash_attention's index_map);
- **pos-aware block skipping**: per-slot [lo, hi] block bounds ride in
  scalar-prefetch SMEM.  The k/v index_map clamps the block index into
  [lo_b, hi_b] — consecutive grid steps that map to the same block are
  not re-fetched, so out-of-range blocks cost no HBM reads — and
  ``pl.when`` skips their compute entirely.  A slot at position p reads
  O(p) cache blocks, not O(max_seq);
- ring (sliding-window cache) and windowed variants use the same valid
  masks as the XLA path, so both layouts stay bit-compatible with the
  decode writes in ``models.model``.

``kernels/ref.py: decode_attention_ref`` is the pure-jnp oracle;
``kernels/ops.decode_attention`` is the public wrapper (Pallas on TPU,
grouped-einsum XLA elsewhere).  ``cache_read_bytes`` is the analytic
HBM traffic model the decode-path benchmark gates on.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _attend_block(q, k, v, ok, acc, m_scr, l_scr, *, scale, softcap):
    """One online-softmax step of q [G, hd] against a k/v block [bk, hd].

    ``ok(shape)`` gives the valid-key mask for the [G, bk] scores; the
    running max / denominator / accumulator live in the scratch refs.
    Shared by the dense and the paged kernel, so the two stay
    block-for-block identical at equal block sizes.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(ok(s.shape), s, -jnp.inf)
    m_prev = m_scr[...]                        # [G, 1]
    m_new = jnp.maximum(m_prev[:, 0], s.max(-1))[:, None]
    m_safe = jnp.maximum(m_new, -1e30)         # fully-masked block guard
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.maximum(m_prev, -1e30) - m_safe)
    l_scr[...] = l_scr[...] * corr + p.sum(-1)[:, None]
    acc[...] = acc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new


def _kernel(pos_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, o_ref,
            acc, m_scr, l_scr, *, scale, window, ring, softcap,
            block_k, nk, C):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    pos_b = pos_ref[b]
    lo = lo_ref[b]
    hi = hi_ref[b]

    def ok(shape):
        idx = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if ring:
            # slot i holds absolute position p with p % C == i; every
            # slot younger than the window is valid once written
            age = (pos_b - idx) % C
            m = age < (window if window else C)
            m &= pos_b >= age                 # not yet written early on
        else:
            m = idx <= pos_b
            if window:
                m &= idx > pos_b - window
        return m & (idx < C)                   # C % block_k padding guard

    @pl.when(jnp.logical_and(ki >= lo, ki <= hi))
    def _compute():
        _attend_block(q_ref[0, 0].astype(jnp.float32),        # [G, hd]
                      k_ref[0, 0].astype(jnp.float32),        # [bk, hd]
                      v_ref[0, 0].astype(jnp.float32), ok,
                      acc, m_scr, l_scr, scale=scale, softcap=softcap)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


def block_bounds(pos, *, seq_len, window=0, ring=False, block_k=128):
    """Per-slot [lo, hi] k-block range a decode step must read.

    Shared by the kernel launch and ``cache_read_bytes`` so the analytic
    traffic model can never drift from what the kernel actually fetches.
    """
    pos = jnp.asarray(pos, jnp.int32)
    bk = min(block_k, seq_len)
    hi = jnp.minimum(pos, seq_len - 1) // bk
    if window and not ring:
        lo = jnp.maximum(pos - window + 1, 0) // bk
    else:
        # ring: early steps only fill slots [0, pos]; after wrap the
        # whole C = min(window, max_seq) buffer IS the window
        lo = jnp.zeros_like(hi)
    return lo, hi


def cache_read_bytes(pos, *, seq_len, kv_heads, head_dim, window=0,
                     ring=False, block_k=128, dtype_bytes=2):
    """Analytic K+V HBM bytes one fused decode step reads at ``pos``.

    The full-``max_seq`` XLA baseline reads every row every step:
    ``2 * seq_len * kv_heads * head_dim * dtype_bytes`` per slot.
    """
    lo, hi = block_bounds(pos, seq_len=seq_len, window=window, ring=ring,
                          block_k=block_k)
    bk = min(block_k, seq_len)
    per_block = 2 * bk * kv_heads * head_dim * dtype_bytes  # k + v tiles
    return int(jnp.sum(hi - lo + 1)) * per_block


@functools.partial(
    jax.jit, static_argnames=("window", "ring", "softcap", "scale",
                              "block_k", "interpret"))
def decode_attention_fwd(q, k_cache, v_cache, pos, *, window=0, ring=False,
                         softcap=0.0, scale=None, block_k=128,
                         interpret=False):
    """q [B, 1, H, hd]; k/v caches [B, C, KV, hd]; pos scalar or [B].

    Returns o [B, 1, H, hd] — same contract as
    ``models.layers.attention_decode``.
    """
    B, C, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bk = min(block_k, C)
    nk = pl.cdiv(C, bk)

    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    lo, hi = block_bounds(pos_b, seq_len=C, window=window, ring=ring,
                          block_k=bk)

    qt = q.reshape(B, KV, G, hd)       # head h = kv * G + g
    kt = k_cache.swapaxes(1, 2)        # [B, KV, C, hd]
    vt = v_cache.swapaxes(1, 2)

    def kv_map(b, h, j, pos_ref, lo_ref, hi_ref):
        # out-of-range grid steps re-visit the boundary block: Pallas
        # elides the DMA when the mapped block index does not change, so
        # skipped blocks cost no HBM traffic
        return b, h, jnp.clip(j, lo_ref[b], hi_ref[b]), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, scale=scale, window=window, ring=ring, softcap=softcap,
        block_k=bk, nk=nk, C=C)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_b, lo, hi, qt, kt, vt)
    return out.reshape(B, 1, H, hd)


def _paged_kernel(pos_ref, lo_ref, hi_ref, tbl_ref, act_ref,
                  q_ref, nk_ref, nv_ref, k_ref, v_ref,
                  o_ref, ko_ref, vo_ref, acc, m_scr, l_scr, *,
                  scale, window, softcap, ps, npg, kv_heads):
    """Fused write+attend over paged KV pools.

    One grid step = one logical page of one slot, all kv-heads at once
    (a page block is ``[ps, KV, hd]``: its last two dims are whole, as
    the TPU tiling wants); the k/v index_maps resolve the page table in
    SMEM, so the kernel sweeps *physical* pages while the masks reason
    in logical positions.  The new token's K/V row never takes a
    separate scatter dispatch: at the boundary page (``ki == hi``) the
    kernel splices the row into the fetched block and emits it through
    the aliased pool output (the out index_map pins the slot's write
    page — the null page 0 for inactive slots), and the attention
    compute reads the row from the same in-register splice, so scores
    never depend on the HBM write having landed.  COW guarantees the
    write page's refcount is 1, so no other slot can map it — the only
    cross-slot page traffic is reads.
    """
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    pos_b = pos_ref[b]
    lo = lo_ref[b]
    hi = hi_ref[b]
    live = act_ref[b] > 0
    rows = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
    wsel = ((ki * ps + rows) == pos_b) & live                # [ps, 1]

    @pl.when(ki == hi)
    def _store():
        w3 = wsel[:, :, None]
        ko_ref[0] = jnp.where(w3, nk_ref[...].astype(ko_ref.dtype),
                              k_ref[0])
        vo_ref[0] = jnp.where(w3, nv_ref[...].astype(vo_ref.dtype),
                              v_ref[0])

    def ok(shape):
        idx = ki * ps + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        m = idx <= pos_b
        if window:
            m &= idx > pos_b - window
        return m

    @pl.when(jnp.logical_and(ki >= lo, ki <= hi))
    def _compute():
        for h in range(kv_heads):
            k = jnp.where(wsel, nk_ref[0, pl.ds(h, 1), :],
                          k_ref[0, :, h, :].astype(jnp.float32))  # [ps, hd]
            v = jnp.where(wsel, nv_ref[0, pl.ds(h, 1), :],
                          v_ref[0, :, h, :].astype(jnp.float32))
            _attend_block(q_ref[0, h].astype(jnp.float32), k, v, ok,
                          acc.at[h], m_scr.at[h], l_scr.at[h],
                          scale=scale, softcap=softcap)

    @pl.when(ki == npg - 1)
    def _finalize():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


def paged_cache_read_bytes(pos, *, num_pages_per_slot, page_size, kv_heads,
                           head_dim, window=0, dtype_bytes=2):
    """Analytic K+V HBM bytes one fused *paged* decode step moves at
    ``pos``: page reads (same [lo, hi] sweep as the dense kernel with
    ``block_k = page_size``) plus the boundary-page write-back."""
    reads = cache_read_bytes(pos, seq_len=num_pages_per_slot * page_size,
                             kv_heads=kv_heads, head_dim=head_dim,
                             window=window, ring=False, block_k=page_size,
                             dtype_bytes=dtype_bytes)
    n = int(jnp.asarray(pos).reshape(-1).shape[0])
    writes = n * 2 * page_size * kv_heads * head_dim * dtype_bytes
    return reads + writes


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def paged_decode_attention_fwd(q, new_k, new_v, k_pool, v_pool, pos,
                               page_table, active, *, window=0, softcap=0.0,
                               scale=None, interpret=False):
    """Fused write+attend decode step over paged KV pools.

    q [B, 1, H, hd]; new_k/new_v [B, KV, hd] — the new token's K/V rows
    (any float dtype; rounded to the pool dtype before use so paged and
    dense streams stay bit-identical); k/v pools [P, ps, KV, hd];
    page_table [B, NP] int32 physical page per logical page; active [B]
    bool (inactive slots write nothing — their boundary block flushes
    to the null page 0).

    Returns ``(o [B, 1, H, hd], k_pool', v_pool')``.  With
    ``ps == block_k`` the attention math is block-for-block identical
    to ``decode_attention_fwd`` on the gathered dense view.
    """
    P, ps, KV, hd = k_pool.shape
    B, NP = page_table.shape
    H = q.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    lo, hi = block_bounds(pos_b, seq_len=NP * ps, window=window, ring=False,
                          block_k=ps)
    act = jnp.asarray(active).astype(jnp.int32)
    qt = q.reshape(B, KV, G, hd)
    # the new rows ride in f32 (already rounded to the pool dtype): a
    # 32-bit [KV, hd] tile can be indexed per head without sublane
    # packing, and the store casts back exactly
    nk = new_k.astype(k_pool.dtype).astype(jnp.float32)
    nv = new_v.astype(v_pool.dtype).astype(jnp.float32)

    def kv_map(b, j, pos_ref, lo_ref, hi_ref, tbl_ref, act_ref):
        # page-table indirection in SMEM; the clamp makes out-of-range
        # grid steps re-visit the boundary page (no DMA, no compute)
        return tbl_ref[b, jnp.clip(j, lo_ref[b], hi_ref[b])], 0, 0, 0

    def wr_map(b, j, pos_ref, lo_ref, hi_ref, tbl_ref, act_ref):
        # constant per slot: the slot's write page, flushed once at the
        # sweep boundary with the spliced block from _store
        return jnp.where(act_ref[b] > 0, tbl_ref[b, hi_ref[b]], 0), 0, 0, 0

    page = (1, ps, KV, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, NP),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, KV, hd), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec(page, kv_map),
            pl.BlockSpec(page, kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, G, hd), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec(page, wr_map),
            pl.BlockSpec(page, wr_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, window=window, softcap=softcap,
        ps=ps, npg=NP, kv_heads=KV)
    o, kp, vp = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operand numbering includes the 5 scalar-prefetch args
        input_output_aliases={8: 1, 9: 2},
        # both dims "arbitrary": slots read pages other slots may be
        # flushing their boundary block to (shared prefix pages are
        # read-only, but the in/out pool aliasing still wants a defined
        # step order)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(pos_b, lo, hi, jnp.asarray(page_table, jnp.int32), act, qt, nk, nv,
      k_pool, v_pool)
    return o.reshape(B, 1, H, hd), kp, vp
