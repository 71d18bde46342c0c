"""Jit'd public wrappers around the Pallas kernels.

- ``flash_attention``: the splash kernels, forward and backward (training
  attention; ``models/model.py`` takes it in train mode).
- ``masked_adam_tree``: applies the fused update leaf-wise over a pytree
  (2-D flattening; the kernel grid handles padding).
- ``rglru_scan``: linear-recurrence kernel with associative-scan VJP.

All wrappers take ``use_pallas``: on this CPU container the kernels run
in interpret mode for tests only; production code paths select Pallas on
TPU backends and the pure-XLA fallbacks elsewhere (``default_backend()``).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as da
from repro.kernels import masked_adam as ma
from repro.kernels import flash_attention as fa
from repro.kernels import rglru_scan as rg
from repro.kernels import scatter_apply as sa
from repro.models import layers

Pytree = Any


def pallas_available() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
# flash attention (training: forward and backward kernels)
# --------------------------------------------------------------------- #


def flash_attention(q, k, v, causal=True, window=0, interpret=False,
                    scale=None):
    """Self-attention q [B, S, H, hd], k [B, S, KV, hd], v [B, S, KV,
    hd_v] -> [B, S, H, hd_v] through the splash kernels, forward and
    backward (see kernels/flash_attention.py); S a multiple of 128
    (``flash_attention_supports``); scores scaled by ``scale`` (default
    1/sqrt(hd)).  Its device ops carry the scope ``flash_attn``, which
    ``bench/metrics/attn_ms.train.py`` reads."""
    with jax.named_scope("flash_attn"):
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  interpret=interpret, scale=scale)


flash_attention_supports = fa.supports


# --------------------------------------------------------------------- #
# fused decode attention (serving hot path; no backward — inference only)
# --------------------------------------------------------------------- #


def decode_attention(q, k_cache, v_cache, pos, *, window=0, ring=False,
                     softcap=0.0, mode: str = "auto", block_k: int = 128):
    """One-token attention against a slot-batched KV cache.

    q [B, 1, H, hd]; caches [B, C, KV, hd]; pos scalar or [B].  ``mode``:
    ``pallas`` | ``interpret`` | ``xla`` | ``auto`` (Pallas on TPU, the
    grouped-einsum XLA path elsewhere).  The Pallas kernel's HBM reads
    scale with ``pos`` (see kernels/decode_attention.py); the XLA path
    scores the full cache but never materializes GQA-repeated heads.
    """
    if mode == "auto":
        mode = "pallas" if pallas_available() else "xla"
    if mode == "xla":
        return layers.attention_decode(q, k_cache, v_cache, pos,
                                       window=window, softcap=softcap,
                                       ring=ring)
    return da.decode_attention_fwd(q, k_cache, v_cache, pos, window=window,
                                   ring=ring, softcap=softcap,
                                   block_k=block_k,
                                   interpret=(mode == "interpret"))


def paged_decode_attention(q, new_k, new_v, k_pool, v_pool, pos, page_table,
                           active, *, window=0, softcap=0.0,
                           mode: str = "auto"):
    """One-token fused write+attend against paged KV pools.

    q [B, 1, H, hd]; new_k/new_v [B, KV, hd] (the new token's rows);
    pools [P, page_size, KV, hd]; page_table [B, NP] int32; active [B]
    bool.  Returns ``(o, k_pool', v_pool')`` — the row write happens
    inside the op (kernel prologue on the Pallas path), so the serving
    loop dispatches one op per layer instead of scatter + attend.
    ``mode``: ``pallas`` | ``interpret`` | ``xla`` | ``auto``.
    """
    if mode == "auto":
        mode = "pallas" if pallas_available() else "xla"
    if mode != "xla":
        return da.paged_decode_attention_fwd(
            q, new_k, new_v, k_pool, v_pool, pos, page_table, active,
            window=window, softcap=softcap,
            interpret=(mode == "interpret"))
    # XLA fallback: scatter the new row, gather the dense-shaped view
    # through the page table, and run the *same* grouped-einsum
    # attention the dense cache path runs — identical shapes and values
    # keep paged and dense token streams bit-identical.
    P, ps, KV, hd = k_pool.shape
    B, NP = page_table.shape
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    act = jnp.asarray(active, bool)
    tbl = jnp.asarray(page_table, jnp.int32)
    phys = jnp.take_along_axis(tbl, (pos_b // ps)[:, None], axis=1)[:, 0]
    widx = jnp.where(act, phys * ps + pos_b % ps, P * ps)
    kf = k_pool.reshape(P * ps, KV, hd).at[widx].set(
        new_k.astype(k_pool.dtype), mode="drop")
    vf = v_pool.reshape(P * ps, KV, hd).at[widx].set(
        new_v.astype(v_pool.dtype), mode="drop")
    ridx = (tbl[:, :, None] * ps
            + jnp.arange(ps, dtype=jnp.int32)[None, None]).reshape(B, NP * ps)
    ck = jnp.take(kf, ridx, axis=0)
    cv = jnp.take(vf, ridx, axis=0)
    o = layers.attention_decode(q, ck, cv, pos_b, window=window,
                                softcap=softcap, ring=False)
    return o, kf.reshape(k_pool.shape), vf.reshape(v_pool.shape)


# --------------------------------------------------------------------- #
# fused masked adam over pytrees
# --------------------------------------------------------------------- #


def _to_2d(a):
    if a.ndim == 0:
        return a.reshape(1, 1)
    if a.ndim == 1:
        return a.reshape(1, -1)
    return a.reshape(-1, a.shape[-1])


def masked_adam_tree(params: Pytree, grads: Pytree, mu: Pytree, nu: Pytree,
                     masks: Pytree, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                     weight_decay=0.0, count=0, tau=0.0, use_tau=False,
                     interpret=False):
    """Fused masked-Adam across every leaf.  Returns (params, mu, nu)."""
    cf = jnp.asarray(count, jnp.float32) + 1.0
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        1.0 - b1 ** cf, 1.0 - b2 ** cf, jnp.asarray(tau, jnp.float32)])

    def one(p, g, m, v, msk):
        shape = p.shape
        p2, m2, v2 = ma.masked_adam_2d(
            _to_2d(p), _to_2d(g), _to_2d(m), _to_2d(v),
            _to_2d(msk if msk is not None else jnp.ones(p.shape, jnp.bool_)),
            scal, use_tau=use_tau, interpret=interpret)
        return p2.reshape(shape), m2.reshape(shape), v2.reshape(shape)

    flat_p, td = jax.tree.flatten(params)
    out = [one(p, g, m, v, msk) for p, g, m, v, msk in zip(
        flat_p, td.flatten_up_to(grads), td.flatten_up_to(mu),
        td.flatten_up_to(nu),
        td.flatten_up_to(masks) if masks is not None
        else [None] * len(flat_p))]
    return (td.unflatten([o[0] for o in out]),
            td.unflatten([o[1] for o in out]),
            td.unflatten([o[2] for o in out]))


def _to_q8_view(a):
    """Flatten/pad a leaf into the [NB, BLOCK] codec view the quantized
    moments are stored in (same block walk as runtime/compression.py)."""
    from repro.runtime.compression import BLOCK
    flat = a.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)


def masked_adam_q8_tree(params: Pytree, grads: Pytree, mu_q: Pytree,
                        mu_scale: Pytree, nu_q: Pytree, nu_scale: Pytree,
                        masks: Pytree, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                        weight_decay=0.0, count=0, tau=0.0, use_tau=False,
                        interpret=False):
    """Fused dequant->masked-Adam->requant across every leaf.

    Moments stay in their quantized storage layout (int8 [NB, BLOCK] +
    f32 [NB] scales, mirroring the param treedef) — no fp32 moment tree
    is ever materialized.  Returns
    ``(params', mu_q', mu_scale', nu_q', nu_scale')``.
    """
    cf = jnp.asarray(count, jnp.float32) + 1.0
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(b1, jnp.float32),
        jnp.asarray(b2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        1.0 - b1 ** cf, 1.0 - b2 ** cf, jnp.asarray(tau, jnp.float32)])

    def one(p, mq, ms, nq, ns, g, msk):
        shape = p.shape
        pv = _to_q8_view(p)
        gv = _to_q8_view(g)
        mv = _to_q8_view(msk if msk is not None
                         else jnp.ones(shape, jnp.bool_))
        p2, mq2, ms2, nq2, ns2 = ma.masked_adam_q8_2d(
            pv, gv, mq, ms.reshape(-1, 1), nq, ns.reshape(-1, 1), mv,
            scal, use_tau=use_tau, interpret=interpret)
        return (p2.reshape(-1)[:p.size].reshape(shape), mq2,
                ms2.reshape(-1), nq2, ns2.reshape(-1))

    flat_p, td = jax.tree.flatten(params)
    out = [one(p, mq, ms, nq, ns, g, msk) for p, mq, ms, nq, ns, g, msk
           in zip(flat_p, td.flatten_up_to(mu_q),
                  td.flatten_up_to(mu_scale), td.flatten_up_to(nu_q),
                  td.flatten_up_to(nu_scale), td.flatten_up_to(grads),
                  td.flatten_up_to(masks) if masks is not None
                  else [None] * len(flat_p))]
    return (td.unflatten([o[0] for o in out]),
            td.unflatten([o[1] for o in out]),
            td.unflatten([o[2] for o in out]),
            td.unflatten([o[3] for o in out]),
            td.unflatten([o[4] for o in out]))


# --------------------------------------------------------------------- #
# adapter row scatter-swap
# --------------------------------------------------------------------- #


# NB: the 2-D reshapes live INSIDE the jitted bodies.  Outside jit,
# ``x.reshape`` eagerly allocates a fresh buffer — an O(leaf) copy that
# would defeat the donated O(delta) swap for the common 3-D stacked
# leaves; inside jit XLA aliases them for free.


def _swap_body(full, idx, rows):
    f2 = full.reshape(full.shape[0], -1)
    r2 = rows.reshape(rows.shape[0], -1)
    out = f2.at[idx].set(r2.astype(f2.dtype))
    return out.reshape(full.shape), f2[idx].reshape(rows.shape)


_scatter_swap_xla_donated = jax.jit(_swap_body, donate_argnums=(0,))
_scatter_swap_xla = jax.jit(_swap_body)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _scatter_swap_kernel(full, idx, rows, *, interpret):
    f2 = full.reshape(full.shape[0], -1)
    r2 = rows.reshape(rows.shape[0], -1)
    out2, disp2 = sa.scatter_swap_2d(f2, idx, r2, interpret=interpret)
    return out2.reshape(full.shape), disp2.reshape(rows.shape)


def scatter_swap(full, idx, rows, *, mode: str = "auto",
                 donate: bool = False):
    """Swap rows ``idx`` of an arbitrary-rank leaf with ``rows``.

    ``full`` [G, ...]; ``rows`` [K, ...] with matching trailing dims.
    Returns ``(new_full, displaced_rows)`` — an exact involution (see
    kernels/scatter_apply.py).  ``mode``: ``pallas`` | ``interpret`` |
    ``xla`` | ``auto`` (Pallas on TPU, XLA scatter elsewhere).

    ``donate=True`` consumes ``full`` (in-place on device — O(K) bytes
    moved instead of an O(G) copy; the caller must drop its reference).
    The default keeps the input alive and pays a one-time copy — the
    safe choice for offline extract/apply paths.
    """
    if idx.shape[0] == 0:
        return full, rows
    if mode == "auto":
        mode = "pallas" if pallas_available() else "xla"
    if mode == "xla":
        fn = _scatter_swap_xla_donated if donate else _scatter_swap_xla
        return fn(full, idx, rows)
    # the Pallas kernel aliases full->out unconditionally; copy first
    # when the caller wants its input kept alive
    if not donate:
        full = jnp.array(full, copy=True)
    return _scatter_swap_kernel(full, idx, rows,
                                interpret=(mode == "interpret"))


# --------------------------------------------------------------------- #
# RG-LRU
# --------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rglru_scan_op(a, b, h0, interpret=False):
    y, hN = rg.rglru_scan_kernel(a, b, h0, interpret=interpret)
    return y, hN


def _rg_fwd(a, b, h0, interpret):
    y, hN = rg.rglru_scan_kernel(a, b, h0, interpret=interpret)
    return (y, hN), (a, y, h0)


def _rg_bwd(interpret, res, cts):
    a, y, h0 = res
    dy, dhN = cts
    # reverse-time linear recurrence: lam_t = a_{t+1} lam_{t+1} + dy_t
    B, S, W = a.shape
    a_next = jnp.concatenate(
        [a[:, 1:], jnp.zeros((B, 1, W), a.dtype)], axis=1)
    dy = dy.at[:, -1].add(dhN)
    lam, _ = rg.rglru_scan_kernel(
        jnp.flip(a_next, 1), jnp.flip(dy, 1),
        jnp.zeros((B, W), jnp.float32), interpret=interpret)
    lam = jnp.flip(lam, 1)                     # [B,S,W] adjoint of h_t
    y_prev = jnp.concatenate([h0[:, None], y[:, :-1]], axis=1)
    da = lam * y_prev
    db = lam
    dh0 = lam[:, 0] * a[:, 0]
    return da, db, dh0


_rglru_scan_op.defvjp(_rg_fwd, _rg_bwd)


def rglru_scan(a, b, h0, interpret=False):
    """Public entry: the custom_vjp scan."""
    return _rglru_scan_op(a, b, h0, interpret)
