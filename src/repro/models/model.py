"""Model zoo dispatcher: ``ModelConfig`` -> pure (init / forward / decode).

Layer stacking: the decoder is partitioned into *stages* (``cfg.stages()``);
each stage scans over ``G`` repetitions of a block ``pattern`` with
parameters stacked ``[G, ...]`` per pattern position.  This keeps the HLO
small at 26-48 layer depth, makes remat policy uniform, and gives the
BlockLLM static-BCD mode its gather axis (a "block" = one stacked row).

Modes:
  train   — full-sequence teacher forcing, returns loss-ready logits.
  prefill — full sequence, additionally returns the decode cache.
  decode  — one token against a cache (``pos`` = index of the new token).

Families: dense/moe LMs, VLM (stub patch-embedding frontend), hybrid
(RG-LRU), SSM (xLSTM), audio (whisper enc-dec with stub conv frontend).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import (
    BLOCK_GLOBAL_ATTN, BLOCK_LOCAL_ATTN, BLOCK_MLSTM, BLOCK_RECURRENT,
    BLOCK_SLSTM, ModelConfig)
from repro.models import layers, moe as moe_lib, rglru, xlstm
from repro.runtime import shard_ctx, ssm_parallel
from repro.runtime.moe_parallel import moe_apply_maybe_sharded

Pytree = Any

ATTN_BLOCKS = (BLOCK_GLOBAL_ATTN, BLOCK_LOCAL_ATTN)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig, btype: str, *, cross=False):
    ks = jax.random.split(key, 6)
    if btype in ATTN_BLOCKS:
        p = {
            "ln1": layers.norm_init(cfg.d_model),
            "attn": (layers.mla_init(ks[0], cfg) if cfg.mla
                     else layers.attention_init(ks[0], cfg)),
            "ln2": layers.norm_init(cfg.d_model),
        }
        if cfg.num_experts:
            p["moe"] = moe_lib.moe_init(ks[1], cfg)
        elif cfg.d_ff:
            p["mlp"] = layers.mlp_init(ks[1], cfg)
        if cross:
            p["lnx"] = layers.norm_init(cfg.d_model)
            p["xattn"] = layers.attention_init(ks[2], cfg, cross=True)
        return p
    if btype == BLOCK_RECURRENT:
        return {
            "ln1": layers.norm_init(cfg.d_model),
            "rec": rglru.block_init(ks[0], cfg),
            "ln2": layers.norm_init(cfg.d_model),
            "mlp": layers.mlp_init(ks[1], cfg),
        }
    if btype == BLOCK_MLSTM:
        return xlstm.mlstm_init(ks[0], cfg)
    if btype == BLOCK_SLSTM:
        return xlstm.slstm_init(ks[0], cfg)
    raise ValueError(btype)


def _stage_init(key, cfg, pattern, n_groups, *, cross=False):
    """Stacked params: {posJ: pytree with leading [n_groups] axis}."""
    out = {}
    for j, btype in enumerate(pattern):
        ks = jax.random.split(jax.random.fold_in(key, j), n_groups)
        stacked = jax.vmap(
            lambda k: _block_init(k, cfg, btype, cross=cross))(ks)
        out[f"pos{j}"] = stacked
    return out


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Pytree:
    ks = jax.random.split(key, 8)
    p: dict = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02,
        "final_norm": layers.norm_init(cfg.d_model),
        "stages": [
            _stage_init(jax.random.fold_in(ks[1], si), cfg.stage_config(si),
                        pattern, groups, cross=cfg.is_encoder_decoder)
            for si, (pattern, groups) in enumerate(cfg.stages())
        ],
    }
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(ks[2], (cfg.d_model, cfg.vocab_size)) * 0.02
    if cfg.vision_embed_dim:
        p["vision_proj"] = layers.dense_init(
            ks[3], cfg.vision_embed_dim, cfg.d_model)
    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(num_layers=cfg.num_encoder_layers,
                              pattern=(BLOCK_GLOBAL_ATTN,), num_experts=0,
                              is_encoder_decoder=False,
                              num_kv_heads=cfg.num_heads)  # encoder is MHA
        p["encoder"] = {
            "frontend": layers.dense_init(
                ks[4], cfg.encoder_feature_dim or cfg.d_model, cfg.d_model),
            "stages": [
                _stage_init(jax.random.fold_in(ks[5], si), enc_cfg, pat, g)
                for si, (pat, g) in enumerate(enc_cfg.stages())
            ],
            "final_norm": layers.norm_init(cfg.d_model),
        }
    if dtype != jnp.float32:
        p = jax.tree.map(lambda a: a.astype(dtype), p)
    return p


# ---------------------------------------------------------------------------
# block apply
# ---------------------------------------------------------------------------


def _attn_cache_len(cfg, btype, seq_len):
    if btype == BLOCK_LOCAL_ATTN:
        return min(cfg.window_size or seq_len, seq_len)
    return seq_len


def _train_flash(attn_impl, seq_len, cfg) -> bool:
    """Whether a train-mode self-attention runs as the flash kernel
    (``kernels.ops.flash_attention``, forward and backward).

    ``"pallas"``/``"pallas_interpret"`` force it; ``"auto"`` takes it on a
    TPU with no shard rules installed (the distributed launcher keeps XLA
    attention) and otherwise runs as ``"full"``.  Either way only where
    the kernel covers the shape and the mask: no logit softcap, S a
    multiple of 128.  ``"full"`` and ``"chunked"`` never take it.
    """
    from repro.kernels import ops as kernel_ops
    if attn_impl == "auto":
        if not kernel_ops.pallas_available() or shard_ctx.get() is not None:
            return False
    elif attn_impl not in ("pallas", "pallas_interpret"):
        return False
    return not cfg.attn_softcap and kernel_ops.flash_attention_supports(
        seq_len)


def refuse_mla(cfg, what: str) -> None:
    """Multi-head latent attention runs in train mode only: its serving
    cache (the latent and the rope key) does not exist here."""
    if cfg.mla:
        raise ValueError(
            f"{cfg.name}: {what} is not supported for multi-head latent "
            "attention (kv_lora_rank > 0), which runs in train mode only")


def _mla_attention(cfg, params, h, positions, attn_impl):
    """Multi-head latent attention over full sequences, no q compression:
    q = h W_q, [c ‖ k_pe] = h W_kv_a, c normed, [k_nope ‖ v] = c W_kv_b;
    q's and k_pe's rope parts rotated (YaRN where configured; k_pe is one
    head shared by all), causal softmax of the scaled [q_nope ‖ q_pe] ·
    [k_nope ‖ k_pe], times v, then W_o."""
    B, S, _ = h.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    a = params["attn"]
    with jax.named_scope("mla"):
        q = (h @ a["wq"].astype(h.dtype)).reshape(B, S, H, dn + dr)
        ckv = h @ a["wkv_a"].astype(h.dtype)
        c = layers.rms_norm(a["kv_norm"], ckv[..., :r], cfg.norm_eps)
        kv = (c @ a["wkv_b"].astype(h.dtype)).reshape(B, S, H, dn + dv)
        yarn = cfg.rope_yarn
        if yarn is None:
            inv, mscale = None, 1.0
            scale = (dn + dr) ** -0.5
        else:
            inv = layers.yarn_inv_freq(dr, cfg.rope_theta, yarn)
            mscale = (layers.yarn_mscale(yarn.factor, yarn.mscale)
                      / layers.yarn_mscale(yarn.factor, yarn.mscale_all_dim))
            scale = layers.yarn_softmax_scale(dn + dr, yarn)
        q_pe = layers.apply_rope(q[..., dn:], positions, cfg.rope_theta, inv,
                                 mscale)
        k_pe = layers.apply_rope(ckv[..., None, r:], positions,
                                 cfg.rope_theta, inv, mscale)
        q = jnp.concatenate([q[..., :dn], q_pe], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))], -1)
        v = kv[..., dn:]
        if _train_flash(attn_impl, S, cfg):
            from repro.kernels import ops as kernel_ops
            o = kernel_ops.flash_attention(
                q, k, v, True, 0, attn_impl == "pallas_interpret", scale=scale)
        else:
            o = layers.attention_full(q, k, v, positions, positions,
                                      causal=True, scale=scale)
        return o.reshape(B, S, H * dv) @ a["wo"].astype(h.dtype)


def _block_apply(cfg, btype, params, x, *, positions, mode, cache,
                 enc_out=None, pos=None, attn_impl="chunked",
                 chunk_start=0, page_table=None, active=None, begin=None):
    """Returns (y, new_cache, stats): ``stats["aux"]`` the auxiliary
    loss, and an MoE layer's counters (``moe.moe_apply``).

    Paged KV (``init_paged_cache``): global-attention block caches are
    ``{"pk", "pv"}`` pools ``[num_pages, page_size, KV, hd]`` addressed
    through ``page_table`` [B, pages_per_slot] (physical page per
    logical page; see runtime/paged_kv.py).  ``active`` [B] masks slot
    writes (inactive slots' rows are never touched — the paged path
    needs no server-side cache blend), ``begin`` [B] is the first
    prompt position a slot prefills itself (earlier rows come from
    shared prefix pages).
    """
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if btype in ATTN_BLOCKS and cfg.mla:
        if mode != "train":
            refuse_mla(cfg, f"mode {mode!r}")
        h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
        x = x + _mla_attention(cfg, params, h, positions, attn_impl)
        h = layers.rms_norm(params["ln2"], x, cfg.norm_eps)
        if cfg.num_experts:
            y, stats = moe_apply_maybe_sharded(params["moe"], h, cfg)
        else:
            y = layers.mlp_apply(params["mlp"], h, cfg.mlp_type)
        return x + y, None, stats
    if btype in ATTN_BLOCKS:
        window = cfg.window_size if btype == BLOCK_LOCAL_ATTN else 0
        h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
        # Megatron-SP gather point: sequence-sharded -> full, in bf16
        # (without it GSPMD gathers f32 norm internals / MLP weights)
        h = shard_ctx.constrain(h, "block_in")
        B, S, D = h.shape
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        q = (h @ params["attn"]["wq"].astype(h.dtype)).reshape(B, S, H, hd)
        k = (h @ params["attn"]["wk"].astype(h.dtype)).reshape(B, S, KV, hd)
        v = (h @ params["attn"]["wv"].astype(h.dtype)).reshape(B, S, KV, hd)
        if mode not in ("decode", "prefill_slots", "verify"):
            # Megatron-SP: attention runs head-sharded with full sequence
            # (one reshard per layer; pruned when heads don't divide)
            q = shard_ctx.constrain(q, "attn_heads")
            k = shard_ctx.constrain(k, "attn_kv_heads")
            v = shard_ctx.constrain(v, "attn_kv_heads")
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
        new_cache = None
        if mode == "decode" and cache is not None and "pk" in cache:
            # paged decode: write the new row into its physical page and
            # attend through the page indirection.  The fused kernel
            # (write+attend in one pass) and the XLA fallback
            # (scatter -> gather the dense-shaped view -> the *same*
            # attention_decode the dense path runs) both keep token
            # streams bit-identical to the dense cache.
            from repro.kernels import ops as kernel_ops
            pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
            act = (jnp.ones((B,), bool) if active is None
                   else jnp.asarray(active, bool))
            o, npk, npv = kernel_ops.paged_decode_attention(
                q, k[:, 0], v[:, 0], cache["pk"], cache["pv"], pos_b,
                page_table, act, window=window, softcap=cfg.attn_softcap,
                mode={"pallas": "pallas",
                      "pallas_interpret": "interpret"}.get(attn_impl, "xla"))
            new_cache = {"pk": npk, "pv": npv}
        elif mode == "decode":
            ring = btype == BLOCK_LOCAL_ATTN
            C = cache["k"].shape[1]
            pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
            slot = (pos_b % C) if ring else pos_b
            bidx = jnp.arange(B)
            if active is not None:
                # paged serving, dense ring block: mask the write so
                # inactive slots' rows stay bit-exact without the
                # server-side whole-tree blend
                slot = jnp.where(jnp.asarray(active, bool), slot, C)
                ck = cache["k"].at[bidx, slot].set(
                    k[:, 0].astype(cache["k"].dtype), mode="drop")
                cv = cache["v"].at[bidx, slot].set(
                    v[:, 0].astype(cache["v"].dtype), mode="drop")
            else:
                ck = cache["k"].at[bidx, slot].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[bidx, slot].set(
                    v[:, 0].astype(cache["v"].dtype))
            if attn_impl in ("pallas", "pallas_interpret"):
                from repro.kernels import ops as kernel_ops
                o = kernel_ops.decode_attention(
                    q, ck, cv, pos_b, window=window,
                    softcap=cfg.attn_softcap, ring=ring,
                    mode=("interpret" if attn_impl == "pallas_interpret"
                          else "pallas"))
            else:
                o = layers.attention_decode(q, ck, cv, pos_b, window=window,
                                            softcap=cfg.attn_softcap,
                                            ring=ring)
            new_cache = {"k": ck, "v": cv}
        elif mode == "prefill_slots" and cache is not None and "pk" in cache:
            # paged chunked prefill: scatter the chunk's K/V rows into
            # their physical pages (skipping rows below ``begin`` —
            # those live in shared prefix pages already), then attend
            # exactly like the dense path: history rows gathered through
            # the page table, the chunk's own rows in-register through
            # the cache-dtype round trip.  Identical shapes and values
            # to the dense concat keep the streams bit-identical.
            pk, pv = cache["pk"], cache["pv"]
            P_, ps = pk.shape[0], pk.shape[1]
            lengths = jnp.broadcast_to(jnp.asarray(pos), (B,))
            last = jnp.minimum(lengths, chunk_start + S)[:, None]
            valid = positions < last
            if begin is not None:
                valid &= positions >= jnp.asarray(begin, jnp.int32)[:, None]
            phys = jnp.take_along_axis(page_table, positions // ps, axis=1)
            flat = jnp.where(valid, phys * ps + positions % ps, P_ * ps)
            pkf = pk.reshape(P_ * ps, KV, hd).at[flat].set(
                k.astype(pk.dtype), mode="drop")
            pvf = pv.reshape(P_ * ps, KV, hd).at[flat].set(
                v.astype(pv.dtype), mode="drop")
            new_cache = {"pk": pkf.reshape(pk.shape),
                         "pv": pvf.reshape(pv.shape)}
            hp = np.arange(chunk_start)
            ridx = (jnp.take(page_table, hp // ps, axis=1) * ps
                    + jnp.asarray(hp % ps, jnp.int32)[None])
            kh = jnp.take(pk.reshape(P_ * ps, KV, hd), ridx,
                          axis=0).astype(q.dtype)
            vh = jnp.take(pv.reshape(P_ * ps, KV, hd), ridx,
                          axis=0).astype(q.dtype)
            kc = k.astype(pk.dtype).astype(q.dtype)
            vc = v.astype(pv.dtype).astype(q.dtype)
            kp = jnp.broadcast_to(jnp.asarray(hp, jnp.int32)[None],
                                  (B, chunk_start))
            o = layers.attention_full(
                q, jnp.concatenate([kh, kc], axis=1),
                jnp.concatenate([vh, vc], axis=1),
                positions, jnp.concatenate([kp, positions], axis=1),
                causal=True, window=window, softcap=cfg.attn_softcap)
        elif mode == "prefill_slots":
            # chunked batched prefill: scatter this chunk's K/V rows into
            # the slot-batched decode cache (positions are absolute,
            # ``pos`` carries per-slot prompt LENGTHS — 0 for slots not
            # being primed), then attend causally over the already
            # written history plus the chunk.  One dispatch primes a
            # whole admitted group for ``S`` positions — vs one decode
            # dispatch per token per request on the legacy path.
            ring = btype == BLOCK_LOCAL_ATTN
            C = cache["k"].shape[1]
            lengths = jnp.broadcast_to(jnp.asarray(pos), (B,))
            last = jnp.minimum(lengths, chunk_start + S)[:, None]
            valid = positions < last
            if ring:
                # only the last C valid rows land (ring layout
                # slot(p) = p % C, matching decode writes); dropping the
                # older ones also keeps scatter indices collision-free
                valid &= positions + C >= last
                slot = positions % C
            else:
                slot = positions
            slot = jnp.where(valid, slot, C)   # OOB rows -> dropped
            bidx = jnp.arange(B)[:, None]
            ck = cache["k"].at[bidx, slot].set(
                k.astype(cache["k"].dtype), mode="drop")
            cv = cache["v"].at[bidx, slot].set(
                v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": ck, "v": cv}
            # attend over [written history, this chunk].  The chunk's
            # own k/v go through the cache dtype round-trip so the
            # scores match what the per-token path reads back.
            hist = min(chunk_start, C)
            hp = np.arange(chunk_start - hist, chunk_start)
            hidx = jnp.asarray(hp % C if ring else hp, jnp.int32)
            kh = jnp.take(cache["k"], hidx, axis=1).astype(q.dtype)
            vh = jnp.take(cache["v"], hidx, axis=1).astype(q.dtype)
            kc = k.astype(cache["k"].dtype).astype(q.dtype)
            vc = v.astype(cache["v"].dtype).astype(q.dtype)
            kp = jnp.broadcast_to(jnp.asarray(hp, jnp.int32)[None],
                                  (B, hist))
            o = layers.attention_full(
                q, jnp.concatenate([kh, kc], axis=1),
                jnp.concatenate([vh, vc], axis=1),
                positions, jnp.concatenate([kp, positions], axis=1),
                causal=True, window=window, softcap=cfg.attn_softcap)
        elif mode == "verify" and cache is not None and "pk" in cache:
            # paged speculative verify: like paged prefill_slots, but the
            # chunk starts at a *traced per-slot* position (``pos`` [B] =
            # each slot's next write index) and every row is live.  The
            # scatter overwrites the base model's draft rows with the
            # adapter's K/V; history is the whole table range with key
            # positions pushed past any query where the chunk supersedes
            # them (kp >= start), so stale draft rows are masked to an
            # exact-zero softmax weight.
            if btype != BLOCK_GLOBAL_ATTN:
                raise ValueError(
                    "verify mode needs global-attention blocks "
                    "(see supports_spec_decode)")
            pk, pv = cache["pk"], cache["pv"]
            P_, ps = pk.shape[0], pk.shape[1]
            act = (jnp.ones((B,), bool) if active is None
                   else jnp.asarray(active, bool))
            valid = jnp.broadcast_to(act[:, None], (B, S))
            phys = jnp.take_along_axis(page_table, positions // ps, axis=1)
            flat = jnp.where(valid, phys * ps + positions % ps, P_ * ps)
            pkf = pk.reshape(P_ * ps, KV, hd).at[flat].set(
                k.astype(pk.dtype), mode="drop")
            pvf = pv.reshape(P_ * ps, KV, hd).at[flat].set(
                v.astype(pv.dtype), mode="drop")
            new_cache = {"pk": pkf.reshape(pk.shape),
                         "pv": pvf.reshape(pv.shape)}
            S_hist = page_table.shape[1] * ps
            hp = np.arange(S_hist)
            ridx = (jnp.take(page_table, hp // ps, axis=1) * ps
                    + jnp.asarray(hp % ps, jnp.int32)[None])
            kh = jnp.take(pk.reshape(P_ * ps, KV, hd), ridx,
                          axis=0).astype(q.dtype)
            vh = jnp.take(pv.reshape(P_ * ps, KV, hd), ridx,
                          axis=0).astype(q.dtype)
            kc = k.astype(pk.dtype).astype(q.dtype)
            vc = v.astype(pv.dtype).astype(q.dtype)
            start_b = positions[:, :1]
            kp = jnp.where(jnp.asarray(hp, jnp.int32)[None] < start_b,
                           jnp.asarray(hp, jnp.int32)[None],
                           jnp.int32(2 ** 30))
            o = layers.attention_full(
                q, jnp.concatenate([kh, kc], axis=1),
                jnp.concatenate([vh, vc], axis=1),
                positions, jnp.concatenate([kp, positions], axis=1),
                causal=True, window=window, softcap=cfg.attn_softcap)
        elif mode == "verify":
            # dense speculative verify.  Rejected rows need no rollback:
            # rows at/after a slot's next write index are never read (the
            # decode path masks by position), so overwriting them with
            # candidate K/V is free — only the scheduler's ``pos`` decides
            # what is real.  Ring-buffer local attention breaks this (a
            # write at p clobbers the live row at p - C), hence the
            # all-global gate in supports_spec_decode.
            if btype != BLOCK_GLOBAL_ATTN:
                raise ValueError(
                    "verify mode needs global-attention blocks "
                    "(see supports_spec_decode)")
            C = cache["k"].shape[1]
            act = (jnp.ones((B,), bool) if active is None
                   else jnp.asarray(active, bool))
            slot = jnp.where(act[:, None], positions, C)
            bidx = jnp.arange(B)[:, None]
            ck = cache["k"].at[bidx, slot].set(
                k.astype(cache["k"].dtype), mode="drop")
            cv = cache["v"].at[bidx, slot].set(
                v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": ck, "v": cv}
            # history = every cache row, with rows the chunk supersedes
            # (kp >= per-slot start) masked by position: exp(-1e30) == 0.0
            # in f32, so the extra rows are bitwise-neutral padding and
            # the per-slot ragged starts never enter a shape.
            hp = jnp.arange(C, dtype=jnp.int32)
            start_b = positions[:, :1]
            kp = jnp.where(hp[None, :] < start_b, hp[None, :],
                           jnp.int32(2 ** 30))
            kh = cache["k"].astype(q.dtype)
            vh = cache["v"].astype(q.dtype)
            kc = k.astype(cache["k"].dtype).astype(q.dtype)
            vc = v.astype(cache["v"].dtype).astype(q.dtype)
            o = layers.attention_full(
                q, jnp.concatenate([kh, kc], axis=1),
                jnp.concatenate([vh, vc], axis=1),
                positions, jnp.concatenate([kp, positions], axis=1),
                causal=True, window=window, softcap=cfg.attn_softcap)
        else:
            if mode == "train" and _train_flash(attn_impl, S, cfg):
                from repro.kernels import ops as kernel_ops
                o = kernel_ops.flash_attention(
                    q, k, v, True, window, attn_impl == "pallas_interpret")
            elif attn_impl in ("full", "auto") or S <= 2048:
                o = layers.attention_full(
                    q, k, v, positions, positions, causal=True, window=window,
                    softcap=cfg.attn_softcap)
            else:
                o = layers.attention_chunked(
                    q, k, v, positions, positions, causal=True, window=window,
                    softcap=cfg.attn_softcap)
            if mode == "prefill":
                C = _attn_cache_len(cfg, btype, S)
                if btype == BLOCK_LOCAL_ATTN and C < S:
                    # ring layout: slot(p) = p % C, matching decode writes
                    slots = jnp.arange(S - C, S) % C
                    new_cache = {
                        "k": jnp.zeros_like(k[:, :C]).at[:, slots].set(
                            k[:, -C:]),
                        "v": jnp.zeros_like(v[:, :C]).at[:, slots].set(
                            v[:, -C:]),
                    }
                else:
                    new_cache = {"k": k[:, -C:], "v": v[:, -C:]}
        y = o.reshape(B, S, H * hd) @ params["attn"]["wo"].astype(x.dtype)
        y = shard_ctx.constrain(y, "residual")  # reduce-scatter point
        x = x + y
        if enc_out is not None and "xattn" in params:
            h = layers.rms_norm(params["lnx"], x, cfg.norm_eps)
            xk, xv = enc_out  # precomputed cross k,v [B, Se, H, hd]
            xq = (h @ params["xattn"]["wq"].astype(h.dtype)).reshape(B, S, H, hd)
            Se = xk.shape[1]
            kp = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))
            qp = jnp.zeros((B, S), jnp.int32)  # non-causal cross attention
            o = layers.attention_full(xq, xk, xv, qp, kp, causal=False)
            x = x + o.reshape(B, S, H * hd) @ params["xattn"]["wo"].astype(x.dtype)
        h = layers.rms_norm(params["ln2"], x, cfg.norm_eps)
        if cfg.num_experts:
            y, stats = moe_apply_maybe_sharded(params["moe"], h, cfg)
        elif cfg.d_ff:
            h = shard_ctx.constrain(h, "block_in")
            y = layers.mlp_apply(params["mlp"], h, cfg.mlp_type)
            y = shard_ctx.constrain(y, "residual")
        else:
            y = jnp.zeros_like(h)
        return x + y, new_cache, stats

    if mode in ("prefill_slots", "verify"):
        # recurrent/SSM states would advance on the right-padding of
        # shorter prompts — the server falls back to per-token priming
        # for these families (see supports_slot_prefill)
        raise ValueError(f"{mode} does not support {btype} blocks")

    if btype == BLOCK_RECURRENT:
        h = layers.rms_norm(params["ln1"], x, cfg.norm_eps)
        y, new_cache = rglru.block_apply(params["rec"], h, mode=mode,
                                         cache=cache)
        x = x + y
        h = layers.rms_norm(params["ln2"], x, cfg.norm_eps)
        return x + layers.mlp_apply(params["mlp"], h, cfg.mlp_type), \
            new_cache, stats

    if btype == BLOCK_MLSTM:
        y, new_cache = ssm_parallel.block_shard_map(
            lambda p, xx, c: xlstm.mlstm_block_apply(p, xx, mode=mode,
                                                     cache=c),
            params, x, cache)
        return x + y, new_cache, stats

    if btype == BLOCK_SLSTM:
        y, new_cache = ssm_parallel.block_shard_map(
            lambda p, xx, c: xlstm.slstm_block_apply(p, xx, cfg, mode=mode,
                                                     cache=c),
            params, x, cache)
        return x + y, new_cache, stats
    raise ValueError(btype)


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> Pytree:
    """Decode cache pytree mirroring the stage/scan structure."""
    refuse_mla(cfg, "a decode cache")
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def block_cache(btype):
        if btype in ATTN_BLOCKS:
            C = _attn_cache_len(cfg, btype, seq_len)
            return {"k": jnp.zeros((batch, C, KV, hd), dtype),
                    "v": jnp.zeros((batch, C, KV, hd), dtype)}
        if btype == BLOCK_RECURRENT:
            return rglru.init_cache(cfg, batch, dtype)
        if btype == BLOCK_MLSTM:
            return xlstm.mlstm_init_cache(cfg, batch)
        if btype == BLOCK_SLSTM:
            return xlstm.slstm_init_cache(cfg, batch)
        raise ValueError(btype)

    stages = []
    for pattern, groups in cfg.stages():
        st = {}
        for j, btype in enumerate(pattern):
            one = block_cache(btype)
            st[f"pos{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (groups,) + a.shape), one)
        stages.append(st)
    cache = {"stages": stages}
    if cfg.is_encoder_decoder:
        H = cfg.num_heads
        cache["cross_kv"] = [
            {f"pos{j}": {"k": jnp.zeros((groups, batch, cfg.encoder_seq_len,
                                         H, hd), dtype),
                         "v": jnp.zeros((groups, batch, cfg.encoder_seq_len,
                                         H, hd), dtype)}
             for j in range(len(pattern))}
            for pattern, groups in cfg.stages()]
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, seq_len: int,
                     dtype=jnp.bfloat16) -> Pytree:
    """Paged decode cache (PagedKV, runtime/paged_kv.py).

    Global-attention blocks get a shared pool ``[num_pages, page_size,
    KV, hd]`` per layer instead of dense ``[batch, seq_len]`` rows —
    HBM is paid per live token, not per worst-case slot.  Page 0 is
    the null page (unmapped table entries / inactive-slot write sink).
    Local-attention blocks keep their dense ring (already bounded by
    the window, and ring indexing is incompatible with page sharing).
    """
    refuse_mla(cfg, "a paged decode cache")
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def block_cache(btype):
        if btype == BLOCK_GLOBAL_ATTN:
            return {"pk": jnp.zeros((num_pages, page_size, KV, hd), dtype),
                    "pv": jnp.zeros((num_pages, page_size, KV, hd), dtype)}
        if btype == BLOCK_LOCAL_ATTN:
            C = _attn_cache_len(cfg, btype, seq_len)
            return {"k": jnp.zeros((batch, C, KV, hd), dtype),
                    "v": jnp.zeros((batch, C, KV, hd), dtype)}
        raise ValueError(
            f"paged KV supports attention blocks only, got {btype}")

    stages = []
    for pattern, groups in cfg.stages():
        st = {}
        for j, btype in enumerate(pattern):
            one = block_cache(btype)
            st[f"pos{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (groups,) + a.shape), one)
        stages.append(st)
    return {"stages": stages}


def copy_cache_pages(cache, src, dst):
    """Duplicate physical pages ``src -> dst`` in every pooled leaf —
    the device half of a copy-on-write split.  ``src``/``dst`` are
    int32 [n]; pad unused pairs with (0, 0) (a null-page self-copy is
    a no-op), so the caller can bucket ``n`` for jit reuse."""
    def one_stage(st):
        out = {}
        for name, blk in st.items():
            if isinstance(blk, dict) and "pk" in blk:
                out[name] = {kk: a.at[:, dst].set(a[:, src])
                             for kk, a in blk.items()}
            else:
                out[name] = blk
        return out

    new_cache = dict(cache)
    new_cache["stages"] = [one_stage(st) for st in cache["stages"]]
    return new_cache


def supports_paged_kv(cfg: ModelConfig) -> bool:
    """Paged KV needs position-addressable K/V rows in every block and
    a token-only frontend — same bar as chunked slot prefill."""
    return supports_slot_prefill(cfg)


def supports_spec_decode(cfg: ModelConfig) -> bool:
    """Self-speculative serving (``verify_into_slots``) needs chunked
    slot prefill plus every block global: rejected draft rows are rolled
    back by position masking alone, which ring-buffer local-attention
    rows do not support — a speculative write at position p clobbers the
    live row at p - C."""
    return (supports_slot_prefill(cfg)
            and all(t == BLOCK_GLOBAL_ATTN for t in cfg.layer_types()))


def supports_prefix_share(cfg: ModelConfig) -> bool:
    """Prefix sharing additionally needs every block global: a shared
    prefix only covers the *pooled* caches, and local-attention blocks
    keep per-slot ring rows the sharer would be missing."""
    return (supports_paged_kv(cfg)
            and all(t == BLOCK_GLOBAL_ATTN for t in cfg.layer_types()))


# ---------------------------------------------------------------------------
# stack apply (scan over stages)
# ---------------------------------------------------------------------------


def _resolve_overlay(gp, g, ov):
    """Per-layer lazy BCD merge (beyond-paper, EXPERIMENTS.md §Perf I10).

    ``ov`` = {"idx": [K] int32, "rows": pytree [K, ...],
              "pidx"/"probe": optional probe set}.  Instead of scattering
    active rows into the full stack up front (whose cotangent is a
    FULL-SIZE [L, ...] buffer that GSPMD all-reduces at full size), each
    scan step resolves its own row: gradients accumulate directly at
    [K, ...] and the DP gradient reduction scales with the active
    fraction.
    """
    def pick(base, idx, rows):
        # NB: `base` passes through UN-touched on miss — it is either the
        # stop-gradient'd frozen row or the (differentiable!) result of a
        # previous pick; re-stop-gradding here would sever sel gradients
        # whenever a probe set exists (bug caught by
        # tests/test_blockllm.py::test_mask_sparsity_matches_q).
        hit = idx == g
        any_hit = hit.any()
        p = jnp.argmax(hit)
        return jax.tree.map(
            lambda f, a: jnp.where(
                any_hit, lax.dynamic_index_in_dim(
                    a, p, 0, keepdims=False).astype(f.dtype), f),
            base, rows)

    out = jax.tree.map(lax.stop_gradient, gp)
    if ov.get("rows") is not None:
        out = pick(out, ov["idx"], ov["rows"])
    if ov.get("probe") is not None:
        out = pick(out, ov["pidx"], ov["probe"])
    return out


def _zero_stats(cfg):
    """The stack's totals: the auxiliary loss, and with experts the rows
    routed to held experts (summed over layers) and the largest group."""
    z = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.num_experts:
        z["moe_rows_held"] = jnp.zeros((), jnp.int32)
        z["moe_group_max"] = jnp.zeros((), jnp.int32)
    return z


def _add_stats(total, s):
    out = dict(total)
    for k, v in s.items():
        if k in out:
            out[k] = (jnp.maximum(out[k], v) if k == "moe_group_max"
                      else out[k] + v.astype(out[k].dtype))
    return out


def _stack_apply(cfg, stage_params, x, *, positions, mode, caches=None,
                 cross_kv=None, enc_present=False, attn_impl="chunked",
                 pos=None, overlay=None, chunk_start=0, page_table=None,
                 active=None, begin=None):
    """Scan the staged block stack.  Returns (x, new_caches, stats) with
    the totals of ``_zero_stats``.

    ``overlay``: optional {sid: {"idx", "rows", "pidx", "probe"}} — the
    BlockLLM active/probe rows, resolved lazily per layer (see
    ``_resolve_overlay``).
    """
    stats_total = _zero_stats(cfg)
    new_caches = []
    for si, (pattern, groups) in enumerate(cfg.stages()):
        scfg = cfg.stage_config(si)
        sp = stage_params[si]
        scache = caches[si] if caches is not None else None
        sxkv = cross_kv[si] if cross_kv is not None else None
        sov = {f"pos{j}": (overlay or {}).get(f"s{si}/pos{j}")
               for j in range(len(pattern))}

        def body(carry, per_group, scfg=scfg):
            h, stats = carry
            h = shard_ctx.constrain(h, "residual")  # sequence parallelism
            gp, gc, gx, g = per_group
            new_gc = {}
            for j, btype in enumerate(pattern):
                cj = gc[f"pos{j}"] if gc is not None else None
                ex = None
                if enc_present and btype in ATTN_BLOCKS:
                    ex = (gx[f"pos{j}"]["k"], gx[f"pos{j}"]["v"]) \
                        if gx is not None else None
                bp = gp[f"pos{j}"]
                if sov[f"pos{j}"] is not None:
                    bp = _resolve_overlay(bp, g, sov[f"pos{j}"])
                h, cj_new, st = _block_apply(
                    scfg, btype, bp, h, positions=positions,
                    mode=mode, cache=cj, enc_out=ex, pos=pos,
                    attn_impl=attn_impl, chunk_start=chunk_start,
                    page_table=page_table, active=active, begin=begin)
                if cj_new is not None:
                    new_gc[f"pos{j}"] = cj_new
                stats = _add_stats(stats, st)
            return (h, stats), (new_gc if new_gc else None)

        if cfg.remat and mode == "train":
            body = jax.checkpoint(body, prevent_cse=False)
        (x, stats_total), out_caches = lax.scan(
            body, (x, stats_total),
            (sp, scache, sxkv, jnp.arange(groups, dtype=jnp.int32)))
        new_caches.append(out_caches)
    return x, new_caches, stats_total


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def _embed(params, cfg, tokens, *, patch_embeds=None, base_pos=0):
    x = params["embed"].astype(_cdtype(cfg))[tokens]
    if patch_embeds is not None:
        proj = (patch_embeds.astype(x.dtype)
                @ params["vision_proj"].astype(x.dtype))
        P = proj.shape[1]
        x = jnp.concatenate([proj, x[:, P:]], axis=1)  # multimodal packing
    if not cfg.rope_theta:  # absolute (whisper): sinusoidal positions
        S = x.shape[1]
        pe = layers.sinusoidal_positions(S + base_pos, cfg.d_model, x.dtype)
        x = x + pe[base_pos:base_pos + S]
    return x


def _unembed(params, cfg, x):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(x.dtype).T
    else:
        logits = x @ params["head"].astype(x.dtype)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def _cdtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------


def _encode(params, cfg, frames, attn_impl="chunked"):
    enc = params["encoder"]
    x = frames.astype(_cdtype(cfg)) @ enc["frontend"].astype(_cdtype(cfg))
    S = x.shape[1]
    x = x + layers.sinusoidal_positions(S, cfg.d_model, x.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None], x.shape[:2])
    enc_cfg = cfg.replace(num_layers=cfg.num_encoder_layers,
                          pattern=(BLOCK_GLOBAL_ATTN,), num_experts=0,
                          is_encoder_decoder=False, rope_theta=0.0,
                          num_kv_heads=cfg.num_heads)  # encoder is MHA

    for si, (pattern, groups) in enumerate(enc_cfg.stages()):
        sp = enc["stages"][si]

        def body(h, gp):
            hn = layers.rms_norm(gp["pos0"]["ln1"], h, cfg.norm_eps)
            B, S, D = hn.shape
            H, hd = cfg.num_heads, cfg.resolved_head_dim
            a = gp["pos0"]["attn"]
            q = (hn @ a["wq"].astype(hn.dtype)).reshape(B, S, H, hd)
            k = (hn @ a["wk"].astype(hn.dtype)).reshape(B, S, H, hd)
            v = (hn @ a["wv"].astype(hn.dtype)).reshape(B, S, H, hd)
            o = layers.attention_full(q, k, v, positions, positions,
                                      causal=False)
            h = h + o.reshape(B, S, H * hd) @ a["wo"].astype(h.dtype)
            hn = layers.rms_norm(gp["pos0"]["ln2"], h, cfg.norm_eps)
            h = h + layers.mlp_apply(gp["pos0"]["mlp"], hn, cfg.mlp_type)
            return h, None

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, sp)
    return layers.rms_norm(enc["final_norm"], x, cfg.norm_eps)


def _cross_kv(params, cfg, enc_out):
    """Precompute per-decoder-layer cross k/v from encoder output."""
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    B, Se, D = enc_out.shape
    out = []
    for si, (pattern, groups) in enumerate(cfg.stages()):
        sp = params["stages"][si]
        st = {}
        for j in range(len(pattern)):
            xa = sp[f"pos{j}"]["xattn"]  # stacked [G, ...]
            k = jnp.einsum("bsd,gde->gbse", enc_out,
                           xa["wk"].astype(enc_out.dtype))
            v = jnp.einsum("bsd,gde->gbse", enc_out,
                           xa["wv"].astype(enc_out.dtype))
            st[f"pos{j}"] = {"k": k.reshape(groups, B, Se, H, hd),
                             "v": v.reshape(groups, B, Se, H, hd)}
        out.append(st)
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, batch, *, mode="train",
            attn_impl="chunked", return_hidden=False, overlay=None):
    """Full-sequence forward.  Returns (logits|hidden, aux, caches|None)."""
    out, stats, cache = _forward(params, cfg, batch, mode=mode,
                                 attn_impl=attn_impl,
                                 return_hidden=return_hidden, overlay=overlay)
    return out, stats["aux"], cache


def _forward(params, cfg, batch, *, mode, attn_impl, return_hidden,
             overlay):
    """``forward`` with the stack's totals (``_zero_stats``) in place of
    the auxiliary loss."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _embed(params, cfg, tokens, patch_embeds=batch.get("patch_embeds"))
    cross_kv = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, cfg, batch["frames"], attn_impl)
        cross_kv = _cross_kv(params, cfg, enc_out)
    x, caches, stats = _stack_apply(
        cfg, params["stages"], x, positions=positions,
        mode=mode, cross_kv=cross_kv, enc_present=cfg.is_encoder_decoder,
        attn_impl=attn_impl, overlay=overlay)
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    out = x if return_hidden else _unembed(params, cfg, x)
    if mode == "prefill":
        cache = {"stages": caches}
        if cross_kv is not None:
            cache["cross_kv"] = cross_kv
        return out, stats, cache
    return out, stats, None


def _labels_mask(batch):
    tokens = batch["tokens"]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        mask = jnp.concatenate(
            [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, :1])],
            axis=1).astype(jnp.float32)
    else:
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
    return labels, mask


def _xent_from_logits(logits, labels, mask):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return ((logz - gold) * mask).sum()


def _chunked_xent(params, cfg, hidden, labels, mask, chunk):
    """Cross entropy without materializing [B, S, V] logits.

    Scans the sequence in chunks; each chunk's logits are rematerialized in
    the backward pass (jax.checkpoint) => peak logits memory is
    [B, chunk, V] instead of [B, S, V].  Beyond-paper memory optimization
    (DESIGN.md §5) — exact same math as the direct path (tested).
    """
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    n = S // chunk

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def piece(carry, xs):
        xc, lc, mc = xs  # [B, chunk, D], [B, chunk], [B, chunk]
        logits = _unembed(params, cfg, xc)
        return carry + _xent_from_logits(logits, lc, mc), None

    xs = (hidden.reshape(B, n, chunk, D).swapaxes(0, 1),
          labels.reshape(B, n, chunk).swapaxes(0, 1),
          mask.reshape(B, n, chunk).swapaxes(0, 1))
    total, _ = lax.scan(piece, jnp.zeros((), jnp.float32), xs)
    return total


def loss_fn(params, cfg: ModelConfig, batch, *, attn_impl="chunked",
            loss_chunk=None, overlay=None):
    """Next-token cross entropy (+ MoE aux).  Returns (loss, metrics);
    with experts the metrics also count the rows routed to held experts
    (``moe_rows_held``, summed over layers) and the largest group
    (``moe_group_max``).

    ``loss_chunk``: None => auto (chunked when S*V is large); 0 => direct.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    labels, mask = _labels_mask(batch)
    if loss_chunk is None:
        loss_chunk = 512 if S * cfg.vocab_size > (1 << 27) else 0
    out, stats, _ = _forward(params, cfg, batch, mode="train",
                             attn_impl=attn_impl,
                             return_hidden=bool(loss_chunk), overlay=overlay)
    if loss_chunk:
        nll_sum = _chunked_xent(params, cfg, out, labels, mask, loss_chunk)
    else:
        nll_sum = _xent_from_logits(out, labels, mask)
    denom = jnp.maximum(mask.sum(), 1.0)
    nll = nll_sum / denom
    loss = nll + stats["aux"]
    metrics = {"nll": nll, "tokens": mask.sum(), **stats}
    return loss, metrics


def prefill(params, cfg, batch, *, attn_impl="chunked"):
    refuse_mla(cfg, "prefill")
    logits, _, cache = forward(params, cfg, batch, mode="prefill",
                               attn_impl=attn_impl)
    return logits[:, -1], cache


def supports_slot_prefill(cfg: ModelConfig) -> bool:
    """Chunked batched prefill needs every block to be attention (K/V
    rows are position-addressable; recurrent/SSM states would advance on
    right-padding) and a token-only frontend."""
    return (not cfg.is_encoder_decoder and not cfg.vision_embed_dim
            and not cfg.mla
            and all(t in ATTN_BLOCKS for t in cfg.layer_types()))


def prefill_into_slots(params, cfg: ModelConfig, cache, tokens, lengths,
                       *, chunk_start=0, attn_impl="full", page_table=None,
                       begin=None):
    """Chunked batched prefill into a slot-batched decode cache.

    ``tokens`` [B, K]: positions ``[chunk_start, chunk_start + K)`` of
    each slot's prompt, right-padded; ``lengths`` [B] int32: the full
    prompt length per slot (0 for slots not being primed — their cache
    rows pass through bit-exactly).  Scatters the chunk's K/V rows into
    each slot's cache rows (ring layout for local-attention blocks,
    matching decode writes), attends causally over the already-written
    history plus the chunk through the full-sequence attention path, and
    returns ``(logits [B, vocab] at each slot's last valid position of
    this chunk, new_cache)``.  The final chunk's logits predict each
    request's first generated token — a P-token prompt costs
    ``ceil(P / K)`` dispatches for a whole admitted group instead of P
    whole-model decode dispatches per request.
    """
    refuse_mla(cfg, "prefill into slots")
    B, K = tokens.shape
    positions = jnp.broadcast_to(
        chunk_start + jnp.arange(K, dtype=jnp.int32)[None], (B, K))
    x = _embed(params, cfg, tokens, base_pos=chunk_start)
    x, new_stage_caches, _ = _stack_apply(
        cfg, params["stages"], x, positions=positions,
        mode="prefill_slots", caches=cache["stages"],
        pos=jnp.asarray(lengths, jnp.int32), attn_impl=attn_impl,
        chunk_start=chunk_start, page_table=page_table, begin=begin)
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    # unembed ONLY each slot's last valid row of this chunk — [B, 1, D]
    # through the same matmul shape the decode path uses (fp parity),
    # and no [B, K, vocab] logits are ever materialized
    li = jnp.clip(jnp.minimum(jnp.asarray(lengths, jnp.int32),
                              chunk_start + K) - 1 - chunk_start, 0, K - 1)
    xg = jnp.take_along_axis(x, li[:, None, None], axis=1)
    logits = _unembed(params, cfg, xg)
    new_cache = dict(cache)
    new_cache["stages"] = new_stage_caches
    return logits[:, 0], new_cache


def verify_into_slots(params, cfg: ModelConfig, cache, tokens, starts,
                      active, *, page_table=None):
    """Score K candidate positions per slot in ONE dispatch — the
    verifier half of self-speculative serving (SpecServe).

    ``tokens`` [B, K] int32: position ``starts[b] + j`` holds
    ``tokens[b, j]`` — each slot's last emitted token followed by the
    K - 1 base-model draft tokens.  ``starts`` [B] int32 is each slot's
    next cache write index (traced, ragged across slots — unlike
    ``prefill_into_slots`` whose chunk_start is static and shared).
    ``active`` [B] bool masks untouched slots; their cache rows pass
    through bit-exactly.

    Writes the chunk's K/V rows under the CURRENT params (overwriting
    the base model's draft rows with adapter-correct values) and returns
    ``(logits [B, K, vocab], new_cache)`` where ``logits[b, j]`` scores
    the token following ``tokens[b, j]`` — so ``argmax(logits[b, j])``
    is exactly what ``decode_step`` would emit after feeding
    ``tokens[b, :j + 1]`` token by token.  Each position is unembedded
    through the same [B, 1, D] matmul shape the decode path uses (fp
    parity; K is small and static).
    """
    refuse_mla(cfg, "verify")
    B, K = tokens.shape
    starts = jnp.asarray(starts, jnp.int32)
    act = jnp.asarray(active, bool)
    positions = starts[:, None] + jnp.arange(K, dtype=jnp.int32)[None]
    x = params["embed"].astype(_cdtype(cfg))[tokens]
    if not cfg.rope_theta:  # absolute positions: sinusoidal rows
        d = cfg.d_model
        div = jnp.exp(jnp.arange(0, d, dtype=jnp.float32)[0::2]
                      * (-math.log(10000.0) / d))
        ang = positions[..., None].astype(jnp.float32) * div[None, None]
        pe = jnp.zeros((B, K, d), jnp.float32)
        pe = pe.at[..., 0::2].set(jnp.sin(ang)).at[..., 1::2].set(jnp.cos(ang))
        x = x + pe.astype(x.dtype)
    x, new_stage_caches, _ = _stack_apply(
        cfg, params["stages"], x, positions=positions, mode="verify",
        caches=cache["stages"], pos=starts, attn_impl="full",
        page_table=page_table, active=act)
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = jnp.stack(
        [_unembed(params, cfg, x[:, j:j + 1])[:, 0] for j in range(K)],
        axis=1)
    new_cache = dict(cache)
    new_cache["stages"] = new_stage_caches
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, cache, token, pos,
                *, attn_impl="chunked", page_table=None, active=None):
    """One decode step.  token [B,1] int32; pos = scalar int32 or [B]
    per-slot positions (slot-batched serving).

    Paged caches (``init_paged_cache``) additionally take
    ``page_table`` [B, pages_per_slot] int32 and ``active`` [B] bool —
    inactive slots write nothing (no server-side cache blend needed).

    Returns (logits [B, vocab], new_cache).
    """
    refuse_mla(cfg, "decode")
    pos = jnp.asarray(pos, jnp.int32)
    B = token.shape[0]
    pos_b = jnp.broadcast_to(pos, (B,))
    positions = pos_b[:, None]
    x = params["embed"].astype(_cdtype(cfg))[token]
    if not cfg.rope_theta:  # absolute positions: sinusoidal rows at pos_b
        d = cfg.d_model
        div = jnp.exp(jnp.arange(0, d, dtype=jnp.float32)[0::2]
                      * (-math.log(10000.0) / d))
        ang = pos_b[:, None].astype(jnp.float32) * div[None]  # [B, d/2]
        pe = jnp.zeros((B, d), jnp.float32).at[:, 0::2].set(jnp.sin(ang))
        pe = pe.at[:, 1::2].set(jnp.cos(ang))
        x = x + pe[:, None, :].astype(x.dtype)
    x, new_stage_caches, _ = _stack_apply(
        cfg, params["stages"], x, positions=positions, mode="decode",
        caches=cache["stages"], cross_kv=cache.get("cross_kv"),
        enc_present=cfg.is_encoder_decoder, pos=pos_b, attn_impl=attn_impl,
        page_table=page_table, active=active)
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    new_cache = dict(cache)
    new_cache["stages"] = new_stage_caches
    return logits[:, 0], new_cache


def param_labels(cfg: ModelConfig, params) -> list:
    """Flat list of selectable block-unit labels (BlockLLM granularity).

    One label per (stage, pos, group) = one real layer, plus 'embed',
    'head', 'encoder' and 'final_norm' units.
    """
    labels = ["embed", "final_norm"]
    if "head" in params:
        labels.append("head")
    if "vision_proj" in params:
        labels.append("vision_proj")
    if "encoder" in params:
        labels.append("encoder")
    for si, (pattern, groups) in enumerate(cfg.stages()):
        for j in range(len(pattern)):
            for g in range(groups):
                labels.append(f"s{si}/pos{j}/g{g}")
    return labels
