"""Core neural-net layers (pure-functional, no flax).

Every layer is an (init, apply) pair over plain dict pytrees.  Attention
supports three execution modes:

- ``full``     : standard masked attention (O(S^2) memory) — small seqs.
- ``chunked``  : blockwise online-softmax attention (lax.scan over KV
                 blocks) — the XLA fallback of the Pallas flash kernel,
                 O(S * chunk) memory; used for 32k prefill / long training
                 and for CPU dry-run lowering.
- ``pallas``   : the Pallas flash-attention kernel (TPU target).

Decode (single query token against a KV cache) is a separate path.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in, d_out, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


def norm_init(dim, dtype=jnp.float32):
    return {"scale": jnp.zeros((dim,), dtype)}  # (1+scale) parameterization


def rms_norm(params, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim, theta):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [hd/2]


def yarn_mscale(factor, mscale=1.0):
    """DeepSeek-V2's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(dim, theta, yarn):
    """(low, high): the rotary pairs below ``low`` keep their frequency,
    those above ``high`` are interpolated by the whole factor; DeepSeek's
    ``yarn_find_correction_range`` with ``beta_fast`` and ``beta_slow``
    rotations over ``original_max_position``."""
    def dim_of(rotations):
        return dim * math.log(yarn.original_max_position
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(dim_of(yarn.beta_fast)), 0),
            min(math.ceil(dim_of(yarn.beta_slow)), dim - 1))


def yarn_inv_freq(dim, theta, yarn):
    """[dim/2] YaRN frequencies: ``theta^(-2i/dim) * ((1 - r_i) + r_i /
    factor)`` with ``r_i`` the linear ramp from ``low`` to ``high``."""
    low, high = yarn_range(dim, theta, yarn)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return rope_freqs(dim, theta) * ((1.0 - ramp) + ramp / yarn.factor)


def yarn_softmax_scale(qk_head_dim, yarn):
    """``qk_head_dim^-1/2`` times ``mscale(factor, mscale_all_dim)^2``."""
    m = yarn_mscale(yarn.factor, yarn.mscale_all_dim) if yarn.mscale_all_dim \
        else 1.0
    return qk_head_dim ** -0.5 * m * m


def apply_rope(x, positions, theta, inv_freq=None, mscale=1.0):
    """x: [..., S, n, hd]; positions: [..., S] int32.  ``inv_freq``
    [hd/2] replaces theta's frequencies (YaRN); ``mscale`` scales cos and
    sin."""
    if not theta:
        return x
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    sin = sin[..., None, :]  # broadcast over heads
    cos = cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(S, d, dtype=jnp.float32):
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((S, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg, *, cross=False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    if cross:
        KV = H  # whisper cross-attn is MHA
    ks = jax.random.split(key, 4)
    out_scale = 1.0 / math.sqrt(H * hd) / math.sqrt(2 * cfg.num_layers)
    return {
        "wq": dense_init(ks[0], d, H * hd),
        "wk": dense_init(ks[1], d, KV * hd),
        "wv": dense_init(ks[2], d, KV * hd),
        "wo": dense_init(ks[3], H * hd, d, scale=out_scale),
    }


def mla_init(key, cfg):
    """Multi-head latent attention, no q compression: ``wq`` [d, H (dn +
    dr)], ``wkv_a`` [d, r + dr] (the latent and the one shared rope key),
    the latent's norm, ``wkv_b`` [r, H (dn + dv)], ``wo`` [H dv, d]."""
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    out_scale = 1.0 / math.sqrt(H * dv) / math.sqrt(2 * cfg.num_layers)
    return {
        "wq": dense_init(ks[0], d, H * (dn + dr)),
        "wkv_a": dense_init(ks[1], d, r + dr),
        "kv_norm": norm_init(r),
        "wkv_b": dense_init(ks[2], r, H * (dn + dv)),
        "wo": dense_init(ks[3], H * dv, d, scale=out_scale),
    }


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    B, S, KV, hd = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def _mask_bias(q_pos, k_pos, *, causal, window):
    """[.., Sq, Sk] additive bias from position ids (int32)."""
    ok = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        ok &= diff >= 0
    if window:
        ok &= diff < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def attention_full(q, k, v, q_pos, k_pos, *, causal=True, window=0, softcap=0.0,
                   scale=None):
    """q [B,Sq,H,hd] k [B,Sk,KV,hd] v [B,Sk,KV,hd_v] -> [B,Sq,H,hd_v];
    the scores scaled by ``scale`` (default 1/sqrt(hd))."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    k = _repeat_kv(k, H // KV)
    v = _repeat_kv(v, H // KV)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits *= 1.0 / math.sqrt(hd) if scale is None else scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = logits + _mask_bias(q_pos, k_pos, causal=causal, window=window)[:, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_chunked(q, k, v, q_pos, k_pos, *, causal=True, window=0,
                      q_chunk=1024, kv_chunk=1024, softcap=0.0):
    """Blockwise online-softmax attention; O(Sq/qc * qc * kc) live memory.

    Numerically identical (up to fp assoc.) to ``attention_full``; the
    long-context path where the flash kernel is not taken.

    Structure (§Perf iteration 1, see EXPERIMENTS.md): the q loop is a
    *static python loop* so that for q-chunk ``i`` the inner kv scan has
    static length covering only blocks ``<= i`` (causal skipping, ~2x
    FLOPs) and blocks inside the sliding window; k/v are consumed as whole
    arrays so GSPMD reshards them ONCE per layer rather than per
    (q-block x kv-step) — the baseline re-gathered k/v 384x per layer
    (measured); matmuls accumulate in f32 via preferred_element_type
    (keeps the collectives/HBM traffic in bf16).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    n_rep = H // KV
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = 1.0 / math.sqrt(hd)
    f32 = jnp.float32

    qr = q.reshape(B, nq, q_chunk, H, hd)
    qpr = q_pos.reshape(B, nq, q_chunk)
    kr = k.reshape(B, nk, kv_chunk, KV, hd).swapaxes(0, 1)  # [nk,B,kc,KV,hd]
    vr = v.reshape(B, nk, kv_chunk, KV, hd).swapaxes(0, 1)
    kpr = k_pos.reshape(B, nk, kv_chunk).swapaxes(0, 1)

    @functools.partial(jax.checkpoint, prevent_cse=False,
                       static_argnums=(3,))
    def q_block(qi, qp, kv_slice, n_steps):
        # qi [B,qc,H,hd]; kv_slice: (k,v,kpos) stacked [n_steps, ...]
        # checkpointed: backward recomputes block probabilities (flash
        # semantics) instead of saving [B,H,qc,kc] per block pair.
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, vi, kp = inp  # [B, kc, KV, hd], [B, kc]
            kif = _repeat_kv(ki, n_rep)
            vif = _repeat_kv(vi, n_rep)
            # NB: cast AFTER the einsums (not preferred_element_type=f32):
            # a f32 dot output makes the attention COTANGENTS f32, which
            # doubles every backward collective/HBM byte (measured — §Perf
            # I4).  TPU accumulates bf16 dots in f32 internally anyway.
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kif).astype(f32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = s + _mask_bias(qp, kp, causal=causal, window=window)[:, None]
            m_new = jnp.maximum(m, s.max(-1))
            m_safe = jnp.maximum(m_new, -1e30)  # fully-masked row guard
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(jnp.maximum(m, -1e30) - m_safe)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qi.dtype), vif).astype(f32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), -jnp.inf, f32)
        l0 = jnp.zeros((B, H, q_chunk), f32)
        a0 = jnp.zeros((B, H, q_chunk, hd), f32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), kv_slice,
                                  length=n_steps)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.swapaxes(1, 2).astype(q.dtype)  # [B, qc, H, hd]

    outs = []
    for i in range(nq):
        lo = 0
        hi = nk
        if causal:
            hi = min(nk, ((i + 1) * q_chunk + kv_chunk - 1) // kv_chunk)
        if window:
            lo = max(0, (i * q_chunk - window) // kv_chunk)
        sl = (kr[lo:hi], vr[lo:hi], kpr[lo:hi])
        outs.append(q_block(qr[:, i], qpr[:, i], sl, hi - lo))
    return jnp.concatenate(outs, axis=1)  # [B, Sq, H, hd]


def attention_decode(q, k_cache, v_cache, cur_pos, *, window=0, softcap=0.0,
                     ring=False):
    """One-token attention. q [B,1,H,hd]; caches [B,S,KV,hd].

    ``cur_pos`` is the index of the NEW token (already written into the
    cache) — a scalar or a per-batch [B] vector (slot-batched serving).
    With ``ring=True`` the cache is a ring buffer of size ``window`` and
    every slot whose age < window is valid.

    GQA runs as a grouped einsum (query heads reshaped ``H -> (KV,
    group)``) so the repeated k/v heads are never materialized — the
    cache leaves stream through at their stored [B, S, KV, hd] size.
    The Pallas kernel (``kernels/decode_attention.py``) additionally
    makes the HBM reads scale with ``cur_pos``.
    """
    B, S, KV, hd = k_cache.shape
    H = q.shape[2]
    cur_pos = jnp.asarray(cur_pos)
    pos_b = jnp.broadcast_to(cur_pos.reshape(-1, *([1] * 0))
                             if cur_pos.ndim else cur_pos, (B,))
    k = k_cache.astype(q.dtype)
    v = v_cache.astype(q.dtype)
    qg = q.reshape(B, q.shape[1], KV, H // KV, hd)   # head h = kv*g + g'
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    s *= 1.0 / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    idx = jnp.arange(S)[None, :]          # [1, S]
    pb = pos_b[:, None]                    # [B, 1]
    if ring:
        # slot i holds the token with absolute position p, p % S == i.
        age = (pb - idx) % S
        valid = age < (window if window else S)
        valid &= pb >= age  # slot not yet written on early steps
    else:
        valid = idx <= pb
        if window:
            valid &= idx > pb - window
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(B, q.shape[1], H, hd)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, d_ff=None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    down_scale = 1.0 / math.sqrt(f) / math.sqrt(2 * cfg.num_layers)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], d, f),
            "w_up": dense_init(ks[1], d, f),
            "w_down": dense_init(ks[2], f, d, scale=down_scale),
        }
    return {  # plain 2-matrix MLP (whisper)
        "w_up": dense_init(ks[1], d, f),
        "w_down": dense_init(ks[2], f, d, scale=down_scale),
    }


def mlp_apply(params, x, mlp_type):
    wg = params.get("w_gate")
    wu = params["w_up"].astype(x.dtype)
    wd = params["w_down"].astype(x.dtype)
    if mlp_type == "swiglu":
        h = jax.nn.silu(x @ wg.astype(x.dtype)) * (x @ wu)
    elif mlp_type == "geglu":
        h = jax.nn.gelu(x @ wg.astype(x.dtype), approximate=True) * (x @ wu)
    else:
        h = jax.nn.gelu(x @ wu, approximate=True)
    return h @ wd


# ---------------------------------------------------------------------------
# conv1d (depthwise, causal) — recurrentgemma temporal conv
# ---------------------------------------------------------------------------


def conv1d_init(key, width, channels):
    return {"w": jax.random.normal(key, (width, channels), jnp.float32) * 0.1,
            "b": jnp.zeros((channels,), jnp.float32)}


def causal_conv1d(params, x, *, state=None):
    """Depthwise causal conv.  x [B,S,C]; state [B,W-1,C] (decode).

    Returns (y, new_state).
    """
    w = params["w"]  # [W, C]
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros(x.shape[:1] + (W - 1,) + x.shape[2:], x.dtype)
        xp = jnp.concatenate([pad, x], axis=1)
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    windows = jnp.stack([xp[:, i:i + x.shape[1]] for i in range(W)], axis=0)
    y = jnp.einsum("wbsc,wc->bsc", windows, w.astype(x.dtype))
    y = y + params["b"].astype(x.dtype)
    new_state = xp[:, -(W - 1):]
    return y, new_state
