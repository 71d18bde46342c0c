"""Plain reference of a dense decoder (Llama-style: RMSNorm with a
(1 + s) gain, rotary positions on split halves, grouped-query causal
attention, SwiGLU MLP, untied output head), in float32 at the highest
matmul precision, with no cache, kernel or batching trick.

It serves every configuration file here whose ``"reference"`` is
``dense_decoder``: InternLM2 and DeepSeek-LLM both follow this
description (InternLM2 fuses q/k/v into one matrix and scales its norms
by ``w`` where this writes ``1 + s``; both are changes of layout, not of
the function).  It imports nothing of the program under test.

``rnd`` rounds every matmul operand: the identity for the reference,
a round trip through a lower precision for the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def ident(x):
    return x


def fp8(x):
    """Round to float8 e4m3 and back: the control one precision below the
    configurations' bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def dims(m: dict):
    d, H, KV = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    return d, H, KV, m.get("head_dim") or d // H


def mm(a, b, rnd=ident):
    return jnp.matmul(rnd(a), rnd(b), precision=HI,
                      preferred_element_type=jnp.float32)


def rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + s)


def rope(x, pos, theta):
    """x [B, S, n, hd]; pos [S]."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(x, w, m, rnd=ident):
    """The attention half of a layer over full sequences x [B, S, d]
    (its residual included); w holds the layer's leaves by name."""
    B, S, _ = x.shape
    d, H, KV, hd = dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(S)
    h = rms(x, w["ln1"], eps)
    q = rope(mm(h, w["wq"], rnd).reshape(B, S, H, hd), pos, theta)
    k = rope(mm(h, w["wk"], rnd).reshape(B, S, KV, hd), pos, theta)
    v = mm(h, w["wv"], rnd).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)       # head h reads kv head h // (H/KV)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k), precision=HI) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", rnd(p), rnd(v), precision=HI)
    return x + mm(o.reshape(B, S, H * hd), w["wo"], rnd)


def swiglu(h, w_gate, w_up, w_down, rnd=ident):
    return mm(jax.nn.silu(mm(h, w_gate, rnd)) * mm(h, w_up, rnd), w_down, rnd)


def block(x, w, m, rnd=ident):
    """One decoder layer over full sequences x [B, S, d]; w holds the
    layer's leaves by name.  Returns the layer's output and its auxiliary
    loss (none here)."""
    x = attention(x, w, m, rnd)
    h = rms(x, w["ln2"], m["rms_norm_eps"])
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], rnd), 0.0


def logits(x, final_norm, head, m, rnd=ident):
    return mm(rms(x, final_norm, m["rms_norm_eps"]), head, rnd)


def next_token_loss(x, final_norm, head, tokens, m, rnd=ident, chunk=512):
    """Mean next-token cross entropy over positions 0..S-2 of final hidden
    states x [B, S, d], in sequence chunks so [B, S, V] never exists."""
    B, S, d = x.shape
    labels = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
    valid = (jnp.arange(S) < S - 1).astype(jnp.float32)
    chunk = min(chunk, S)
    n = S // chunk

    @jax.checkpoint
    def piece(args):
        xc, lc, vc = args
        lg = logits(xc, final_norm, head, m, rnd)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return (nll * vc).sum()

    xs = (x.reshape(B, n, chunk, d).swapaxes(0, 1),
          labels.reshape(B, n, chunk).swapaxes(0, 1),
          jnp.broadcast_to(valid.reshape(n, 1, chunk), (n, B, chunk)))
    return jax.lax.map(piece, xs).sum() / (B * (S - 1))
