"""Plain reference of DeepSeek-V2 at one chip's expert-parallel share
(DeepSeek-V2-Lite's ``config.json``; arXiv:2405.04434), in float32 at
the highest matmul precision, with no cache, kernel or batching trick.
It imports nothing of the program under test.

A layer over one sequence h [S, d] (the batch is taken one sequence at
a time, ``lax.map``, so the [H, S, S] scores of one sequence are all
that exists at once):

- multi-head latent attention, no q compression: ``q = h W_q`` split per
  head into ``q_nope`` (``qk_nope_head_dim``) and ``q_pe``
  (``qk_rope_head_dim``); ``[c ‖ k_pe] = h W_kv_a``, ``k_pe`` one head
  shared by all; ``c = RMSNorm(c)``; ``[k_nope ‖ v] = c W_kv_b`` per
  head; ``q_pe`` and ``k_pe`` rotated by YaRN rope; causal softmax of
  ``[q_nope ‖ q_pe] · [k_nope ‖ k_pe] × scale``, times ``v``, then
  ``W_o``;
- YaRN as DeepSeek's ``DeepseekV2YarnRotaryEmbedding``: correction dim
  ``D(r) = dr ln(L0 / (2 pi r)) / (2 ln theta)``, ``low = floor(D(beta
  _fast))``, ``high = ceil(D(beta_slow))``, ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``, ``inv_freq_i = theta^(-2i/dr) ((1 - ramp_i) +
  ramp_i / s)``; cos and sin times ``mscale(s, mscale) / mscale(s,
  mscale_all_dim)``; ``scale = (dn + dr)^-1/2 mscale(s,
  mscale_all_dim)^2``, ``mscale(s, m) = 0.1 m ln s + 1``;
- the first ``first_k_dense_replace`` layers: a SwiGLU MLP of
  ``intermediate_size`` (``dense_block``); the others (``moe_block``):
  router logits ``h W_r`` over ``router_experts`` experts (the published
  count), softmax, the top ``num_experts_per_tok`` probabilities, not
  renormalised, times ``routed_scaling_factor``; the layer sums, over
  the ``n_routed_experts`` experts held here (``expert_offset`` on) that
  are among a token's top k, the gate times the expert's SwiGLU
  (``moe_intermediate_size``), computed densely over every token, so
  nothing is dropped; plus the shared experts, one SwiGLU of width
  ``n_shared_experts x moe_intermediate_size`` with no gate, on every
  token;
- the sequence-wise balance loss: ``f_e = count_e E / (S k)``, ``P_e``
  the sequence's mean probability of expert e, over all E router
  experts; the layer's loss is ``aux_loss_alpha`` times the batch mean
  of ``sum_e f_e P_e``;
- the model's loss: next-token cross entropy over the vocabulary slice
  plus every MoE layer's balance loss.

Departures from DeepSeek's code, each a relabelling under seeded weights:
its rope takes the rope columns interleaved (pairs ``2i, 2i+1``), here
split into halves (a fixed permutation of W_q's and W_kv_a's rope
columns); its RMSNorm gains are ``w``, here ``1 + s``.  What the absent
experts would add is left out, as the program leaves it out.

``rnd`` rounds every matmul operand, as in ``dense_decoder``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs.dense_decoder import (HI, fp8, ident, logits, mm,  # noqa: F401
                                         next_token_loss, rms, swiglu)


def _mscale(s, m):
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn(m: dict):
    """(inv_freq [dr/2], cos/sin factor, softmax scale) by the equations
    above; plain rope and ``(dn + dr)^-1/2`` without ``rope_scaling``."""
    dr, theta = m["qk_rope_head_dim"], m["rope_theta"]
    base = theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    scale = (m["qk_nope_head_dim"] + dr) ** -0.5
    rs = m.get("rope_scaling")
    if not rs:
        return base, 1.0, scale
    s, L0 = rs["factor"], rs["original_max_position_embeddings"]

    def corr(r):
        return dr * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dr - 1)
    i = np.arange(dr // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = base * ((1.0 - ramp) + ramp / s)
    m_all = _mscale(s, rs.get("mscale_all_dim", 0))    # 1 where it is 0
    return inv, _mscale(s, rs.get("mscale", 1)) / m_all, scale * m_all ** 2


def rope(x, inv, mscale):
    """x [S, n, dr], halves rotated by position * inv."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    c = (jnp.cos(ang) * mscale)[:, None]
    s = (jnp.sin(ang) * mscale)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def mla(h, w, m, rnd=ident):
    """Latent attention of one sequence h [S, d] (no residual)."""
    S = h.shape[0]
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    inv, msc, scale = yarn(m)
    q = mm(h, w["wq"], rnd).reshape(S, H, dn + dr)
    ckv = mm(h, w["wkv_a"], rnd)
    c = rms(ckv[:, :r], w["kv_norm"], m["rms_norm_eps"])
    kv = mm(c, w["wkv_b"], rnd).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv, msc)], -1)
    k_pe = rope(ckv[:, None, r:], inv, msc)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))], -1)
    s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k), precision=HI) * scale
    pos = jnp.arange(S)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", rnd(p), rnd(kv[..., dn:]), precision=HI)
    return mm(o.reshape(S, H * dv), w["wo"], rnd)


def experts(h, w, m, rnd=ident):
    """Held and shared experts of one sequence h [S, d]: (y [S, d], the
    sequence's ``sum_e f_e P_e``)."""
    S = h.shape[0]
    E, k = m["router_experts"], m["num_experts_per_tok"]
    held, off = m["n_routed_experts"], m.get("expert_offset", 0)
    probs = jax.nn.softmax(mm(h, w["router"], rnd), axis=-1)        # [S, E]
    topw, topi = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        topw = topw / topw.sum(-1, keepdims=True)
    topw = topw * m["routed_scaling_factor"]
    gate = ((topi[..., None] == off + jnp.arange(held)) * topw[..., None]).sum(1)
    hg = jnp.einsum("td,edf->etf", rnd(h), rnd(w["e_gate"]), precision=HI)
    hu = jnp.einsum("td,edf->etf", rnd(h), rnd(w["e_up"]), precision=HI)
    ye = jnp.einsum("etf,efd->etd", rnd(jax.nn.silu(hg) * hu),
                    rnd(w["e_down"]), precision=HI)                  # [held, S, d]
    y = jnp.einsum("te,etd->td", rnd(gate), rnd(ye), precision=HI)
    y = y + swiglu(h, w["s_gate"], w["s_up"], w["s_down"], rnd)
    f = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) * E / (S * k)
    return y, jnp.sum(f * probs.mean(0))


def _per_sequence(layer, x, w, m, rnd):
    """``layer(x_b) -> (y_b, a_b)`` over the batch one sequence at a
    time; (y [B, S, d], the batch mean of a_b)."""
    y, a = jax.lax.map(jax.checkpoint(lambda xb: layer(xb, w, m, rnd)), x)
    return y, jnp.mean(a)


def _dense(x, w, m, rnd):
    x = x + mla(rms(x, w["ln1"], m["rms_norm_eps"]), w, m, rnd)
    h = rms(x, w["ln2"], m["rms_norm_eps"])
    y = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], rnd)
    return y, jnp.zeros((), jnp.float32)


def _moe(x, w, m, rnd):
    x = x + mla(rms(x, w["ln1"], m["rms_norm_eps"]), w, m, rnd)
    y, a = experts(rms(x, w["ln2"], m["rms_norm_eps"]), w, m, rnd)
    return x + y, a


def dense_block(x, w, m, rnd=ident):
    """A leading dense layer over x [B, S, d]: (output, 0)."""
    return _per_sequence(_dense, x, w, m, rnd)[0], 0.0


def moe_block(x, w, m, rnd=ident):
    """An MoE layer over x [B, S, d]: (output, its balance loss)."""
    y, a = _per_sequence(_moe, x, w, m, rnd)
    return y, m["assumed"]["aux_loss_alpha"] * a
