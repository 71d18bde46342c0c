"""Plain reference of a mixture-of-experts decoder: the dense decoder's
attention and norms (``dense_decoder.py``) with the MLP replaced by
routed experts, as the program documents them:

- the router is a softmax over ``n_routed_experts`` of ``h @ router``;
- each token keeps its ``num_experts_per_tok`` largest gates,
  renormalised to sum to 1;
- each expert is a SwiGLU MLP of width ``moe_intermediate_size``, and
  the layer's output is the sum of every expert's output on every token
  weighted by its gate (0 for an expert not chosen): computed densely,
  so no token is ever dropped;
- ``n_shared_experts`` shared experts, one SwiGLU MLP of their summed
  width with no gate, are added for every token;
- the load-balancing loss is ``aux_loss_alpha * E * sum_e P_e f_e``, with
  ``P_e`` the mean gate of expert ``e`` before the top-k and ``f_e`` the
  share of the ``T k`` choices that went to it, over the ``T`` tokens of
  the batch (the program takes it over chunks of at most 8192 tokens,
  the whole batch up to that size, and averages the chunks).

Float32 at the highest matmul precision; ``rnd`` rounds every matmul
operand, as in ``dense_decoder``.  It imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.configs.dense_decoder import (HI, attention, fp8, ident,  # noqa: F401
                                         logits, mm, next_token_loss, rms,
                                         swiglu)


def experts(h, w, m, rnd=ident):
    """Routed and shared experts over h [B, S, d]: (y [B, S, d], aux)."""
    B, S, d = h.shape
    T = B * S
    x = h.reshape(T, d)
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(x, w["router"], rnd), axis=-1)        # [T, E]
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / topw.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], topi].set(topw)
    hg = jnp.einsum("td,edf->etf", rnd(x), rnd(w["e_gate"]), precision=HI)
    hu = jnp.einsum("td,edf->etf", rnd(x), rnd(w["e_up"]), precision=HI)
    ye = jnp.einsum("etf,efd->etd", rnd(jax.nn.silu(hg) * hu),
                    rnd(w["e_down"]), precision=HI)                  # [E, T, d]
    y = jnp.einsum("te,etd->td", rnd(gate), rnd(ye), precision=HI)
    if "s_gate" in w:
        y = y + swiglu(x, w["s_gate"], w["s_up"], w["s_down"], rnd)
    share = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (T * k)
    aux = m["aux_loss_alpha"] * E * jnp.sum(probs.mean(0) * share)
    return y.reshape(B, S, d), aux


def block(x, w, m, rnd=ident):
    """One layer over full sequences x [B, S, d]; w holds the layer's
    leaves by name.  Returns the layer's output and its auxiliary loss."""
    x = attention(x, w, m, rnd)
    y, aux = experts(rms(x, w["ln2"], m["rms_norm_eps"]), w, m, rnd)
    return x + y, aux
