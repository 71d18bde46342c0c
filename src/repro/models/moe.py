"""Mixture-of-Experts feed-forward.

Two dispatches share the router (softmax, top-k experts per token):

- ``moe_apply``, what a single-device step runs: the held-experts layer,
  dropless.  The device holds experts ``[expert_offset, expert_offset +
  num_experts)`` of the router's ``total_router_experts`` (expert
  parallelism; all of them by default), routes every token over all of
  them, and computes its own experts' part of the result: the (token,
  slot) pairs routed to a held expert are sorted by expert and run as
  grouped matmuls (Pallas ``gmm`` on a TPU, ``jax.lax.ragged_dot``
  elsewhere) over a row buffer of ``T * min(k, held)`` rows (rounded up
  to whole ``gmm`` row tiles), which holds every such pair, so no token
  is ever dropped.  The shared experts and the balance loss are computed
  alike on every device.
- ``moe_apply_capacity``, the GShard capacity dispatch the sharded path
  (``runtime/moe_parallel.py``) calls: capacity ``C = ceil(k * T *
  capacity_factor / E)`` per token chunk; tokens over capacity are
  dropped (the pure-jnp *dense* reference used in tests computes every
  expert and proves equality when no token is dropped).

Memory structure of the capacity path (measured on the 512-device
dry-run):
- tokens are processed in chunks of ``token_chunk`` under a rematerialized
  scan — the dispatch/combine intermediates live for one chunk at a time;
- the combine loops over the k routing slots so no [T*k, D] tensor is ever
  materialized.

Tensor parallelism: expert counts here (60, 40) do not divide the 16-way
model axis, so experts are *replicated* across `model` and the per-expert
hidden dim is sharded (column->row parallel pair with one psum at the end,
shared expert folded into the same psum) — driven by the fully-manual
shard_map in ``runtime/moe_parallel.py``; see DESIGN.md §5.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers


def moe_init(key, cfg):
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    down_scale = 1.0 / math.sqrt(f) / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": layers.dense_init(ks[0], d, cfg.total_router_experts,
                                    scale=0.02),
        "w_gate": jax.random.normal(ks[1], (E, d, f)) * (1.0 / math.sqrt(d)),
        "w_up": jax.random.normal(ks[2], (E, d, f)) * (1.0 / math.sqrt(d)),
        "w_down": jax.random.normal(ks[3], (E, f, d)) * down_scale,
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = layers.mlp_init(ks[4], cfg, d_ff=cfg.shared_expert_d_ff)
    return p


def _route(xt, router, cfg):
    """Router in float32 (DeepSeek's ``MoEGate``): (probs [T, E], top-k
    gates [T, k], top-k expert ids [T, k])."""
    logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return probs, topw * cfg.routed_scaling_factor, topi


def _counts(ids, n):
    """[..., m] ids -> [..., n] float32 counts of each id."""
    return jax.nn.one_hot(ids, n, dtype=jnp.float32).sum(-2)


def _balance_loss(probs, topi, cfg, B, S):
    """``seq_aux``: per sequence, ``f_e = count_e E / (S k)`` and ``P_e``
    the sequence's mean probability; ``coef * mean_b sum_e f_e P_e``.
    Otherwise ``coef * E * sum_e P_e f_e`` with ``f_e`` the share of the
    ``T k`` choices, over chunks of at most 8192 tokens, averaged."""
    E, k = probs.shape[-1], cfg.num_experts_per_tok
    if cfg.seq_aux:
        f = _counts(topi.reshape(B, S * k), E) * (E / (S * k))
        P = probs.reshape(B, S, E).mean(1)
        return cfg.router_aux_coef * jnp.mean(jnp.sum(f * P, -1))
    T = B * S
    chunk = min(8192, T)
    while T % chunk:
        chunk -= 1
    n = T // chunk
    f = _counts(topi.reshape(n, chunk * k), E) / (chunk * k)
    P = probs.reshape(n, chunk, E).mean(1)
    return cfg.router_aux_coef * E * jnp.mean(jnp.sum(f * P, -1))


GMM_ROWS = 512     # the grouped matmul's row tile; the buffer is whole tiles


@jax.custom_vjp
def _take_rows(x, idx, back):
    """``x[idx]``, an index ``len(x)`` giving a zero row.  ``back``
    [len(x), c] lists, for each row of x, the rows of the result taken
    from it (``len(idx)`` where fewer): the transpose sums those, a
    gather too, where autodiff's would scatter-add."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _take_rows_fwd(x, idx, back):
    return _take_rows(x, idx, back), back


def _take_rows_bwd(back, dy):
    dx = sum(jnp.take(dy, back[:, j], axis=0, mode="fill", fill_value=0
                      ).astype(jnp.float32) for j in range(back.shape[1]))
    return dx.astype(dy.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _grouped_matmul(x, w, group_sizes, impl):
    """Rows of x sorted by group times their group's w [G, K, N].
    ``impl``: "gmm" the Pallas grouped matmul (megablox), whose grid
    skips the tiles past the groups; "gmm_interpret" the same kernel
    interpreted (CPU tests); "ragged" ``lax.ragged_dot``."""
    if impl == "ragged":
        return lax.ragged_dot(x, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    return megablox.gmm(x, w, group_sizes, x.dtype, (GMM_ROWS, 1024, 1024),
                        interpret=impl == "gmm_interpret")


def _grouped_swiglu(xs, group_sizes, params, dt, impl):
    """SwiGLU of each held expert over its rows of ``xs`` (sorted by
    expert; ``group_sizes`` [held] rows each; rows past them are not
    read)."""
    with jax.named_scope("moe_gmm"):
        h = _grouped_matmul(xs, params["w_gate"].astype(dt), group_sizes, impl)
        u = _grouped_matmul(xs, params["w_up"].astype(dt), group_sizes, impl)
        return _grouped_matmul(jax.nn.silu(h) * u,
                               params["w_down"].astype(dt), group_sizes, impl)


def _held_part(xt, topw, topi, params, cfg, impl):
    """The held experts' part for tokens xt [t, D]: (y [t, D], group
    sizes [held]).  The (token, slot) pairs are sorted by expert; buffer
    row r holds pair ``pair[r]``, and pair p sits at row ``slot[p]``;
    both moves are gathers, forward and backward."""
    t, D = xt.shape
    E, k, off = cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_offset
    local = topi.reshape(-1) - off                                 # [t*k]
    held = (local >= 0) & (local < E)
    eid = jnp.where(held, local, E)                                # E: not held
    group_sizes = _counts(eid[None], E + 1)[0, :E].astype(jnp.int32)
    rows = t * min(k, E)          # a token takes a held expert <= min(k, E) times
    rows += -rows % GMM_ROWS
    order = jnp.argsort(eid, stable=True)
    slot = jnp.where(held, jnp.argsort(order), rows)               # rows: none
    order = jnp.pad(order, (0, max(0, rows - t * k)))[:rows]
    pair = jnp.where(jnp.arange(rows) < group_sizes.sum(), order,
                     t * k)                                        # t*k: none
    xs = _take_rows(xt, pair // k, slot.reshape(t, k))
    ys = _grouped_swiglu(xs, group_sizes, params, xt.dtype, impl)
    yk = _take_rows(ys, slot, pair[:, None]).reshape(t, k, D)
    gate = jnp.where(held, topw.reshape(-1), 0.0).reshape(t, k)
    y = jnp.einsum("tk,tkd->td", gate.astype(xt.dtype), yk,
                   preferred_element_type=jnp.float32).astype(xt.dtype)
    return y, group_sizes


def moe_apply(params, x, cfg, gmm_impl=None):
    """Held experts, dropless.  x [B, S, D] -> (y [B, S, D], stats) with
    stats ``aux`` (the balance loss), ``moe_rows_held`` (pairs routed to
    held experts) and ``moe_group_max`` (the largest group).

    y sums, over the held experts e among a token's top k, gate_e times
    expert e's SwiGLU, plus the shared experts for every token.
    ``gmm_impl`` (``_grouped_matmul``): None takes "gmm" where Pallas
    runs on a TPU and "ragged" elsewhere.
    """
    if gmm_impl is None:
        from repro.kernels.ops import pallas_available
        gmm_impl = "gmm" if pallas_available() else "ragged"
    B, S, D = x.shape
    with jax.named_scope("moe"):
        xt = x.reshape(B * S, D)
        probs, topw, topi = _route(xt, params["router"], cfg)
        aux = _balance_loss(probs, topi, cfg, B, S)
        y, group_sizes = _held_part(xt, topw, topi, params, cfg, gmm_impl)
        if "shared" in params:
            y = y + layers.mlp_apply(params["shared"], xt, "swiglu")
        stats = {"aux": aux, "moe_rows_held": group_sizes.sum(),
                 "moe_group_max": group_sizes.max()}
    return y.reshape(B, S, D), stats


def _capacity(T, cfg):
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    c = int(math.ceil(k * T * cfg.capacity_factor / E))
    return max(8, c)


def _moe_chunk(params, xt, cfg, capacity, tp_axis):
    """One token chunk: xt [T, D] -> (y [T, D] partial, aux scalar)."""
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = capacity

    logits = (xt @ params["router"].astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # [T, E]
    topw, topi = lax.top_k(probs, k)                              # [T, k]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (T * k)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # rank of each (token, slot) within its expert
    flat_e = topi.reshape(-1)                                     # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    rank = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = rank < C

    token_id = jnp.arange(T * k) // k
    disp = jnp.full((E, C), T, jnp.int32)
    disp = disp.at[flat_e, rank].set(jnp.where(keep, token_id, T),
                                     mode="drop")

    xpad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    xe = xpad[disp]                                               # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                    params["w_down"].astype(xe.dtype))            # [E, C, D]

    # combine: loop over the k slots — no [T*k, D] intermediate
    y = jnp.zeros((T, D), xt.dtype)
    rank_k = rank.reshape(T, k)
    keep_k = keep.reshape(T, k)
    for j in range(k):
        ej = topi[:, j]                                           # [T]
        rj = jnp.minimum(rank_k[:, j], C - 1)
        wj = jnp.where(keep_k[:, j], topw[:, j], 0.0).astype(xt.dtype)
        y = y + ye[ej, rj] * wj[:, None]

    if "shared" in params:
        y = y + layers.mlp_apply(params["shared"], xt, "swiglu")
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y, aux


def moe_apply_capacity(params, x, cfg, *, capacity=None, tp_axis=None,
                       token_chunk=8192):
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar), GShard capacity
    dispatch over every expert, top-k renormalised.

    Tokens are flattened and processed in rematerialized chunks; capacity
    is per chunk.  ``tp_axis``: see module docstring.
    """
    if (cfg.total_router_experts != cfg.num_experts or cfg.seq_aux
            or not cfg.norm_topk_prob or cfg.routed_scaling_factor != 1.0):
        raise ValueError(
            f"{cfg.name}: the capacity dispatch holds every expert and "
            "renormalises the top-k gates with a batch balance loss; held "
            "experts, unnormalised or scaled gates and seq_aux run on "
            "moe_apply only")
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    chunk = min(token_chunk, T)
    while T % chunk:
        chunk -= 1
    C = capacity if capacity else _capacity(chunk, cfg)
    if chunk == T:
        y, aux = _moe_chunk(params, xt, cfg, C, tp_axis)
        return y.reshape(B, S, D), aux

    n = T // chunk

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def piece(carry, xc):
        y, aux = _moe_chunk(params, xc, cfg, C, tp_axis)
        return carry + aux, y

    aux, ys = lax.scan(piece, jnp.zeros((), jnp.float32),
                       xt.reshape(n, chunk, D))
    return ys.reshape(B, S, D), aux / n


def moe_apply_dense_ref(params, x, cfg):
    """Exact dense reference: every expert on every token (tests only)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    logits = (xt @ params["router"].astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.num_experts_per_tok
    topw, topi = lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], topi].set(topw)  # [T,E]
    h = jnp.einsum("td,edf->etf", xt, params["w_gate"].astype(xt.dtype))
    u = jnp.einsum("td,edf->etf", xt, params["w_up"].astype(xt.dtype))
    ye = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u,
                    params["w_down"].astype(xt.dtype))
    y = jnp.einsum("te,etd->td", gate.astype(xt.dtype), ye)
    if "shared" in params:
        y = y + layers.mlp_apply(params["shared"], xt, "swiglu")
    return y.reshape(B, S, D), jnp.zeros((), jnp.float32)
