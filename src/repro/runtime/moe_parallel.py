"""Fully-manual shard_map island for MoE dispatch + expert tensor-parallel.

Token dispatch/combine (data-dependent gather/scatter) does not partition
well under plain GSPMD — the combine scatter forces an all-gather of every
token (measured: 254 GiB/device temp on qwen2-moe train_4k).  Instead the
MoE FF runs inside a shard_map that is manual over ALL mesh axes:

- data axes: per-shard capacity dispatch (GShard semantics) — each data
  shard routes its local tokens; no cross-shard token traffic.
- model axis: the per-expert hidden dim is column/row parallel; each shard
  computes partial expert outputs and a single psum("model") combines
  routed + shared contributions (Megatron pair).

If the expert hidden dims don't divide the model axis, weights fall back
to replication and every model shard computes the full MoE redundantly
(correct, no psum) — the divisibility fallback of DESIGN.md §5.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import moe as moe_lib
from repro.runtime import shard_ctx

TP = "model"


def _moe_param_specs(params, cfg, mesh, tp_ok: bool):
    """PartitionSpec tree for the MoE params inside the manual region."""
    if not tp_ok:
        return jax.tree.map(lambda _: P(), params)
    specs = {
        "router": P(),
        "w_gate": P(None, None, TP),
        "w_up": P(None, None, TP),
        "w_down": P(None, TP, None),
    }
    if "shared" in params:
        specs["shared"] = {"w_gate": P(None, TP), "w_up": P(None, TP),
                           "w_down": P(TP, None)}
    return specs


def moe_apply_maybe_sharded(params, x, cfg):
    ctx = shard_ctx.get()
    if ctx is None or not ctx.moe_shard_map:
        return moe_lib.moe_apply(params, x, cfg)
    mesh, dp = ctx.mesh, tuple(ctx.dp_axes)
    ndp = ctx.axis_size(dp)
    tp_size = int(mesh.shape[ctx.tp_axis]) if ctx.tp_axis in mesh.shape else 1
    if (ndp <= 1 and tp_size <= 1) or x.shape[0] % max(ndp, 1) != 0:
        return moe_lib.moe_apply(params, x, cfg)

    tp_ok = (tp_size > 1 and cfg.moe_d_ff % tp_size == 0
             and (not cfg.shared_expert_d_ff
                  or cfg.shared_expert_d_ff % tp_size == 0))

    def local(px, xl):
        y, aux = moe_lib.moe_apply(
            px, xl, cfg, tp_axis=(ctx.tp_axis if tp_ok else None))
        if ndp > 1:
            aux = jax.lax.pmean(aux, dp)
        return y, aux

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_moe_param_specs(params, cfg, mesh, tp_ok),
                  P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False)
    return fn(params, x)
