"""Manual shard_map island for SSM (mLSTM/sLSTM) blocks.

Under plain GSPMD, the recurrent weight-gradient accumulation inside the
sLSTM time scan gets an all-reduce PER TIME STEP (measured: 1.92 TB/step
on xlstm-1.3b train_4k — §Perf I6).  Running the block body inside a
fully-manual shard_map over the (pure-DP) batch axes makes every in-loop
value shard-local; the weight gradients psum exactly once at the
shard_map boundary (the VJP of a replicated-in parameter).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime import shard_ctx


def _batch_specs(tree, dp):
    return jax.tree.map(
        lambda a: P(dp, *((None,) * (a.ndim - 1))), tree)


def block_shard_map(fn, params, x, cache):
    """fn(params, x, cache) -> (y, new_cache).  Shards batch over ctx.dp."""
    ctx = shard_ctx.get()
    if ctx is None:
        return fn(params, x, cache)
    dp = tuple(ctx.dp_axes)
    ndp = ctx.axis_size(dp)
    if ndp <= 1 or x.shape[0] % ndp != 0:
        return fn(params, x, cache)

    out_shape = jax.eval_shape(fn, params, x, cache)
    out_specs = (_batch_specs(out_shape[0], dp),
                 _batch_specs(out_shape[1], dp))
    sm = jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(), P(dp, None, None), _batch_specs(cache, dp)),
        out_specs=out_specs,
        axis_names=set(dp) | ({ctx.tp_axis} if ctx.tp_axis in dp else set()),
        check_vma=False)
    return sm(params, x, cache)
