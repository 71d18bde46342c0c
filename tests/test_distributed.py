"""Distributed execution tests (subprocess with 8 forced host devices).

These actually RUN sharded computations on a small mesh — complementing
the compile-only dry-run: a BlockLLM train step under pjit matches the
single-device trainer, the MoE shard_map island matches the unsharded
path, and the int8 error-feedback psum approximates the exact mean.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    # Pin the cpu platform EXPLICITLY (don't unset): containers with
    # libtpu installed but no TPU hardware hang in TPU client init when
    # jax is left to probe platforms.  --xla_force_host_platform_device
    # _count composes fine with JAX_PLATFORMS=cpu.
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-3000:])
    return p.stdout


SHARDED_TRAIN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh_compat
from repro.launch.specs import concrete_batch
from repro.models import model
from repro.runtime import shard_ctx

cfg = ModelConfig(name="t", family="dense", num_layers=4, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                  remat=False, dtype="float32")
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
mesh = make_mesh_compat((4, 2), ("data", "model"))
setup = steps_lib.build_train_setup(cfg, shape, mesh, sparsity=0.8,
                                    k_frac=0.5, attn_impl="full")
# materialize concrete args from the abstract ones
key = jax.random.PRNGKey(0)
params = model.init_params(key, cfg)
from repro.core import units as units_lib
index = units_lib.build_unit_index(cfg, params)
plan = setup.meta["plan"]
active = units_lib.extract_active(params, index, plan)
from repro.optim.adam import Adam
adam = Adam(lr=1e-3)
opt = adam.init(active["sel"])
masks = jax.tree.map(lambda a: jnp.ones(a.shape, jnp.bool_), active["sel"])
batch = concrete_batch(cfg, setup.args[7], key=jax.random.PRNGKey(1))
batch["tokens"] = batch["tokens"] % cfg.vocab_size

args = (params, active["sel"], active["probe"], plan.stack_idx,
        plan.probe_idx, opt, masks, batch, jnp.asarray(1.0, jnp.float32))
with shard_ctx.use(setup.rules):
    fn = jax.jit(setup.fn, in_shardings=setup.in_shardings)
    sel2, opt2, masks2, loss_sharded, metrics, norms = fn(*args)

# same step on 1 logical device (replicated jit, no shardings)
fn1 = jax.jit(setup.fn)
sel1, opt1, m1, loss_single, *_ = fn1(*args)
print("LOSSES", float(loss_sharded), float(loss_single))
np.testing.assert_allclose(float(loss_sharded), float(loss_single),
                           rtol=2e-4)
for a, b in zip(jax.tree.leaves(sel2), jax.tree.leaves(sel1)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-4)
print("SHARDED_TRAIN_OK")
"""


def test_sharded_train_step_matches_single():
    out = _run(SHARDED_TRAIN)
    assert "SHARDED_TRAIN_OK" in out


MOE_SHARDMAP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh_compat
from repro.models import moe as moe_lib
from repro.runtime import shard_ctx
from repro.runtime.moe_parallel import moe_apply_maybe_sharded

cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=0, vocab_size=64,
                  num_experts=4, num_experts_per_tok=2, moe_d_ff=64,
                  capacity_factor=16.0, remat=False, dtype="float32")
mesh = make_mesh_compat((4, 2), ("data", "model"))
p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
rules = shard_ctx.ShardRules(mesh=mesh, dp_axes=("data",))

with shard_ctx.use(rules):
    y_sh, aux_sh = jax.jit(
        lambda p, x: moe_apply_maybe_sharded(p, x, cfg))(p, x)
y_ref, aux_ref = jax.jit(lambda p, x: moe_lib.moe_apply_capacity(
    p, x, cfg, token_chunk=16))(p, x)
np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref), atol=2e-4)
print("MOE_SHARDMAP_OK")
"""


def test_moe_shardmap_matches_unsharded():
    out = _run(MOE_SHARDMAP)
    assert "MOE_SHARDMAP_OK" in out


COMPRESSED_PSUM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh_compat
from repro.runtime.compression import (compressed_psum_tree, init_errors,
                                        quantize_int8, dequantize_int8)

# quantize/dequantize bound: block max-scale => error <= scale/2
x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3
q, s = quantize_int8(x)
deq = dequantize_int8(q, s, x.shape)
err = np.abs(np.asarray(deq - x))
bound = np.repeat(np.asarray(s), 256)[:1024] * 0.5 + 1e-6
assert (err <= bound).all()

mesh = make_mesh_compat((8,), ("data",))
g = {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 64))}
e = init_errors(g)

@jax.jit
def step(g, e):
    return compressed_psum_tree(g, e, mesh, ("data",))

mean_g, new_e = step(g, e)
# with identical replicas the mean must equal the (dequantized) input
np.testing.assert_allclose(np.asarray(mean_g["w"]), np.asarray(g["w"]),
                           atol=0.05)
# error feedback: residual + dequantized == original
print("COMPRESSED_PSUM_OK")
"""


def test_compressed_psum():
    out = _run(COMPRESSED_PSUM)
    assert "COMPRESSED_PSUM_OK" in out


COMM_SCALING = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig
from repro.launch import steps as steps_lib, hlo_cost
from repro.launch.mesh import make_mesh_compat
from repro.runtime import shard_ctx

# large enough that GSPMD must reduce gradients rather than replicate
# the batch (its toy-scale escape hatch)
cfg = ModelConfig(name="t", family="dense", num_layers=8, d_model=256,
                  num_heads=4, num_kv_heads=4, d_ff=1024, vocab_size=2048,
                  remat=False, dtype="float32")
shape = ShapeConfig("t", seq_len=256, global_batch=32, kind="train")
mesh = make_mesh_compat((8, 1), ("data", "model"))

def grad_comm_bytes(k_frac):
    setup = steps_lib.build_train_setup(cfg, shape, mesh, sparsity=0.5,
                                        k_frac=k_frac, attn_impl="full")
    txt = setup.lower().compile().as_text()
    t = hlo_cost.analyze(txt)
    return (t.collective_bytes.get("all-reduce", 0.0)
            + t.collective_bytes.get("reduce-scatter", 0.0))

small = grad_comm_bytes(0.125)   # 1 of 8 layers active
large = grad_comm_bytes(1.0)     # all 8 layers active
print("grad-reduce bytes: k=1/8 ->", small, " k=8/8 ->", large,
      " ratio", small / large)
assert small < 0.6 * large, (small, large)
print("COMM_SCALING_OK")
"""


@pytest.mark.xfail(strict=False, reason=
    "GSPMD places the per-layer cotangent all-reduce INSIDE the layer scan "
    "(it keeps the replicated grad accumulator consistent every iteration), "
    "so DP wire bytes do not yet scale with the active fraction even though "
    "grad BUFFERS do (the lazy overlay accumulates at [K,...]). Known "
    "limitation, documented in EXPERIMENTS.md §Perf I10; fixing it needs an "
    "explicit dp-manual shard_map around the whole backward.")
def test_blockllm_scales_dp_allreduce_with_active_fraction():
    """The paper's technique as gradient compression: DP all-reduce bytes
    should shrink with the active fraction (EXPERIMENTS.md §Perf I10)."""
    out = _run(COMM_SCALING)
    assert "COMM_SCALING_OK" in out
