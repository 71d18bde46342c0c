"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles.

Kernels execute in interpret mode (CPU container); the same pallas_call
lowers natively on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.masked_adam import masked_adam_2d
from repro.kernels.ref import (flash_attention_ref, masked_adam_ref,
                               rglru_ref)
from repro.kernels.rglru_scan import rglru_scan_kernel

K = jax.random.PRNGKey


# ------------------------------------------------------------ masked adam

@pytest.mark.parametrize("shape", [(8, 128), (256, 512), (100, 257),
                                   (1, 128), (513, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_tau", [False, True])
def test_masked_adam_sweep(shape, dtype, use_tau):
    R, C = shape
    p = jax.random.normal(K(1), shape, dtype)
    g = jax.random.normal(K(2), shape, dtype)
    m = jax.random.normal(K(3), shape, jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(K(4), shape, jnp.float32)) * 0.01
    mask = jax.random.uniform(K(5), shape) > 0.5
    scal = jnp.array([1e-3, 0.9, 0.999, 1e-8, 0.01, 0.1, 0.01, 0.7],
                     jnp.float32)
    out = masked_adam_2d(p, g, m, v, mask, scal, use_tau=use_tau,
                         interpret=True)
    ref = masked_adam_ref(p, g, m, v, mask, scal, use_tau=use_tau)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=rtol, atol=1e-5)


def test_masked_adam_tree_wrapper():
    tree = {"a": jax.random.normal(K(1), (16, 32)),
            "b": jax.random.normal(K(2), (7,))}
    g = jax.tree.map(lambda a: a * 0.1, tree)
    mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
    nu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), tree)
    masks = jax.tree.map(lambda a: jnp.ones(a.shape, bool), tree)
    p2, m2, v2 = ops.masked_adam_tree(tree, g, mu, nu, masks, lr=0.1,
                                      interpret=True)
    from repro.optim.adam import Adam
    adam = Adam(lr=0.1)
    st = adam.init(tree)
    ref, _ = adam.update(g, st, tree)
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


# ------------------------------------------------------------ flash attn
# ops.flash_attention: the splash kernels, forward and backward, with
# block sizes taken from the shape; interpret mode here.

@pytest.mark.parametrize(
    "B,S,H,KV,hd,causal,window",
    [(2, 256, 4, 2, 64, True, 0),
     (1, 512, 4, 1, 64, True, 64),
     (2, 128, 2, 2, 32, False, 0),
     (1, 384, 4, 4, 128, True, 0),
     (1, 256, 8, 2, 64, True, 128)])
def test_flash_attention_sweep(B, S, H, KV, hd, causal, window):
    q = jax.random.normal(K(1), (B, S, H, hd))
    k = jax.random.normal(K(2), (B, S, KV, hd))
    v = jax.random.normal(K(3), (B, S, KV, hd))
    o = ops.flash_attention(q, k, v, causal, window, True)
    r = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(K(1), (1, 128, 2, 64), dtype)
    k = jax.random.normal(K(2), (1, 128, 2, 64), dtype)
    v = jax.random.normal(K(3), (1, 128, 2, 64), dtype)
    o = ops.flash_attention(q, k, v, True, 0, True)
    assert o.dtype == dtype
    r = flash_attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 48),
                                           (False, 0)])
def test_flash_attention_grad_matches_ref(causal, window):
    # GQA: 4 q heads over 2 kv heads, so dk/dv sum over each group
    q = jax.random.normal(K(1), (1, 256, 4, 32))
    k = jax.random.normal(K(2), (1, 256, 2, 32))
    v = jax.random.normal(K(3), (1, 256, 2, 32))

    gk = jax.grad(lambda *a: (ops.flash_attention(*a, causal, window, True)
                              ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (flash_attention_ref(*a, causal=causal,
                                                  window=window) ** 2
                              ).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_refuses_uncovered_lengths():
    # a sequence off the 128-lane blocks is not covered: the model keeps
    # XLA attention there (models/model._train_flash)
    assert ops.flash_attention_supports(256)
    assert not ops.flash_attention_supports(100)
    q = jnp.zeros((1, 100, 2, 32))
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.flash_attention(q, q, q, True, 0, True)


# ------------------------------------------------------------ rglru

@pytest.mark.parametrize("B,S,W", [(1, 64, 128), (2, 96, 192), (1, 33, 130)])
def test_rglru_kernel_sweep(B, S, W):
    a = jax.random.uniform(K(1), (B, S, W), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(K(2), (B, S, W)) * 0.1
    h0 = jax.random.normal(K(3), (B, W)) * 0.1
    y, hN = rglru_scan_kernel(a, b, h0, block_t=32, block_w=64,
                              interpret=True)
    yr, hr = rglru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hN), np.asarray(hr), atol=1e-5)


def test_rglru_kernel_grad():
    B, S, W = 1, 48, 64
    a = jax.random.uniform(K(1), (B, S, W), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(K(2), (B, S, W)) * 0.1
    h0 = jax.random.normal(K(3), (B, W)) * 0.1

    def f_k(a, b, h0):
        y, hN = ops.rglru_scan(a, b, h0, True)
        return (y ** 2).sum() + (hN ** 2).sum()

    def f_r(a, b, h0):
        y, hN = rglru_ref(a, b, h0)
        return (y ** 2).sum() + (hN ** 2).sum()

    gk = jax.grad(f_k, argnums=(0, 1, 2))(a, b, h0)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(a, b, h0)
    for x, y_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y_), atol=1e-3)
