"""Train-mode attention through the flash kernel: parity with the XLA
path at the model level, and where ``attn_impl="auto"`` takes it.

The kernel runs in interpret mode here.  ``"auto"`` takes it only on a
TPU, so the dispatch tests stand in for the backend by patching
``kernels.ops.pallas_available`` and watch the op with a spy that runs
the interpreted kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models import model
from repro.runtime import shard_ctx

K = jax.random.PRNGKey
S = 128


def _cfg(**kw):
    base = dict(name="t", family="dense", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=96,
                remat=True, dtype="bfloat16")
    base.update(kw)
    return ModelConfig(**base)


def _batch(cfg, B=2):
    return {"tokens": jax.random.randint(K(1), (B, S), 0, cfg.vocab_size)}


def _loss_and_grads(cfg, params, batch, attn_impl):
    f = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, cfg, batch, attn_impl=attn_impl)[0]))
    return f(params)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)


@pytest.mark.parametrize("window", [0, 64])
def test_train_loss_and_grads_match_xla_attention(window):
    """float32: the flash path agrees with XLA attention to rounding.
    bfloat16 (the trained dtype): against the float32 numbers, the loss
    agrees within 2e-3 and every parameter gradient is as close as the
    bfloat16 XLA path's (its bf16 logits make it the looser of the two)."""
    from repro.configs.base import BLOCK_LOCAL_ATTN
    kw = dict(pattern=(BLOCK_LOCAL_ATTN,), window_size=window) \
        if window else {}
    c32, c16 = _cfg(dtype="float32", **kw), _cfg(**kw)
    params = model.init_params(K(0), c32)
    batch = _batch(c32)
    l32, g32 = _loss_and_grads(c32, params, batch, "full")
    lf32, gf32 = _loss_and_grads(c32, params, batch, "pallas_interpret")
    np.testing.assert_allclose(float(lf32), float(l32), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gf32), jax.tree.leaves(g32)):
        assert _rel(a, b) < 1e-4
    lf, gf = _loss_and_grads(c16, params, batch, "pallas_interpret")
    lx, gx = _loss_and_grads(c16, params, batch, "full")
    np.testing.assert_allclose(float(lf), float(l32), rtol=2e-3)
    for (path, a), x, r in zip(jax.tree_util.tree_leaves_with_path(gf),
                               jax.tree.leaves(gx), jax.tree.leaves(g32)):
        assert _rel(a, r) <= 1.25 * _rel(x, r) + 1e-3, \
            (jax.tree_util.keystr(path), _rel(a, r), _rel(x, r))


@pytest.fixture
def on_tpu(monkeypatch):
    """``pallas_available`` reads True; ``ops.flash_attention`` runs the
    interpreted kernel and counts its calls."""
    calls = []

    def spy(q, k, v, causal=True, window=0, interpret=False):
        calls.append((q.shape, causal, window))
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  interpret=True)

    monkeypatch.setattr(ops, "pallas_available", lambda: True)
    monkeypatch.setattr(ops, "flash_attention", spy)
    return calls


def test_auto_takes_flash_in_train_mode(on_tpu):
    cfg = _cfg()
    params = model.init_params(K(0), cfg)
    batch = _batch(cfg)
    la, ga = _loss_and_grads(cfg, params, batch, "auto")
    assert on_tpu and all(c == ((2, S, 4, 16), True, 0) for c in on_tpu)
    lp, gp = _loss_and_grads(cfg, params, batch, "pallas_interpret")
    assert float(la) == float(lp)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("what", ["shard_rules", "softcap", "length",
                                  "full", "chunked"])
def test_auto_keeps_xla_attention(on_tpu, what):
    cfg = _cfg(attn_softcap=30.0) if what == "softcap" else _cfg()
    params = model.init_params(K(0), cfg)
    batch = _batch(cfg)
    if what == "length":
        batch = {"tokens": batch["tokens"][:, :100]}
    impl = what if what in ("full", "chunked") else "auto"
    fn = lambda p: model.loss_fn(p, cfg, batch, attn_impl=impl)[0]
    if what == "shard_rules":
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        with shard_ctx.use(shard_ctx.ShardRules(mesh=mesh)):
            jax.make_jaxpr(jax.grad(fn))(params)
    else:
        jax.make_jaxpr(jax.grad(fn))(params)
    assert on_tpu == []


def _serving_jaxprs(cfg, params, attn_impl):
    """jaxprs of prefill, chunked slot prefill, speculative verify and
    decode at ``attn_impl`` (verify has none of its own)."""
    B = 2
    toks = _batch(cfg, B)["tokens"]
    cache = model.init_cache(cfg, B, 2 * S)
    lengths = jnp.full((B,), S, jnp.int32)
    return [
        jax.make_jaxpr(lambda p: model.prefill(
            p, cfg, {"tokens": toks}, attn_impl=attn_impl))(params),
        jax.make_jaxpr(lambda p: model.prefill_into_slots(
            p, cfg, cache, toks, lengths, attn_impl=attn_impl))(params),
        jax.make_jaxpr(lambda p: model.verify_into_slots(
            p, cfg, cache, toks[:, :4], lengths,
            jnp.ones((B,), bool)))(params),
        jax.make_jaxpr(lambda p: model.decode_step(
            p, cfg, cache, toks[:, :1], S, attn_impl=attn_impl))(params),
    ]


def test_serving_modes_lower_as_before(monkeypatch):
    cfg = _cfg()
    params = model.init_params(K(0), cfg)
    before = [str(j) for j in _serving_jaxprs(cfg, params, "full")]
    calls = []
    monkeypatch.setattr(ops, "pallas_available", lambda: True)
    monkeypatch.setattr(ops, "flash_attention",
                        lambda *a, **k: calls.append(a) or a[0])
    for impl in ("full", "auto"):
        after = [str(j) for j in _serving_jaxprs(cfg, params, impl)]
        assert after == before, impl
    assert calls == []
