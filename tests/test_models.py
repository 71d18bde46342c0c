"""Model substrate: layer equivalences, family forward/loss/decode paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (BLOCK_GLOBAL_ATTN, BLOCK_LOCAL_ATTN,
                                BLOCK_MLSTM, BLOCK_RECURRENT, BLOCK_SLSTM,
                                ModelConfig)
from repro.models import layers, model, moe as moe_lib, rglru, xlstm

K = jax.random.PRNGKey


def _mk(family="dense", **kw):
    base = dict(name="t", family=family, num_layers=4, d_model=32,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=96,
                remat=False)
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------- attention

def test_chunked_matches_full():
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(K(1), (B, S, H, hd))
    k = jax.random.normal(K(2), (B, S, KV, hd))
    v = jax.random.normal(K(3), (B, S, KV, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for window in (0, 9):
        a = layers.attention_full(q, k, v, pos, pos, causal=True,
                                  window=window)
        b = layers.attention_chunked(q, k, v, pos, pos, causal=True,
                                     window=window, q_chunk=16, kv_chunk=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_decode_matches_full_last_token():
    B, S, H, KV, hd = 2, 32, 4, 1, 16
    q = jax.random.normal(K(1), (B, S, H, hd))
    k = jax.random.normal(K(2), (B, S, KV, hd))
    v = jax.random.normal(K(3), (B, S, KV, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    for window in (0, 8):
        full = layers.attention_full(q, k, v, pos, pos, causal=True,
                                     window=window)
        dec = layers.attention_decode(q[:, -1:], k, v, S - 1, window=window)
        np.testing.assert_allclose(np.asarray(full[:, -1:]),
                                   np.asarray(dec), atol=2e-5)


def test_ring_buffer_decode():
    B, S, KV, hd, W = 1, 48, 2, 8, 8
    H = 4
    q = jax.random.normal(K(1), (B, S, H, hd))
    k = jax.random.normal(K(2), (B, S, KV, hd))
    v = jax.random.normal(K(3), (B, S, KV, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    rk = jnp.zeros((B, W, KV, hd))
    rv = jnp.zeros((B, W, KV, hd))
    for t in range(S):
        rk = rk.at[:, t % W].set(k[:, t])
        rv = rv.at[:, t % W].set(v[:, t])
    ref = layers.attention_full(q, k, v, pos, pos, causal=True, window=W)
    out = layers.attention_decode(q[:, -1:], rk, rv, S - 1, window=W,
                                  ring=True)
    np.testing.assert_allclose(np.asarray(ref[:, -1:]), np.asarray(out),
                               atol=2e-5)


# ---------------------------------------------------------------- recurrent

def test_mlstm_chunkwise_vs_recurrent():
    B, S, H, hd = 2, 64, 2, 16
    q = jax.random.normal(K(1), (B, S, H, hd))
    k = jax.random.normal(K(2), (B, S, H, hd))
    v = jax.random.normal(K(3), (B, S, H, hd))
    li = jax.random.normal(K(4), (B, S, H)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(K(5), (B, S, H)) + 2)
    for chunk in (8, 16, 64):
        hc, sc = xlstm.mlstm_chunkwise(q, k, v, li, lf, chunk=chunk)
        hr, sr = xlstm.mlstm_recurrent_ref(q, k, v, li, lf)
        np.testing.assert_allclose(np.asarray(hc), np.asarray(hr),
                                   atol=5e-5)
        for a, b in zip(sc, sr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)


def test_rglru_scan_vs_step():
    class C:
        d_model = 32
        lru_width = 32
        conv1d_width = 4
        num_layers = 4
    pr = rglru.rglru_init(K(7), C)
    B, S = 2, 33
    x = jax.random.normal(K(6), (B, S, 32))
    y_scan, h_last = rglru.rglru_scan(pr, x)
    h = jnp.zeros((B, 32))
    ys = []
    for t in range(S):
        yt, h = rglru.rglru_step(pr, x[:, t], h)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y_scan),
                               np.asarray(jnp.stack(ys, 1)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h), atol=2e-5)


def test_rglru_stability_long():
    """|h| stays bounded over long sequences (decay in (0,1))."""
    class C:
        d_model = 16
        lru_width = 16
        conv1d_width = 4
        num_layers = 2
    pr = rglru.rglru_init(K(0), C)
    x = jax.random.normal(K(1), (1, 2048, 16)) * 3.0
    y, h = rglru.rglru_scan(pr, x)
    assert np.isfinite(np.asarray(y)).all()
    assert float(jnp.abs(y).max()) < 100.0


# ---------------------------------------------------------------- MoE

def test_moe_capacity_matches_dense_when_no_drop():
    cfg = _mk("moe", num_experts=4, num_experts_per_tok=2, moe_d_ff=32,
              capacity_factor=8.0)  # capacity >> tokens: nothing dropped
    p = moe_lib.moe_init(K(0), cfg)
    x = jax.random.normal(K(1), (2, 8, 32))
    y1, _ = moe_lib.moe_apply_capacity(p, x, cfg)
    y2, _ = moe_lib.moe_apply_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_moe_token_chunking_equivalent():
    cfg = _mk("moe", num_experts=4, num_experts_per_tok=2, moe_d_ff=32,
              capacity_factor=8.0)
    p = moe_lib.moe_init(K(0), cfg)
    x = jax.random.normal(K(1), (2, 32, 32))
    y1, _ = moe_lib.moe_apply_capacity(p, x, cfg, token_chunk=1 << 20)
    y2, _ = moe_lib.moe_apply_capacity(p, x, cfg, token_chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_moe_drops_when_over_capacity():
    cfg = _mk("moe", num_experts=2, num_experts_per_tok=1, moe_d_ff=16,
              capacity_factor=0.1)
    p = moe_lib.moe_init(K(0), cfg)
    x = jax.random.normal(K(1), (1, 64, 32))
    y, aux = moe_lib.moe_apply_capacity(p, x, cfg, capacity=8)
    assert np.isfinite(np.asarray(y)).all()
    # most rows must be zero (dropped, no shared expert)
    row_norms = np.linalg.norm(np.asarray(y[0], np.float32), axis=-1)
    assert (row_norms < 1e-6).sum() >= 40


def test_moe_dropless_matches_dense_and_counts_rows():
    """The single-device layer drops nothing even where the capacity
    dispatch would (factor 0.1), and counts every routed pair."""
    cfg = _mk("moe", num_experts=4, num_experts_per_tok=2, moe_d_ff=32,
              capacity_factor=0.1)
    p = moe_lib.moe_init(K(0), cfg)
    x = jax.random.normal(K(1), (2, 8, 32))
    y1, st = moe_lib.moe_apply(p, x, cfg)
    y2, _ = moe_lib.moe_apply_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    assert int(st["moe_rows_held"]) == 2 * 8 * 2
    assert 8 <= int(st["moe_group_max"]) <= 16


def _held_dense_ref(p, x, cfg):
    """Every held expert on every token, weighted by its top-k gate (zero
    where the expert is not among the token's top k)."""
    xt = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xt @ p["router"], axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        topw = topw / topw.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], topi].set(topw)
    gate = gate[:, cfg.expert_offset:cfg.expert_offset + cfg.num_experts]
    h = jnp.einsum("td,edf->etf", xt, p["w_gate"])
    u = jnp.einsum("td,edf->etf", xt, p["w_up"])
    ye = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, p["w_down"])
    return jnp.einsum("te,etd->td", gate, ye).reshape(x.shape)


@pytest.mark.parametrize("held", [
    dict(num_experts=4),
    dict(num_experts=2, router_experts=8, expert_offset=2, seq_aux=True,
         norm_topk_prob=False),
], ids=["all_held", "2_of_8_held"])
def test_moe_gmm_kernel_matches_ragged_dot_and_dense(held):
    """The layer through the Pallas grouped matmul (interpreted) and
    through ``lax.ragged_dot`` equals a dense held-experts reference,
    forward and backward; the row buffer has rows past the groups."""
    cfg = _mk("moe", num_experts_per_tok=2, moe_d_ff=32, **held)
    p = moe_lib.moe_init(K(0), cfg)
    x = jax.random.normal(K(1), (2, 16, 32))
    r = jax.random.normal(K(2), x.shape)

    def f(y_of):
        return jax.value_and_grad(lambda p, x: (y_of(p, x) * r).sum(),
                                  argnums=(0, 1))(p, x)

    ref = f(lambda p, x: _held_dense_ref(p, x, cfg))
    for impl in ("gmm_interpret", "ragged"):
        got = f(lambda p, x: moe_lib.moe_apply(p, x, cfg, gmm_impl=impl)[0])
        np.testing.assert_allclose(float(got[0]), float(ref[0]), atol=1e-4)
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(ref[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["gmm_interpret", "ragged"])
def test_grouped_matmul_reads_no_row_past_the_groups(impl):
    """Rows of the buffer past the groups hold NaN: the rows in the
    groups, their dX and every dW come out as a dense matmul gives them."""
    G, n, rows, d, f = 3, 40, 512, 32, 16
    group_sizes = jnp.array([7, 0, 33], jnp.int32)
    x = jax.random.normal(K(0), (rows, d))
    x = jnp.where(jnp.arange(rows)[:, None] < n, x, jnp.nan)
    w = jax.random.normal(K(1), (G, d, f))
    r = jax.random.normal(K(2), (n, f))
    gid = jnp.repeat(jnp.arange(G), group_sizes, total_repeat_length=n)

    def loss(x, w, mm):
        return (mm(x, w)[:n] * r).sum()

    got = jax.grad(loss, argnums=(0, 1))(
        x, w, lambda x, w: moe_lib._grouped_matmul(x, w, group_sizes, impl))
    want = jax.grad(loss, argnums=(0, 1))(
        x[:n], w, lambda x, w: jnp.einsum("td,tdf->tf", x, w[gid]))
    np.testing.assert_allclose(np.asarray(got[0][:n]), np.asarray(want[0]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-4, rtol=1e-4)


def test_moe_held_experts_refused_by_the_capacity_path():
    cfg = _mk("moe", num_experts=2, router_experts=4, num_experts_per_tok=2,
              moe_d_ff=16)
    p = moe_lib.moe_init(K(0), cfg)
    with pytest.raises(ValueError, match="moe_apply only"):
        moe_lib.moe_apply_capacity(p, jnp.zeros((1, 8, 32)), cfg)


# ---------------------------------------------------------------- DeepSeek-V2

def test_leading_dense_stage():
    from repro.configs import base
    cfg = base.get_config("deepseek-v2-lite")
    assert cfg.stages() == [((BLOCK_GLOBAL_ATTN,), 1), ((BLOCK_GLOBAL_ATTN,), 26)]
    assert cfg.stage_config(0).num_experts == 0
    assert cfg.stage_config(1) is cfg
    assert cfg.param_count() == 15_706_482_176      # 15.7 B published
    tiny = base.get_config("deepseek-v2-lite-tiny")
    p = model.init_params(K(0), tiny)
    assert p["stages"][0]["pos0"]["mlp"]["w_gate"].shape == (1, 64, 128)
    assert p["stages"][1]["pos0"]["moe"]["w_gate"].shape == (2, 2, 64, 32)
    assert p["stages"][1]["pos0"]["moe"]["router"].shape == (2, 64, 8)
    n = sum(a.size for a in jax.tree.leaves(p))
    assert n == tiny.param_count() + tiny.d_model     # + the final norm
    with pytest.raises(ValueError, match="whole repetitions"):
        _mk(first_dense_layers=4).stages()


def test_yarn_range_and_scale():
    """DeepSeek-V2-Lite's YaRN: dim 64, base 1e4, factor 40 over 4096."""
    from repro.configs.deepseek_v2_lite import YARN
    assert layers.yarn_range(64, 1e4, YARN) == (10, 23)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    assert layers.yarn_softmax_scale(192, YARN) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)
    inv = np.asarray(layers.yarn_inv_freq(64, 1e4, YARN), np.float64)
    i = np.arange(32)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = 1e4 ** (-2 * i / 64) * ((1 - ramp) + ramp / 40)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(1e4 ** (-62 / 64) / 40,
                                                      rel=1e-6)


@pytest.mark.parametrize("mode", ["decode", "prefill", "prefill_into_slots",
                                  "verify", "init_cache", "init_paged_cache"])
def test_mla_refuses_serving_modes(mode):
    from repro.configs import base
    cfg = base.get_config("deepseek-v2-lite-tiny")
    p = model.init_params(K(0), cfg)
    toks = jnp.zeros((2, 4), jnp.int32)
    cache = {"stages": [None, None]}
    calls = {
        "decode": lambda: model.decode_step(p, cfg, cache, toks[:, :1], 3),
        "prefill": lambda: model.prefill(p, cfg, {"tokens": toks}),
        "prefill_into_slots": lambda: model.prefill_into_slots(
            p, cfg, cache, toks, jnp.full((2,), 4)),
        "verify": lambda: model.verify_into_slots(
            p, cfg, cache, toks, jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), bool)),
        "init_cache": lambda: model.init_cache(cfg, 2, 16),
        "init_paged_cache": lambda: model.init_paged_cache(cfg, 2, 8, 4, 16),
    }
    with pytest.raises(ValueError, match="multi-head latent attention"):
        calls[mode]()
    assert not model.supports_slot_prefill(cfg)


def test_mla_flash_matches_full_in_the_model():
    """Loss and gradients of the tiny DeepSeek-V2 through the flash op
    (interpret mode) equal the XLA attention path's."""
    from repro.configs import base
    cfg = base.get_config("deepseek-v2-lite-tiny").replace(dtype="float32")
    p = model.init_params(K(0), cfg)
    toks = jax.random.randint(K(1), (1, 128), 0, cfg.vocab_size)

    def lg(impl):
        return jax.value_and_grad(lambda q: model.loss_fn(
            q, cfg, {"tokens": toks}, attn_impl=impl)[0])(p)

    (l1, g1), (l2, g2) = lg("full"), lg("pallas_interpret")
    assert abs(float(l1) - float(l2)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


# ---------------------------------------------------------------- loss

def test_chunked_xent_matches_direct(tiny_cfg, tiny_params, tiny_batch):
    l1, m1 = model.loss_fn(tiny_params, tiny_cfg, tiny_batch, loss_chunk=0)
    l2, m2 = model.loss_fn(tiny_params, tiny_cfg, tiny_batch, loss_chunk=4)
    assert abs(float(l1) - float(l2)) < 1e-5


def test_labels_mask_ignores_negative():
    cfg = _mk()
    p = model.init_params(K(0), cfg)
    toks = jax.random.randint(K(1), (2, 8), 0, cfg.vocab_size)
    labels = toks.at[:, :4].set(-1)
    l_all, m = model.loss_fn(p, cfg, {"tokens": toks, "labels": labels})
    assert float(m["tokens"]) == 2 * 4


# ---------------------------------------------------------------- decode == forward

@pytest.mark.parametrize("fam_kw", [
    dict(family="dense"),
    dict(family="dense", pattern=(BLOCK_LOCAL_ATTN, BLOCK_GLOBAL_ATTN),
         window_size=8),
    dict(family="hybrid", pattern=(BLOCK_RECURRENT, BLOCK_RECURRENT,
                                   BLOCK_LOCAL_ATTN), window_size=8,
         lru_width=32),
    dict(family="ssm", pattern=(BLOCK_MLSTM, BLOCK_MLSTM, BLOCK_SLSTM),
         mlp_type="none", d_ff=0, num_layers=3),
])
def test_decode_consistent_with_forward(fam_kw):
    """prefill(x[:t]) + decode(x[t]) logits == forward(x[:t+1]) last logits."""
    cfg = _mk(**fam_kw)
    p = model.init_params(K(0), cfg)
    toks = jax.random.randint(K(1), (2, 12), 0, cfg.vocab_size)
    # full forward on t+1 tokens
    logits_full, _, _ = model.forward(p, cfg, {"tokens": toks},
                                      mode="train", attn_impl="full")
    # prefill on first 11, then decode token 11
    lg, cache = model.prefill(p, cfg, {"tokens": toks[:, :11]},
                              attn_impl="full")
    # prefill caches for attention are sized to the prefill length; decode
    # needs a slot for the new token -> rebuild into a larger cache
    big = model.init_cache(cfg, 2, 16, dtype=lg.dtype)
    big = _copy_cache(cfg, cache, big, 11)
    logits_dec, _ = model.decode_step(p, cfg, big, toks[:, 11:12], 11,
                                      attn_impl="full")
    np.testing.assert_allclose(
        np.asarray(logits_full[:, 11], np.float32),
        np.asarray(logits_dec, np.float32), atol=2e-2, rtol=2e-2)


def _copy_cache(cfg, small, big, n):
    """Copy prefill cache entries into a larger decode cache."""
    def cp(s, b):
        if s.ndim >= 3 and s.shape[-3] <= b.shape[-3] and s.ndim == b.ndim \
                and s.shape[-2:] == b.shape[-2:]:
            # attention kv: [..., C, KV, hd] — ring/window caches may be
            # smaller; write the last entries at positions (n - C) .. n
            C = s.shape[-3]
            if b.shape[-3] == C:
                return b.at[..., :C, :, :].set(s)
            start = 0
            return jax.lax.dynamic_update_slice_in_dim(
                b, s, start, axis=b.ndim - 3)
        return s  # recurrent states: same shape, pass through

    return jax.tree.map(cp, small, big)
