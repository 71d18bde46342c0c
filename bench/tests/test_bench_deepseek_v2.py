"""DeepSeek-V2 at one chip's share on new files alone:
``tiny-deepseek-v2.json`` (1 dense + 2 MoE layers, d 64, 2 of 8 routed
experts held, top-3, one shared expert, a 64-dim latent, a 16 + 8 qk
head, YaRN on) through ``bench/layouts/deepseek_v2.py`` and
``bench/configs/deepseek_v2.py``: the program against the plain
reference on seeded weights, the share and no-drop tests of the
held-experts layer, and the three checks ``test_bench_controls.py``
makes (a sound run is correct, the float8 control reads well above the
program, planted faults are not correct)."""
import importlib.util
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, layouts, ref_blockllm, train_cell, weights
from bench.configs import deepseek_v2
from bench.tests.test_bench_controls import TRAIN_LIMITS
from bench.tests.test_bench_harness import DATA, ROOT, SEED, tiny_cell

CELL_CONFIG = ROOT / "bench" / "configs" / "deepseek-v2-lite.json"


def _cell(limits=None):
    return tiny_cell("train", limits=limits, config="tiny-deepseek-v2")


def _tiny():
    return json.loads((DATA / "tiny-deepseek-v2.json").read_text())


def _metric(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the layout


def test_cell_config_maps_to_the_program():
    m = json.loads(CELL_CONFIG.read_text())
    layouts.check(m)
    cfg = layouts.of(m).model_config(m)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads) == (6, 2048, 16)
    assert (cfg.num_experts, cfg.router_experts, cfg.num_experts_per_tok) == (8, 64, 6)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert cfg.first_dense_layers == 1 and cfg.d_ff == 10944
    assert not cfg.norm_topk_prob and cfg.seq_aux
    assert cfg.router_aux_coef == 0.001 and cfg.rope_yarn.factor == 40
    assert [s.rows for s in layouts.of(m).stacks(m)] == [1, 5]
    assert sum(layouts.numel(m, l) for l in layouts.table(m).values()) \
        == 635_466_752
    assert flops.train_flops_per_token(m, 2048) == 2_151_462_912


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("topk_method", "group_limited_greedy"),
    ("n_group", 8), ("scoring_func", "sigmoid"), ("seq_aux", False)])
def test_layout_refuses_what_it_does_not_implement(key, value):
    m = dict(_tiny(), **{key: value})
    with pytest.raises(layouts.ConfigError):
        layouts.of(m).model_config(m)


def test_seeded_weights_have_the_program_layout():
    from repro.models import model
    m = _tiny()
    cfg = layouts.of(m).model_config(m)
    mine = jax.tree.map(lambda a: a.shape, weights.program_params(SEED, m))
    prog = jax.tree.map(lambda a: a.shape,
                        jax.eval_shape(lambda: model.init_params(
                            jax.random.PRNGKey(0), cfg)))
    assert mine == prog
    ids = [l.id for l in layouts.table(m).values()]
    assert len(ids) == len(set(ids)) and not set(ids) & set(range(16))


# ------------------------------------------------------ program vs reference


def _ref_loss(m, tokens, params):
    tab = layouts.table(m)
    flat = weights.by_path(params)
    x = flat["embed"][tokens]
    aux = 0.0
    for st in layouts.of(m).stacks(m):
        for row in range(st.rows):
            w = {l.name: flat[l.path][row] for l in tab.values() if l.stack == st.sid}
            x, a = getattr(deepseek_v2, st.block)(x, w, m)
            aux = aux + a
    return deepseek_v2.next_token_loss(x, flat["final_norm/scale"],
                                       flat["head"], tokens, m) + aux


def test_program_loss_and_gradients_equal_the_reference():
    """In float32 the program's loss and every gradient follow the plain
    reference: MLA, YaRN, the leading dense layer, the held share over the
    published router and the sequence-wise balance loss."""
    from repro.models import model
    m = _tiny()
    cfg = layouts.of(m).model_config(m).replace(dtype="float32")
    params = weights.program_params(SEED, m)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 64)),
                       jnp.int32)
    lp, gp = jax.value_and_grad(lambda p: model.loss_fn(
        p, cfg, {"tokens": toks}, attn_impl="full")[0])(params)
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(lambda p: _ref_loss(m, toks, p))(params)
    assert abs(float(lp) - float(lr)) < 2e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


def _layer_inputs(m, seed=0, T=64):
    d, E = m["hidden_size"], m["router_experts"]
    k = jax.random.PRNGKey(seed)
    f = m["moe_intermediate_size"]
    w = {"router": jax.random.normal(k, (d, E)) / np.sqrt(d),
         "e_gate": jax.random.normal(jax.random.fold_in(k, 1), (E, d, f)) / np.sqrt(d),
         "e_up": jax.random.normal(jax.random.fold_in(k, 2), (E, d, f)) / np.sqrt(d),
         "e_down": jax.random.normal(jax.random.fold_in(k, 3), (E, f, d)) / np.sqrt(f),
         "s_gate": jax.random.normal(jax.random.fold_in(k, 4), (d, f)) / np.sqrt(d),
         "s_up": jax.random.normal(jax.random.fold_in(k, 5), (d, f)) / np.sqrt(d),
         "s_down": jax.random.normal(jax.random.fold_in(k, 6), (f, d)) / np.sqrt(f)}
    x = jax.random.normal(jax.random.fold_in(k, 7), (2, T // 2, d))
    return w, x


def _program_share(w, x, m, rank):
    """The program's held-experts layer of share ``rank``, float32."""
    from repro.models import moe
    held = m["n_routed_experts"]
    cfg = layouts.of(m).model_config(dict(m, expert_offset=rank * held)).replace(
        dtype="float32")
    sl = slice(rank * held, (rank + 1) * held)
    p = {"router": w["router"], "w_gate": w["e_gate"][sl], "w_up": w["e_up"][sl],
         "w_down": w["e_down"][sl],
         "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"], "w_down": w["s_down"]}}
    with jax.default_matmul_precision("highest"):
        return moe.moe_apply(p, x, cfg)


def _uncut_reference(w, x, m):
    """The reference's layer with every expert held: (y, aux)."""
    full = dict(m, n_routed_experts=m["router_experts"])
    ys, a = zip(*[deepseek_v2.experts(xb, w, full) for xb in x])
    return jnp.stack(ys), m["assumed"]["aux_loss_alpha"] * jnp.mean(jnp.stack(a))


def test_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares (ranks 0-3, 2 of 8 experts each) the held parts
    add up to the uncut reference layer, with the shared expert and the
    balance loss (computed alike on every share) counted once."""
    m = _tiny()
    w, x = _layer_inputs(m)
    outs = [_program_share(w, x, m, r) for r in range(4)]
    shared = deepseek_v2.swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    y = sum(o[0] - shared for o in outs) + shared
    y_ref, aux_ref = _uncut_reference(w, x, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    for o in outs:
        assert float(o[1]["aux"]) == pytest.approx(float(aux_ref), rel=1e-5)
    assert sum(int(o[1]["moe_rows_held"]) for o in outs) == x.shape[0] * x.shape[1] * 3


def test_every_token_to_one_held_expert_drops_none():
    """All T tokens routed to held expert 0 (a group of T rows, far past
    any capacity) give the dense result: the reference's held share,
    which computes every held expert on every token."""
    m = _tiny()
    w, x = _layer_inputs(m, seed=1)
    x = x + 3.0
    w["router"] = w["router"].at[:, 0].set(1.0)      # expert 0 wins every token
    y, st = _program_share(w, x, m, 0)
    T = x.shape[0] * x.shape[1]
    assert int(st["moe_group_max"]) == T
    held = {n: w[n][:2] for n in ("e_gate", "e_up", "e_down")}
    y_ref = jnp.stack([deepseek_v2.experts(xb, dict(w, **held), m)[0] for xb in x])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)


def test_reference_yarn_is_the_programs():
    from repro.configs.base import Yarn
    from repro.models import layers
    m = json.loads(CELL_CONFIG.read_text())
    inv, msc, scale = deepseek_v2.yarn(m)
    y = Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    np.testing.assert_allclose(inv, np.asarray(layers.yarn_inv_freq(64, 1e4, y)),
                               rtol=1e-6)
    assert msc == 1.0
    assert scale == pytest.approx(layers.yarn_softmax_scale(192, y), rel=1e-12)
    assert scale * 192 ** 0.5 == pytest.approx(1.58963, abs=1e-5)


# ------------------------------------------------------------- the checks


def test_control_reads_above_the_program():
    cell = _cell()
    ref = train_cell.reference_numbers(cell)
    handle = train_cell.make_trainer(cell)
    d = train_cell.drive(cell, handle, train_cell.make_pipeline(cell),
                         t_start=time.perf_counter(), window=False)
    prog = ref_blockllm.gaps(d.numbers(), ref)
    ctrl = ref_blockllm.gaps(
        train_cell.reference_numbers(cell, deepseek_v2.fp8), ref)
    assert all(prog[k] <= TRAIN_LIMITS[k] for k in prog), prog
    assert any(ctrl[k] > TRAIN_LIMITS[k] for k in ctrl), ctrl
    assert max(ctrl[k] / max(prog[k], 1e-12) for k in ctrl) >= 3.0


def test_sound_run_is_correct():
    cell = _cell(TRAIN_LIMITS)
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    assert harness.verdict(out.checks), out.checks


def _half_batch(monkeypatch):
    from repro.models import model
    real = model.loss_fn

    def half(params, cfg, batch, **kw):
        t = batch["tokens"]
        return real(params, cfg, {"tokens": t[: t.shape[0] // 2]}, **kw)
    monkeypatch.setattr(model, "loss_fn", half)


def _unchanged(monkeypatch):
    from repro.optim import adam
    real = adam.Adam.update
    monkeypatch.setattr(adam.Adam, "update", lambda self, g, s, p, **kw:
                        (p, real(self, g, s, p, **kw)[1]))


@pytest.mark.parametrize("fault", ["half_batch", "unchanged"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    {"half_batch": _half_batch, "unchanged": _unchanged}[fault](monkeypatch)
    cell = _cell(TRAIN_LIMITS)
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    got = {n: v for n, v, _ in out.checks}
    assert not harness.verdict(out.checks), got
    if fault == "unchanged":
        assert got["change_gap"] == pytest.approx(1.0)


# ------------------------------------------------------------- the readers


def _ops(*rows):
    return {"window": [0, 10_000_000], "ops": [
        ["/device:TPU:0", f"op{i}", path, s, d] for i, (path, s, d) in enumerate(rows)]}


def test_moe_readers_on_a_hand_made_trace():
    ms = _metric("moe_ms.train")
    ev = _ops(("jit(step)/blockllm_grad/moe/moe_gmm/ragged_dot", 0, 1_000_000),
              ("jit(step)/blockllm_grad/moe/gather", 500_000, 1_000_000),
              ("jit(step)/transpose(jvp(moe))/moe_gmm/dot", 3_000_000, 2_000_000),
              ("jit(step)/blockllm_grad/flash_attn/fwd", 6_000_000, 1_000_000),
              ("jit(step)/moe_other/x", 8_000_000, 1_000_000))
    assert ms.union_ms_per_step(ev, 2, "moe") == pytest.approx(1.75)
    assert ms.union_ms_per_step(ev, 2, "moe_gmm") == pytest.approx(1.5)
    assert ms.union_ms_per_step(_ops(), 2, "moe") is None
    assert ms.read({}) is None


def test_gmm_roofline_work_counts_the_held_rows():
    rl = _metric("moe_gmm_roofline.train")
    m = json.loads(CELL_CONFIG.read_text())
    f, b = rl.work(m, 16 * 2048)
    rows = 16 * 2048 * 6 * 8 / 64                   # 3072 a held expert
    assert f == 4 * 5 * 2 * 3 * 2048 * 1408 * rows
    assert b == 4 * 5 * 2 * (3 * 2048 * 1408 * 8 + 3 * (2048 + 1408) * rows)
    assert rl.read({"cell": _cell(), "peaks": None}) is None
