"""The configuration's layout: the paths, keys and stacks that the seeded
weights, the reference and the driver take from it, and the refusal of
a key no layout maps."""
import dataclasses
import hashlib
import json
import sys
import time
import types

import numpy as np
import pytest

from bench import flops, harness, layouts, ref_blockllm, train_cell, weights
from bench.configs import dense_decoder
from bench.layouts import dense_decoder as dense_layout
from bench.tests.test_bench_harness import DATA, SEED, tiny_cell

TINY = json.loads((DATA / "tiny.json").read_text())
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.05, "change_gap": 0.1}

# the dense decoder's leaves as the benchmark named and seeded them before
# layouts: name -> (program path, key id)
DENSE_BEFORE = {
    "ln1": ("stages/0/pos0/ln1/scale", 0), "wq": ("stages/0/pos0/attn/wq", 1),
    "wk": ("stages/0/pos0/attn/wk", 2), "wv": ("stages/0/pos0/attn/wv", 3),
    "wo": ("stages/0/pos0/attn/wo", 4), "ln2": ("stages/0/pos0/ln2/scale", 5),
    "w_gate": ("stages/0/pos0/mlp/w_gate", 6),
    "w_up": ("stages/0/pos0/mlp/w_up", 7),
    "w_down": ("stages/0/pos0/mlp/w_down", 8),
    "embed": ("embed", 100), "final_norm": ("final_norm/scale", 101),
    "head": ("head", 102)}
# sha256 over the sorted paths and bytes of program_params(SEED, tiny.json),
# recorded before layouts
TINY_PARAMS_SHA256 = \
    "d97fadd3370dfdda3afb410f9a981d7d92c04baa43b845f1d38158f2b2ab1969"


def test_dense_leaf_table_keeps_paths_and_ids():
    got = {l.name: (l.path, l.id) for l in dense_layout.leaves(TINY)}
    assert got == DENSE_BEFORE


def test_tiny_program_params_are_bit_identical_to_before():
    by = weights.by_path(weights.program_params(SEED, TINY))
    h = hashlib.sha256()
    for k in sorted(by):
        h.update(k.encode())
        h.update(np.asarray(by[k]).tobytes())
    assert h.hexdigest() == TINY_PARAMS_SHA256


def _spec_root(tmp_path, config):
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "c.json").write_text(json.dumps(config))
    (tmp_path / "bench" / "traffic" / "t.json").write_text(
        (DATA / "tiny-train.json").read_text())
    return {"configs": [{"name": config["name"], "file": "c.json"}],
            "workloads": [{"name": "w", "config": config["name"],
                           "traffic": "t", "chips": 1}]}


def test_find_cell_refuses_a_key_the_layout_does_not_map(tmp_path):
    cfg = dict(TINY, kv_lora_rank=512)
    spec = _spec_root(tmp_path, cfg)
    with pytest.raises(layouts.ConfigError, match="kv_lora_rank"):
        harness.find_cell("w", seed=1, seconds=1, trace=False, root=tmp_path,
                          spec=spec)


def test_find_cell_takes_every_key_the_layout_maps(tmp_path):
    for name in ("tiny", "tiny-moe"):
        cfg = json.loads((DATA / f"{name}.json").read_text())
        spec = _spec_root(tmp_path / name, cfg)
        cell = harness.find_cell("w", seed=1, seconds=1, trace=False,
                                 root=tmp_path / name, spec=spec)
        assert cell.model_config().name == name


def _two_stack_module():
    """A dense decoder whose program runs a pattern of two blocks over five
    layers: stage 0 holds two groups of (pos0, pos1), stage 1 one group
    of pos0, so three stacks of 2, 2 and 1 rows, run g0.pos0, g0.pos1,
    g1.pos0, g1.pos1, then stage 1."""
    mod = types.ModuleType("bench.layouts.two_stack")
    mod.KEYS = dense_layout.KEYS
    mod.model_config = lambda m: dense_layout.model_config(m).replace(
        pattern=("global", "global"))

    def stacks(m):
        return (layouts.Stack(0, 0, 2, "block"), layouts.Stack(0, 1, 2, "block"),
                layouts.Stack(1, 0, 1, "block"))

    def leaves(m):
        (one,) = dense_layout.stacks(m)
        out = []
        for j, st in enumerate(stacks(m)):
            for l in dense_layout.leaves(m):
                if l.stack:
                    out.append(dataclasses.replace(
                        l, path=l.path.replace(one.path, st.path, 1),
                        id=l.id + 20 * j, stack=st.sid))
        return tuple(out) + dense_layout.top_leaves(m)

    mod.stacks, mod.leaves = stacks, leaves
    mod.active_params_per_token = flops.non_embedding_params
    mod.attention_flops_per_token = dense_layout.attention_flops_per_token
    return mod


@pytest.fixture
def two_stack(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.layouts.two_stack",
                        _two_stack_module())
    monkeypatch.setitem(sys.modules, "bench.configs.two_stack", dense_decoder)
    return dict(TINY, name="tiny-two-stack", reference="two_stack",
                num_hidden_layers=5)


def test_reference_selects_rows_in_every_stack_as_the_program(two_stack):
    from repro.core import selection, units
    m, mix = two_stack, json.loads((DATA / "tiny-train.json").read_text())
    rows = ref_blockllm.selected_rows(m, mix)
    assert rows == {"s0/pos0": [0], "s0/pos1": [0], "s1/pos0": [0]}
    tab = layouts.table(m).values()
    index = units.UnitIndex(
        tuple(units.StackInfo(s.sid, s.si, f"pos{s.pos}", s.rows,
                              sum(l.size for l in tab if l.stack == s.sid))
              for s in layouts.of(m).stacks(m)),
        tuple(units.LeafInfo(l.path.split("/")[0], l.size)
              for l in tab if not l.stack),
        sum(layouts.numel(m, l) for l in tab))
    plan, q = selection.select(
        index, selection.NormTracker(), selection.VisitTracker(),
        selection.SelectorConfig(sparsity=mix["sparsity"], policy="static",
                                 static_k_frac=mix["k_frac"],
                                 selectable_leaves=()))
    assert {k: np.asarray(v).tolist() for k, v in plan.stack_idx.items()} == rows
    assert q == pytest.approx(ref_blockllm.keep_fraction(m, mix), rel=1e-12)


def test_two_stack_rehearsal_is_correct(two_stack):
    cell = tiny_cell("train", limits=TRAIN_LIMITS)
    cell.config = two_stack
    params = weights.program_params(SEED, two_stack)
    assert [sorted(s) for s in params["stages"]] == [["pos0", "pos1"], ["pos0"]]
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    assert harness.verdict(out.checks), out.checks


def test_path_keyed_norms_keep_shared_experts_apart():
    a, b = np.ones((2, 3)), 2 * np.ones((2, 3))
    tree = {"stacks": {"s0/pos0": {"moe": {"w_gate": a, "shared": {"w_gate": b}}}},
            "leaves": {"final_norm": {"scale": np.ones(4)}}}
    named = train_cell._named(tree)
    assert set(named) == {"stages/0/pos0/moe/w_gate",
                          "stages/0/pos0/moe/shared/w_gate", "final_norm/scale"}
    norms = train_cell._norms(tree)
    assert norms["stages/0/pos0/moe/shared/w_gate"] == pytest.approx(
        2 * norms["stages/0/pos0/moe/w_gate"])
    m = json.loads((DATA / "tiny-moe.json").read_text())
    tab = layouts.table(m)
    assert {"stages/0/pos0/moe/w_gate", "stages/0/pos0/moe/shared/w_gate"} <= set(tab)
    assert len({l.name for l in tab.values()}) == len(tab)
    assert len({l.id for l in tab.values()}) == len(tab)
    assert set(weights.by_path(weights.program_params(SEED, m))) == set(tab)
