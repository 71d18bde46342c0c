"""A configuration whose reference is not the dense decoder runs on new
files alone: ``tiny-moe.json`` (2 layers, d 64, 8 routed experts, top-2,
one shared expert) through ``bench/layouts/moe_decoder.py`` and
``bench/configs/moe_decoder.py``, with the three checks
``test_bench_controls.py`` makes for ``tiny.json``: a sound run is
correct, the float8 control reads well above the program, a step that
leaves its state unchanged is not correct."""
import time

import pytest

from bench import harness, ref_blockllm, train_cell
from bench.configs import moe_decoder
from bench.tests.test_bench_controls import TRAIN_LIMITS
from bench.tests.test_bench_harness import tiny_cell


def _cell(limits=None):
    return tiny_cell("train", limits=limits, config="tiny-moe")


def test_moe_config_drops_no_token():
    from repro.models import moe
    cell = _cell()
    cfg = cell.model_config()
    assert cfg.family == "moe" and cfg.num_experts == 8
    assert cfg.num_experts_per_tok == 2 and cfg.shared_expert_d_ff == 32
    tokens = cell.traffic["batch"] * cell.traffic["seq_len"]
    # one chunk of every token a step; an expert takes each token at most once
    assert tokens <= 8192 and moe._capacity(tokens, cfg) >= tokens


def test_moe_control_reads_above_the_program():
    cell = _cell()
    ref = train_cell.reference_numbers(cell)
    handle = train_cell.make_trainer(cell)
    d = train_cell.drive(cell, handle, train_cell.make_pipeline(cell),
                         t_start=time.perf_counter(), window=False)
    prog = ref_blockllm.gaps(d.numbers(), ref)
    ctrl = ref_blockllm.gaps(
        train_cell.reference_numbers(cell, moe_decoder.fp8), ref)
    assert all(prog[k] <= TRAIN_LIMITS[k] for k in prog), prog
    assert any(ctrl[k] > TRAIN_LIMITS[k] for k in ctrl), ctrl
    assert max(ctrl[k] / max(prog[k], 1e-12) for k in ctrl) >= 3.0


def test_moe_sound_run_is_correct():
    cell = _cell(TRAIN_LIMITS)
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    assert harness.verdict(out.checks), out.checks


def test_moe_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.optim import adam
    real = adam.Adam.update
    monkeypatch.setattr(adam.Adam, "update", lambda self, g, s, p, **kw:
                        (p, real(self, g, s, p, **kw)[1]))
    cell = _cell(TRAIN_LIMITS)
    out = harness.driver("train").run(cell, t_start=time.perf_counter())
    got = {n: v for n, v, _ in out.checks}
    assert not harness.verdict(out.checks) and got["change_gap"] == pytest.approx(1.0)
