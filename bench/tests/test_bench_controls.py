"""The comparison that decides ``correct`` fails where it should, at a
size a test run holds: the control (the reference one precision below
the configuration's, float8 operands for bfloat16) reads well above the
program, and a run with the timed path broken underneath comes out not
correct.  The limits here are this tiny size's own (the cells' limits
and the chip readings they come from are in ``bench/limits`` and
PERF.md)."""
import time

import pytest

from bench import harness, ref_blockllm, train_cell
from bench.configs import dense_decoder
from bench.tests.test_bench_harness import tiny_cell

TRAIN_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.05, "change_gap": 0.1}


def _run(kind, limits):
    cell = tiny_cell(kind, limits=limits)
    out = harness.driver(kind).run(cell, t_start=time.perf_counter())
    return harness.verdict(out.checks), dict((n, v) for n, v, _ in out.checks)


def test_train_control_reads_above_the_program():
    cell = tiny_cell("train")
    ref = train_cell.reference_numbers(cell)
    handle = train_cell.make_trainer(cell)
    d = train_cell.drive(cell, handle, train_cell.make_pipeline(cell),
                         t_start=time.perf_counter(), window=False)
    prog = ref_blockllm.gaps(d.numbers(), ref)
    ctrl = ref_blockllm.gaps(
        train_cell.reference_numbers(cell, dense_decoder.fp8), ref)
    assert all(prog[k] <= TRAIN_LIMITS[k] for k in prog), prog
    assert any(ctrl[k] > TRAIN_LIMITS[k] for k in ctrl), ctrl
    assert max(ctrl[k] / max(prog[k], 1e-12) for k in ctrl) >= 3.0


def test_train_sound_run_is_correct():
    ok, got = _run("train", TRAIN_LIMITS)
    assert ok, got


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.optim import adam
    real = adam.Adam.update
    monkeypatch.setattr(adam.Adam, "update", lambda self, g, s, p, **kw:
                        (p, real(self, g, s, p, **kw)[1]))
    ok, got = _run("train", TRAIN_LIMITS)
    assert not ok and got["change_gap"] == pytest.approx(1.0)


def test_train_half_batch_left_out_is_not_correct(monkeypatch):
    from repro.models import model
    real = model.loss_fn

    def half(params, cfg, batch, **kw):
        t = batch["tokens"]
        return real(params, cfg, {"tokens": t[: t.shape[0] // 2]}, **kw)
    monkeypatch.setattr(model, "loss_fn", half)
    ok, got = _run("train", TRAIN_LIMITS)
    assert not ok, got
