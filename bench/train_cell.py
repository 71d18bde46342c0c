"""Driver of a ``train`` mix: a closed-loop BlockLLM fine-tune.

Set-up builds one trainer (``BlockLLMCore`` through ``trainers.make``,
held by a ``TrainerHandle``) on weights made from the seed, and one
``TokenPipeline`` as the training launcher builds it.  One call of
``runtime/train_loop.run``, with the pipeline as its feed, drives the
whole run: its first ``check_steps`` steps compile every program the
window runs and give the numbers the reference is compared with; the
step that ends them closes set-up and opens the window, and the loop
runs on until ``--seconds`` have passed.  After the window the trainer is
freed and the reference follows the first steps from the seed.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, gen, harness, peaks, ref_blockllm, weights


def make_trainer(cell):
    from repro import trainers
    from repro.core.blockllm import BlockLLMConfig
    from repro.core.selection import SelectorConfig
    from repro.optim.adam import Adam
    mix = cell.traffic
    cfg = cell.model_config()
    bcfg = BlockLLMConfig(
        selector=SelectorConfig(
            sparsity=mix["sparsity"], patience=mix["patience"],
            policy=mix["policy"], static_k_frac=mix["k_frac"],
            selectable_leaves=tuple(mix["selectable_leaves"])),
        quantile_sample=mix["quantile_sample"])
    adam = Adam(lr=mix["lr"], b1=mix["b1"], b2=mix["b2"], eps=mix["eps"])
    core = trainers.make(mix["optimizer"], cfg, adam=adam, bcfg=bcfg)
    params = weights.program_params(cell.seed, cell.config)
    return trainers.TrainerHandle(core, core.init(jax.random.PRNGKey(0),
                                                  params))


def make_pipeline(cell):
    """The program's token pipeline, built as the training launcher
    builds it, with the run's seed."""
    from repro.data.pipeline import DataConfig, TokenPipeline
    mix = cell.traffic
    return TokenPipeline(DataConfig(
        vocab_size=cell.config["vocab_size"], seq_len=mix["seq_len"],
        global_batch=mix["batch"], seed=cell.seed,
        structure=mix["structure"]))


def _program_path(path) -> str:
    """The program path of a leaf of the trainer's active tree
    (``stacks/s0/pos0/attn/wq`` -> ``stages/0/pos0/attn/wq``,
    ``leaves/final_norm/scale`` -> ``final_norm/scale``)."""
    keys = [str(getattr(k, "key", k)) for k in path]
    if keys[0] == "stacks":
        si, pos = keys[1].split("/")
        return "/".join(["stages", si[1:], pos] + keys[2:])
    return "/".join(keys[1:])


def _named(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_program_path(p): v for p, v in flat}


@jax.jit
def _norm_tree(tree):
    return {n: jnp.linalg.norm(v.astype(jnp.float32)) for n, v in tree.items()}


def _norms(tree, scale=1.0):
    return {n: float(v) * scale for n, v in _norm_tree(_named(tree)).items()}


class _Deadline(Exception):
    """Raised by ``Drive`` to end the loop's one call."""


class Drive:
    """The loop's ``on_step``.  Over the first ``check_steps`` steps it
    reads the loss of each, each leaf's gradient norm as the optimizer
    holds it after step 1 (first moment over 1 - b1) and each leaf's
    change after the last; the last of them closes set-up.  With a window
    it then opens the window and ends the loop once ``cell.seconds`` have
    passed; without one it ends the loop there.  ``keep_mask`` also keeps
    the step-1 update mask on the host (for calibration only)."""

    def __init__(self, cell, handle, *, t_start, stack=None,
                 keep_mask=False):
        self.cell, self.handle, self.t_start = cell, handle, t_start
        self.stack, self.keep_mask = stack, keep_mask
        self.n_check = cell.traffic["check_steps"]
        self.rows = {sid: [int(r) for r in idx]
                     for sid, idx in handle.state.meta["stack_idx"].items()}
        self.losses, self.gnorms, self.change, self.mask = [], {}, {}, None
        self.setup_s, self.w, self.steps = None, None, 0

    def __call__(self, step, metrics):
        if step >= self.n_check:
            self.steps += 1
            if time.perf_counter() - self.w.t0 >= self.cell.seconds:
                raise _Deadline
            return
        arrays = self.handle.state.arrays
        self.losses.append(float(metrics["loss"]))
        harness.stage(f"step {step + 1}", self.t_start)
        if step == 0:
            self.gnorms = _norms(arrays["opt"].mu,
                                 1.0 / (1.0 - self.cell.traffic["b1"]))
            if self.keep_mask:
                self.mask = {n: np.asarray(v)
                             for n, v in _named(arrays["masks"]).items()}
        if step < self.n_check - 1:
            return
        c = self.cell
        self.change = weights.change_norms(c.seed, c.config, self.rows,
                                           _named(arrays["sel"]))
        self.setup_s = time.perf_counter() - self.t_start
        if self.stack is None:
            raise _Deadline
        self.w = self.stack.enter_context(harness.window(self.cell))

    def numbers(self) -> dict:
        return {"losses": self.losses, "grad_norms": self.gnorms,
                "change_norms": self.change, "rows": self.rows}


def drive(cell, handle, pipe, *, t_start, window, tracer=None,
          keep_mask=False) -> Drive:
    """One call of the program's loop over the pipeline: the first steps,
    then, with ``window``, the measured window."""
    from repro.runtime.train_loop import TrainLoopConfig, run
    cfg = TrainLoopConfig(total_steps=2 ** 62, log_every=2 ** 62)
    with contextlib.ExitStack() as stack:
        d = Drive(cell, handle, t_start=t_start,
                  stack=stack if window else None, keep_mask=keep_mask)
        try:
            run(handle, pipe.batch, cfg, on_step=d, tracer=tracer)
        except _Deadline:
            pass
    d.handle = None
    return d


def reference_numbers(cell, rnd=None, half_batch=False, mask=None):
    """The reference's numbers over the same rows; ``half_batch`` plants
    the fault of a step that leaves half of each batch out; ``mask``
    drives it with a given step-1 update mask (calibration only)."""
    mix = cell.traffic
    batches = [gen.train_rows(cell.seed, i, mix["batch"], mix["seq_len"],
                              cell.config["vocab_size"], mix["structure"])
               for i in range(mix["check_steps"])]
    if half_batch:
        batches = [b[:len(b) // 2] for b in batches]
    return ref_blockllm.steps(cell.seed, cell.config, mix, batches,
                              harness.reference(cell), rnd, mask=mask)


def run(cell, *, t_start):
    from repro.obs import Tracer
    mix = cell.traffic
    handle = make_trainer(cell)
    harness.stage("trainer built", t_start)
    pipe = make_pipeline(cell)
    tracer = Tracer() if cell.trace else None
    d = drive(cell, handle, pipe, t_start=t_start, window=True, tracer=tracer)
    w = d.w
    peak = harness.peak_bytes()
    harness.log_memory("window closed")
    steps, setup_s, prog = d.steps, d.setup_s, d.numbers()
    tokens = steps * mix["batch"] * mix["seq_len"]
    del handle, pipe, d
    gc.collect()

    ref = reference_numbers(cell)
    g = ref_blockllm.gaps(prog, ref)
    checks = [(k, g[k], cell.limits.get(k, 0.0))
              for k in ("loss_gap", "grad_gap", "change_gap")]
    rate = tokens / w.seconds
    ctx = {"cell": cell, "window_s": w.seconds, "steps": steps,
           "tokens_per_s": rate, "trace": w.reduce(tracer),
           "spans": [] if tracer is None else [
               (e.name, e.t0_ns, e.t1_ns) for e in tracer.spans()
               if e.t0_ns >= w.mono0_ns],
           "flops_per_token": flops.train_flops_per_token(
               cell.config, mix["seq_len"]),
           "peaks": peaks.PEAKS.get(jax.devices()[0].device_kind)}
    return harness.Outcome(
        setup_s=setup_s,
        values={"train_tokens_per_s": rate, "peak_hbm_gib": peak / 2 ** 30},
        checks=checks, attempted=steps, failed=0, ctx=ctx,
        memory_peak_bytes=peak)
