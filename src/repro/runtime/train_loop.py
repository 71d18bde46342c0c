"""Fault-tolerant training driver, generic over the TrainerCore protocol.

Wires together: data pipeline (step-indexed, restart-safe), any trainer
speaking the ``repro.trainers`` protocol (a ``TrainerHandle``, or
anything else carrying a ``(core, state)`` pair), atomic checkpointing
with auto-resume, straggler monitoring, and crash recovery (a
simulated-failure test rides on this loop).

There is exactly ONE checkpoint/restore path for every trainer: the
state's **array pytree** (``TrainState.arrays`` — params, moments, active
rows, masks, factors…) goes in the npz payload; the state's **host
meta** (``TrainState.meta`` — for BlockLLM the norm dictionary, visit
counts, plan indices, loss history) rides JSON-serialized in the
checkpoint manifest.  No trainer-specific serializers, no isinstance
branches: what a trainer needs to resume is whatever its core declared
in its ``state_spec``.

Construct trainers with ``trainers.handle(name, cfg, params, …)`` —
the PR-2 legacy classes (``BlockLLMTrainer`` & friends) were removed in
the registry redesign and now raise ImportError naming their registry
replacement.
"""
from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import jax

from repro.checkpoint import checkpointer as ckpt_lib
from repro.obs import StepEmitter, maybe_span
from repro.runtime.straggler import StragglerConfig, StragglerMonitor
from repro.trainers.api import TrainState, jsonable


@dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    log_every: int = 10
    # TraceKit: dump the metrics registry as text every N steps (0: off;
    # needs a registry passed to run(..., metrics=...))
    metrics_every: int = 0
    straggler: StragglerConfig = dataclasses.field(
        default_factory=lambda: StragglerConfig(action="none"))
    # BlockDelta export: at every checkpoint (and at run end) diff the
    # trainer's merged params against the pre-finetune base and publish
    # the row-sparse delta to an adapter registry (repro.adapters).
    adapter_dir: Optional[str] = None
    adapter_id: str = "adapter"
    # int8-quantize exported delta payloads (rows -> int8 codec blocks +
    # f32 scales; ~4x smaller registry entries, dequantized on apply)
    quantize_deltas: bool = False


def _protocol_state(trainer) -> Optional[TrainState]:
    """The trainer's functional state, if it speaks the protocol."""
    st = getattr(trainer, "state", None)
    return st if isinstance(st, TrainState) else None


def _save_ckpt(trainer, cfg: TrainLoopConfig, step: int):
    st = _protocol_state(trainer)
    if st is None:  # pre-protocol object: params(+opt) only, no host meta
        tree = {"params": trainer.params,
                "opt": getattr(trainer, "opt_state",
                               getattr(trainer, "state", None))}
        ckpt_lib.save(cfg.ckpt_dir, step, tree, meta={},
                      keep=cfg.keep_ckpts)
        return
    meta = {"trainer": getattr(trainer.core, "name", "?"),
            "host": jsonable(st.meta)}
    ckpt_lib.save(cfg.ckpt_dir, step, st.arrays, meta=meta,
                  keep=cfg.keep_ckpts)


def _restore_ckpt(trainer, cfg: TrainLoopConfig, step: int):
    st = _protocol_state(trainer)
    if st is None:
        like = {"params": trainer.params,
                "opt": getattr(trainer, "opt_state",
                               getattr(trainer, "state", None))}
        tree, _ = ckpt_lib.restore(cfg.ckpt_dir, step, like)
        trainer.params = tree["params"]
        if tree.get("opt") is not None:
            if hasattr(trainer, "opt_state"):
                trainer.opt_state = tree["opt"]
            else:
                trainer.state = tree["opt"]
        if hasattr(trainer, "step"):
            trainer.step = step
        return
    # validate the manifest BEFORE loading arrays: a wrong-trainer or
    # pre-protocol checkpoint should fail with a clear message, not a
    # leaf-shape assert deep in restore
    meta = ckpt_lib.read_meta(cfg.ckpt_dir, step)
    if "host" not in meta:
        raise ValueError(
            f"checkpoint step {step} in {cfg.ckpt_dir} has no 'host' "
            "meta — it predates the TrainerCore checkpoint format and "
            "cannot be resumed by this loop")
    saved = meta.get("trainer")
    name = getattr(trainer.core, "name", "?")
    if saved is not None and saved != name:
        raise ValueError(
            f"checkpoint step {step} was written by trainer "
            f"{saved!r} but the active trainer is {name!r}")
    arrays, _ = ckpt_lib.restore(cfg.ckpt_dir, step, st.arrays)
    trainer.state = TrainState(arrays, dict(meta["host"]))


def run(trainer, batch_fn: Callable[[int], dict], cfg: TrainLoopConfig,
        *, on_step: Optional[Callable[[int, Dict], None]] = None,
        crash_at: Optional[int] = None, tracer=None, metrics=None,
        emitter: Optional[StepEmitter] = None) -> Dict:
    """Run (or resume) training.  ``batch_fn(step) -> batch``.

    ``crash_at``: raise at that step AFTER state mutation — used by the
    fault-tolerance test to prove checkpoint/restart recovers exactly.

    TraceKit: pass ``tracer``/``metrics`` (``repro.obs``) and every step
    lands as spans on per-stage lanes (``data``, ``step``, ``ckpt``,
    ``export``; a BlockLLM core adds its step's phases on ``step``; each
    compile lands on ``compile``) plus structured per-step metrics via a
    ``StepEmitter`` (stdout stays the ``step N: loss=…`` line at
    ``log_every``).  An explicit ``emitter`` overrides the
    default-constructed one.
    """
    start_step = 0
    if cfg.ckpt_dir:
        latest = ckpt_lib.latest_step(cfg.ckpt_dir)
        if latest is not None:
            _restore_ckpt(trainer, cfg, latest)
            start_step = latest

    emit = emitter if emitter is not None else StepEmitter(
        log_every=cfg.log_every, tracer=tracer, metrics=metrics,
        metrics_every=cfg.metrics_every)
    export = _AdapterExporter.maybe(trainer, cfg, start_step, emitter=emit)
    mon = StragglerMonitor(cfg.straggler)
    history = []
    with _traced(trainer, tracer):
        for step in range(start_step, cfg.total_steps):
            mon.step_begin()
            with maybe_span(tracer, "data", lane="data", step=step + 1):
                batch = batch_fn(step)
            with maybe_span(tracer, "train_step", lane="step",
                            step=step + 1):
                metrics_d = trainer.train_step(batch)
            action = mon.step_end()
            metrics_d["straggler_action"] = action
            history.append(metrics_d["loss"])
            if on_step:
                on_step(step, metrics_d)
            emit.on_step(step + 1, metrics_d)
            if cfg.ckpt_dir and (step + 1) % cfg.ckpt_every == 0:
                with maybe_span(tracer, "checkpoint", lane="ckpt",
                                step=step + 1):
                    _save_ckpt(trainer, cfg, step + 1)
                if export:
                    with maybe_span(tracer, "adapter_export", lane="export",
                                    step=step + 1):
                        export.emit(trainer, step + 1)
            if crash_at is not None and step + 1 == crash_at:
                raise RuntimeError(
                    f"simulated node failure at step {step + 1}")
    if export:
        export.emit(trainer, cfg.total_steps)
    return {"losses": history, "final_step": cfg.total_steps}


# JAX's duration events of a backend compile (a persistent-cache hit
# included) and of a persistent-cache retrieval, by the ``event`` they
# take in the ``compile`` spans
_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}


@contextlib.contextmanager
def _traced(trainer, tracer):
    """While the loop runs with a tracer: the trainer's core (if it has a
    ``tracer`` attribute) splits its step into spans on it, and every
    compile lands as a retroactive ``compile`` span on lane ``compile``,
    ending when JAX reports it."""
    if tracer is None:
        yield
        return

    def on_duration(event, secs, **kw):
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            t1 = tracer.now()
            tracer.add_span("compile", t1 - round(secs * 1e9), t1,
                            lane="compile", event=kind, **kw)

    core = getattr(trainer, "core", None)
    if not hasattr(core, "tracer"):
        core = None
    if core is not None:
        core.tracer = tracer
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        if core is not None:
            core.tracer = None


def _merged(trainer):
    return (trainer.merged_params()
            if hasattr(trainer, "merged_params") else trainer.params)


class _AdapterExporter:
    """Publishes the trainer's row-sparse delta vs. the pre-finetune base
    to an adapter registry at checkpoint boundaries (export hook).

    The pre-finetune base snapshot is persisted (checkpointer payload
    format) under ``<adapter_dir>/_base/<adapter_id>`` on the first run,
    and reloaded from there on resume — so a crash/restart keeps
    exporting correct deltas instead of bailing out.
    """

    def __init__(self, registry, base, adapter_id: str,
                 quantize: bool = False):
        self.registry = registry
        self.base = base
        self.adapter_id = adapter_id
        self.quantize = quantize
        self.last_step = -1

    @staticmethod
    def _snapshot_dir(cfg: "TrainLoopConfig") -> Path:
        # under "_base/": never listed by AdapterRegistry.list_adapters
        # (the dir itself carries no DONE marker)
        return Path(cfg.adapter_dir) / "_base" / cfg.adapter_id

    @staticmethod
    def maybe(trainer, cfg: "TrainLoopConfig", start_step: int,
              emitter: Optional[StepEmitter] = None):
        if not cfg.adapter_dir:
            return None
        from repro.adapters import AdapterRegistry, copy_tree
        snap = _AdapterExporter._snapshot_dir(cfg)
        if start_step == 0:
            # deep copy: merged trees can alias buffers the jitted train
            # step donates (e.g. BlockLLM active leaves) — the snapshot
            # must outlive the whole run
            base = copy_tree(_merged(trainer))
            ckpt_lib.save(snap, 0, base,
                          meta={"kind": "adapter-base-snapshot",
                                "adapter_id": cfg.adapter_id}, keep=1)
        else:
            if ckpt_lib.latest_step(snap) is None:
                msg = ("adapter export skipped: resume without a base "
                       "snapshot")
                if emitter is not None:
                    emitter.warn(msg, start_step=start_step)
                else:
                    print(msg, flush=True)
                return None
            base, _ = ckpt_lib.restore(snap, 0, _merged(trainer))
        return _AdapterExporter(AdapterRegistry(cfg.adapter_dir), base,
                                cfg.adapter_id,
                                quantize=cfg.quantize_deltas)

    def emit(self, trainer, step: int):
        if step == self.last_step:
            return  # final step coincides with a checkpoint boundary
        from repro.adapters import delta_from_trainer, quantize_delta
        d = delta_from_trainer(trainer, self.base,
                               meta={"step": step,
                                     "adapter_id": self.adapter_id})
        if self.quantize:
            d = quantize_delta(d)
        self.registry.put(self.adapter_id, d)
        self.last_step = step
