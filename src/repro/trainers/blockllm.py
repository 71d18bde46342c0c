"""BlockLLM as a ``TrainerCore`` (paper Algorithm 1 over explicit state).

The device math (the jitted masked-Adam step over the active subset) is
``core.blockllm.build_step_fn``, unchanged.  This module is the
*orchestration* — selection, probe rotation, the loss-patience trigger —
rewritten against the functional protocol: all mutable training state
lives in a ``TrainState`` and every host quantity the next step depends
on (norm dictionary, visit counts, plan indices, loss history, the
mask-refresh flag) is JSON host meta, so the generic checkpoint path
resumes BlockLLM bit-exactly with zero trainer-specific code.

State layout (see ``BlockLLMCore.state_spec``):

- arrays: ``params`` (full frozen tree), ``sel`` (active rows/leaves),
  ``probe`` (rotating probe rows), ``opt`` (Adam moments over ``sel``),
  ``masks`` (within-layer update masks, or None when disabled)
- meta: norm dict + ages, visit counts, plan indices, q, loss history,
  step/reselection counters, the pending-mask-refresh flag
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import selection as sel_lib
from repro.core import units as units_lib
from repro.core.selection import NormTracker, SelectorConfig, VisitTracker
from repro.core.units import Plan, PlanStructure
from repro.models import model as model_lib
from repro.obs import maybe_span
from repro.optim.adam import Adam, AdamState
from repro.trainers.api import (HISTORY_CAP, Lowerable, StateSpec,
                                TrainerCore, TrainState, nbytes)
from repro.trainers.registry import register

Pytree = Any


def _ones_masks_like(sel_tree):
    return jax.tree.map(lambda a: jnp.ones(a.shape, jnp.bool_), sel_tree)


def _idx_lists(idx_dict) -> Dict[str, list]:
    return {k: np.asarray(v).tolist() for k, v in idx_dict.items()}


def _carry_moments(new_plan: Plan, old_plan: Plan, new_state: AdamState,
                   old_state: AdamState) -> AdamState:
    """Carry BOTH Adam moments (mu and nu) for rows selected in
    consecutive rounds.  (Carrying mu with fresh nu — the old behavior —
    made the moments inconsistent: the first post-carry update divided a
    warm first moment by a cold second moment.)"""
    new_mu = jax.tree.map(jnp.copy, new_state.mu)
    new_nu = jax.tree.map(jnp.copy, new_state.nu)
    for sid, new_idx in new_plan.stack_idx.items():
        old_idx = np.asarray(old_plan.stack_idx.get(
            sid, jnp.zeros((0,), jnp.int32)))
        new_np = np.asarray(new_idx)
        common = [(int(np.where(old_idx == g)[0][0]), j)
                  for j, g in enumerate(new_np) if g in old_idx]
        if not common:
            continue
        src = np.asarray([c[0] for c in common])
        dst = np.asarray([c[1] for c in common])

        def carry(new, old):
            return new.at[dst].set(old[src])

        new_mu["stacks"][sid] = jax.tree.map(
            carry, new_mu["stacks"][sid], old_state.mu["stacks"][sid])
        new_nu["stacks"][sid] = jax.tree.map(
            carry, new_nu["stacks"][sid], old_state.nu["stacks"][sid])
    return AdamState(old_state.count, new_mu, new_nu)


class BlockLLMCore(TrainerCore):
    name = "blockllm"
    state_spec = StateSpec(
        arrays=("params", "sel", "probe", "opt", "masks"),
        meta=("step", "loss_history", "norms", "norm_age", "visit_counts",
              "visit_rounds", "reselections", "q", "stack_idx", "probe_idx",
              "active_leaves", "needs_mask_refresh", "sel_churn",
              "last_reselect_step"),
        donate=("sel", "opt", "masks"),
        roles=(("params", "params"), ("sel", "active"), ("probe", "active"),
               ("opt", "opt"), ("masks", "active")),
    )

    def __init__(self, cfg, *, bcfg=None, adam: Optional[Adam] = None,
                 loss_fn=None, attn_impl: str = "full",
                 quantize_state: bool = False):
        from repro.core.blockllm import BlockLLMConfig
        from repro.optim.q8adam import Q8Adam
        self.cfg = cfg
        self.bcfg = bcfg or BlockLLMConfig()
        self.adam = adam or Adam(lr=1e-3)
        # Q8State: persistent Adam moments stored int8 + block scales
        # (~25% of fp32 moment bytes); the int8/scale leaves live in the
        # ordinary ``opt`` array group, so checkpointing is unchanged
        if quantize_state and not isinstance(self.adam, Q8Adam):
            self.adam = Q8Adam(self.adam)
        self.quantize_state = quantize_state
        self._loss_fn = loss_fn or (
            lambda p, batch, overlay=None: model_lib.loss_fn(
                p, cfg, batch, attn_impl=attn_impl, overlay=overlay))
        self._step_fns: Dict = {}
        self._index = None
        self.recompiles = 0
        # a repro.obs Tracer splits ``step`` into spans; the train loop
        # sets it for the run it traces
        self.tracer = None

    # ------------------------------------------------------------------ #
    # state plumbing
    # ------------------------------------------------------------------ #

    def index_for(self, params) -> units_lib.UnitIndex:
        if self._index is None:
            self._index = units_lib.build_unit_index(self.cfg, params)
        return self._index

    def plan_of(self, state: TrainState) -> Plan:
        """Rebuild the selection Plan from host meta (the structure is a
        pure function of the stored index lists + active leaves)."""
        index = self.index_for(state.arrays["params"])
        sidx, pidx = state.meta["stack_idx"], state.meta["probe_idx"]
        structure = PlanStructure(
            k_per_stack=tuple((s.sid, len(sidx.get(s.sid, ())))
                              for s in index.stacks),
            probe_per_stack=tuple((s.sid, len(pidx.get(s.sid, ())))
                                  for s in index.stacks),
            active_leaves=tuple(sorted(state.meta["active_leaves"])),
        )
        return Plan(
            structure=structure,
            stack_idx={k: jnp.asarray(v, jnp.int32)
                       for k, v in sidx.items() if len(v)},
            probe_idx={k: jnp.asarray(v, jnp.int32)
                       for k, v in pidx.items() if len(v)},
        )

    def _use_masks(self) -> bool:
        return (self.bcfg.selector.mask_updates
                and self.bcfg.mask_refresh != "never")

    def _trackers(self, meta, *, copy: bool = True
                  ) -> Tuple[NormTracker, VisitTracker]:
        """Materialize host trackers from meta.  ``copy=False`` binds the
        trackers to the live meta dicts (the deprecation shims use this
        so legacy in-place mutation — e.g. seeding the norm dictionary —
        still reaches the state)."""
        norms, visits = NormTracker(), VisitTracker()
        if copy:
            norms.norms = {k: float(v) for k, v in meta["norms"].items()}
            norms.age = {k: int(v) for k, v in meta["norm_age"].items()}
            visits.counts = {k: int(v)
                             for k, v in meta["visit_counts"].items()}
        else:
            norms.norms = meta["norms"]
            norms.age = meta["norm_age"]
            visits.counts = meta["visit_counts"]
        visits.total_rounds = int(meta["visit_rounds"])
        return norms, visits

    def _pack(self, params, active, opt, masks, plan: Plan, q, *,
              norms: NormTracker, visits: VisitTracker, step: int,
              loss_history, reselections: int, needs_mask_refresh: bool,
              sel_churn: float = 1.0,
              last_reselect_step: int = 0) -> TrainState:
        arrays = {"params": params, "sel": active["sel"],
                  "probe": active["probe"], "opt": opt, "masks": masks}
        # bounded history: the patience trigger only reads its window
        cap = max(HISTORY_CAP, self.bcfg.selector.patience + 1)
        meta = {
            "step": int(step),
            "loss_history": list(loss_history)[-cap:],
            "norms": norms.norms, "norm_age": norms.age,
            "visit_counts": visits.counts,
            "visit_rounds": visits.total_rounds,
            "reselections": int(reselections), "q": float(q),
            "stack_idx": _idx_lists(plan.stack_idx),
            "probe_idx": _idx_lists(plan.probe_idx),
            "active_leaves": list(plan.structure.active_leaves),
            "needs_mask_refresh": bool(needs_mask_refresh),
            # selection telemetry (TraceKit): churn of the most recent
            # reselection + when it happened, so resumed runs keep an
            # accurate reselection cadence
            "sel_churn": float(sel_churn),
            "last_reselect_step": int(last_reselect_step),
        }
        return TrainState(arrays, meta)

    # ------------------------------------------------------------------ #
    # protocol: init / step / reselect
    # ------------------------------------------------------------------ #

    def init(self, rng, params: Optional[Pytree] = None) -> TrainState:
        if params is None:
            params = model_lib.init_params(
                rng if rng is not None else jax.random.PRNGKey(0), self.cfg)
        index = self.index_for(params)
        norms, visits = NormTracker(), VisitTracker()
        plan, q = sel_lib.select(index, norms, visits, self.bcfg.selector,
                                 cursor=0)
        visits.record(plan.selected_labels())
        active = units_lib.extract_active(params, index, plan)
        opt = self.adam.init(active["sel"])
        use_masks = self._use_masks()
        masks = _ones_masks_like(active["sel"]) if use_masks else None
        return self._pack(params, active, opt, masks, plan, q, norms=norms,
                          visits=visits, step=0, loss_history=[],
                          reselections=1, needs_mask_refresh=use_masks)

    def init_abstract(self, params_abstract: Pytree) -> TrainState:
        index = self.index_for(params_abstract)
        norms, visits = NormTracker(), VisitTracker()
        plan, q = sel_lib.select(index, norms, visits, self.bcfg.selector,
                                 cursor=0)
        visits.record(plan.selected_labels())
        active = jax.eval_shape(
            lambda p: units_lib.extract_active(p, index, plan),
            params_abstract)
        opt = jax.eval_shape(self.adam.init, active["sel"])
        use_masks = self._use_masks()
        masks = (jax.eval_shape(_ones_masks_like, active["sel"])
                 if use_masks else None)
        return self._pack(params_abstract, active, opt, masks, plan, q,
                          norms=norms, visits=visits, step=0,
                          loss_history=[], reselections=1,
                          needs_mask_refresh=use_masks)

    def _get_step_fn(self, structure: PlanStructure, refresh: bool,
                     with_masks: bool):
        from repro.core.blockllm import build_step_fn
        key = (structure, refresh, with_masks)
        if key in self._step_fns:
            return self._step_fns[key]
        self.recompiles += 1
        index = self._index
        step = build_step_fn(self.cfg, index, self.adam, self.bcfg,
                             structure, refresh=refresh,
                             with_masks=with_masks, loss_fn=self._loss_fn)
        fn = jax.jit(step, donate_argnums=(1, 5, 6))
        self._step_fns[key] = fn
        return fn

    def step(self, state: TrainState, batch):
        tr = self.tracer
        arrays, meta = state.arrays, state.meta
        with maybe_span(tr, "plan", lane="step"):
            params = arrays["params"]
            self.index_for(params)
            plan = self.plan_of(state)
            norms, visits = self._trackers(meta)
            refresh = bool(meta["needs_mask_refresh"])
            with_masks = arrays["masks"] is not None

        with maybe_span(tr, "dispatch", lane="step"):
            fn = self._get_step_fn(plan.structure, refresh, with_masks)
            sel, opt, masks, loss, dev_metrics, norm_out = fn(
                params, arrays["sel"], arrays["probe"], plan.stack_idx,
                plan.probe_idx, arrays["opt"],
                arrays["masks"] if with_masks
                else _ones_masks_like(arrays["sel"]),
                batch, jnp.asarray(meta["q"], jnp.float32))
        if tr is not None:
            # the host's first read below waits for the device anyway;
            # this wait only gives it a span of its own
            with tr.span("device_wait", lane="step"):
                jax.block_until_ready((loss, norm_out))

        with maybe_span(tr, "ingest_norms", lane="step"):
            # fresh probe dict: probe rotation mutates it, and the input
            # state's arrays must stay intact (probe is not donated)
            active = {"sel": sel, "probe": dict(arrays["probe"])}
            if not with_masks:
                masks = None

            step_no = int(meta["step"])
            self._ingest_norms(norm_out, plan, params, active, norms,
                               step_no)
            loss_f = float(loss)
            loss_history = list(meta["loss_history"]) + [loss_f]
            step_no += 1

            new_state = self._pack(
                params, active, opt, masks, plan, meta["q"], norms=norms,
                visits=visits, step=step_no, loss_history=loss_history,
                reselections=int(meta["reselections"]),
                needs_mask_refresh=False,
                sel_churn=float(meta["sel_churn"]),
                last_reselect_step=int(meta["last_reselect_step"]))

        every = self.bcfg.selector.reselect_every
        if (every and step_no % every == 0) or (
                not every and sel_lib.should_reselect(
                    loss_history, self.bcfg.selector.patience)):
            with maybe_span(tr, "reselect", lane="step"):
                new_state = self.reselect(new_state)

        with maybe_span(tr, "step_metrics", lane="step"):
            nm = new_state.meta
            metrics = {"loss": loss_f, "step": step_no,
                       "reselections": int(nm["reselections"]),
                       # selection telemetry (TraceKit): fraction
                       # selected, churn of the latest reselection,
                       # gradient energy share of the top (1-s) units,
                       # cadence
                       "sel_q": float(nm["q"]),
                       "sel_churn": float(nm["sel_churn"]),
                       "sel_grad_concentration": sel_lib.norm_concentration(
                           norms.norms, 1.0 - self.bcfg.selector.sparsity),
                       "sel_steps_since_reselect": step_no - int(
                           nm["last_reselect_step"])}
            metrics.update({k: float(v) for k, v in dev_metrics.items()})
        return new_state, metrics

    def reselect(self, state: TrainState) -> TrainState:
        """Fold trained rows back, re-run selection (Algorithm 2), reset
        (or carry) the optimizer — returns the post-selection state."""
        index = self.index_for(state.arrays["params"])
        old_plan = self.plan_of(state)
        norms, visits = self._trackers(state.meta)
        params = units_lib.write_back(
            state.arrays["params"], index, old_plan,
            {"sel": state.arrays["sel"], "probe": state.arrays["probe"]})
        plan, q = sel_lib.select(index, norms, visits, self.bcfg.selector,
                                 cursor=int(state.meta["reselections"]))
        visits.record(plan.selected_labels())
        active = units_lib.extract_active(params, index, plan)
        carry = (self.bcfg.carry_surviving
                 and old_plan.structure == plan.structure)
        if not carry:
            opt = self.adam.init(active["sel"])
        else:
            from repro.optim.q8adam import (Q8Adam, from_adam_state,
                                            to_adam_state)
            if isinstance(self.adam, Q8Adam):
                # carry in fp32 view: codec blocks of the flattened
                # moment tree do not align with selection rows, so
                # dequantize the old state, row-carry into a fresh fp32
                # zero state (base.init — quantizing zeros only to
                # dequantize them back would be wasted codec passes),
                # requantize once
                opt = from_adam_state(_carry_moments(
                    plan, old_plan, self.adam.base.init(active["sel"]),
                    to_adam_state(state.arrays["opt"],
                                  state.arrays["sel"])))
            else:
                opt = _carry_moments(plan, old_plan,
                                     self.adam.init(active["sel"]),
                                     state.arrays["opt"])
        use_masks = self._use_masks()
        # masks are always materialized (all-ones until the refresh step)
        # so the train-state pytree structure is checkpoint-stable
        masks = _ones_masks_like(active["sel"]) if use_masks else None
        return self._pack(
            params, active, opt, masks, plan, q, norms=norms, visits=visits,
            step=int(state.meta["step"]), loss_history=[],
            reselections=int(state.meta["reselections"]) + 1,
            needs_mask_refresh=use_masks,
            sel_churn=sel_lib.plan_churn(old_plan, plan),
            last_reselect_step=int(state.meta["step"]))

    def _ingest_norms(self, norm_out, plan: Plan, params, active,
                      norms: NormTracker, step: int):
        """Fold per-unit gradient norms into the host dictionary and
        advance the rotating probes (stale-first order next round).
        Mutates ``plan.probe_idx`` and ``active['probe']`` in place."""
        index = self._index
        updates = {}
        for sid, sq in norm_out["stacks"].items():
            idx = np.asarray(plan.stack_idx[sid])
            vals = np.sqrt(np.asarray(sq, np.float64))
            for g, v in zip(idx, vals):
                updates[f"{sid}/g{int(g)}"] = v
        for name, sq in norm_out["leaves"].items():
            updates[name] = float(np.sqrt(float(sq)))
        for sid, sq in norm_out["probe"].items():
            pidx = np.asarray(plan.probe_idx[sid])
            vals = np.sqrt(np.asarray(sq, np.float64))
            for g, v in zip(pidx, vals):
                updates[f"{sid}/g{int(g)}"] = v
        norms.update(updates, step)
        for sid in list(plan.probe_idx):
            info = index.stack(sid)
            excl = set(np.asarray(plan.stack_idx.get(
                sid, np.zeros(0, np.int32))).tolist())
            cands = [g for g in range(info.n_rows) if g not in excl]
            if not cands:
                continue
            cands.sort(key=lambda g: norms.age.get(f"{sid}/g{g}", -1))
            take = cands[:len(np.asarray(plan.probe_idx[sid]))]
            plan.probe_idx[sid] = jnp.asarray(take, np.int32)
            active["probe"][sid] = jax.tree.map(
                lambda a: a[plan.probe_idx[sid]],
                params["stages"][info.si][info.pos])

    # ------------------------------------------------------------------ #
    # protocol: reporting / export / distributed lowering
    # ------------------------------------------------------------------ #

    def merged_params(self, state: TrainState) -> Pytree:
        index = self.index_for(state.arrays["params"])
        return units_lib.write_back(
            state.arrays["params"], index, self.plan_of(state),
            {"sel": state.arrays["sel"], "probe": state.arrays["probe"]})

    def memory_report(self, state: TrainState) -> Dict[str, int]:
        report = {
            "params_bytes": nbytes(state.arrays["params"]),
            "grads_bytes": nbytes(state.arrays["sel"]),
            "opt_state_bytes": self.adam.state_bytes(state.arrays["opt"]),
            "mask_bytes": (nbytes(state.arrays["masks"])
                           if state.arrays["masks"] is not None else 0),
            "probe_bytes": nbytes(state.arrays["probe"]),
        }
        report["total_train_state"] = sum(
            v for k, v in report.items() if k != "params_bytes")
        return report

    def lowerable(self, state: TrainState, batch) -> Lowerable:
        """The SAME raw step the single-host path jits, in the positional
        layout the distributed builder pjits (launch/steps.py)."""
        from repro.core.blockllm import build_step_fn
        index = self.index_for(state.arrays["params"])
        plan = self.plan_of(state)
        with_masks = state.arrays["masks"] is not None
        raw = build_step_fn(self.cfg, index, self.adam, self.bcfg,
                            plan.structure, refresh=False,
                            with_masks=with_masks, loss_fn=self._loss_fn)
        args = (state.arrays["params"], state.arrays["sel"],
                state.arrays["probe"], plan.stack_idx, plan.probe_idx,
                state.arrays["opt"],
                state.arrays["masks"] if with_masks else None,
                batch, jnp.asarray(float(state.meta["q"]), jnp.float32))
        roles = ("params", "active", "active", "index", "index", "opt",
                 "active", "batch", "scalar")
        sizes = index.unit_sizes()
        tot = sum(sizes[u] for u in plan.selected_labels() if u in sizes)
        return Lowerable(
            fn=raw, args=args, roles=roles, donate=(1, 5, 6),
            meta={"plan": plan, "q": float(state.meta["q"]),
                  "active_fraction": tot / index.total_params})


@register("blockllm")
def make_blockllm(cfg, *, adam=None, bcfg=None, loss_fn=None,
                  attn_impl="full", sparsity=0.95, patience=100,
                  policy="static", k_frac=0.25, probe_rows=1,
                  quantize_state=False, **_) -> BlockLLMCore:
    if bcfg is None:
        from repro.core.blockllm import BlockLLMConfig
        # quantized state on TPU defaults to the fused dequant->Adam->
        # requant kernel: the host codec path materializes fp32 moment
        # temporaries inside the step, so only the fused kernel delivers
        # the step-time HBM win on real hardware (an explicit bcfg
        # always takes precedence)
        fused = "off"
        if quantize_state:
            from repro.kernels.ops import pallas_available
            fused = "pallas" if pallas_available() else "off"
        bcfg = BlockLLMConfig(selector=SelectorConfig(
            sparsity=sparsity, patience=patience, policy=policy,
            static_k_frac=k_frac, probe_rows_per_stack=probe_rows),
            fused_update=fused)
    return BlockLLMCore(cfg, bcfg=bcfg, adam=adam, loss_fn=loss_fn,
                        attn_impl=attn_impl, quantize_state=quantize_state)


@register("blockllm+q8")
def make_blockllm_q8(cfg, **kw) -> BlockLLMCore:
    """BlockLLM with Q8State moments (int8 + block scales)."""
    kw["quantize_state"] = True
    return make_blockllm(cfg, **kw)
