"""BlockLLM device math (paper Algorithm 1): config + the raw step fn.

``build_step_fn`` is the jitted masked-Adam step over the *active*
parameter subset — the single source of truth compiled by BOTH the
single-host path and the distributed launcher.  The orchestration
(selection, probe rotation, loss-patience trigger) lives in
``repro.trainers.blockllm.BlockLLMCore`` on the functional
init/step/state protocol; imperative drivers wrap it with
``trainers.handle("blockllm", cfg, params, ...)``.

Memory model (the paper's contribution): gradients, Adam moments and masks
exist ONLY for the active subset.  The jitted step differentiates w.r.t.
the gathered active rows; frozen parameters sit behind stop_gradient so XLA
prunes their whole backward slice.

Compilation model: the *structure* of a plan (per-stack K, active leaf
set, probe counts) is static; index *values* are traced.  With the
``static`` selection policy the structure never changes => zero recompiles
across re-selections (TPU-native mode).  The ``greedy`` paper-faithful
policy may change K per stack => recompile, amortized over ``patience``
steps (the paper's PyTorch reference rebuilds the optimizer at the same
points).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import units as units_lib
from repro.core.selection import SelectorConfig
from repro.core.units import Plan, PlanStructure, UnitIndex
from repro.optim.adam import Adam, AdamState

Pytree = Any


@dataclass
class BlockLLMConfig:
    selector: SelectorConfig = field(default_factory=SelectorConfig)
    mask_refresh: str = "select"   # select | never  (paper: at selection)
    quantile_sample: int = 65536   # subsample size for large-tensor quantiles
    carry_surviving: bool = False  # keep Adam state of re-selected survivors
    fused_update: str = "off"      # off | pallas | interpret — use the
    #                                kernels/masked_adam fused optimizer
    #                                (pallas on TPU; interpret for CPU tests)


def _masked_quantile_threshold(u, q_keep, sample):
    """Per-row threshold tau s.t. |u| >= tau keeps ~q_keep fraction.

    u: [K, ...] (stacked) or [...] (leaf).  Exact quantile for small
    tensors; random-offset strided subsample for large ones (documented
    estimator; the Pallas kernel uses the same).
    """
    flat = u.reshape((u.shape[0], -1)) if u.ndim > 1 else u.reshape(1, -1)
    n = flat.shape[1]
    if n > sample:
        stride = n // sample
        flat = flat[:, ::stride][:, :sample]
    a = jnp.abs(flat.astype(jnp.float32))
    return jnp.quantile(a, jnp.clip(1.0 - q_keep, 0.0, 1.0), axis=1)


def build_step_fn(cfg, index: UnitIndex, adam: Adam, bcfg: BlockLLMConfig,
                  structure: PlanStructure, *, refresh: bool,
                  with_masks: bool, loss_fn: Callable):
    """The raw (un-jitted) BlockLLM train step.

    Shared between the single-host ``BlockLLMCore`` (plain jit) and the
    distributed launcher (pjit with explicit shardings — launch/steps.py).

    Signature of the returned fn:
        step(params, sel, probe, stack_idx, probe_idx, opt_state, masks,
             batch, q) -> (new_sel, new_opt, new_masks, loss, metrics,
                           norm_out)
    """

    import inspect
    supports_overlay = "overlay" in inspect.signature(loss_fn).parameters

    def step(params, sel, probe, stack_idx, probe_idx, opt_state, masks,
             batch, q):
        plan = Plan(structure, stack_idx, probe_idx)

        def lossf(sel_, probe_):
            if not supports_overlay:  # custom loss: explicit scatter merge
                merged = units_lib.merge_active(
                    params, index, plan, {"sel": sel_, "probe": probe_})
                return loss_fn(merged, batch)
            # stacked rows merge LAZILY per scan step (overlay): the active
            # cotangent accumulates at [K, ...] and the DP grad reduction
            # scales with the active fraction (§Perf I10).  Whole-leaf
            # units (embed/head/...) still swap in directly.
            overlay = {}
            for sid, k in structure.k_per_stack:
                if k:
                    overlay[sid] = {"idx": stack_idx[sid],
                                    "rows": sel_["stacks"][sid],
                                    "pidx": None, "probe": None}
            for sid, p_ in structure.probe_per_stack:
                if p_:
                    ov = overlay.setdefault(
                        sid, {"idx": None, "rows": None})
                    ov["pidx"] = probe_idx[sid]
                    ov["probe"] = probe_[sid]
            merged = dict(jax.tree.map(jax.lax.stop_gradient, params))
            for name, sub in sel_["leaves"].items():
                merged[name] = sub
            return loss_fn(merged, batch, overlay=overlay)

        with jax.named_scope("blockllm_grad"):
            (loss, metrics), grads = jax.value_and_grad(
                lossf, argnums=(0, 1), has_aux=True)(sel, probe)
        g_sel, g_probe = grads

        # per-unit gradient norms -> host norm dictionary
        with jax.named_scope("blockllm_grad_norms"):
            norm_out = {"stacks": {}, "leaves": {}, "probe": {}}
            for sid, rows in g_sel["stacks"].items():
                norm_out["stacks"][sid] = units_lib.per_row_sq_norms(rows)
            for name, sub in g_sel["leaves"].items():
                norm_out["leaves"][name] = units_lib.subtree_sq_norm(sub)
            for sid, rows in g_probe.items():
                norm_out["probe"][sid] = units_lib.per_row_sq_norms(rows)

        if refresh:
            with jax.named_scope("blockllm_mask_refresh"):
                upds, _ = adam.processed_grad(g_sel, opt_state)

                def stack_mask(u):  # per-row (=per-layer) tau — paper's mask
                    tau = _masked_quantile_threshold(
                        u, q, bcfg.quantile_sample)
                    return jnp.abs(u) >= tau.reshape(
                        (-1,) + (1,) * (u.ndim - 1))

                def leaf_mask(u):  # whole-leaf unit: one tau per tensor
                    tau = _masked_quantile_threshold(
                        u.reshape(1, -1), q, bcfg.quantile_sample)[0]
                    return jnp.abs(u) >= tau

                new_masks = {
                    "stacks": jax.tree.map(stack_mask, upds["stacks"]),
                    "leaves": jax.tree.map(leaf_mask, upds["leaves"]),
                }
        else:
            new_masks = masks

        with jax.named_scope("blockllm_update"):
            if bcfg.fused_update != "off" and not refresh:
                # fused masked-Adam Pallas kernel: one VMEM pass per tile
                # (5 reads + 3 writes vs ~12 HBM round-trips unfused)
                from repro.kernels import ops as kernel_ops
                from repro.optim.q8adam import Q8Adam, Q8AdamState
                lr = (adam.lr(opt_state.count) if callable(adam.lr)
                      else adam.lr)
                mask_arg = new_masks if (with_masks or refresh) else None
                if isinstance(adam, Q8Adam):
                    # Q8State: moments stream through VMEM as int8+scale —
                    # dequant/requant fused, no fp32 moment HBM round-trip
                    (new_sel, mq2, ms2, nq2,
                     ns2) = kernel_ops.masked_adam_q8_tree(
                        sel, g_sel, opt_state.mu_q, opt_state.mu_scale,
                        opt_state.nu_q, opt_state.nu_scale, mask_arg,
                        lr=lr, b1=adam.b1, b2=adam.b2, eps=adam.eps,
                        weight_decay=adam.weight_decay, count=opt_state.count,
                        interpret=(bcfg.fused_update == "interpret"))
                    new_opt = Q8AdamState(opt_state.count + 1, mq2, ms2,
                                          nq2, ns2)
                else:
                    new_sel, mu2, nu2 = kernel_ops.masked_adam_tree(
                        sel, g_sel, opt_state.mu, opt_state.nu, mask_arg,
                        lr=lr, b1=adam.b1, b2=adam.b2, eps=adam.eps,
                        weight_decay=adam.weight_decay, count=opt_state.count,
                        interpret=(bcfg.fused_update == "interpret"))
                    new_opt = AdamState(opt_state.count + 1, mu2, nu2)
            else:
                new_sel, new_opt = adam.update(
                    g_sel, opt_state, sel,
                    update_mask=new_masks if with_masks or refresh else None)
        return new_sel, new_opt, new_masks, loss, metrics, norm_out

    return step


# ---------------------------------------------------------------------- #
# The PR-2 legacy trainer classes that used to live here were removed in
# the trainer-registry redesign.  Imports fail loudly with the registry
# replacement instead of an AttributeError.
# ---------------------------------------------------------------------- #

_REMOVED_TRAINERS = {"BlockLLMTrainer": "blockllm",
                     "FullAdamTrainer": "adam"}


def __getattr__(name: str):
    if name in _REMOVED_TRAINERS:
        raise ImportError(
            f"{name} was removed: the trainer logic lives in the "
            f"repro.trainers registry.  Use trainers.handle("
            f"{_REMOVED_TRAINERS[name]!r}, cfg, params, **hyperparams) "
            f"for the imperative surface, or trainers.make("
            f"{_REMOVED_TRAINERS[name]!r}, cfg, **hyperparams) + "
            f"core.init/step for the functional protocol.")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
