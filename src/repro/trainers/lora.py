"""LoRA baseline as a ``TrainerCore``.

Factor init/merge math is ``baselines.lora`` (unchanged); this core
hosts it on the functional protocol: arrays ``{params, factors, opt}``
(base weights frozen; Adam runs on the factor tree), host meta
``{step, loss_history}``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from repro.models import model as model_lib
from repro.optim.adam import Adam
from repro.trainers.api import StateSpec, TrainerCore, TrainState, nbytes
from repro.trainers.registry import register

Pytree = Any


class LoRACore(TrainerCore):
    name = "lora"
    state_spec = StateSpec(
        arrays=("params", "factors", "opt"),
        meta=("step", "loss_history"),
        donate=("factors", "opt"),
        roles=(("params", "params"), ("factors", "active"),
               ("opt", "opt")),
    )

    def __init__(self, cfg, *, rank: int = 8, alpha=None,
                 adam: Optional[Adam] = None, loss_fn=None,
                 attn_impl: str = "auto"):
        self.cfg = cfg
        self.rank = rank
        self.alpha = alpha if alpha is not None else 4 * rank
        self.adam = adam or Adam(lr=1e-3)
        self._loss_fn = loss_fn or (lambda p, b: model_lib.loss_fn(
            p, cfg, b, attn_impl=attn_impl))
        self._jit_step = jax.jit(self._raw_step)

    def _init_arrays(self, rng, params: Pytree) -> Dict[str, Pytree]:
        from repro.baselines.lora import lora_init
        factors = lora_init(rng, params, self.rank)
        return {"params": params, "factors": factors,
                "opt": self.adam.init(factors)}

    def init(self, rng, params: Optional[Pytree] = None) -> TrainState:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if params is None:
            params = model_lib.init_params(rng, self.cfg)
        return TrainState(self._init_arrays(rng, params), self._init_meta())

    def _merge(self, params, factors):
        from repro.baselines.lora import lora_merge
        return lora_merge(params, factors, alpha=self.alpha,
                          rank=self.rank)

    def _raw_step(self, arrays, batch):
        params = arrays["params"]

        def lossf(f):
            return self._loss_fn(self._merge(params, f), batch)

        (loss, metrics), g = jax.value_and_grad(
            lossf, has_aux=True)(arrays["factors"])
        new_f, new_s = self.adam.update(g, arrays["opt"],
                                        arrays["factors"])
        return {"params": params, "factors": new_f, "opt": new_s}, \
            loss, metrics

    def merged_params(self, state: TrainState) -> Pytree:
        return self._merge(state.arrays["params"],
                           state.arrays["factors"])

    def memory_report(self, state: TrainState) -> Dict[str, int]:
        factors = state.arrays["factors"]
        report = {
            "params_bytes": nbytes(state.arrays["params"])
            + nbytes(factors),
            "grads_bytes": nbytes(factors),
            "opt_state_bytes": self.adam.state_bytes(state.arrays["opt"]),
            "mask_bytes": 0, "probe_bytes": 0,
        }
        report["total_train_state"] = sum(
            v for k, v in report.items() if k != "params_bytes")
        return report


@register("lora")
def make_lora(cfg, *, rank=8, alpha=None, adam=None, loss_fn=None,
              attn_impl="auto", **_) -> LoRACore:
    return LoRACore(cfg, rank=rank, alpha=alpha, adam=adam,
                    loss_fn=loss_fn, attn_impl=attn_impl)
