"""GaLore baseline as a ``TrainerCore``.

The optimizer math (rank-r gradient projection + projected Adam moments)
is ``baselines.galore.GaLore``, unchanged — this core just hosts it on
the functional protocol: arrays ``{params, opt}`` (``opt`` is the
``GaLoreState`` NamedTuple: projections + projected moments), host meta
``{step, loss_history}``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from repro.models import model as model_lib
from repro.trainers.api import StateSpec, TrainerCore, TrainState, nbytes
from repro.trainers.registry import register

Pytree = Any


class GaLoreCore(TrainerCore):
    name = "galore"
    state_spec = StateSpec(
        arrays=("params", "opt"),
        meta=("step", "loss_history"),
        donate=("params", "opt"),
        roles=(("params", "params"), ("opt", "opt")),
    )

    def __init__(self, cfg, *, galore=None, loss_fn=None,
                 attn_impl: str = "auto"):
        from repro.baselines.galore import GaLore
        self.cfg = cfg
        self.galore = galore or GaLore()
        self._loss_fn = loss_fn or (lambda p, b: model_lib.loss_fn(
            p, cfg, b, attn_impl=attn_impl))
        self._jit_step = jax.jit(self._raw_step)

    def _init_arrays(self, rng, params: Pytree) -> Dict[str, Pytree]:
        return {"params": params, "opt": self.galore.init(params)}

    def init(self, rng, params: Optional[Pytree] = None) -> TrainState:
        if params is None:
            params = model_lib.init_params(rng, self.cfg)
        return TrainState(self._init_arrays(rng, params), self._init_meta())

    def _raw_step(self, arrays, batch):
        (loss, metrics), g = jax.value_and_grad(
            self._loss_fn, has_aux=True)(arrays["params"], batch)
        new_p, new_s = self.galore.update(g, arrays["opt"],
                                          arrays["params"])
        return {"params": new_p, "opt": new_s}, loss, metrics

    def memory_report(self, state: TrainState) -> Dict[str, int]:
        report = {
            "params_bytes": nbytes(state.arrays["params"]),
            "grads_bytes": nbytes(state.arrays["params"]),
            "opt_state_bytes": self.galore.state_bytes(state.arrays["opt"]),
            "mask_bytes": 0, "probe_bytes": 0,
        }
        report["total_train_state"] = sum(
            v for k, v in report.items() if k != "params_bytes")
        return report


@register("galore")
def make_galore(cfg, *, galore=None, loss_fn=None, attn_impl="auto",
                rank=8, lr=1e-3, update_proj_gap=200, **_) -> GaLoreCore:
    if galore is None:
        from repro.baselines.galore import GaLore
        galore = GaLore(rank=rank, lr=lr, update_proj_gap=update_proj_gap)
    return GaLoreCore(cfg, galore=galore, loss_fn=loss_fn,
                      attn_impl=attn_impl)
