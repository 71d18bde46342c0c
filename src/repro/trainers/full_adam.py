"""Full-Adam reference trainer as a ``TrainerCore`` (the paper's
"Adam exceeds 80GB" baseline: dense gradients + dense moments).

``quantize_state=True`` (registry name ``adam+q8``) swaps the moment
storage for Q8State: int8 values + per-256-block f32 scales
(``optim.q8adam``), ~25% of the fp32 moment bytes in *persistent*
optimizer state (what lives between steps, what checkpoints, what
``memory_report`` counts) with the identical init/step/checkpoint
surface — the int8/scale leaves ride the same ``state_spec`` array
pytree, so crash-resume stays bit-exact with zero checkpointer changes.
Note the step itself dequantizes into fp32 moment temporaries inside
jit; the fused no-fp32-round-trip kernel path is BlockLLM's
(``kernels/masked_adam.masked_adam_q8_2d`` via ``fused_update``)."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax

from repro.models import model as model_lib
from repro.optim.adam import Adam
from repro.optim.q8adam import Q8Adam
from repro.trainers.api import StateSpec, TrainerCore, TrainState, nbytes
from repro.trainers.registry import register

Pytree = Any


class FullAdamCore(TrainerCore):
    name = "adam"
    state_spec = StateSpec(
        arrays=("params", "opt"),
        meta=("step", "loss_history"),
        donate=("params", "opt"),
        roles=(("params", "params"), ("opt", "opt")),
    )

    def __init__(self, cfg, *, adam: Optional[Adam] = None, loss_fn=None,
                 attn_impl: str = "auto", quantize_state: bool = False):
        self.cfg = cfg
        self.adam = adam or Adam(lr=1e-3)
        if quantize_state and not isinstance(self.adam, Q8Adam):
            self.adam = Q8Adam(self.adam)
        self.quantize_state = quantize_state
        self._loss_fn = loss_fn or (lambda p, b: model_lib.loss_fn(
            p, cfg, b, attn_impl=attn_impl))
        self._jit_step = jax.jit(self._raw_step)

    def _init_arrays(self, rng, params: Pytree) -> Dict[str, Pytree]:
        return {"params": params, "opt": self.adam.init(params)}

    def init(self, rng, params: Optional[Pytree] = None) -> TrainState:
        if params is None:
            params = model_lib.init_params(rng, self.cfg)
        return TrainState(self._init_arrays(rng, params), self._init_meta())

    def _raw_step(self, arrays, batch):
        (loss, metrics), g = jax.value_and_grad(
            self._loss_fn, has_aux=True)(arrays["params"], batch)
        new_p, new_s = self.adam.update(g, arrays["opt"], arrays["params"])
        return {"params": new_p, "opt": new_s}, loss, metrics

    def memory_report(self, state: TrainState) -> Dict[str, int]:
        report = {
            "params_bytes": nbytes(state.arrays["params"]),
            "grads_bytes": nbytes(state.arrays["params"]),
            "opt_state_bytes": self.adam.state_bytes(state.arrays["opt"]),
            "mask_bytes": 0, "probe_bytes": 0,
        }
        report["total_train_state"] = sum(
            v for k, v in report.items() if k != "params_bytes")
        return report


@register("adam")
def make_full_adam(cfg, *, adam=None, loss_fn=None, attn_impl="auto",
                   quantize_state=False, **_) -> FullAdamCore:
    return FullAdamCore(cfg, adam=adam, loss_fn=loss_fn,
                        attn_impl=attn_impl, quantize_state=quantize_state)


@register("adam+q8")
def make_full_adam_q8(cfg, **kw) -> FullAdamCore:
    """Full Adam with Q8State moments (int8 + block scales)."""
    kw["quantize_state"] = True
    return make_full_adam(cfg, **kw)
