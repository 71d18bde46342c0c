"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import and then calls these.

Target hardware model: TPU v5e pods — 16x16 = 256 chips per pod; the
multi-pod mesh is 2 pods = 512 chips with a leading "pod" axis (data
parallelism across DCN).  Axis semantics:
  pod   — data parallelism across pods (gradient all-reduce over DCN)
  data  — data parallelism within a pod (ICI)
  model — tensor/sequence parallelism (ICI)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh_compat(shape, axis_names) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (sharding left to GSPMD,
    as the ``shard_ctx`` constraints expect)."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh_compat((n // model_axis, model_axis),
                            ("data", "model"))


def dp_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# Roofline hardware constants (TPU v5e) — see EXPERIMENTS.md §Roofline.
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (~per chip effective)
HBM_BYTES = 16 * 1024 ** 3    # 16 GiB per chip
