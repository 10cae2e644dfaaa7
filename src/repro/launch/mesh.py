"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches JAX device state — the dry-run driver must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
JAX initialization.

The ``model`` axis doubles as the expert-parallel axis on MoE configs:
``repro.sharding`` places the stacked expert buffers — bf16 *and* the
fused path's prepared int8 ``{"iq","isw","izw"}`` leaves — with the expert
dim over ``model``, so the capacity dispatch/combine einsums lower to
all-to-alls over the same axis on both the reference and grouped-kernel
paths.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / single-host training)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
