"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto-typed: the sharding rules
    (distributed.sharding, models.layers.constrain) hint layouts and let
    GSPMD propagate the rest."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:   (pod=2, data=16, model=16) = 512 chips; "pod" is pure DP
    (gradient all-reduce crosses the inter-pod links only once per step)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, context_parallel: int = 1):
    """Mesh over whatever devices exist (tests / examples / CPU).

    ``context_parallel > 1`` adds a "context" axis for ring
    sequence-parallel attention (distributed.ring_attention): the sequence
    dimension shards over it, so it is *not* a data-parallel axis —
    sharding rules (distributed.sharding, models.layers.constrain) exclude
    it from batch-dim expansion."""
    n = len(jax.devices())
    if n % (model_parallel * context_parallel):
        raise ValueError(
            f"{n} device(s) cannot host model_parallel={model_parallel} × "
            f"context_parallel={context_parallel} (need a divisor of the "
            f"device count)"
        )
    if context_parallel > 1:
        return make_mesh(
            (n // (model_parallel * context_parallel), context_parallel,
             model_parallel),
            ("data", "context", "model"),
        )
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
