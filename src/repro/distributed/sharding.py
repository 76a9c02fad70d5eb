"""Logical-axis → mesh sharding rules (DP/FSDP × TP × EP).

Parameters carry logical axis tuples (from ``models.*_axes``); this module
maps them onto the production mesh:

  vocab / mlp / heads / kv_heads / experts → "model"   (TP / EP)
  one large unsharded dim per tensor       → "data"    (FSDP, if cfg.fsdp)

FSDP picks the largest None-axis (excluding the layer-stack dim) whose size
divides the data-axis size and is ≥ MIN_FSDP_DIM; optimizer state shards
exactly like its parameter.  Activations: batch → every non-"model" axis
(so the "pod" axis is pure DP in the multi-pod mesh).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

LOGICAL_RULES = {
    "vocab": "model",
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
    None: None,
}

MIN_FSDP_DIM = 1024


@contextlib.contextmanager
def maybe_set_mesh(mesh):
    """``jax.sharding.set_mesh(mesh)``, or nothing when ``mesh`` is None."""
    if mesh is None:
        yield
        return
    with jax.sharding.set_mesh(mesh):
        yield

# The reserved mesh-axis name for ring sequence-parallel attention
# (distributed.ring_attention).  The ring functions themselves accept any
# axis name, but the built-in sharding rules — dp_axes here and the
# "data"/"seq" expansion in models.layers.constrain — special-case this
# literal: model-integrated training/serving must name the mesh axis
# CONTEXT_AXIS (and set AttentionConfig.context_axis to it) or the batch
# dim would shard over the ring and every layer would re-gather it.
CONTEXT_AXIS = "context"

# Parameter subtrees that are layer-stacked (leading dim = scan axis; never
# FSDP-shard it — scan would reshard every step).
STACKED_KEYS = ("blocks", "dense_blocks", "enc_blocks", "groups", "tail")


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes the batch dimension shards over: everything except "model" (TP)
    and CONTEXT_AXIS (sequence-sharded ring attention — the batch must stay
    whole across it or each ring device would hold a different batch)."""
    return tuple(
        a for a in mesh.axis_names if a not in ("model", CONTEXT_AXIS)
    )


def data_axis_size(mesh) -> int:
    return int(mesh.shape.get("data", 1))


def _spec_for(axes: tuple, shape: tuple, mesh, *, fsdp: bool, stacked: bool) -> P:
    assignment = [LOGICAL_RULES.get(a, None) for a in axes]
    # Explicit in/out shardings must divide evenly; drop assignments that
    # don't (e.g. a 3352-wide mamba in_proj on a 16-way model axis).
    for i, a in enumerate(assignment):
        if a is not None and shape[i] % int(mesh.shape.get(a, 1)):
            assignment[i] = None
    if fsdp and "data" in mesh.axis_names:
        dsz = data_axis_size(mesh)
        candidates = [
            i
            for i, a in enumerate(axes)
            if a is None
            and not (stacked and i == 0)
            and shape[i] >= MIN_FSDP_DIM
            and shape[i] % dsz == 0
        ]
        if candidates:
            best = max(candidates, key=lambda i: shape[i])
            assignment[best] = "data"
    return P(*assignment)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def param_pspecs(axes_tree, shapes_tree, mesh, *, fsdp: bool = True):
    """Tree of PartitionSpec matching the params tree.

    axes_tree: from models.lm.param_axes(cfg);
    shapes_tree: from jax.eval_shape(init_params, ...).
    """

    def walk(axes, shapes, stacked):
        if _is_axes_leaf(axes):
            return _spec_for(axes, shapes.shape, mesh, fsdp=fsdp, stacked=stacked)
        if isinstance(axes, dict):
            return {
                k: walk(
                    v, shapes[k], stacked or (k in STACKED_KEYS)
                )
                for k, v in axes.items()
            }
        if isinstance(axes, (list, tuple)):
            return type(axes)(
                walk(a, s, stacked) for a, s in zip(axes, shapes)
            )
        raise TypeError(f"unexpected axes node {type(axes)}")

    return walk(axes_tree, shapes_tree, False)


def param_shardings(axes_tree, shapes_tree, mesh, *, fsdp: bool = True):
    specs = param_pspecs(axes_tree, shapes_tree, mesh, fsdp=fsdp)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_pspec(mesh) -> P:
    """Token batches: batch dim over every non-model axis."""
    return P(dp_axes(mesh))


def dp_axes_for(mesh, dim: int) -> tuple[str, ...] | None:
    """DP axes whose product divides ``dim`` (prefix of the axis list)."""
    axes = []
    prod = 1
    for a in dp_axes(mesh):
        size = int(mesh.shape[a])
        if dim % (prod * size) == 0:
            axes.append(a)
            prod *= size
    return tuple(axes) or None


def batch_shardings(batch_specs: dict, mesh) -> dict:
    """Shardings for an input_specs() dict: dim0 = batch, rest replicated."""
    out = {}
    for k, v in batch_specs.items():
        spec = [None] * len(v.shape)
        spec[0] = dp_axes_for(mesh, v.shape[0])
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_params(params, shardings):
    """Device_put a params tree onto its shardings (host → mesh)."""
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


def kv_cache_pspec(mesh, *, seq_axis_sharded: bool) -> P:
    """(B, Hkv, S, dh) cache: batch over DP axes; seq over model when the
    head count doesn't divide the TP size (flash-decoding style)."""
    if seq_axis_sharded:
        return P(dp_axes(mesh), None, "model", None)
    return P(dp_axes(mesh), "model", None, None)
