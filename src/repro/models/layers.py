"""Primitive layers: linear, norm, embedding, rotary, MLP.

Pure-function style (no flax): ``*_init(key, ...) -> params`` and
``*_apply(params, x, ...) -> y``.  Every init has a matching ``*_axes(...)``
returning the same pytree structure with logical-axis tuples for the
distributed sharding rules (repro.distributed.sharding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Sharding constraint helper — no-op when no mesh is active so the same model
# code runs in smoke tests (1 device) and under the production mesh.
# ---------------------------------------------------------------------------


def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """with_sharding_constraint(x, P(*spec)) if a mesh is active, else x.

    ``"data"`` entries denote *batch* dims and expand to every non-"model"
    mesh axis, so the same model code data-parallelises over the extra "pod"
    axis of the multi-pod mesh.  The "context" axis (ring sequence-parallel
    attention) is excluded: the sequence dim shards over it, never the
    batch.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return x
    from repro.distributed.sharding import CONTEXT_AXIS

    dp = tuple(a for a in mesh.axis_names if a not in ("model", CONTEXT_AXIS))
    # "seq" entries denote the sequence dim: sharded over the reserved
    # context axis when the mesh rings it, replicated otherwise.
    ctx = CONTEXT_AXIS if CONTEXT_AXIS in mesh.axis_names else None

    def resolve(s):
        if s == "data":
            return dp
        if s == "seq":
            return ctx
        # A named axis the mesh lacks (e.g. "model" on a pure context
        # ring) replicates that dim.
        return s if s is None or s in mesh.axis_names else None

    expanded = tuple(resolve(s) for s in spec)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*expanded)
    )


# ---------------------------------------------------------------------------
# Backward-stream dtype guard (§Perf iteration 5)
#
# The loss head computes in f32, so without intervention every cotangent down
# the residual stream stays f32 — doubling backward HBM traffic and the
# activation-gradient collectives vs the bf16 forward.  ``grad_cast`` is an
# identity whose VJP casts the cotangent back to bf16; applied at block
# boundaries it keeps the whole backward stream in the compute dtype.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _grad_cast_bf16(x):
    return x


def _gc_fwd(x):
    return x, None


def _gc_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


_grad_cast_bf16.defvjp(_gc_fwd, _gc_bwd)


def grad_cast(x: jnp.ndarray) -> jnp.ndarray:
    """Clamp the backward stream to the forward compute dtype (bf16)."""
    if x.dtype == jnp.bfloat16:
        return _grad_cast_bf16(x)
    return x


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------


def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32, scale: float | None = None):
    w_scale = scale if scale is not None else d_in**-0.5
    params = {"w": (jax.random.normal(key, (d_in, d_out)) * w_scale).astype(dtype)}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
    return params


def linear_axes(in_axis: str | None, out_axis: str | None, *, bias: bool = False):
    axes = {"w": (in_axis, out_axis)}
    if bias:
        axes["b"] = (out_axis,)
    return axes


def linear_apply(params, x: jnp.ndarray, compute_dtype=None) -> jnp.ndarray:
    # Mixed precision: params may be stored fp32; compute follows the
    # activation dtype unless an explicit compute_dtype is given.
    dtype = compute_dtype if compute_dtype is not None else x.dtype
    y = x.astype(dtype) @ params["w"].astype(dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_axes():
    return {"scale": (None,)}


def rmsnorm_apply(params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_axes():
    return {"scale": (None,), "bias": (None,)}


def layernorm_apply(params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embedding_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embedding_axes():
    return {"table": ("vocab", None)}


def embedding_apply(params, tokens: jnp.ndarray, compute_dtype=None) -> jnp.ndarray:
    table = params["table"]
    if compute_dtype is not None:
        table = table.astype(compute_dtype)
    return jnp.take(table, tokens, axis=0)


def embedding_logits(params, x: jnp.ndarray) -> jnp.ndarray:
    """Tied-embedding readout."""
    return x @ params["table"].astype(x.dtype).T


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
               rope_dim: int | None = None) -> jnp.ndarray:
    """Rotate the leading ``rope_dim`` features of x.

    x: (B, H, N, d); positions: (B, N) int32.
    """
    d = x.shape[-1]
    rd = rope_dim if rope_dim is not None else d
    freqs = rope_frequencies(rd, theta)  # (rd/2,)
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,N,rd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    x1, x2 = x_rot[..., ::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape).astype(x.dtype)
    if rd == d:
        return rotated
    return jnp.concatenate([rotated, x_pass], axis=-1)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, *, act: str = "silu", dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "up": linear_init(k1, d_model, d_ff, dtype=dtype),
        "down": linear_init(k2, d_ff, d_model, dtype=dtype),
    }
    if act == "silu":  # SwiGLU needs the gate
        params["gate"] = linear_init(k3, d_model, d_ff, dtype=dtype)
    return params


def mlp_axes(act: str = "silu"):
    axes = {
        "up": linear_axes(None, "mlp"),
        "down": linear_axes("mlp", None),
    }
    if act == "silu":
        axes["gate"] = linear_axes(None, "mlp")
    return axes


def mlp_apply(params, x: jnp.ndarray, *, act: str = "silu") -> jnp.ndarray:
    up = linear_apply(params["up"], x)
    if act == "silu":
        gate = linear_apply(params["gate"], x)
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(f"unknown act {act!r}")
    h = constrain(h, "data", None, "model")
    return linear_apply(params["down"], h)
