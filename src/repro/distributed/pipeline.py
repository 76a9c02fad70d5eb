"""GPipe-style pipeline parallelism over a mesh axis (default: "pod").

The multi-pod dry-run's default config runs DP over the pod axis; this module
provides the alternative PP mapping: layer stages live on successive pods and
activations hop pod→pod with ``collective_permute`` while microbatches fill
the pipeline (M + S - 1 ticks, GPipe schedule).

``pipeline_apply`` is deliberately generic — ``stage_fn(stage_params, x)``
is any per-stage transform (e.g. a slice of transformer layers) — and is
validated in tests against running the stages sequentially on one device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn,
    stage_params,
    x: jnp.ndarray,
    mesh,
    *,
    axis: str = "pod",
    num_microbatches: int | None = None,
):
    """Run ``x`` through S pipeline stages laid out along ``axis``.

    stage_params: pytree with a leading stage dim (S, ...), sharded over
      ``axis`` on that dim (each pod holds one stage's params).
    x: (M, mb, ...) — M microbatches (M = num_microbatches or x.shape[0]).
    Returns (M, mb, ...) with every stage applied in order.
    """
    s_total = int(mesh.shape[axis])
    m = num_microbatches or x.shape[0]
    assert x.shape[0] == m

    def local(params_local, x_local):
        # params_local: (1, ...) — this pod's stage; x_local: (M, mb, ...)
        stage = jax.lax.axis_index(axis)
        params_stage = jax.tree_util.tree_map(lambda t: t[0], params_local)
        mb_shape = x_local.shape[1:]

        def tick(carry, t):
            outs, cur = carry
            # Stage 0 injects microbatch t while t < M; drain ticks (t >= M)
            # inject zeros — re-injecting the clamped index M-1 would make
            # every stage recompute the final microbatch S-1 extra times
            # (pure waste: those late copies can never reach the emit tick).
            inj = jax.lax.dynamic_index_in_dim(
                x_local, jnp.minimum(t, m - 1), axis=0, keepdims=False
            )
            inj = jnp.where(t < m, inj, jnp.zeros_like(inj))
            cur = jnp.where(stage == 0, inj, cur)
            y = stage_fn(params_stage, cur)
            # Last stage emits microbatch t - (S-1).
            emit_idx = t - (s_total - 1)
            do_emit = (stage == s_total - 1) & (emit_idx >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y.astype(outs.dtype), jnp.maximum(emit_idx, 0), axis=0
            )
            outs = jnp.where(do_emit, upd, outs)
            # Rotate activations one hop around the ring.
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s_total) for i in range(s_total)]
            )
            return (outs, nxt), None

        outs0 = jax.lax.pvary(jnp.zeros((m,) + mb_shape, x_local.dtype), (axis,))
        cur0 = jax.lax.pvary(jnp.zeros(mb_shape, x_local.dtype), (axis,))
        (outs, _), _ = jax.lax.scan(
            tick, (outs0, cur0), jnp.arange(m + s_total - 1)
        )
        # Only the last stage holds real outputs; broadcast via psum-mask.
        mask = (stage == s_total - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    n_extra = x.ndim - 1
    stage_specs = jax.tree_util.tree_map(
        lambda t: P(axis, *([None] * (t.ndim - 1))), stage_params
    )
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(stage_specs, P(*([None] * (n_extra + 1)))),
        out_specs=P(*([None] * (n_extra + 1))),
    )(stage_params, x)


def stage_split(params_stacked, n_stages: int):
    """Reshape a (L, ...) layer-stacked tree into (S, L/S, ...) stages."""

    def reshape(t):
        l = t.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return t.reshape((n_stages, l // n_stages) + t.shape[1:])

    return jax.tree_util.tree_map(reshape, params_stacked)
