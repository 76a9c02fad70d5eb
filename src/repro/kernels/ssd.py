"""Mamba-2 SSD (state-space duality) chunked Pallas TPU kernel.

Used by the attention-free / hybrid assigned architectures (mamba2-130m,
zamba2-7b).  DistrAttention itself is inapplicable there (no QKᵀ stage —
DESIGN.md §4); this kernel is the corresponding perf-critical hot spot.

Chunked SSD: the sequence is split into chunks of ``chunk`` steps.  Within a
chunk the recurrence is expanded into a (masked, decay-weighted) quadratic
form evaluated on the MXU; across chunks a small (S × P) state is carried in
VMEM scratch — grid dim 1 is sequential ("arbitrary").

Recurrence (per head): state_t = exp(a_t)·state_{t-1} + b_t xᵀ_t,
y_t = c_tᵀ·state_t.  Heads share B/C projections in groups (like GQA); the
head→group mapping happens in the BlockSpec index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)  # (chunk, P)
    a = a_ref[...].astype(jnp.float32)  # (chunk, 1) log-decays
    b = b_ref[...].astype(jnp.float32)  # (chunk, S)
    c = c_ref[...].astype(jnp.float32)  # (chunk, S)
    state = state_scr[...]  # (S, P)

    a_cum = jnp.cumsum(a[:, 0])  # (chunk,) inclusive

    # Intra-chunk: L[i, j] = exp(a_cum[i] - a_cum[j]) for i >= j (else 0).
    li = a_cum[:, None] - a_cum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l_mat = jnp.where(col <= row, jnp.exp(li), 0.0)
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * l_mat  # (chunk, chunk)
    y = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # Inter-chunk: carry-in state contribution, decayed to each step.
    y = y + jnp.exp(a_cum)[:, None] * jax.lax.dot_general(
        c, state, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # State update for the next chunk.
    w = jnp.exp(a_cum[-1] - a_cum)  # (chunk,)
    state_scr[...] = jnp.exp(a_cum[-1]) * state + jax.lax.dot_general(
        b * w[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    y_ref[...] = y.astype(y_ref.dtype)


def ssd_kernel_call(
    x: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    *,
    heads_per_group: int,
    chunk: int = 64,
    interpret: bool = True,
) -> jnp.ndarray:
    """Raw pallas_call.

    x: (BH, N, P);  a: (BH, N, 1);  b, c: (BG, N, S) with BH = BG·heads_per_group
    (flattened batch-major, head/group-minor).  N must divide by ``chunk``.
    """
    bh, n, p = x.shape
    bg, _, s = b.shape
    assert bh == bg * heads_per_group, (bh, bg, heads_per_group)
    assert n % chunk == 0, (n, chunk)

    grid = (bh, n // chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, p), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, chunk, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, chunk, s), lambda h, i: (h // heads_per_group, i, 0)),
            pl.BlockSpec((None, chunk, s), lambda h, i: (h // heads_per_group, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, chunk, p), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((s, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_fwd",
    )(x, a, b, c)
