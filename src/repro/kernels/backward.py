"""FA-2-style backward Pallas TPU kernels for flash / DistrAttention.

Design (DESIGN.md §Backward): the forward saves only the per-row logsumexp
``L = m + log l``; the backward recomputes each score block from (Q, K) —
IO-aware recomputation instead of materialising the N×N probability matrix
(Dao, 2023).  Three kernel families:

* ``delta``  — D = rowsum(dO ∘ O), one cheap VPU pass.  Like the LSE it is
  stored per-row f32 ``(BHq, N)`` in HBM (no ×128 lane replication; the
  kernels see it in ``row_blocks`` layout); the matmul kernels
  re-broadcast it to a (block_q, 1) column on load.
* ``dq``     — grid (B·Hq, N/l, Nk/m), KV innermost, dQ accumulated in VMEM
  scratch across KV blocks:  dQ = Σ_j dS_j K_j · scale.
* ``dkv``    — grid (B·Hq, Nk/m, N/l), Q innermost, dK/dV accumulated across
  Q blocks:  dV = Σ_i P_iᵀ dO_i,  dK = Σ_i dS_iᵀ Q_i · scale.  Outputs are
  per *query* head; the ops.py wrapper sums the ``q_per_kv`` group (GQA).

The distr variants re-fuse K̂ in-kernel under the saved per-Q-block
permutation (the same one-hot fusion matmul ``K̂ = K·Fᵀ`` as the forward)
and route dK̂ back through its transpose, ``dK = dK̂·F``: each fused
column's gradient reaches its ``G*`` member columns in original order — no
gather or scatter op.
The LSH permutation itself is non-differentiable (straight-through): the
paper's grouping is a fixed discrete choice per block, so no gradient flows
into the hash.  Q̂ gradients leave the kernel in sampled space; the wrapper
transposes the sampling gather back to full-d dQ.

Everywhere ``p = where(mask, exp(s - L), 0)``: masking P directly (rather
than relying on s = -inf) keeps padded rows/columns exactly zero-gradient
even when a row's L is itself -inf (fully-masked query padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distr_attention import (
    column_groups,
    fuse_k_columns,
    fusion_matrix,
    groups_spec,
)
from repro.kernels.flash_attention import NEG_INF, row_blocks, row_stat_spec


# ---------------------------------------------------------------------------
# D = rowsum(dO ∘ O) precompute
# ---------------------------------------------------------------------------


def _delta_kernel(o_ref, do_ref, d_ref):
    o = o_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    # Per-row f32 write (not lane-replicated): the matmul kernels
    # re-broadcast on load.
    d_ref[...] = (o * do).sum(axis=1, keepdims=True).reshape(d_ref.shape)


def delta_kernel_call(
    o: jnp.ndarray,
    do: jnp.ndarray,
    *,
    block_q: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """D = rowsum(dO ∘ O).  o, do: (BHq, N, d) → (BHq, N) f32 per-row."""
    bhq, n, d = o.shape
    grid = (bhq, n // block_q)
    return pl.pallas_call(
        _delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, 0)),
        ],
        out_specs=row_stat_spec(block_q, lambda bh, i: (bh, i)),
        out_shape=jax.ShapeDtypeStruct((bhq, n // block_q, 1, block_q), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="attention_bwd_delta",
    )(o, do).reshape(bhq, n)


# ---------------------------------------------------------------------------
# Shared block math
# ---------------------------------------------------------------------------


def _block_mask(iq, ik, shape, *, causal, block_q, block_k, kv_len):
    col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = col < kv_len
    if causal:
        row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        mask = jnp.logical_and(mask, col <= row)
    return mask


def _p_and_ds(s, mask, lse, delta, do, v):
    """P from the saved LSE, then dS = P ∘ (dOVᵀ − D).  All f32."""
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (block_q, block_k)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    return p, ds


# ---------------------------------------------------------------------------
# Exact flash backward
# ---------------------------------------------------------------------------


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, block_q, block_k, kv_len,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    should_run = True
    if causal:
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        # Per-row residuals: re-broadcast the (1, block_q) row stats to the
        # (block_q, 1) column layout the block math wants.
        lse = lse_ref[...].reshape(block_q, 1)
        delta = delta_ref[...].reshape(block_q, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _block_mask(
            iq, ik, s.shape, causal=causal, block_q=block_q, block_k=block_k,
            kv_len=kv_len,
        )
        _, ds = _p_and_ds(s, mask, lse, delta, do, v)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def flash_dq_kernel_call(
    q, k, v, do, lse, delta, *,
    q_per_kv: int, scale: float, causal: bool,
    block_q: int, block_k: int, kv_len: int, interpret: bool = True,
) -> jnp.ndarray:
    """dQ for the exact kernel.  All seq dims padded; returns (BHq, N, d) f32."""
    bhq, n, d = q.shape
    bhkv, nk_len, _ = k.shape
    assert bhq == bhkv * q_per_kv

    grid = (bhq, n // block_q, nk_len // block_k)
    q_index = lambda bh, i, j: (bh, i, 0)
    kv_index = lambda bh, i, j: (bh // q_per_kv, j, 0)

    kernel = functools.partial(
        _flash_dq_kernel,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_q, d), q_index),
            row_stat_spec(block_q, lambda bh, i, j: (bh, i)),
            row_stat_spec(block_q, lambda bh, i, j: (bh, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((bhq, n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, row_blocks(lse, block_q), row_blocks(delta, block_q))


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, causal, block_q, block_k, kv_len,
):
    ik = pl.program_id(1)  # KV block: outer/parallel here
    iq = pl.program_id(2)  # Q block: innermost, accumulated over
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    should_run = True
    if causal:
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        # Per-row residuals: re-broadcast the (1, block_q) row stats to the
        # (block_q, 1) column layout the block math wants.
        lse = lse_ref[...].reshape(block_q, 1)
        delta = delta_ref[...].reshape(block_q, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        mask = _block_mask(
            iq, ik, s.shape, causal=causal, block_q=block_q, block_k=block_k,
            kv_len=kv_len,
        )
        p, ds = _p_and_ds(s, mask, lse, delta, do, v)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_dkv_kernel_call(
    q, k, v, do, lse, delta, *,
    q_per_kv: int, scale: float, causal: bool,
    block_q: int, block_k: int, kv_len: int, interpret: bool = True,
):
    """dK, dV per *query* head: (BHq, Nk, d) f32 each; caller sums the GQA
    group (wrapper-side accumulation keeps the kernel grid race-free)."""
    bhq, n, d = q.shape
    bhkv, nk_len, _ = k.shape
    assert bhq == bhkv * q_per_kv

    grid = (bhq, nk_len // block_k, n // block_q)
    q_index = lambda bh, j, i: (bh, i, 0)
    kv_index = lambda bh, j, i: (bh // q_per_kv, j, 0)
    dkv_index = lambda bh, j, i: (bh, j, 0)

    kernel = functools.partial(
        _flash_dkv_kernel,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_q, d), q_index),
            row_stat_spec(block_q, lambda bh, j, i: (bh, i)),
            row_stat_spec(block_q, lambda bh, j, i: (bh, i)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), dkv_index),
            pl.BlockSpec((None, block_k, d), dkv_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhq, nk_len, d), jnp.float32),
            jax.ShapeDtypeStruct((bhq, nk_len, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, row_blocks(lse, block_q), row_blocks(delta, block_q))


# ---------------------------------------------------------------------------
# DistrAttention backward
# ---------------------------------------------------------------------------


def _distr_dq_kernel(
    q_hat_ref, k_ref, v_ref, groups_ref, do_ref, lse_ref, delta_ref,
    dq_hat_ref, dq_scr,
    *, causal, block_q, block_k, kv_len,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    should_run = True
    if causal:
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _body():
        q_hat = q_hat_ref[...].astype(jnp.float32)  # (block_q, dg) pre-scaled
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        # Per-row residuals: re-broadcast the (1, block_q) row stats to the
        # (block_q, 1) column layout the block math wants.
        lse = lse_ref[...].reshape(block_q, 1)
        delta = delta_ref[...].reshape(block_q, 1)

        fuse = fusion_matrix(groups_ref[...], q_hat.shape[1])
        k_hat = fuse_k_columns(k, fuse)  # (block_k, dg)
        s = jax.lax.dot_general(
            q_hat, k_hat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _block_mask(
            iq, ik, s.shape, causal=causal, block_q=block_q, block_k=block_k,
            kv_len=kv_len,
        )
        _, ds = _p_and_ds(s, mask, lse, delta, do, v)
        # q_hat is pre-scaled, so no scale factor here: the ops.py wrapper
        # folds 1/sqrt(d) into the q̂ chain rule when scattering back to dQ.
        dq_scr[...] += jax.lax.dot_general(
            ds, k_hat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_hat_ref[...] = dq_scr[...].astype(dq_hat_ref.dtype)


def distr_dq_kernel_call(
    q_hat, k, v, perm, do, lse, delta, *,
    q_per_kv: int, causal: bool, group_size: int,
    block_q: int, block_k: int, kv_len: int, interpret: bool = True,
) -> jnp.ndarray:
    """dQ̂ (gradient w.r.t. the pre-scaled sampled queries): (BHq, N, d/G*)."""
    bhq, n, dg = q_hat.shape
    bhkv, nk_len, d = k.shape
    assert bhq == bhkv * q_per_kv
    assert dg * group_size == d

    grid = (bhq, n // block_q, nk_len // block_k)

    kernel = functools.partial(
        _distr_dq_kernel,
        causal=causal, block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, dg), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh // q_per_kv, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh // q_per_kv, j, 0)),
            groups_spec(d, lambda bh, i, j: (bh, i)),
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0)),
            row_stat_spec(block_q, lambda bh, i, j: (bh, i)),
            row_stat_spec(block_q, lambda bh, i, j: (bh, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dg), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, n, dg), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, dg), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="distr_attention_bwd_dq",
    )(q_hat, k, v, column_groups(perm, group_size), do,
      row_blocks(lse, block_q), row_blocks(delta, block_q))


def _distr_dkv_kernel(
    q_hat_ref, k_ref, v_ref, groups_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, causal, block_q, block_k, kv_len,
):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    should_run = True
    if causal:
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _body():
        q_hat = q_hat_ref[...].astype(jnp.float32)  # (block_q, dg)
        k = k_ref[...].astype(jnp.float32)  # (block_k, d)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        # Per-row residuals: re-broadcast the (1, block_q) row stats to the
        # (block_q, 1) column layout the block math wants.
        lse = lse_ref[...].reshape(block_q, 1)
        delta = delta_ref[...].reshape(block_q, 1)

        fuse = fusion_matrix(groups_ref[...], q_hat.shape[1])
        k_hat = fuse_k_columns(k, fuse)  # re-fused under this Q block
        s = jax.lax.dot_general(
            q_hat, k_hat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = _block_mask(
            iq, ik, s.shape, causal=causal, block_q=block_q, block_k=block_k,
            kv_len=kv_len,
        )
        p, ds = _p_and_ds(s, mask, lse, delta, do, v)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_hat = jax.lax.dot_general(
            ds, q_hat, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_k, dg)
        # Transpose of the fusion: every member of a fused group receives
        # the group's gradient, in original column order.
        dk_scr[...] += jax.lax.dot_general(
            dk_hat, fuse, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def distr_dkv_kernel_call(
    q_hat, k, v, perm, do, lse, delta, *,
    q_per_kv: int, causal: bool, group_size: int,
    block_q: int, block_k: int, kv_len: int, interpret: bool = True,
):
    """dK, dV per *query* head (dK already scattered back through each head's
    permutation): (BHq, Nk, d) f32 each; caller sums the GQA group."""
    bhq, n, dg = q_hat.shape
    bhkv, nk_len, d = k.shape
    assert bhq == bhkv * q_per_kv
    assert dg * group_size == d

    grid = (bhq, nk_len // block_k, n // block_q)
    q_index = lambda bh, j, i: (bh, i, 0)
    kv_index = lambda bh, j, i: (bh // q_per_kv, j, 0)
    dkv_index = lambda bh, j, i: (bh, j, 0)

    kernel = functools.partial(
        _distr_dkv_kernel,
        causal=causal, block_q=block_q, block_k=block_k, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, dg), q_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            groups_spec(d, lambda bh, j, i: (bh, i)),
            pl.BlockSpec((None, block_q, d), q_index),
            row_stat_spec(block_q, lambda bh, j, i: (bh, i)),
            row_stat_spec(block_q, lambda bh, j, i: (bh, i)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), dkv_index),
            pl.BlockSpec((None, block_k, d), dkv_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhq, nk_len, d), jnp.float32),
            jax.ShapeDtypeStruct((bhq, nk_len, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="distr_attention_bwd_dkv",
    )(q_hat, k, v, column_groups(perm, group_size), do,
      row_blocks(lse, block_q), row_blocks(delta, block_q))
