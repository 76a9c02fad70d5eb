"""DistrAttention Pallas TPU kernel (paper §3.3 fused into FA-2).

Differences from the exact flash kernel:

* Q arrives pre-sampled (``q_hat``, trailing dim ``d/G*``, pre-scaled): the
  per-Q-block LSH permutation is computed outside the kernel (the paper also
  runs grouping as a separate lightweight stage, §4.8) and Q-sampling is a
  cheap one-off gather there.
* Each KV block is **fused in-kernel** under the current Q-block's
  permutation: K's d columns are permuted by ``perm`` and each run of
  ``G*`` is summed.  This must live in the kernel: K̂ depends on (Q block,
  K block) jointly, and materialising it outside would cost O(N²·d/G*)
  memory.
* The score matmul contracts over ``d/G*`` instead of ``d`` — the paper's
  compute reduction.  V and the PV matmul are untouched (full context).

TPU note (DESIGN.md §2): Mosaic cannot gather lanes by a dynamic index
vector, so the permute-and-sum runs as one matmul against a one-hot
``(d/G*, d)`` matrix built from the block's column groups (``fusion_matrix``);
on GPUs the paper uses warp shuffles.  Validated against
``ref.distr_attention_ref`` in interpret mode and compiled for v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF, STATS_LANES, row_stat_spec


def column_groups(perm: jnp.ndarray, group_size: int) -> jnp.ndarray:
    """(BH, nq, d) per-Q-block permutations → (BH, nq, 1, d) int32: the
    fused column each of K's d columns lands in (``argsort(perm) // G*``).
    The unit axis makes one block's ``(1, d)`` row a whole tile for Mosaic."""
    groups = jnp.argsort(perm, axis=-1) // group_size
    return groups.astype(jnp.int32)[:, :, None, :]


def fusion_matrix(groups: jnp.ndarray, n_groups: int) -> jnp.ndarray:
    """One-hot ``F`` (n_groups, d) f32 with ``F[g, c] = 1`` iff K column
    ``c`` is in fused group ``g``; ``groups`` is one ``(1, d)`` row of
    ``column_groups``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_groups, groups.shape[-1]), 0)
    return (rows == groups).astype(jnp.float32)


def fuse_k_columns(k, fuse):
    """The paper's fusion — permute K's columns, sum each run of ``G*`` —
    as the matmul ``K̂ = K·Fᵀ`` (Mosaic has no lane gather by a dynamic
    index vector).  Shared by the forward and backward kernels: the
    backward's recomputed K̂ must be bit-identical to what produced the
    saved LSE."""
    return jax.lax.dot_general(
        k, fuse, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def groups_spec(d: int, index_map) -> pl.BlockSpec:
    """BlockSpec of one Q block's ``column_groups`` row; ``index_map``
    returns ``(bh, q_block)``."""
    return pl.BlockSpec((None, None, 1, d), lambda *g: (*index_map(*g), 0, 0))


def _distr_kernel(
    q_hat_ref,
    k_ref,
    v_ref,
    groups_ref,
    o_ref,
    *rest,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    with_lse: bool,
):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    should_run = True
    if causal:
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _body():
        q_hat = q_hat_ref[...].astype(jnp.float32)  # (block_q, dg) pre-scaled
        k = k_ref[...].astype(jnp.float32)  # (block_k, d)
        v = v_ref[...].astype(jnp.float32)  # (block_k, d)
        # --- the paper's fusion under this Q block's permutation.
        fuse = fusion_matrix(groups_ref[...], q_hat.shape[1])
        k_hat = fuse_k_columns(k, fuse)

        s = jax.lax.dot_general(
            q_hat, k_hat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k) — contraction over d/G* only.

        col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)

        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l_final = l_scr[...][:, :1]
        denom = jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)
        if with_lse:
            m_final = m_scr[...][:, :1]
            lse = jnp.where(l_final == 0.0, NEG_INF, m_final + jnp.log(denom))
            lse_ref[...] = lse.reshape(1, block_q)  # per-row f32


def distr_attention_kernel_call(
    q_hat: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    perm: jnp.ndarray,
    *,
    q_per_kv: int,
    causal: bool,
    group_size: int,
    block_q: int,
    block_k: int,
    kv_len: int,
    interpret: bool = True,
    return_residuals: bool = False,
):
    """Raw pallas_call.

    q_hat: (BHq, N, d/G*) pre-sampled & pre-scaled queries (padded N).
    k, v:  (BHkv, Nk, d) (padded Nk).
    perm:  (BHq, N/block_q, d) int32 per-Q-block permutations.

    Returns ``o`` or ``(o, lse)`` (per-row logsumexp, ``(BHq, N)`` f32) when
    ``return_residuals`` — the residual consumed by kernels/backward.py.
    """
    bhq, n, dg = q_hat.shape
    bhkv, nk_len, d = k.shape
    assert bhq == bhkv * q_per_kv, (bhq, bhkv, q_per_kv)
    assert dg * group_size == d, (dg, group_size, d)

    grid = (bhq, n // block_q, nk_len // block_k)

    kernel = functools.partial(
        _distr_kernel,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=kv_len,
        with_lse=return_residuals,
    )
    out_specs = pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, 0))
    out_shape = jax.ShapeDtypeStruct((bhq, n, d), q_hat.dtype)
    if return_residuals:
        out_specs = [out_specs, row_stat_spec(block_q, lambda bh, i, j: (bh, i))]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((bhq, n // block_q, 1, block_q), jnp.float32),
        ]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, dg), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh // q_per_kv, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh // q_per_kv, j, 0)),
            groups_spec(d, lambda bh, i, j: (bh, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="distr_attention_fwd",
    )(q_hat, k, v, column_groups(perm, group_size))
    if return_residuals:
        o, lse = res
        return o, lse.reshape(bhq, n)
    return res
