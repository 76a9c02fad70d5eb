"""FlashAttention-2 Pallas TPU kernel (exact baseline).

TPU adaptation of the paper's §2.2.2 baseline: grid ``(B·Hq, N/l, Nk/m)``,
``BlockSpec`` VMEM tiles, online softmax with fp32 scratch accumulators.
KV-block iteration is the innermost ("arbitrary") grid dimension so the
``(m, l, acc)`` scratch persists across it — the Pallas equivalent of FA-2's
inner loop held in registers/SMEM.

With ``return_residuals=True`` the kernel additionally emits the per-row
logsumexp ``L = m + log l`` as a ``(BHq, N)`` f32 row vector — the only
softmax statistic the FA-2 backward needs; dQ/dK/dV then recompute the
score blocks instead of materialising them (kernels/backward.py).  Only
the VMEM scratch keeps the lane-replicated ``(block_q, 128)`` layout (TPU
vector layouts want a lane-width minor dim); the HBM residual is per-row —
128× less stats traffic than replicating the scratch layout out
(DESIGN.md §Backward).  Inside the pallas_call a per-row statistic travels
as ``(BHq, N/block_q, 1, block_q)`` (``row_blocks``): Mosaic requires a
block's last two dims to be (8, 128)-aligned or whole, and ``(1, block_q)``
is whole for any ``block_q``.

Validated against ``ref.flash_attention_ref`` under ``interpret=True`` on
CPU; on TPU the ops.py wrapper selects compiled mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# In-kernel softmax-stat *scratch* is lane-replicated: TPU vector layouts
# want the minor dimension to be a multiple of the 128-lane width.  HBM
# residuals (LSE, D) are per-row f32 — re-broadcast on load in the backward
# kernels (one sublane↔lane relayout per block, vs 128× the HBM traffic).
STATS_LANES = 128


def row_blocks(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """(BH, N) per-row statistic → (BH, N/block, 1, block), the layout a
    kernel reads or writes it in (see module docstring)."""
    return x.reshape(x.shape[0], x.shape[1] // block, 1, block)


def row_stat_spec(block: int, index_map) -> pl.BlockSpec:
    """BlockSpec of one ``(1, block)`` row-statistic tile of ``row_blocks``
    layout; ``index_map`` returns ``(bh, q_block)``."""
    return pl.BlockSpec(
        (None, None, 1, block), lambda *g: (*index_map(*g), 0, 0)
    )


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *rest,
    scale: float,
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

    # Causal: skip KV blocks strictly above the diagonal band.
    should_run = True
    if causal:
        should_run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(should_run)
    def _body():
        q = q_ref[...].astype(jnp.float32)  # (block_q, d)
        k = k_ref[...].astype(jnp.float32)  # (block_k, d)
        v = v_ref[...].astype(jnp.float32)  # (block_k, d)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < kv_len
        if causal:
            row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]  # (block_q, 1)
        l_prev = l_scr[...][:, :1]
        m_cur = s.max(axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)

        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l_final = l_scr[...][:, :1]
        # Fully-masked rows (query padding) have l == 0; emit zeros.
        denom = jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)
        if with_lse:
            m_final = m_scr[...][:, :1]
            lse = jnp.where(
                l_final == 0.0, NEG_INF, m_final + jnp.log(denom)
            )
            lse_ref[...] = lse.reshape(1, block_q)  # per-row f32


def flash_attention_kernel_call(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_per_kv: int,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    interpret: bool = True,
    return_residuals: bool = False,
):
    """Raw pallas_call.  q: (BHq, N, d); k, v: (BHkv, Nk, d); N, Nk padded.

    The KV head for flattened q index ``bh`` is resolved inside the BlockSpec
    index maps (GQA without materialising repeated K/V).

    Returns ``o`` or ``(o, lse)`` with ``lse: (BHq, N)`` f32 (per-row
    logsumexp) when ``return_residuals``.
    """
    bhq, n, d = q.shape
    bhkv, nk_len, _ = k.shape
    # Flattened layouts: bhq = B·Hq, bhkv = B·Hkv with Hq = q_per_kv·Hkv, so
    # bh → kv row is bh // q_per_kv IF heads are flattened per-batch-major,
    # which the ops.py wrapper guarantees by flattening (B, Hkv, r) → B·Hkv·r.
    assert bhq == bhkv * q_per_kv, (bhq, bhkv, q_per_kv)

    grid = (bhq, n // block_q, nk_len // block_k)

    def q_index(bh, i, j):
        return (bh, i, 0)

    def kv_index(bh, i, j):
        return (bh // q_per_kv, j, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=kv_len,
        with_lse=return_residuals,
    )
    out_specs = pl.BlockSpec((None, block_q, d), q_index)
    out_shape = jax.ShapeDtypeStruct((bhq, n, d), q.dtype)
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
            pl.BlockSpec((None, block_q, d), q_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d), kv_index),
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
        name="flash_attention_fwd",
    )(q, k, v)
    if return_residuals:
        o, lse = res
        return o, lse.reshape(bhq, n)
    return res
