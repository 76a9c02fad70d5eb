"""Flash-decoding Pallas TPU kernel: split-K partitioning over the KV length.

Decode is the opposite regime from the training/prefill kernels: one (or a
handful of speculative) query rows against a long KV cache.  The forward
kernels' grid — many Q blocks, KV innermost — collapses to a single serial
KV walk per head, leaving the chip idle.  FlashAttention-2's split-K
work-partitioning (Dao, 2023) restores parallelism: the KV length is cut
into independent splits, each split computes an *unnormalised* partial

    o_j = exp(s_j − m_j) · V_j,   m_j = rowmax(s_j),   l_j = rowsum(exp(s_j − m_j))

and a cheap cross-split logsumexp merge combines them:

    m* = max_j m_j,   l* = Σ_j l_j·exp(m_j − m*),
    o  = Σ_j o_j·exp(m_j − m*) / l*.

The merge is O(splits · rows · d) — noise next to the KV stream — and runs
as plain XLA in the ops.py wrapper (kernels/ops.py::decode_attention).

Design points:

* **GQA head-packing.**  The grid is ``(B, Hkv, splits)``; all ``q_per_kv``
  query heads sharing a KV head (× the small ``q_len``) are packed into the
  kernel's row dimension, so one kernel instance amortises the K/V stream
  over the whole GQA group — K/V are read once per *KV* head, the decode
  bandwidth bound.  Rows are padded to the f32 sublane width (8) by the
  wrapper.

* **Length-aware grid.**  Per-slot live lengths arrive via scalar prefetch
  (``PrefetchScalarGridSpec``): the K/V BlockSpec index maps clamp dead
  split indices to the slot's last live split, so the pipeline re-fetches an
  already-resident block instead of streaming dead cache — per-token KV
  traffic scales with ``ceil(length/block_k)``, not ``max_len`` (the ring
  cache invariant, DESIGN.md §Decode).  Dead splits skip compute entirely
  (``@pl.when``) and emit ``m = −inf, l = 0`` so the merge ignores them; the
  tail split masks columns ``≥ length`` within the block.

* **One kernel, two cache layouts.**  The score width is whatever ``q``/``k``
  carry: the plain variant streams the raw K cache (width ``d``); the distr
  fused-K̂ variant streams the ``d/G*``-wide ``k_fused`` cache with
  column-sampled queries (the layer's static permutation is applied by the
  wrapper — decode has no per-Q-block LSH stage).  The value stage always
  reads full-width V.

* **Small-q_len causality.**  For speculative decode (``q_len > 1``) packed
  row ``r`` holds query token ``i = r mod q_len``; it may attend to cache
  positions ``< length − (q_len − 1 − i)`` — the standard "each new token
  sees the cache plus its predecessors" band, degenerate for ``q_len = 1``.

Validated against the pure-JAX decode references in
``tests/test_kernels_decode.py`` (interpret mode on CPU; compiled on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF

ROW_ALIGN = 8  # f32 sublane width: the wrapper pads packed rows to this


def _decode_kernel(
    lens_ref,  # scalar prefetch: (B,) int32 live lengths
    q_ref,  # (1, 1, rows, d_score)
    k_ref,  # (1, 1, block_k, d_score)
    v_ref,  # (1, 1, block_k, d)
    o_ref,  # (1, 1, 1, rows, d)      unnormalised partial
    m_ref,  # (1, rows)               per-split row max
    l_ref,  # (1, rows)               per-split row sum
    *,
    scale: float,
    block_k: int,
    q_len: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = lens_ref[b]

    # Dead split: this slot's live KV ends before block j.  The index map
    # already re-pointed the DMA at the last live block; skip the math and
    # emit identity stats for the merge.
    live = j * block_k < length

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (rows, d_score)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d_score)
        v = v_ref[0, 0].astype(jnp.float32)  # (block_k, d)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rows, block_k)

        col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # Packed row r is query token i = r % q_len; it sees the cache up to
        # length − (q_len − 1 − i) tokens (q_len = 1 ⇒ plain `col < length`).
        row_tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % q_len
        row_len = length - (q_len - 1 - row_tok)
        mask = col < row_len
        s = jnp.where(mask, s, NEG_INF)

        m = s.max(axis=1, keepdims=True)  # (rows, 1)
        p = jnp.where(mask, jnp.exp(s - m), 0.0)
        o_ref[0, 0, 0] = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m.reshape(m_ref.shape)
        l_ref[...] = p.sum(axis=1, keepdims=True).reshape(l_ref.shape)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[0, 0, 0] = jnp.zeros_like(o_ref[0, 0, 0])
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def decode_kernel_call(
    q: jnp.ndarray,  # (B, Hkv, rows, d_score) — GQA-packed (+ padded) queries
    k: jnp.ndarray,  # (B, Hkv, Nk, d_score)   — raw K or fused K̂ cache
    v: jnp.ndarray,  # (B, Hkv, Nk, d)
    lengths: jnp.ndarray,  # (B,) int32 live token counts (≤ Nk)
    *,
    scale: float,
    block_k: int,
    q_len: int,
    interpret: bool = True,
):
    """Raw pallas_call → unnormalised split partials ``(o, m, l)``.

    o: (B, Hkv, splits, rows, d) f32;  m, l: (B, Hkv, splits, rows) f32.
    The caller performs the cross-split LSE merge (ops.py) — keeping the
    merge outside lets the splits run fully parallel with no cross-split
    scratch carry.
    """
    b, hkv, rows, d_score = q.shape
    nk, d = k.shape[2], v.shape[3]
    assert nk % block_k == 0, (nk, block_k)
    assert rows % ROW_ALIGN == 0, rows
    splits = nk // block_k

    def q_index(bi, h, j, lens):
        return (bi, h, 0, 0)

    def kv_index(bi, h, j, lens):
        # Clamp dead splits to the slot's last live split: the pipeline sees
        # a repeated block index and skips the DMA — dead KV is never
        # streamed, so per-token traffic tracks the live length.
        last_live = jnp.maximum(pl.cdiv(lens[bi], block_k) - 1, 0)
        return (bi, h, jnp.minimum(j, last_live), 0)

    def out_index(bi, h, j, lens):
        return (bi, h, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, splits),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d_score), q_index),
            pl.BlockSpec((1, 1, block_k, d_score), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, rows, d), out_index),
            # Row stats carry a unit axis: Mosaic wants a block's last two
            # dims whole or (8, 128)-aligned, and (1, rows) is whole.
            pl.BlockSpec((None, None, None, 1, rows), out_index),
            pl.BlockSpec((None, None, None, 1, rows), out_index),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, q_len=q_len
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, splits, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, splits, 1, rows), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, splits, 1, rows), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="flash_decode_splitk",
    )(lengths, q, k, v)
    return o, m[..., 0, :], l[..., 0, :]


def merge_splits(o: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    """Cross-split LSE merge (flash-decoding reduction).

    o: (..., splits, rows, d) unnormalised partials; m, l: (..., splits, rows).
    Returns the normalised (..., rows, d) attention output (f32).  Rows whose
    every split is dead (length 0 / padding) come out exactly zero.
    """
    m_star = m.max(axis=-2)  # (..., rows)
    alpha = jnp.exp(m - m_star[..., None, :])  # (..., splits, rows)
    l_star = (l * alpha).sum(axis=-2)  # (..., rows)
    o_sum = (o * alpha[..., None]).sum(axis=-3)  # (..., rows, d)
    denom = jnp.where(l_star == 0.0, 1.0, l_star)
    return o_sum / denom[..., None]
