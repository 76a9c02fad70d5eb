"""Block-table (paged) split-K flash-decoding Pallas kernel.

The contiguous decode kernel (kernels/decode.py) assumes each slot owns a
``(Hkv, max_len, d)`` slab — the allocation model the paged serving
subsystem replaces.  Here KV lives in a shared *block pool* and each
request describes its sequence as a **block table**: logical block ``j``
of request ``b`` holds tokens ``[j·bs, (j+1)·bs)`` and lives in physical
pool block ``bt[b, j]`` (serve/paged.py owns allocation; DESIGN.md §Paged
serving).

The split-K structure carries over unchanged — one grid step per logical
block, unnormalised partials, the same cross-split LSE merge
(``kernels.decode.merge_splits``) — only the *addressing* differs:

* **Scalar-prefetched block table.**  ``PrefetchScalarGridSpec`` makes the
  per-request live lengths *and* the block table available to the K/V
  BlockSpec index maps, so grid step ``(b, h, j)`` DMAs physical block
  ``bt[b, j]`` straight out of the pool — no gather materialises a
  contiguous copy of the request's KV.

* **Clamped index maps.**  Dead logical blocks (``j·bs ≥ length``) clamp to
  the request's last live table entry: the pipeline sees a repeated block
  index and skips the DMA, so dead pool blocks are never streamed and
  per-token KV traffic tracks ``ceil(length/bs)`` blocks — the paged analog
  of the ring cache's length-aware grid.

* **One kernel, two cache widths.**  Exactly like the contiguous kernel,
  the score width is whatever ``q``/``k_pool`` carry: the flash variant
  streams the raw K pool (width ``d``), the fused-K̂ distr variant streams
  the ``d/G*``-wide fused pool with column-sampled queries (static per-layer
  permutation, applied by the ops wrapper).  V is always full width.

* **Token-major pool layout** ``(P, block_size, Hkv·d)``: one pool row is
  one token's K (or V) for every KV head, so the KV heads share the 128
  TPU lanes.  A head-major ``(P, Hkv, block_size, d)`` pool pads ``d = 64``
  to 128 lanes in HBM (twice the KV memory), and XLA's scatter of new
  tokens wants another layout than the kernel's, so it would copy the
  whole pool each layer.  Token rows are what the insert scatter writes
  (``models.attention.paged_insert``) and they need no relayout.  A grid
  step reads ``hb`` adjacent KV heads (``heads_per_step``): the most whose
  double-buffered blocks fit ``VMEM_BLOCK_BUDGET``, which is every head at
  decode, so the K/V block is the whole pool row.  A grid step costs a
  fixed pipeline overhead (about 0.25 µs on a v5e) whatever it moves, and
  one head's block of one pool block moves 32 KB; reading them all cuts
  the grid 18–20×.  The step loops over its lane tiles in VMEM
  (``tiles_unrolled`` of them unrolled an iteration).

* **GQA head-packing + small-q_len banding** are shared verbatim with
  kernels/decode.py: rows pack ``q_per_kv × q_len`` queries per KV head,
  and packed row ``r`` (query token ``i = r mod q_len``) attends to cache
  positions ``< length − (q_len − 1 − i)`` — which is also what makes
  *chunked prefill* ride this kernel (a width-``c`` chunk is a ``q_len=c``
  banded decode).

Validated against gathered-contiguous oracles in tests/test_paged.py
(interpret mode on CPU; compiled on TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import NEG_INF

GARBAGE_BLOCK = 0  # pool block 0 is never allocated: dead-lane writes land here


def token_major(pool: jnp.ndarray) -> jnp.ndarray:
    """Head-major ``(..., P, Hkv, bs, d)`` → the token-major pool layout
    ``(..., P, bs, Hkv·d)`` the kernel reads."""
    *lead, p, hkv, bs, d = pool.shape
    return jnp.swapaxes(pool, -3, -2).reshape(*lead, p, bs, hkv * d)


# VMEM that one grid step's pipelined blocks (q, k, v, o, m, l), double
# buffered and padded to the (sublane, 128-lane) tiling, may take.  The
# kernel's scoped limit leaves as much again for the body's f32 temporaries.
VMEM_BLOCK_BUDGET = 16 * 2**20
VMEM_LIMIT = 2 * VMEM_BLOCK_BUDGET
# Query rows the kernel's unrolled head loop may hold: one lane tile of a
# 256-row chunk, whose body compiles for a v5e in about a second.  Unrolled
# over all 18 tiles, minicpm-2b's chunk kernel took 30 s to compile.
UNROLL_ROWS = 256


def vmem_tile_bytes(shape: tuple[int, ...], dtype) -> int:
    """Bytes a VMEM block of ``shape`` occupies: the last dim padded to 128
    lanes, the second-last to the dtype's sublane count (8 rows of 32 bits,
    so 16 of bf16)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    *lead, rows, lanes = shape
    tiles = math.prod(lead) * -(-rows // sublanes) * -(-lanes // 128)
    return tiles * sublanes * 128 * itemsize


def step_vmem_bytes(hb: int, rows: int, d_score: int, d: int,
                    block_size: int, q_dtype, kv_dtype) -> int:
    """Double-buffered VMEM of one grid step that reads ``hb`` KV heads."""
    f32 = jnp.float32
    blocks = (
        vmem_tile_bytes((hb, rows, d_score), q_dtype)
        + vmem_tile_bytes((block_size, hb * d_score), kv_dtype)
        + vmem_tile_bytes((block_size, hb * d), kv_dtype)
        + vmem_tile_bytes((hb, rows, d), f32)
        + 2 * vmem_tile_bytes((hb, 1, rows), f32)
    )
    return 2 * blocks


def heads_per_tile(hkv: int, d_score: int, d: int) -> int:
    """The fewest of ``hkv`` heads (a divisor) whose lanes fill whole
    128-lane tiles at both widths, else all of them."""
    for hb in range(1, hkv + 1):
        if hkv % hb == 0 and hb * d_score % 128 == 0 and hb * d % 128 == 0:
            return hb
    return hkv


def tiles_unrolled(n_tiles: int, rows: int) -> int:
    """Lane tiles one iteration of the kernel's head loop unrolls: the most
    (dividing ``n_tiles``) whose heads hold at most ``UNROLL_ROWS`` query
    rows between them, so all of them at decode (rows 8) and one at a
    256-row chunk."""
    return max(
        (u for u in range(1, n_tiles + 1)
         if n_tiles % u == 0 and u * rows <= UNROLL_ROWS),
        default=1,
    )


def heads_per_step(hkv: int, rows: int, d_score: int, d: int,
                   block_size: int, q_dtype, kv_dtype) -> int:
    """KV heads one grid step reads: the most whose blocks fit
    ``VMEM_BLOCK_BUDGET``.  A grid step pays a fixed pipeline cost whatever
    it moves, so fewer, wider steps are cheaper.  Candidates divide ``hkv``
    and are legal K/V blocks: all heads (the whole pool row) or a whole
    number of 128-lane tiles at both widths.  If none fits, the narrowest."""
    per_tile = heads_per_tile(hkv, d_score, d)
    legal = [hb for hb in range(hkv, 0, -per_tile) if hkv % hb == 0]
    for hb in legal:
        if step_vmem_bytes(hb, rows, d_score, d, block_size, q_dtype,
                           kv_dtype) <= VMEM_BLOCK_BUDGET:
            return hb
    return legal[-1]


def _paged_decode_kernel(
    lens_ref,  # scalar prefetch: (B,) int32 live lengths
    bt_ref,  # scalar prefetch: (B, max_blocks) int32 block table
    layer_ref,  # scalar prefetch: (1,) int32 layer of the pool stack
    q_ref,  # (hb, rows, d_score)
    k_ref,  # (block_size, hb·d_score)   physical block via index map
    v_ref,  # (block_size, hb·d)
    o_ref,  # (hb, rows, d)     unnormalised partials
    m_ref,  # (hb, 1, rows)     per-split row max
    l_ref,  # (hb, 1, rows)     per-split row sum
    *,
    scale: float,
    block_size: int,
    q_len: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = lens_ref[b]
    hb, rows, d_score = q_ref.shape
    d = o_ref.shape[-1]

    # Dead logical block: this request's live KV ends before block j.  The
    # index map already re-pointed the DMA at the last live physical block;
    # skip the math and emit identity stats for the merge.
    live = j * block_size < length

    @pl.when(live)
    def _body():
        col = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1
        )
        # Packed row r is query token i = r % q_len; it sees the cache up to
        # length − (q_len − 1 − i) tokens (q_len = 1 ⇒ plain `col < length`).
        row_tok = jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0
        ) % q_len
        mask = col < length - (q_len - 1 - row_tok)

        def head(i, k, v):  # step head i; k, v its lane slices, f32
            q = q_ref[i].astype(jnp.float32)  # (rows, d_score)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (rows, block_size)
            s = jnp.where(mask, s, NEG_INF)

            m = s.max(axis=1, keepdims=True)  # (rows, 1)
            p = jnp.where(mask, jnp.exp(s - m), 0.0)
            o_ref[i] = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[i] = m.reshape(1, rows)
            l_ref[i] = p.sum(axis=1, keepdims=True).reshape(1, rows)

        # Loop over lane tiles of `per_tile` heads, `unroll` tiles an
        # iteration (static lane slices within a tile).  Unrolled, the heads'
        # small matmuls overlap; rolled, the code and its compile time stay a
        # few tiles long however many heads the step reads.
        per_tile = heads_per_tile(hb, d_score, d)
        n_tiles = hb // per_tile
        unroll = tiles_unrolled(n_tiles, rows)
        wk, wv = per_tile * d_score, per_tile * d

        def tiles(t, carry):
            for u in range(unroll):
                tile = t * unroll + u
                k_t = k_ref[:, pl.ds(pl.multiple_of(tile * wk, wk), wk)]
                v_t = v_ref[:, pl.ds(pl.multiple_of(tile * wv, wv), wv)]
                for i in range(per_tile):
                    k = k_t[:, i * d_score:(i + 1) * d_score]
                    v = v_t[:, i * d:(i + 1) * d]
                    head(tile * per_tile + i, k.astype(jnp.float32),
                         v.astype(jnp.float32))
            return carry

        jax.lax.fori_loop(0, n_tiles // unroll, tiles, 0)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def paged_decode_kernel_call(
    q: jnp.ndarray,  # (B, Hkv, rows, d_score) — GQA-packed (+ padded) queries
    k_pool: jnp.ndarray,  # ([L,] P, block_size, Hkv·d_score) — raw K or fused K̂
    v_pool: jnp.ndarray,  # ([L,] P, block_size, Hkv·d)
    block_tables: jnp.ndarray,  # (B, max_blocks) int32 physical block ids
    lengths: jnp.ndarray,  # (B,) int32 live token counts
    *,
    scale: float,
    q_len: int,
    layer: jnp.ndarray | None = None,
    interpret: bool = True,
):
    """Raw pallas_call → unnormalised split partials ``(o, m, l)``.

    o: (B, Hkv, max_blocks, rows, d) f32; m, l: (B, Hkv, max_blocks, rows).
    One split per *logical* block-table entry; the caller performs the
    cross-split LSE merge (``kernels.decode.merge_splits`` — identical
    algebra, the splits just came from non-contiguous physical blocks).

    Pools are one layer's ``(P, bs, Hkv·d)`` or the model's whole
    ``(L, P, bs, Hkv·d)`` stack with a traced ``layer`` index.  The stack
    form lets a layer scan carry the pools and update them in place: the
    kernel DMAs straight out of the stack, so no per-layer slice is copied.
    """
    if k_pool.ndim == 3:
        k_pool, v_pool = k_pool[None], v_pool[None]
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer, jnp.int32), (1,))
    b, hkv, rows, d_score = q.shape
    block_size = v_pool.shape[2]
    d = v_pool.shape[3] // hkv
    hb = heads_per_step(hkv, rows, d_score, d, block_size, q.dtype,
                        v_pool.dtype)
    max_blocks = block_tables.shape[1]

    def q_index(bi, h, j, lens, bt, layer):
        return (bi, h, 0, 0)

    def kv_index(bi, h, j, lens, bt, layer):
        # Clamp dead logical blocks to the request's last live table entry:
        # the pipeline sees a repeated physical index and skips the DMA —
        # dead pool blocks are never streamed, so per-token traffic tracks
        # ceil(length / block_size), not the table width.
        last_live = jnp.maximum(pl.cdiv(lens[bi], block_size) - 1, 0)
        return (layer[0], bt[bi, jnp.minimum(j, last_live)], 0, h)

    def out_index(bi, h, j, lens, bt, layer):
        return (bi, h, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv // hb, max_blocks),
        in_specs=[
            pl.BlockSpec((None, hb, rows, d_score), q_index),
            pl.BlockSpec((None, None, block_size, hb * d_score), kv_index),
            pl.BlockSpec((None, None, block_size, hb * d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((None, hb, None, rows, d), out_index),
            # Row stats carry a unit axis: Mosaic wants a block's last two
            # dims whole or (8, 128)-aligned, and (1, rows) is whole.
            pl.BlockSpec((None, hb, None, 1, rows), out_index),
            pl.BlockSpec((None, hb, None, 1, rows), out_index),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=block_size, q_len=q_len
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, max_blocks, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, max_blocks, 1, rows), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, max_blocks, 1, rows), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="paged_decode_splitk",
    )(lengths, block_tables, layer, q, k_pool, v_pool)
    return o, m[..., 0, :], l[..., 0, :]
