"""Attention modules: GQA/MHA (with KV cache) and DeepSeek-style MLA.

All modules dispatch the score stage through ``repro.core.attend`` so the
paper's DistrAttention drops in via config.  MLA routes its RoPE
sub-dimensions through the exact path (``q_exact``/``k_exact``) because
fusing rotated rows would break the rotation structure (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.api import AttentionConfig, attend, attend_decode
from repro.core.distr_attention import distr_attention
from repro.core.flash_reference import reference_attention
from repro.models import layers
from repro.models.layers import constrain


def _split_heads(x: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    b, n, _ = x.shape
    return x.reshape(b, n, n_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def _constrain_bhnd(x: jnp.ndarray, attn_shard: str) -> jnp.ndarray:
    if attn_shard == "seq":
        return constrain(x, "data", None, "model", None)
    # Heads over TP; the sequence dim rides the "context" ring axis when
    # the mesh has one (layers.constrain maps "seq" → "context" | None), so
    # activations arrive at the ring attention already sequence-sharded.
    return constrain(x, "data", "model", "seq", None)


# ---------------------------------------------------------------------------
# GQA / MHA
# ---------------------------------------------------------------------------


def attention_init(key, cfg, dtype=jnp.float32):
    dh = cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": layers.linear_init(k1, cfg.d_model, cfg.n_heads * dh,
                                 bias=cfg.qkv_bias, dtype=dtype),
        "wk": layers.linear_init(k2, cfg.d_model, cfg.n_kv_heads * dh,
                                 bias=cfg.qkv_bias, dtype=dtype),
        "wv": layers.linear_init(k3, cfg.d_model, cfg.n_kv_heads * dh,
                                 bias=cfg.qkv_bias, dtype=dtype),
        "wo": layers.linear_init(k4, cfg.n_heads * dh, cfg.d_model, dtype=dtype),
    }


def attention_axes(cfg):
    return {
        "wq": layers.linear_axes(None, "heads", bias=cfg.qkv_bias),
        "wk": layers.linear_axes(None, "kv_heads", bias=cfg.qkv_bias),
        "wv": layers.linear_axes(None, "kv_heads", bias=cfg.qkv_bias),
        "wo": layers.linear_axes("heads", None),
    }


def attention_apply(
    params,
    x: jnp.ndarray,
    cfg,
    *,
    positions: jnp.ndarray | None = None,
    causal: bool = True,
    x_kv: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    attn_cfg: AttentionConfig | None = None,
    use_rope: bool | None = None,
):
    """Self/cross attention for train & prefill.

    Returns ``(out, (k, v))`` — raw per-head K/V so the serve layer can build
    caches from the prefill pass without re-projecting.
    """
    b, n, _ = x.shape
    attn_cfg = attn_cfg if attn_cfg is not None else cfg.attention
    use_rope = (cfg.pos == "rope") if use_rope is None else use_rope
    src = x if x_kv is None else x_kv

    q = _split_heads(layers.linear_apply(params["wq"], x), cfg.n_heads)
    k = _split_heads(layers.linear_apply(params["wk"], src), cfg.n_kv_heads)
    v = _split_heads(layers.linear_apply(params["wv"], src), cfg.n_kv_heads)

    if use_rope:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(n), (b, n))
        if kv_positions is None:
            kv_positions = (
                positions
                if x_kv is None
                else jnp.broadcast_to(jnp.arange(src.shape[1]), (b, src.shape[1]))
            )
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, kv_positions, cfg.rope_theta)

    q = _constrain_bhnd(q, cfg.attn_shard)
    k = _constrain_bhnd(k, cfg.attn_shard)
    v = _constrain_bhnd(v, cfg.attn_shard)

    o = attend(q, k, v, attn_cfg, causal=causal)
    o = _constrain_bhnd(o, cfg.attn_shard)
    out = layers.linear_apply(params["wo"], _merge_heads(o))
    return out, (k, v)


def _as_pos_vector(cache_index, b: int) -> jnp.ndarray:
    """Normalise cache_index (scalar or (B,)) to a (B,) int32 vector —
    per-slot positions enable continuous batching in the serve engine."""
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (b,))
    return idx


def cache_insert(cache: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Insert per-batch rows at per-batch positions (ring layout).

    cache: (B, H, S, d); new: (B, H, 1, d); pos: (B,) int32.  Positions are
    absolute; the write slot is ``pos mod S`` — past ``S`` tokens the ring
    wraps and the oldest entries are overwritten (serve.kv_cache ring
    invariants; the engine rides this as sliding-window eviction, attending
    over the most recent ``min(length, S)`` tokens).
    """
    s = cache.shape[2]
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (0, p % s, 0))
    )(cache, new.astype(cache.dtype), pos)


def _live_lengths(length, pos: jnp.ndarray, max_len: int) -> jnp.ndarray:
    """Per-slot live token counts for the decode kernels: the caller-tracked
    total (serve cache ``length``) when given, else derived from the write
    position; always clamped to the ring capacity."""
    total = length if length is not None else pos + 1
    return jnp.minimum(jnp.asarray(total, jnp.int32), max_len)


def attention_decode_fused(
    params,
    x: jnp.ndarray,
    cfg,
    *,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    cache_k_fused: jnp.ndarray,
    perm: jnp.ndarray,  # (Hkv, dh) static permutation for this layer
    cache_index: jnp.ndarray,
    length: jnp.ndarray | None = None,
):
    """Beyond-paper decode: scores read the fused K̂ cache (d/G* columns per
    token) instead of K — (1-1/G*)·½ fewer KV bytes on the memory-bound
    decode path, on top of the split-K kernel's live-length grid
    (``core.api.attend_decode`` → ``kernels.ops.decode_attention``).  K is
    still written (for re-scoring/eviction) but stays cold.  See
    serve.kv_cache / benchmarks/distr_decode.py."""
    from repro.serve import kv_cache as kvc

    b, n, _ = x.shape  # n == 1
    g = cfg.attention.distr.group_size
    pos = _as_pos_vector(cache_index, b)
    q = _split_heads(layers.linear_apply(params["wq"], x), cfg.n_heads)
    k = _split_heads(layers.linear_apply(params["wk"], x), cfg.n_kv_heads)
    v = _split_heads(layers.linear_apply(params["wv"], x), cfg.n_kv_heads)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, pos[:, None], cfg.rope_theta)
        k = layers.apply_rope(k, pos[:, None], cfg.rope_theta)
    if cache_k is not None:  # raw K optional: pure decode never reads it
        cache_k = cache_insert(cache_k, k, pos)
    cache_v = cache_insert(cache_v, v, pos)
    k_f_new = kvc.fuse_new_k(k, perm, g)
    cache_k_fused = cache_insert(cache_k_fused, k_f_new, pos)

    scale = 1.0 / (cfg.head_dim_**0.5)
    lengths = _live_lengths(length, pos, cache_k_fused.shape[2])
    o = attend_decode(
        q, None, cache_v, cfg.attention, lengths=lengths,
        k_fused=cache_k_fused, perm=perm, group_size=g, scale=scale,
    )
    out = layers.linear_apply(params["wo"], _merge_heads(o.astype(x.dtype)))
    return out, (cache_k, cache_v, cache_k_fused)


def attention_decode_apply(
    params,
    x: jnp.ndarray,
    cfg,
    *,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    cache_index: jnp.ndarray,
    is_cross: bool = False,
    cross_len: jnp.ndarray | None = None,
    length: jnp.ndarray | None = None,
):
    """One-token decode against a (B, Hkv, S, dh) ring cache.

    Self-attention inserts the new K/V at per-slot ``cache_index``;
    cross-attention reads a prefilled cache.  The score/value stages run on
    the split-K flash-decoding kernel via ``core.api.attend_decode`` (exact
    attention — the paper applies DistrAttention to the prefill/score
    stage; see serve.kv_cache for the beyond-paper fused-K̂ decode cache),
    visiting only the ``length`` live KV blocks per slot.
    """
    b, n, _ = x.shape  # n == 1
    pos = _as_pos_vector(cache_index, b)
    q = _split_heads(layers.linear_apply(params["wq"], x), cfg.n_heads)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, pos[:, None], cfg.rope_theta)

    if is_cross:
        lengths = (
            jnp.minimum(cross_len, cache_k.shape[2])
            if cross_len is not None
            else jnp.full((b,), cache_k.shape[2], jnp.int32)
        )
    else:
        k = _split_heads(layers.linear_apply(params["wk"], x), cfg.n_kv_heads)
        v = _split_heads(layers.linear_apply(params["wv"], x), cfg.n_kv_heads)
        if cfg.pos == "rope":
            k = layers.apply_rope(k, pos[:, None], cfg.rope_theta)
        cache_k = cache_insert(cache_k, k, pos)
        cache_v = cache_insert(cache_v, v, pos)
        lengths = _live_lengths(length, pos, cache_k.shape[2])

    o = attend_decode(q, cache_k, cache_v, cfg.attention, lengths=lengths)
    out = layers.linear_apply(params["wo"], _merge_heads(o.astype(x.dtype)))
    return out, (cache_k, cache_v)


def paged_insert(
    pool: jnp.ndarray,
    new: jnp.ndarray,
    block_tables: jnp.ndarray,
    pos: jnp.ndarray,
    count: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Insert a token window into a paged KV pool through the block table.

    pool: token-major (P, bs, Hkv·d) (kernels/paged_decode.py), or the
    (L, P, bs, Hkv·d) stack written at the traced ``layer``; new: (B, Hkv,
    w, d); block_tables: (B, max_blocks) physical ids; pos: (B,) absolute
    start positions.  Token ``t`` of request ``b`` lands at block
    ``bt[b, (pos+t)//bs]`` row ``(pos+t) mod bs``.  ``count`` (B,) gates
    writes: rows ``t ≥ count[b]`` (padding in a chunked prefill, idle
    decode lanes) are redirected to the reserved garbage block so they can
    never corrupt live KV.
    """
    from repro.kernels.paged_decode import GARBAGE_BLOCK

    bs = pool.shape[-2]
    b, hkv, w, d = new.shape
    max_blocks = block_tables.shape[1]
    # One vectorised scatter of whole token rows over all B·w writes (a
    # loop of per-token dynamic_update_slice would copy the whole pool per
    # write on non-donating backends).  A row is the scatter's window, so
    # the pool keeps the layout the decode kernel reads and XLA inserts no
    # layout copy.  Distinct live (block, row) pairs never collide —
    # distinct requests own distinct blocks — and any number of masked
    # writes may collide on the garbage block, whose content is never read.
    p = pos[:, None] + jnp.arange(w)[None, :]  # (B, w) absolute positions
    blk_idx = jnp.minimum(p // bs, max_blocks - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)  # (B, w)
    live = jnp.arange(w)[None, :] < (
        count[:, None] if count is not None else w
    )
    # Positions past the table's capacity also divert to garbage — a
    # clamped blk_idx would silently overwrite the LAST live block.
    live = live & (p < max_blocks * bs)
    blk = jnp.where(live, blk, GARBAGE_BLOCK)
    rows = new.astype(pool.dtype).transpose(0, 2, 1, 3).reshape(b * w, hkv * d)
    idx = (blk.reshape(-1), (p % bs).reshape(-1))
    if layer is not None:
        idx = (layer,) + idx
    return pool.at[idx].set(rows)


def attention_decode_paged(
    params,
    x: jnp.ndarray,
    cfg,
    *,
    pool_k: jnp.ndarray | None,
    pool_v: jnp.ndarray,
    block_tables: jnp.ndarray,
    cache_index: jnp.ndarray,
    count: jnp.ndarray | None = None,
    pool_k_fused: jnp.ndarray | None = None,
    perm: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
):
    """Windowed decode against the paged block pool (w = 1 for token
    decode, w = chunk width for chunked prefill).  Pools are one layer's,
    or the model's stacks read and written in place at the traced
    ``layer``.

    x: (B, w, d_model); ``cache_index``: (B,) absolute start positions;
    ``count``: (B,) live tokens in this window (padding rows write to the
    garbage block and their outputs are ignored by the caller).  The
    attention window is banded — token ``t`` sees positions ``≤ pos + t``
    — so a width-``c`` chunk reproduces causal prefill exactly
    (kernels/paged_decode.py).  Fused-K̂ variant: pass ``pool_k_fused`` +
    the layer's static ``perm``; the raw K pool may be None (it is never
    read or written on the fused paged path).

    Decode slides past the table's capacity: the write position wraps
    (``pos mod capacity``), recycling the request's HEAD blocks in place —
    the oldest token is overwritten and the kernel attends the live window
    ``min(pos + w, capacity)`` — the paged analog of the slot engine's
    ring-cache eviction.  RoPE stays at the *absolute* position, exactly
    like the slot ring write, so the two windowed decodes agree.  Prompts
    are admission-bounded below capacity, so chunked prefill never wraps.
    """
    from repro.serve import kv_cache as kvc

    b, w, _ = x.shape
    pos = _as_pos_vector(cache_index, b)
    positions = pos[:, None] + jnp.arange(w)[None, :]
    q = _split_heads(layers.linear_apply(params["wq"], x), cfg.n_heads)
    k = _split_heads(layers.linear_apply(params["wk"], x), cfg.n_kv_heads)
    v = _split_heads(layers.linear_apply(params["wv"], x), cfg.n_kv_heads)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)

    capacity = block_tables.shape[1] * pool_v.shape[-2]
    wpos = pos % capacity
    pool_v = paged_insert(pool_v, v, block_tables, wpos, count, layer)
    scale = 1.0 / (cfg.head_dim_**0.5)
    # Kernel lengths include the whole window (pos + w): live row t's band
    # col < pos + t + 1 then lands exactly on its own position; padded rows
    # only ever widen *their own* (discarded) reads.  A token decode past
    # capacity sees every pool position live (the ring overwrote the
    # oldest), so its band clamps to the full table.  A prefill window is
    # never clamped: prompts stay below capacity and only its padded rows
    # overhang it, and a clamp would cut the live rows' bands short.
    lengths = jnp.minimum(pos + w, capacity) if w == 1 else pos + w
    if pool_k_fused is not None:
        g = cfg.attention.distr.group_size
        k_f_new = kvc.fuse_new_k(k, perm, g)
        pool_k_fused = paged_insert(pool_k_fused, k_f_new, block_tables, wpos,
                                    count, layer)
        o = attend_decode(
            q, None, pool_v, cfg.attention, lengths=lengths,
            k_fused=pool_k_fused, perm=perm, group_size=g, scale=scale,
            block_tables=block_tables, layer=layer,
        )
        new_pools = (None, pool_v, pool_k_fused)
    else:
        pool_k = paged_insert(pool_k, k, block_tables, wpos, count, layer)
        o = attend_decode(
            q, pool_k, pool_v, cfg.attention, lengths=lengths, scale=scale,
            block_tables=block_tables, layer=layer,
        )
        new_pools = (pool_k, pool_v, None)
    out = layers.linear_apply(params["wo"], _merge_heads(o.astype(x.dtype)))
    return out, new_pools


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank Q, compressed KV cache, decoupled RoPE.
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype=jnp.float32):
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    params = {
        "wq_a": layers.linear_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype=dtype),
        "q_norm": layers.rmsnorm_init(cfg.q_lora_rank, dtype),
        "wq_b": layers.linear_init(ks[1], cfg.q_lora_rank, h * (nope + rope_d), dtype=dtype),
        "wkv_a": layers.linear_init(ks[2], cfg.d_model, cfg.kv_lora_rank + rope_d, dtype=dtype),
        "kv_norm": layers.rmsnorm_init(cfg.kv_lora_rank, dtype),
        "wk_b": layers.linear_init(ks[3], cfg.kv_lora_rank, h * nope, dtype=dtype),
        "wv_b": layers.linear_init(ks[4], cfg.kv_lora_rank, h * vd, dtype=dtype),
        "wo": layers.linear_init(ks[5], h * vd, cfg.d_model, dtype=dtype),
    }
    return params


def mla_axes(cfg):
    return {
        "wq_a": layers.linear_axes(None, None),
        "q_norm": layers.rmsnorm_axes(),
        "wq_b": layers.linear_axes(None, "heads"),
        "wkv_a": layers.linear_axes(None, None),
        "kv_norm": layers.rmsnorm_axes(),
        "wk_b": layers.linear_axes(None, "heads"),
        "wv_b": layers.linear_axes(None, "heads"),
        "wo": layers.linear_axes("heads", None),
    }


def _mla_qkv(params, x, cfg, positions):
    """Shared projection stage → per-head q_nope/q_rope/k_nope/k_rope/v."""
    b, n, _ = x.shape
    h = cfg.n_heads
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim

    q_l = layers.rmsnorm_apply(params["q_norm"], layers.linear_apply(params["wq_a"], x))
    q = _split_heads(layers.linear_apply(params["wq_b"], q_l), h)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = layers.linear_apply(params["wkv_a"], x)
    c_kv = layers.rmsnorm_apply(params["kv_norm"], kv_a[..., : cfg.kv_lora_rank])
    k_rope_raw = kv_a[..., cfg.kv_lora_rank:][:, None]  # (B, 1, N, rope_d)

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = layers.apply_rope(k_rope_raw, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(
    params,
    x: jnp.ndarray,
    cfg,
    *,
    positions: jnp.ndarray | None = None,
    causal: bool = True,
    attn_cfg: AttentionConfig | None = None,
):
    """MLA for train/prefill (naive up-projected path).

    DistrAttention grouping applies to the nope sub-dimension only; RoPE dims
    go through the exact score path.
    """
    b, n, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    attn_cfg = attn_cfg if attn_cfg is not None else cfg.attention
    scale = 1.0 / ((nope + rope_d) ** 0.5)

    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, positions)
    k_nope = _split_heads(layers.linear_apply(params["wk_b"], c_kv), h)
    v = _split_heads(layers.linear_apply(params["wv_b"], c_kv), h)

    q_nope = constrain(q_nope, "data", "model", None, None)
    k_nope = constrain(k_nope, "data", "model", None, None)
    v = constrain(v, "data", "model", None, None)

    if attn_cfg.impl in ("distr", "pallas_distr"):
        # The q_exact/k_exact (RoPE) side-channel only exists on the pure-JAX
        # path, so MLA keeps it for pallas_distr too; GQA/MHA attention is
        # where the kernel custom_vjp path engages (see core.api.attend).
        k_rope_bc = jnp.broadcast_to(k_rope, (b, h, n, rope_d))
        o = distr_attention(
            q_nope, k_nope, v, attn_cfg.distr,
            causal=causal, scale=scale,
            q_exact=q_rope, k_exact=k_rope_bc,
        )
    else:
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, h, n, rope_d))], axis=-1
        )
        o = attend(q_full, k_full, v, attn_cfg, causal=causal, scale=scale)

    o = constrain(o, "data", "model", None, None)
    out = layers.linear_apply(params["wo"], _merge_heads(o))
    return out, (c_kv, k_rope)


def mla_decode_apply(
    params,
    x: jnp.ndarray,
    cfg,
    *,
    cache_ckv: jnp.ndarray,  # (B, S, kv_lora)
    cache_krope: jnp.ndarray,  # (B, S, rope_d)
    cache_index: jnp.ndarray,
):
    """Absorbed-matrix MLA decode: attends in the compressed c_kv space.

    Scores: q_nopeᵀ·W_ukᵀ·c_kv + q_ropeᵀ·k_rope;  output: (P·c_kv)·W_uv.
    The cache stores only (kv_lora + rope_d) per token — MLA's memory win —
    and no per-step up-projection of the full cache is needed.
    """
    b, n, _ = x.shape  # n == 1
    h = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = 1.0 / ((nope + rope_d) ** 0.5)
    pos = _as_pos_vector(cache_index, b)

    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, x, cfg, pos[:, None])

    insert2d = jax.vmap(
        lambda c, nw, p: jax.lax.dynamic_update_slice(c, nw, (p, 0))
    )
    cache_ckv = insert2d(cache_ckv, c_kv_new.astype(cache_ckv.dtype), pos)
    cache_krope = insert2d(
        cache_krope, k_rope_new[:, 0].astype(cache_krope.dtype), pos
    )

    # Absorb W_uk into q: (B,H,1,nope) × (kv_lora, H, nope) → (B,H,1,kv_lora)
    w_uk = params["wk_b"]["w"].reshape(cfg.kv_lora_rank, h, nope)
    q_abs = jnp.einsum("bhnd,chd->bhnc", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))

    # bf16 cache reads + f32 accumulation: no materialised f32 cache copy.
    ckv = cache_ckv  # (B, S, C)
    krp = cache_krope  # (B, S, R)
    s = jnp.einsum("bhnc,bsc->bhns", q_abs.astype(ckv.dtype), ckv,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhnr,bsr->bhns", q_rope.astype(krp.dtype), krp,
                       preferred_element_type=jnp.float32)
    s = s * scale
    kv_mask = (
        jnp.arange(ckv.shape[1])[None, :] <= pos[:, None]
    )[:, None, None, :]
    s = jnp.where(kv_mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)

    ctx = jnp.einsum("bhns,bsc->bhnc", p.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)  # (B,H,1,C)
    w_uv = params["wv_b"]["w"].reshape(cfg.kv_lora_rank, h, vd)
    o = jnp.einsum("bhnc,chd->bhnd", ctx, w_uv.astype(jnp.float32))
    out = layers.linear_apply(params["wo"], _merge_heads(o.astype(x.dtype)))
    return out, (cache_ckv, cache_krope)
