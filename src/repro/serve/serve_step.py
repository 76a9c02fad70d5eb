"""Serving steps: prefill (build cache from a full forward) and one-token
decode, per architecture family.  Both are pure functions of (params, cache)
so they jit cleanly under the production mesh — the decode shapes of the
dry-run lower ``decode_step`` exactly as defined here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers, lm, transformer
from repro.models.attention import _split_heads
from repro.serve import kv_cache


def _compute_dtype(cfg):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def _pad_seq_to(x: jnp.ndarray, max_len: int, axis: int) -> jnp.ndarray:
    pad = max_len - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def make_prefill(cfg, max_len: int, backbone_cfg=None):
    """→ prefill(params, tokens, patches=None, frames=None) → (logits, cache).

    logits: (B, 1, V) for the last position; cache: ready for decode at
    position = prompt length.

    ``backbone_cfg`` (default ``cfg``) drives the forward pass alone — the
    graceful-degradation path (serve.degrade) passes
    ``cfg.attention.degraded(G*)`` here so prefill attention runs
    DistrAttention at a coarser grouping while the *cache layout* (dtypes,
    fused-K̂ width, ring convention) stays exactly the engine's own:
    approximation enters only through the degraded hidden states, decode is
    untouched.
    """
    bcfg = cfg if backbone_cfg is None else backbone_cfg

    def prefill(params, tokens, patches=None, frames=None):
        hidden, _aux, parts, n_prefix = lm.backbone(
            params, bcfg, tokens, patches=patches, frames=frames,
            collect_cache=True,
        )
        logits = lm.logits_fn(params, cfg, hidden[:, -1:])
        cache: dict = {}
        dtype = _compute_dtype(cfg)

        if cfg.family in ("dense", "moe") and not cfg.use_mla:
            k, v = parts["kv"]  # (L, B, Hkv, S, dh)
            cache["k"] = _pad_seq_to(k.astype(dtype), max_len, 3)
            cache["v"] = _pad_seq_to(v.astype(dtype), max_len, 3)
            # Per-slot live length: the whole prompt is live after prefill.
            # The engine overrides this for right-padded prompts.
            cache["length"] = jnp.full(
                (tokens.shape[0],), k.shape[3], jnp.int32
            )
            if cfg.attention.distr_decode:
                from repro.core import grouping

                g = cfg.attention.distr.group_size
                perms = kv_cache.static_perms(cfg)  # (L, Hkv, dh)
                # (L, 1, Hkv, dh) broadcasts over batch & seq inside fuse.
                cache["k_fused"] = grouping.fuse_columns(
                    cache["k"].astype(jnp.float32), perms[:, None], g
                )
        elif cfg.use_mla:
            ckv, krope = parts["kv"]  # (L,B,S,C), (L,B,1,S,R)
            cache["ckv"] = _pad_seq_to(ckv.astype(dtype), max_len, 2)
            cache["krope"] = _pad_seq_to(krope[:, :, 0].astype(dtype), max_len, 2)
        elif cfg.family == "ssm":
            conv, ssm = parts["ssm"]
            cache["conv"] = conv.astype(dtype)
            cache["ssm"] = ssm
        elif cfg.family == "hybrid":
            conv_g, ssm_g = parts["ssm_groups"]
            sk, sv = parts["shared_kv"]
            cache["groups_conv"] = conv_g.astype(dtype)
            cache["groups_ssm"] = ssm_g
            cache["shared_k"] = _pad_seq_to(sk.astype(dtype), max_len, 3)
            cache["shared_v"] = _pad_seq_to(sv.astype(dtype), max_len, 3)
            if parts.get("ssm_tail") is not None:
                conv_t, ssm_t = parts["ssm_tail"]
                cache["tail_conv"] = conv_t.astype(dtype)
                cache["tail_ssm"] = ssm_t
        elif cfg.family == "encdec":
            k, v = parts["kv"]
            cache["k"] = _pad_seq_to(k.astype(dtype), max_len, 3)
            cache["v"] = _pad_seq_to(v.astype(dtype), max_len, 3)
            enc_out = parts["enc_out"]

            def cross_kv(block_params):
                ck = _split_heads(
                    layers.linear_apply(block_params["cross_attn"]["wk"], enc_out),
                    cfg.n_kv_heads,
                )
                cv = _split_heads(
                    layers.linear_apply(block_params["cross_attn"]["wv"], enc_out),
                    cfg.n_kv_heads,
                )
                return ck.astype(dtype), cv.astype(dtype)

            ck, cv = jax.vmap(cross_kv)(params["blocks"])
            cache["cross_k"] = _pad_seq_to(ck, cfg.cross_len, 3)[:, :, :, : cfg.cross_len]
            cache["cross_v"] = _pad_seq_to(cv, cfg.cross_len, 3)[:, :, :, : cfg.cross_len]
            cache["cross_len"] = jnp.full(
                (tokens.shape[0],), min(enc_out.shape[1], cfg.cross_len), jnp.int32
            )
        return logits, cache

    return prefill


def _make_paged_full_prefill(cfg, backbone_cfg):
    """Shared whole-prompt paged prefill body: one backbone forward under
    ``backbone_cfg``, last-live-row logits, and a scatter of every layer's
    K/V into the request's pool blocks through the block table
    (``models.attention.paged_insert``; padded rows divert to the garbage
    block).  The fused K̂ — when the engine decodes fused — is always
    written at the engine's ORIGINAL group size from its static per-layer
    permutations, whatever attention ``backbone_cfg`` ran: the cache
    layout belongs to the engine, the forward pass to the caller."""
    if cfg.family not in ("dense", "moe") or cfg.use_mla:
        raise NotImplementedError(
            f"paged serving covers GQA dense/moe; family={cfg.family!r} "
            f"use_mla={cfg.use_mla} keeps the slot engine"
        )
    from repro.models.attention import paged_insert

    fused = cfg.attention.distr_decode and cfg.family == "dense"

    def prefill(params, tokens, n, pools, block_tables):
        hidden, _aux, parts, _ = lm.backbone(
            params, backbone_cfg, tokens, collect_cache=True
        )
        # Exact last-live-position logits: causal attention means padded
        # rows past n-1 never feed row n-1 (the LSH permutations of the
        # row's block may see padding — an approximation the degraded path
        # already accepts).
        h_last = jnp.take(hidden, n - 1, axis=1)  # (1, 1, d)
        logits = lm.logits_fn(params, cfg, h_last)[0, 0]
        k, v = parts["kv"]  # (L, 1, Hkv, bucket, dh)
        pos0 = jnp.zeros((1,), jnp.int32)
        insert = jax.vmap(paged_insert, in_axes=(0, 0, None, None, None))
        new_pools = dict(pools)
        new_pools["v"] = insert(pools["v"], v, block_tables, pos0, n)
        if fused:
            from repro.core import grouping

            g = cfg.attention.distr.group_size
            perms = kv_cache.static_perms(cfg)  # (L, Hkv, dh)
            k_f = grouping.fuse_columns(
                k.astype(jnp.float32), perms[:, None], g
            )
            new_pools["k_fused"] = insert(
                pools["k_fused"], k_f, block_tables, pos0, n
            )
        else:
            new_pools["k"] = insert(pools["k"], k, block_tables, pos0, n)
        return logits, new_pools

    return prefill


def make_degraded_paged_prefill(cfg, bucket: int, group_size: int):
    """→ prefill(params, tokens (1, bucket), n (1,), pools, block_tables)
    → (last-live-row logits (V,), pools).

    The graceful-degradation prefill (serve.degrade): under sustained
    overload the scheduler trades chunked *exact* prefill for one
    whole-prompt forward whose attention runs DistrAttention at grouping
    fraction 1/``group_size`` (``core.api.AttentionConfig.degraded`` — the
    paper's accuracy↔speed dial), then scatters the resulting K/V into the
    request's pool blocks through the block table.  One step replaces
    ``ceil(n / prefill_chunk)`` chunk steps — TTFT under pressure drops to
    a single tick — at an attributable accuracy cost recorded per request
    (``Request.degrade_group``).

    The KV written is the backbone's own K/V (same convention as the exact
    paths); approximation enters only through the degraded attention's
    effect on the hidden states, so decode continues on the standard paged
    kernels untouched.
    """
    del bucket  # shapes ride on ``tokens``; the engine keys its jit cache
    dcfg = cfg.replace(attention=cfg.attention.degraded(group_size))
    return _make_paged_full_prefill(cfg, dcfg)


def make_mesh_paged_prefill(cfg, bucket: int):
    """→ prefill(params, tokens (1, bucket), n (1,), pools, block_tables)
    → (last-live-row logits (V,), pools).

    The mesh-capable whole-prompt prefill (paged × ring composition): the
    returned function is *traced under the engine's context mesh* —
    ``PagedServeEngine(mesh=)`` wraps the jitted call in ``maybe_set_mesh``
    — so the backbone's attention dispatches through ``core.api.attend`` to
    the ring (``distributed.ring_attention``) whenever the padded bucket
    spans at least ``ring_size × MIN_RING_SHARD`` tokens.  One long prompt
    prefills across the whole ring in a single step; GSPMD gathers each
    layer's K/V back to global arrays at the shard_map boundary, and the
    scatter lands them in ONE device's block pool through the block table —
    the prefill is distributed, the decode-side KV residency is not.

    The forward runs the engine's own *exact* attention config (no
    degradation); the fused K̂ is written at the original group size — the
    same invariant as the degraded prefill — so decode continues on the
    standard paged kernels untouched.
    """
    del bucket  # shapes ride on ``tokens``; the engine keys its jit cache
    return _make_paged_full_prefill(cfg, cfg)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def make_paged_step(cfg, width: int):
    """→ paged_step(params, tokens (B, width), pools, block_tables, pos,
    count) → (logits (B, width, V), pools).

    One jitted tick of the *paged* serve path (serve.paged pools +
    kernels/paged_decode.py): ``width = 1`` is the batched decode tick,
    ``width = chunk`` is one chunked-prefill window — both are the same
    banded windowed-decode computation, so chunked prefill runs on the
    decode kernel instead of a separate full-attention prefill graph.

    tokens: (B, width) int32 (right-padded); pos: (B,) absolute start
    positions; count: (B,) live tokens per row (padding writes are
    redirected to the garbage block and padded logits are ignored by the
    caller).  GQA dense/moe families only — the other families keep the
    slot engine's contiguous caches.
    """
    if cfg.family not in ("dense", "moe") or cfg.use_mla:
        raise NotImplementedError(
            f"paged serving covers GQA dense/moe; family={cfg.family!r} "
            f"use_mla={cfg.use_mla} keeps the slot engine"
        )
    fused = cfg.attention.distr_decode and cfg.family == "dense"

    def paged_step(params, tokens, pools, block_tables, pos, count):
        compute = _compute_dtype(cfg)
        x = layers.embedding_apply(params["embed"], tokens, compute)
        if cfg.pos == "learned":
            positions = pos[:, None] + jnp.arange(width)[None, :]
            x = x + layers.embedding_apply(
                params["pos_embed"], positions, compute
            )
        perms = kv_cache.static_perms(cfg) if fused else None  # (L, Hkv, dh)

        # The pools ride in the scan carry and each layer writes its window
        # in place at its own index (the kernel reads the stack directly):
        # with the pools donated, a step holds one copy of the KV, not two.
        def make_body(layer_type):
            def body(carry, inputs):
                h, pools = carry
                lp, layer = inputs
                h, (pk, pv, pkf) = transformer.block_paged_decode_apply(
                    lp, h, cfg, layer_type,
                    pool_k=pools.get("k"), pool_v=pools["v"],
                    block_tables=block_tables, pos=pos, count=count,
                    pool_k_fused=pools.get("k_fused"),
                    perm=None if perms is None else perms[layer], layer=layer,
                )
                new = {"k": pk, "v": pv, "k_fused": pkf}
                return (h, {key: new[key] for key in pools}), None

            return body

        carry = (x, dict(pools))
        fd = cfg.first_dense_layers if cfg.family == "moe" else 0
        if fd:
            carry, _ = jax.lax.scan(
                make_body("dense"), carry,
                (params["dense_blocks"], jnp.arange(fd)),
            )
        layer_type = "moe" if cfg.family == "moe" else "dense"
        (x, new_pools), _ = jax.lax.scan(
            make_body(layer_type), carry,
            (params["blocks"], jnp.arange(fd, cfg.n_layers)),
        )
        x = transformer.norm_apply(params["final_norm"], x, cfg)
        logits = lm.logits_fn(params, cfg, x)
        return logits, new_pools

    return paged_step


def make_decode_step(cfg):
    """→ decode_step(params, tokens (B,1), cache, pos (B,)) → (logits, cache)."""

    def decode_step(params, tokens, cache, pos):
        compute = _compute_dtype(cfg)
        b = tokens.shape[0]
        x = layers.embedding_apply(params["embed"], tokens, compute)
        if cfg.pos == "learned":
            x = x + layers.embedding_apply(
                params["pos_embed"], pos[:, None], compute
            )

        if cfg.family in ("dense", "moe") and not cfg.use_mla:
            new_cache = dict(cache)
            max_len = cache["k"].shape[3]
            # Length-aware decode: the total token count (incl. the token
            # being decoded) bounds every layer's KV walk — the kernels
            # stream ceil(length/block_k) blocks, not max_len.
            total = jnp.maximum(cache["length"], pos + 1)
            length = jnp.minimum(total, max_len)
            new_cache["length"] = total
            if cfg.family == "moe" and cfg.first_dense_layers:
                fd = cfg.first_dense_layers

                def body_d(h, inputs):
                    lp, k_l, v_l = inputs
                    h, nc = transformer.block_decode_apply(
                        lp, h, cfg, "dense",
                        cache={"k": k_l, "v": v_l}, cache_index=pos,
                        length=length,
                    )
                    return h, (nc["k"], nc["v"])

                x, (kd, vd) = jax.lax.scan(
                    body_d, x,
                    (params["dense_blocks"], cache["k"][:fd], cache["v"][:fd]),
                )
                layer_type = "moe"

                def body_m(h, inputs):
                    lp, k_l, v_l = inputs
                    h, nc = transformer.block_decode_apply(
                        lp, h, cfg, layer_type,
                        cache={"k": k_l, "v": v_l}, cache_index=pos,
                        length=length,
                    )
                    return h, (nc["k"], nc["v"])

                x, (km, vm) = jax.lax.scan(
                    body_m, x, (params["blocks"], cache["k"][fd:], cache["v"][fd:])
                )
                new_cache["k"] = jnp.concatenate([kd, km], axis=0)
                new_cache["v"] = jnp.concatenate([vd, vm], axis=0)
            elif cfg.attention.distr_decode and cfg.family == "dense":
                # Beyond-paper fused-K̂ decode: the score stage reads the
                # d/G*-wide fused cache (see models.attention).
                from repro.models.attention import attention_decode_fused
                from repro.models.transformer import norm_apply

                perms = kv_cache.static_perms(cfg)  # (L, Hkv, dh)

                # The raw K cache is NOT streamed through the decode scan:
                # the score stage reads only K̂ (+V).  Raw K stays as-is in
                # the cache dict (stale for decode; re-fused at prefill) —
                # this is where the (1-1/G*)·½ KV-read saving comes from.
                def body_f(h, inputs):
                    lp, v_l, kf_l, perm_l = inputs
                    hn = norm_apply(lp["norm1"], h, cfg)
                    o, (_, v2, kf2) = attention_decode_fused(
                        lp["attn"], hn, cfg,
                        cache_k=None, cache_v=v_l, cache_k_fused=kf_l,
                        perm=perm_l, cache_index=pos, length=length,
                    )
                    h = h + o
                    h2 = norm_apply(lp["norm2"], h, cfg)
                    h = h + layers.mlp_apply(lp["ffn"], h2, act=cfg.act)
                    return h, (v2, kf2)

                x, (vs, kfs) = jax.lax.scan(
                    body_f, x,
                    (params["blocks"], cache["v"], cache["k_fused"], perms),
                )
                new_cache.update(v=vs, k_fused=kfs)
            else:
                layer_type = "moe" if cfg.family == "moe" else "dense"

                def body(h, inputs):
                    lp, k_l, v_l = inputs
                    h, nc = transformer.block_decode_apply(
                        lp, h, cfg, layer_type,
                        cache={"k": k_l, "v": v_l}, cache_index=pos,
                        length=length,
                    )
                    return h, (nc["k"], nc["v"])

                x, (ks, vs) = jax.lax.scan(
                    body, x, (params["blocks"], cache["k"], cache["v"])
                )
                new_cache["k"], new_cache["v"] = ks, vs
        elif cfg.use_mla:
            new_cache = dict(cache)
            fd = cfg.first_dense_layers

            # dense prefix
            def body_mla_dense(h, inputs):
                lp, ckv_l, kr_l = inputs
                h, nc = transformer.block_decode_apply(
                    lp, h, cfg, "dense",
                    cache={"ckv": ckv_l, "krope": kr_l}, cache_index=pos,
                )
                return h, (nc["ckv"], nc["krope"])

            parts_ckv, parts_kr = [], []
            if fd:
                x, (c1, r1) = jax.lax.scan(
                    body_mla_dense, x,
                    (params["dense_blocks"], cache["ckv"][:fd], cache["krope"][:fd]),
                )
                parts_ckv.append(c1)
                parts_kr.append(r1)

            def body_mla_moe(h, inputs):
                lp, ckv_l, kr_l = inputs
                h, nc = transformer.block_decode_apply(
                    lp, h, cfg, "moe",
                    cache={"ckv": ckv_l, "krope": kr_l}, cache_index=pos,
                )
                return h, (nc["ckv"], nc["krope"])

            x, (c2, r2) = jax.lax.scan(
                body_mla_moe, x,
                (params["blocks"], cache["ckv"][fd:], cache["krope"][fd:]),
            )
            parts_ckv.append(c2)
            parts_kr.append(r2)
            new_cache["ckv"] = (
                jnp.concatenate(parts_ckv, axis=0) if fd else parts_ckv[0]
            )
            new_cache["krope"] = (
                jnp.concatenate(parts_kr, axis=0) if fd else parts_kr[0]
            )
        elif cfg.family == "ssm":

            def body_ssm(h, inputs):
                lp, conv_l, ssm_l = inputs
                h, nc = transformer.block_decode_apply(
                    lp, h, cfg, "mamba",
                    cache={"conv": conv_l, "ssm": ssm_l}, cache_index=pos,
                )
                return h, (nc["conv"], nc["ssm"])

            x, (convs, ssms) = jax.lax.scan(
                body_ssm, x, (params["blocks"], cache["conv"], cache["ssm"])
            )
            new_cache = {"conv": convs, "ssm": ssms}
        elif cfg.family == "hybrid":
            x0 = x
            nsb = cfg.n_shared_attn_blocks

            def mamba_body(h, inputs):
                lp, conv_l, ssm_l = inputs
                h, nc = transformer.block_decode_apply(
                    lp, h, cfg, "mamba",
                    cache={"conv": conv_l, "ssm": ssm_l}, cache_index=pos,
                )
                return h, (nc["conv"], nc["ssm"])

            shared_fns = [
                functools.partial(
                    transformer.shared_block_decode_apply, sp, cfg=cfg
                )
                for sp in params["shared"]
            ]

            def group_body(h, inputs):
                gp, conv_g, ssm_g, sk, sv, gi = inputs
                h, (conv_n, ssm_n) = jax.lax.scan(
                    mamba_body, h, (gp, conv_g, ssm_g)
                )
                h, kv_n = jax.lax.switch(
                    gi % nsb,
                    [
                        lambda hh, fn=fn: fn(
                            hh, x0, cache={"k": sk, "v": sv}, cache_index=pos
                        )
                        for fn in shared_fns
                    ],
                    h,
                )
                return h, (conv_n, ssm_n, kv_n["k"], kv_n["v"])

            n_groups, n_tail = kv_cache._hybrid_layout(cfg)
            x, (conv_g, ssm_g, sks, svs) = jax.lax.scan(
                group_body, x,
                (
                    params["groups"], cache["groups_conv"], cache["groups_ssm"],
                    cache["shared_k"], cache["shared_v"], jnp.arange(n_groups),
                ),
            )
            new_cache = dict(cache)
            new_cache.update(
                groups_conv=conv_g, groups_ssm=ssm_g, shared_k=sks, shared_v=svs
            )
            if n_tail:
                x, (conv_t, ssm_t) = jax.lax.scan(
                    mamba_body, x,
                    (params["tail"], cache["tail_conv"], cache["tail_ssm"]),
                )
                new_cache.update(tail_conv=conv_t, tail_ssm=ssm_t)
        elif cfg.family == "encdec":
            cross_len = cache["cross_len"]

            def body_ed(h, inputs):
                lp, k_l, v_l, ck_l, cv_l = inputs
                h, nc = transformer.block_decode_apply(
                    lp, h, cfg, "dense",
                    cache={"k": k_l, "v": v_l, "cross_k": ck_l, "cross_v": cv_l},
                    cache_index=pos, cross_len=cross_len,
                )
                return h, (nc["k"], nc["v"])

            x, (ks, vs) = jax.lax.scan(
                body_ed, x,
                (params["blocks"], cache["k"], cache["v"],
                 cache["cross_k"], cache["cross_v"]),
            )
            new_cache = dict(cache)
            new_cache.update(k=ks, v=vs)
        else:
            raise ValueError(cfg.family)

        x = transformer.norm_apply(params["final_norm"], x, cfg)
        logits = lm.logits_fn(params, cfg, x)
        return logits, new_cache

    return decode_step
