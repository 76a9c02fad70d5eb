"""Transformer blocks: dense (GQA/MLA) + MoE + Mamba + hybrid shared-attn.

Pre-norm residual blocks.  Every attention goes through ``repro.core.attend``
so DistrAttention is a config flip.  Blocks return ``(x, aux)`` where aux is
the MoE load-balance loss (0.0 for non-MoE blocks) — keeps scan carries
uniform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import layers, mamba, moe
from repro.models.layers import constrain


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return layers.rmsnorm_init(d)
    return layers.layernorm_init(d)


def _norm_axes(cfg):
    if cfg.norm == "rmsnorm":
        return layers.rmsnorm_axes()
    return layers.layernorm_axes()


def norm_apply(params, x, cfg):
    if cfg.norm == "rmsnorm":
        return layers.rmsnorm_apply(params, x, cfg.norm_eps)
    return layers.layernorm_apply(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Dense / MoE / MLA decoder block
# ---------------------------------------------------------------------------


def block_init(key, cfg, layer_type: str, dtype=jnp.float32, *, cross: bool = False):
    """layer_type: dense | moe | mamba."""
    ks = jax.random.split(key, 6)
    if layer_type == "mamba":
        return {
            "norm1": _norm_init(cfg),
            "mixer": mamba.mamba_init(ks[0], cfg, dtype),
        }
    params = {
        "norm1": _norm_init(cfg),
        "norm2": _norm_init(cfg),
    }
    if cfg.use_mla:
        params["attn"] = attn_mod.mla_init(ks[0], cfg, dtype)
    else:
        params["attn"] = attn_mod.attention_init(ks[0], cfg, dtype)
    if cross:
        params["norm_cross"] = _norm_init(cfg)
        params["cross_attn"] = attn_mod.attention_init(ks[1], cfg, dtype)
    if layer_type == "moe":
        params["ffn"] = moe.moe_init(ks[2], cfg, dtype)
    else:
        params["ffn"] = layers.mlp_init(ks[2], cfg.d_model, cfg.d_ff,
                                        act=cfg.act, dtype=dtype)
    return params


def block_axes(cfg, layer_type: str, *, cross: bool = False):
    if layer_type == "mamba":
        return {"norm1": _norm_axes(cfg), "mixer": mamba.mamba_axes(cfg)}
    axes = {"norm1": _norm_axes(cfg), "norm2": _norm_axes(cfg)}
    if cfg.use_mla:
        axes["attn"] = attn_mod.mla_axes(cfg)
    else:
        axes["attn"] = attn_mod.attention_axes(cfg)
    if cross:
        axes["norm_cross"] = _norm_axes(cfg)
        axes["cross_attn"] = attn_mod.attention_axes(cfg)
    if layer_type == "moe":
        axes["ffn"] = moe.moe_axes(cfg)
    else:
        axes["ffn"] = layers.mlp_axes(act=cfg.act)
    return axes


def block_apply(
    params,
    x: jnp.ndarray,
    cfg,
    layer_type: str,
    *,
    positions=None,
    causal: bool = True,
    enc_out=None,
    decode: bool = False,
    collect_cache: bool = False,
):
    """Full-sequence block (train / prefill).

    Returns (x, aux, kv) — kv is (k, v) from self-attention (for mamba with
    collect_cache: (conv_state, ssm_state)); used by prefill to build caches.
    """
    aux = jnp.zeros((), jnp.float32)
    if layer_type == "mamba":
        h = norm_apply(params["norm1"], x, cfg)
        if collect_cache:
            y, states = mamba.mamba_apply(params["mixer"], h, cfg, return_state=True)
            return x + y, aux, states
        x = x + mamba.mamba_apply(params["mixer"], h, cfg)
        return x, aux, None

    h = norm_apply(params["norm1"], x, cfg)
    if cfg.use_mla:
        o, kv = attn_mod.mla_apply(params["attn"], h, cfg, positions=positions,
                                   causal=causal)
    else:
        o, kv = attn_mod.attention_apply(params["attn"], h, cfg,
                                         positions=positions, causal=causal)
    x = x + o

    if enc_out is not None:
        hc = norm_apply(params["norm_cross"], x, cfg)
        oc, _ = attn_mod.attention_apply(
            params["cross_attn"], hc, cfg, x_kv=enc_out, causal=False,
            use_rope=False,
        )
        x = x + oc

    h2 = norm_apply(params["norm2"], x, cfg)
    if layer_type == "moe":
        y, aux = moe.moe_apply(params["ffn"], h2, cfg, decode=decode)
    else:
        y = layers.mlp_apply(params["ffn"], h2, act=cfg.act)
    x = x + y
    # Megatron-style sequence-parallel residual stream: the per-layer scan
    # carry (saved for backward) is sharded over the model axis too, which
    # is what lets 32B+ models fit 16 GiB/chip at batch 256×4k.
    if x.shape[1] > 1:
        x = constrain(x, "data", "model", None)
    else:
        x = constrain(x, "data", None, None)
    # bf16 backward stream (§Perf iter 5): halves activation-grad collectives.
    x = layers.grad_cast(x)
    return x, aux, kv


def block_decode_apply(
    params,
    x: jnp.ndarray,
    cfg,
    layer_type: str,
    *,
    cache: dict,
    cache_index,
    cross_len=None,
    length=None,
):
    """One-token decode.  cache is a per-layer dict (see serve.kv_cache);
    ``length`` is the per-slot live token count incl. the new token (None →
    derived from cache_index) — it bounds the decode kernel's KV walk."""
    if layer_type == "mamba":
        y, (conv_s, ssm_s) = mamba.mamba_decode_apply(
            params["mixer"], norm_apply(params["norm1"], x, cfg), cfg,
            conv_state=cache["conv"], ssm_state=cache["ssm"],
        )
        return x + y, {**cache, "conv": conv_s, "ssm": ssm_s}

    h = norm_apply(params["norm1"], x, cfg)
    if cfg.use_mla:
        o, (ckv, krope) = attn_mod.mla_decode_apply(
            params["attn"], h, cfg,
            cache_ckv=cache["ckv"], cache_krope=cache["krope"],
            cache_index=cache_index,
        )
        new_cache = {**cache, "ckv": ckv, "krope": krope}
    else:
        o, (ck, cv) = attn_mod.attention_decode_apply(
            params["attn"], h, cfg,
            cache_k=cache["k"], cache_v=cache["v"], cache_index=cache_index,
            length=length,
        )
        new_cache = {**cache, "k": ck, "v": cv}
    x = x + o

    if "cross_k" in cache:
        hc = norm_apply(params["norm_cross"], x, cfg)
        oc, _ = attn_mod.attention_decode_apply(
            params["cross_attn"], hc, cfg,
            cache_k=cache["cross_k"], cache_v=cache["cross_v"],
            cache_index=cache_index, is_cross=True, cross_len=cross_len,
        )
        x = x + oc

    h2 = norm_apply(params["norm2"], x, cfg)
    if layer_type == "moe":
        y, _ = moe.moe_apply(params["ffn"], h2, cfg, decode=True)
    else:
        y = layers.mlp_apply(params["ffn"], h2, act=cfg.act)
    return x + y, new_cache


def block_paged_decode_apply(
    params,
    x: jnp.ndarray,
    cfg,
    layer_type: str,
    *,
    pool_k,
    pool_v,
    block_tables,
    pos,
    count=None,
    pool_k_fused=None,
    perm=None,
    layer=None,
):
    """Windowed decode of one transformer block against the paged KV pool
    (serve.paged): w = 1 is token decode, w = chunk width is chunked
    prefill.  GQA dense/moe only — the paged layout replaces the ring slab
    cache, the other families keep the slot engine.  With ``layer`` the
    pools are the model's stacks, updated in place at that layer."""
    h = norm_apply(params["norm1"], x, cfg)
    o, (pk, pv, pkf) = attn_mod.attention_decode_paged(
        params["attn"], h, cfg,
        pool_k=pool_k, pool_v=pool_v, block_tables=block_tables,
        cache_index=pos, count=count, pool_k_fused=pool_k_fused, perm=perm,
        layer=layer,
    )
    x = x + o
    h2 = norm_apply(params["norm2"], x, cfg)
    if layer_type == "moe":
        y, _ = moe.moe_apply(params["ffn"], h2, cfg, decode=True)
    else:
        y = layers.mlp_apply(params["ffn"], h2, act=cfg.act)
    return x + y, (pk, pv, pkf)


# ---------------------------------------------------------------------------
# Hybrid (zamba2) shared attention block: fuse(concat(x, x0)) → dense block
# ---------------------------------------------------------------------------


def shared_block_init(key, cfg, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "fuse": layers.linear_init(k1, 2 * cfg.d_model, cfg.d_model, dtype=dtype),
        "block": block_init(k2, cfg, "dense", dtype),
    }


def shared_block_axes(cfg):
    return {
        "fuse": layers.linear_axes(None, None),
        "block": block_axes(cfg, "dense"),
    }


def shared_block_apply(params, x, x0, cfg, *, positions=None):
    h = layers.linear_apply(params["fuse"], jnp.concatenate([x, x0], axis=-1))
    y, _, kv = block_apply(params["block"], h, cfg, "dense",
                           positions=positions, causal=True)
    # Add the block's residual *delta* to the trunk (the block already
    # carries h through its own residuals).
    return x + (y - h), kv


def shared_block_decode_apply(params, x, x0, cfg, *, cache, cache_index):
    h = layers.linear_apply(params["fuse"], jnp.concatenate([x, x0], axis=-1))
    y, new_cache = block_decode_apply(params["block"], h, cfg, "dense",
                                      cache=cache, cache_index=cache_index)
    return x + (y - h), new_cache
