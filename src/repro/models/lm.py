"""Full model assembly: init / train forward / prefill / decode for every
assigned architecture family.

Layers are parameter-stacked and driven with ``lax.scan`` so the lowered HLO
stays compact at 60-80 layers (essential for the 512-device dry-run compile)
and per-layer remat falls out naturally.  Heterogeneous stacks (DeepSeek's
first dense layer, zamba2's mamba/shared-attention interleave) are split into
multiple homogeneous scans.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers, transformer
from repro.models.layers import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stacked_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _stack_axes(axes_tree):
    """Prepend the layer-stack dim (unsharded) to every leaf's axes."""
    return jax.tree_util.tree_map(
        lambda t: (None,) + tuple(t),
        axes_tree,
        is_leaf=lambda t: isinstance(t, tuple),
    )


def _hybrid_layout(cfg) -> tuple[int, int]:
    """(n_groups, n_tail) for the mamba/shared-attn interleave."""
    n_groups = cfg.n_layers // cfg.attn_every
    n_tail = cfg.n_layers - n_groups * cfg.attn_every
    return n_groups, n_tail


def init_params(key, cfg, dtype=None):
    """Random weights in ``dtype``, by default ``cfg.param_dtype`` (f32:
    the master copy training updates)."""
    dtype = jnp.dtype(cfg.param_dtype if dtype is None else dtype)
    ks = iter(jax.random.split(key, 16))
    params: dict = {
        "embed": layers.embedding_init(next(ks), cfg.padded_vocab, cfg.d_model, dtype)
    }
    if cfg.pos == "learned":
        params["pos_embed"] = layers.embedding_init(
            next(ks), cfg.learned_pos_len, cfg.d_model, dtype
        )

    if cfg.family in ("dense",):
        params["blocks"] = _stacked_init(
            lambda k: transformer.block_init(k, cfg, "dense", dtype), next(ks), cfg.n_layers
        )
    elif cfg.family == "moe":
        if cfg.first_dense_layers:
            dense_cfg = cfg
            params["dense_blocks"] = _stacked_init(
                lambda k: transformer.block_init(k, dense_cfg, "dense", dtype),
                next(ks), cfg.first_dense_layers,
            )
        params["blocks"] = _stacked_init(
            lambda k: transformer.block_init(k, cfg, "moe", dtype),
            next(ks), cfg.n_layers - cfg.first_dense_layers,
        )
    elif cfg.family == "ssm":
        params["blocks"] = _stacked_init(
            lambda k: transformer.block_init(k, cfg, "mamba", dtype), next(ks), cfg.n_layers
        )
    elif cfg.family == "hybrid":
        n_groups, n_tail = _hybrid_layout(cfg)
        group_key = next(ks)

        def group_init(k):
            return _stacked_init(
                lambda kk: transformer.block_init(kk, cfg, "mamba", dtype),
                k, cfg.attn_every,
            )

        params["groups"] = _stacked_init(group_init, group_key, n_groups)
        if n_tail:
            params["tail"] = _stacked_init(
                lambda k: transformer.block_init(k, cfg, "mamba", dtype), next(ks), n_tail
            )
        params["shared"] = [
            transformer.shared_block_init(next(ks), cfg, dtype)
            for _ in range(cfg.n_shared_attn_blocks)
        ]
    elif cfg.family == "encdec":
        params["enc_blocks"] = _stacked_init(
            lambda k: transformer.block_init(k, cfg, "dense", dtype),
            next(ks), cfg.n_encoder_layers or cfg.n_layers,
        )
        params["enc_norm"] = (
            layers.rmsnorm_init(cfg.d_model, dtype)
            if cfg.norm == "rmsnorm"
            else layers.layernorm_init(cfg.d_model, dtype)
        )
        params["blocks"] = _stacked_init(
            lambda k: transformer.block_init(k, cfg, "dense", dtype, cross=True),
            next(ks), cfg.n_layers,
        )
    else:
        raise ValueError(f"unknown family {cfg.family!r}")

    params["final_norm"] = (
        layers.rmsnorm_init(cfg.d_model, dtype)
        if cfg.norm == "rmsnorm"
        else layers.layernorm_init(cfg.d_model, dtype)
    )
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.linear_init(
            next(ks), cfg.d_model, cfg.padded_vocab, dtype=dtype
        )
    return params


def init_serving_params(key, cfg):
    """Random weights for serving: held in ``cfg.compute_dtype`` (bf16 at
    every published width, half the bytes of the f32 training copy) and
    built on the device in one program."""
    return jax.jit(
        functools.partial(
            init_params, cfg=cfg, dtype=jnp.dtype(cfg.compute_dtype)
        )
    )(key)


def param_axes(cfg):
    axes: dict = {"embed": layers.embedding_axes()}
    if cfg.pos == "learned":
        axes["pos_embed"] = layers.embedding_axes()
    if cfg.family == "dense":
        axes["blocks"] = _stack_axes(transformer.block_axes(cfg, "dense"))
    elif cfg.family == "moe":
        if cfg.first_dense_layers:
            axes["dense_blocks"] = _stack_axes(transformer.block_axes(cfg, "dense"))
        axes["blocks"] = _stack_axes(transformer.block_axes(cfg, "moe"))
    elif cfg.family == "ssm":
        axes["blocks"] = _stack_axes(transformer.block_axes(cfg, "mamba"))
    elif cfg.family == "hybrid":
        axes["groups"] = _stack_axes(_stack_axes(transformer.block_axes(cfg, "mamba")))
        n_groups, n_tail = _hybrid_layout(cfg)
        if n_tail:
            axes["tail"] = _stack_axes(transformer.block_axes(cfg, "mamba"))
        axes["shared"] = [
            transformer.shared_block_axes(cfg) for _ in range(cfg.n_shared_attn_blocks)
        ]
    elif cfg.family == "encdec":
        axes["enc_blocks"] = _stack_axes(transformer.block_axes(cfg, "dense"))
        axes["enc_norm"] = (
            layers.rmsnorm_axes() if cfg.norm == "rmsnorm" else layers.layernorm_axes()
        )
        axes["blocks"] = _stack_axes(transformer.block_axes(cfg, "dense", cross=True))
    axes["final_norm"] = (
        layers.rmsnorm_axes() if cfg.norm == "rmsnorm" else layers.layernorm_axes()
    )
    if not cfg.tie_embeddings:
        axes["lm_head"] = layers.linear_axes(None, "vocab")
    return axes


# ---------------------------------------------------------------------------
# scan machinery
# ---------------------------------------------------------------------------


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = None  # full remat
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


def _scan_blocks(blocks, x, cfg, layer_type, *, positions=None, causal=True,
                 enc_out=None, collect_cache=False):
    def body(carry, layer_params):
        h, aux_sum = carry
        h, aux, kv = transformer.block_apply(
            layer_params, h, cfg, layer_type,
            positions=positions, causal=causal, enc_out=enc_out,
            collect_cache=collect_cache,
        )
        return (h, aux_sum + aux), kv

    body = _remat(body, cfg)
    (x, aux), kvs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux, kvs


# ---------------------------------------------------------------------------
# forward (train / prefill trunk)
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg, tokens, patches=None, frames=None):
    """→ (x, positions, n_prefix) where n_prefix = non-text prefix length."""
    compute = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    if cfg.family == "encdec":
        x = layers.embedding_apply(params["embed"], tokens, compute)
    elif patches is not None:
        tok_emb = layers.embedding_apply(params["embed"], tokens, compute)
        x = jnp.concatenate([patches.astype(compute), tok_emb], axis=1)
    else:
        x = layers.embedding_apply(params["embed"], tokens, compute)
    b, n = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(n), (b, n))
    if cfg.pos == "learned":
        x = x + layers.embedding_apply(params["pos_embed"], positions, compute)
    n_prefix = 0 if patches is None else patches.shape[1]
    x = constrain(x, "data", None, None)
    return x, positions, n_prefix


def _encode(params, cfg, frames):
    compute = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    x = frames.astype(compute)  # stub frontend: precomputed frame embeddings
    b, n = x.shape[0], x.shape[1]
    if cfg.pos == "learned":
        pos = jnp.broadcast_to(jnp.arange(n), (b, n))
        x = x + layers.embedding_apply(params["pos_embed"], pos, compute)
    x, _, _ = _scan_blocks(params["enc_blocks"], x, cfg, "dense", causal=False)
    return transformer.norm_apply(params["enc_norm"], x, cfg)


def backbone(params, cfg, tokens, *, patches=None, frames=None,
             collect_cache=False):
    """Shared trunk → (hidden, aux, cache_parts, n_prefix)."""
    cache_parts: dict = {}
    x, positions, n_prefix = _embed_inputs(params, cfg, tokens, patches, frames)

    if cfg.family == "encdec":
        enc_out = _encode(params, cfg, frames)
        cache_parts["enc_out"] = enc_out
        x, aux, kvs = _scan_blocks(
            params["blocks"], x, cfg, "dense", positions=positions,
            causal=True, enc_out=enc_out, collect_cache=collect_cache,
        )
        cache_parts["kv"] = kvs if collect_cache else None
    elif cfg.family == "dense":
        x, aux, kvs = _scan_blocks(
            params["blocks"], x, cfg, "dense", positions=positions,
            collect_cache=collect_cache,
        )
        cache_parts["kv"] = kvs if collect_cache else None
    elif cfg.family == "moe":
        aux = jnp.zeros((), jnp.float32)
        kv_list = []
        if cfg.first_dense_layers:
            x, aux_d, kvs_d = _scan_blocks(
                params["dense_blocks"], x, cfg, "dense", positions=positions,
                collect_cache=collect_cache,
            )
            aux += aux_d
            kv_list.append(kvs_d)
        x, aux_m, kvs_m = _scan_blocks(
            params["blocks"], x, cfg, "moe", positions=positions,
            collect_cache=collect_cache,
        )
        aux += aux_m
        kv_list.append(kvs_m)
        if collect_cache:
            cache_parts["kv"] = (
                jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *kv_list
                )
                if len(kv_list) > 1
                else kv_list[0]
            )
    elif cfg.family == "ssm":
        x, aux, states = _scan_blocks(
            params["blocks"], x, cfg, "mamba", collect_cache=collect_cache
        )
        cache_parts["ssm"] = states if collect_cache else None
    elif cfg.family == "hybrid":
        x0 = x  # trunk input for the shared blocks' concat skip
        aux = jnp.zeros((), jnp.float32)
        n_groups, n_tail = _hybrid_layout(cfg)

        def mamba_step(carry, layer_params):
            h, aux_sum = carry
            h, aux_l, states = transformer.block_apply(
                layer_params, h, cfg, "mamba", collect_cache=collect_cache
            )
            return (h, aux_sum + aux_l), states

        mamba_step = _remat(mamba_step, cfg)
        branches = [
            functools.partial(
                transformer.shared_block_apply, sp, cfg=cfg, positions=positions
            )
            for sp in params["shared"]
        ]

        def group_body(carry, inputs):
            h, aux_sum = carry
            group_params, gi = inputs
            (h, aux_sum), states = jax.lax.scan(
                mamba_step, (h, aux_sum), group_params
            )
            h, kv = jax.lax.switch(
                gi % cfg.n_shared_attn_blocks,
                [lambda hh, bb=bb: bb(hh, x0) for bb in branches],
                h,
            )
            return (h, aux_sum), (states, kv)

        (x, aux), (g_states, g_kv) = jax.lax.scan(
            group_body,
            (x, aux),
            (params["groups"], jnp.arange(n_groups)),
        )
        if n_tail:
            x, aux_t, t_states = _scan_blocks(
                params["tail"], x, cfg, "mamba", collect_cache=collect_cache
            )
            aux += aux_t
        else:
            t_states = None
        if collect_cache:
            cache_parts["ssm_groups"] = g_states
            cache_parts["shared_kv"] = g_kv
            cache_parts["ssm_tail"] = t_states
    else:
        raise ValueError(cfg.family)

    x = transformer.norm_apply(params["final_norm"], x, cfg)
    return x, aux, cache_parts, n_prefix


def logits_fn(params, cfg, hidden):
    if cfg.tie_embeddings:
        logits = layers.embedding_logits(params["embed"], hidden)
    else:
        logits = layers.linear_apply(params["lm_head"], hidden)
    if cfg.padded_vocab != cfg.vocab:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return constrain(logits, "data", None, "model")


def forward(params, cfg, tokens, *, patches=None, frames=None):
    """Train/eval forward → (logits, aux)."""
    hidden, aux, _, n_prefix = backbone(params, cfg, tokens,
                                        patches=patches, frames=frames)
    if n_prefix:
        hidden = hidden[:, n_prefix:]
    return logits_fn(params, cfg, hidden), aux


def loss_fn(params, cfg, batch):
    """Cross-entropy next-token loss (+MoE aux, +z-loss) → (loss, metrics)."""
    logits, aux = forward(
        params, cfg, batch["tokens"],
        patches=batch.get("patches"), frames=batch.get("frames"),
    )
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = (labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / denom
    zloss = ((jax.nn.logsumexp(logits, axis=-1) ** 2) * mask).sum() / denom
    total = ce + cfg.router_aux_weight * aux + 1e-4 * zloss
    return total, {"ce": ce, "aux": aux, "zloss": zloss}
