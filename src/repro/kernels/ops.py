"""Jit'd public wrappers around the Pallas kernels.

Handles everything the raw kernels keep out of their grids: GQA flattening,
sequence padding, LSH permutation precompute, scale folding, and the
analytic cost models used by benchmarks and the §Perf roofline corrections.

Both attention entry points are differentiable end-to-end via
``jax.custom_vjp``: the forward kernels emit the logsumexp row statistics,
and the backward runs the fused FA-2-style kernels in
``repro.kernels.backward`` (dQ, dK/dV, and the D = rowsum(dO ∘ O)
precompute) instead of XLA rematerialisation — so training steps stay on
the kernel path (DESIGN.md §Backward).  The DistrAttention backward treats
the LSH permutation as non-differentiable (straight-through): gradients
flow through the Q-sampling gather and the K̂ segment-sum only.

``interpret=None`` (the default everywhere) picks the mode from the
backend: compiled kernels on TPU, interpreter mode elsewhere — no call-site
changes between a CPU host and real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import grouping, lsh
from repro.core.distr_attention import DistrConfig, compute_block_permutations
from repro.kernels import backward as bwd
from repro.kernels import decode as decode_kernels
from repro.kernels import paged_decode as paged_decode_kernels
from repro.kernels.distr_attention import distr_attention_kernel_call
from repro.kernels.flash_attention import flash_attention_kernel_call
from repro.kernels.ssd import ssd_kernel_call
from repro.tune.block_sizes import BlockSizes
from repro.tune.cache import dtype_str as _dtype_str


def default_interpret() -> bool:
    """Compiled Pallas on TPU, interpreter everywhere else (CPU hosts)."""
    return jax.default_backend() != "tpu"


def _pad_seq(x: jnp.ndarray, block: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[2]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x, n


LSE_PAD = 1e30  # padded residual rows: exp(s − LSE_PAD) ≡ 0 kills their grads


def _pad_rows(x: jnp.ndarray, block: int, value: float = 0.0) -> jnp.ndarray:
    """Pad the row axis of per-row residuals (BHq, N) to a block multiple."""
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=value)
    return x


def _flatten_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, h, n, d = x.shape
    return x.reshape(b * h, n, d)


def _gqa_sum(dx_per_q_head: jnp.ndarray, b: int, hkv: int, q_per_kv: int,
             nk_orig: int) -> jnp.ndarray:
    """(B·Hq, Nk_pad, d) per-query-head grads → (B, Hkv, Nk, d)."""
    bhq, nk_pad, d = dx_per_q_head.shape
    out = dx_per_q_head.reshape(b, hkv, q_per_kv, nk_pad, d).sum(axis=2)
    return out[:, :, :nk_orig, :]


# ---------------------------------------------------------------------------
# Exact FA-2 with custom_vjp
# ---------------------------------------------------------------------------


def _flash_fwd_impl(causal, scale, block_q, block_k, interpret, q, k, v,
                    with_residuals):
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    q_per_kv = hq // hkv

    qp, n_orig = _pad_seq(q, block_q)
    kp, kv_len = _pad_seq(k, block_k)
    vp, _ = _pad_seq(v, block_k)

    res = flash_attention_kernel_call(
        _flatten_heads(qp), _flatten_heads(kp), _flatten_heads(vp),
        q_per_kv=q_per_kv, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len,
        interpret=interpret, return_residuals=with_residuals,
    )
    out, lse = res if with_residuals else (res, None)
    return out.reshape(b, hq, -1, d)[:, :, :n_orig, :], lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_attention(causal, scale, blocks, interpret, q, k, v):
    # Primal (inference / non-differentiated) path: skip the LSE residual —
    # it is only consumed by the backward kernels.
    out, _ = _flash_fwd_impl(
        causal, scale, blocks.block_q, blocks.block_k, interpret, q, k, v,
        with_residuals=False,
    )
    return out


def _flash_vjp_fwd(causal, scale, blocks, interpret, q, k, v):
    out, lse = _flash_fwd_impl(
        causal, scale, blocks.block_q, blocks.block_k, interpret, q, k, v,
        with_residuals=True,
    )
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, blocks, interpret, res, do):
    # The backward kernels run their own tuned tiles (``blocks.dq()`` for
    # the dQ kernel, ``blocks.dkv()`` for the dK/dV kernel) — carried in the
    # custom_vjp static args, not in the residuals.  The fwd LSE is padded
    # to the *forward* q-block, so residuals are re-sliced to the live
    # length and re-padded per kernel; dead rows get LSE=+big ⇒ P ≡ 0,
    # contributing nothing to dK/dV.
    q, k, v, o, lse = res
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    q_per_kv = hq // hkv
    nk = k.shape[2]
    do = do.astype(q.dtype)
    lse_n = lse[:, :n]

    blocks = _resolve_bwd_blocks(blocks, q, k, causal, interpret)
    bq_dq, bk_dq = blocks.dq()
    bq_dkv, bk_dkv = blocks.dkv()

    def q_side(block):
        qp, _ = _pad_seq(q, block)
        dop, _ = _pad_seq(do, block)
        op, _ = _pad_seq(o, block)
        return _flatten_heads(qp), _flatten_heads(dop), _flatten_heads(op)

    def kv_side(block):
        kp, _ = _pad_seq(k, block)
        vp, _ = _pad_seq(v, block)
        return _flatten_heads(kp), _flatten_heads(vp)

    qf1, dof1, of1 = q_side(bq_dq)
    kf1, vf1 = kv_side(bk_dq)
    delta = bwd.delta_kernel_call(of1, dof1, block_q=bq_dq, interpret=interpret)
    delta_n = delta[:, :n]
    dq = bwd.flash_dq_kernel_call(
        qf1, kf1, vf1, dof1,
        _pad_rows(lse_n, bq_dq, LSE_PAD), _pad_rows(delta_n, bq_dq),
        q_per_kv=q_per_kv, scale=scale, causal=causal,
        block_q=bq_dq, block_k=bk_dq, kv_len=nk, interpret=interpret,
    )
    if (bq_dkv, bk_dkv) == (bq_dq, bk_dq):
        qf2, dof2, kf2, vf2 = qf1, dof1, kf1, vf1
    else:
        qf2, dof2, _ = q_side(bq_dkv)
        kf2, vf2 = kv_side(bk_dkv)
    dk_h, dv_h = bwd.flash_dkv_kernel_call(
        qf2, kf2, vf2, dof2,
        _pad_rows(lse_n, bq_dkv, LSE_PAD), _pad_rows(delta_n, bq_dkv),
        q_per_kv=q_per_kv, scale=scale, causal=causal,
        block_q=bq_dkv, block_k=bk_dkv, kv_len=nk, interpret=interpret,
    )
    dq = dq.reshape(b, hq, -1, d)[:, :, :n, :].astype(q.dtype)
    dk = _gqa_sum(dk_h, b, hkv, q_per_kv, nk).astype(k.dtype)
    dv = _gqa_sum(dv_h, b, hkv, q_per_kv, nk).astype(v.dtype)
    return dq, dk, dv


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "blocks", "interpret")
)
def _flash_attention_jit(q, k, v, causal, scale, blocks, interpret):
    return _flash_attention(causal, scale, blocks, interpret, q, k, v)


def _resolve_flash_blocks(q, k, causal, interpret, block_q, block_k):
    """Explicit ints win (a partial pin gets the static default for the
    free dim — never a tuned value measured for a different pair); both
    None resolves the forward pair through the autotuner.  Backward tiles
    stay None here and resolve lazily at backward-trace time."""
    if block_q is not None or block_k is not None:
        return BlockSizes.from_pair(block_q or 128, block_k or 128)
    from repro.tune.autotune import resolve_block_sizes

    return resolve_block_sizes(
        "flash", d=q.shape[-1], n=max(q.shape[2], k.shape[2]),
        dtype=_dtype_str(q), causal=causal, interpret=interpret,
    )


def _resolve_bwd_blocks(blocks, q, k, causal, interpret):
    """Fill the backward dQ/dKV tiles at backward-trace time (measure mode
    only): forward-only dispatch — serving — never pays a backward-kernel
    sweep, and training pays it once, when grad tracing first reaches the
    op.  Explicitly-set backward tiles and off/analytic modes pass through
    (``BlockSizes.dq()/dkv()`` fall back to the fwd pair)."""
    if blocks.block_q_dq is not None or blocks.block_q_dkv is not None:
        return blocks
    from repro.tune.autotune import get_autotuner, tune_mode

    if tune_mode() != "measure":
        return blocks
    kw = dict(
        d=q.shape[-1], n=max(q.shape[2], k.shape[2]), dtype=_dtype_str(q),
        causal=causal, interpret=interpret,
    )
    tuner = get_autotuner()
    dq = tuner.resolve_pair("flash_dq", **kw)
    dkv = tuner.resolve_pair("flash_dkv", **kw)
    return blocks.with_(
        block_q_dq=dq[0], block_k_dq=dq[1],
        block_q_dkv=dkv[0], block_k_dkv=dkv[1],
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    blocks: BlockSizes | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Exact FA-2 Pallas kernel, differentiable.  q: (B,Hq,N,d); k,v:
    (B,Hkv,Nk,d).  ``interpret=None`` auto-detects the backend.

    Block sizes: pass ``blocks`` (a full :class:`BlockSizes`, e.g. from the
    autotuner — carries separate backward dQ/dKV tiles) or the legacy
    ``block_q``/``block_k`` pair; ``None`` means auto (REPRO_TUNE)."""
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = default_interpret()
    if blocks is None:
        blocks = _resolve_flash_blocks(q, k, causal, interpret, block_q, block_k)
    return _flash_attention_jit(q, k, v, causal, scale, blocks, interpret)


# ---------------------------------------------------------------------------
# DistrAttention with custom_vjp
# ---------------------------------------------------------------------------


def distr_stage1(cfg, qp, scale, *, hkv: int | None = None):
    """The paper's lightweight pre-kernel stage (§4.8) on a
    ``block_q``-padded q (B, Hq, N_pad, d): per-Q-block LSH permutations +
    Q̂ sampling, with the softmax scale pre-folded.  Returns
    (q_hat (B, Hq, N_pad, d/G*), perms (B, Hq, nq, d)).  ``hkv`` enables
    the shared-KV-perm variant (one permutation per KV group, hashed from
    the group's mean query block).  The one implementation for the
    single-device op *and* the ring (distributed.ring_attention) — the
    grouping decision must never diverge between them."""
    b, hq, n_pad, d = qp.shape
    g = cfg.group_size
    nq_blocks = n_pad // cfg.block_q

    proj = lsh.make_projection(jax.random.PRNGKey(cfg.proj_seed), cfg.block_q)
    if cfg.shared_kv_perm:
        if hkv is None:
            raise ValueError("shared_kv_perm needs the KV head count")
        q_per_kv = hq // hkv
        q_mean = qp.reshape(b, hkv, q_per_kv, n_pad, d).mean(axis=2)
        perms = compute_block_permutations(q_mean, cfg, proj)  # (b, hkv, nq, d)
        perms = jnp.broadcast_to(
            perms[:, :, None], (b, hkv, q_per_kv, nq_blocks, d)
        ).reshape(b, hq, nq_blocks, d)
    else:
        perms = compute_block_permutations(qp, cfg, proj)  # (b, hq, nq, d)
    # Straight-through: the permutation is a fixed discrete grouping choice;
    # no gradient flows into the hash (paper's fixed-grouping semantics).
    perms = jax.lax.stop_gradient(perms)

    q_blocks = qp.reshape(b, hq, nq_blocks, cfg.block_q, d)
    if cfg.estimator == "sample":
        q_hat = grouping.sample_columns(q_blocks, perms, g)
    elif cfg.estimator == "mean":
        q_hat = grouping.mean_columns(q_blocks, perms, g)
    else:
        raise ValueError(f"unknown estimator {cfg.estimator!r}")
    q_hat = (q_hat * scale).reshape(b, hq, n_pad, d // g).astype(qp.dtype)
    return q_hat, perms


def _distr_fwd_impl(cfg, causal, scale, interpret, q, k, v, with_residuals):
    """Returns (out, lse, q_hat_flat, perms) — the kernel-path residuals
    (lse is None on the primal path, which skips emitting it)."""
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    q_per_kv = hq // hkv
    g = cfg.group_size

    qp, n_orig = _pad_seq(q, cfg.block_q)
    kp, kv_len = _pad_seq(k, cfg.block_k)
    vp, _ = _pad_seq(v, cfg.block_k)
    n_pad = qp.shape[2]
    nq_blocks = n_pad // cfg.block_q

    q_hat, perms = distr_stage1(cfg, qp, scale, hkv=hkv)
    q_hat = q_hat.reshape(b * hq, n_pad, d // g)

    res = distr_attention_kernel_call(
        q_hat,
        _flatten_heads(kp),
        _flatten_heads(vp),
        perms.reshape(b * hq, nq_blocks, d),
        q_per_kv=q_per_kv, causal=causal, group_size=g,
        block_q=cfg.block_q, block_k=cfg.block_k, kv_len=kv_len,
        interpret=interpret, return_residuals=with_residuals,
    )
    out, lse = res if with_residuals else (res, None)
    return out.reshape(b, hq, -1, d)[:, :, :n_orig, :], lse, q_hat, perms


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _distr_attention(cfg, causal, scale, interpret, q, k, v):
    out, _, _, _ = _distr_fwd_impl(
        cfg, causal, scale, interpret, q, k, v, with_residuals=False
    )
    return out


def _distr_vjp_fwd(cfg, causal, scale, interpret, q, k, v):
    out, lse, q_hat, perms = _distr_fwd_impl(
        cfg, causal, scale, interpret, q, k, v, with_residuals=True
    )
    return out, (q, k, v, out, lse, q_hat, perms)


def distr_dq_from_dq_hat(estimator, dq_hat, perms, *, block_q, group_size,
                         scale):
    """dQ̂ → dQ: transpose of the Q̂ sampling/mean gather with the
    forward's pre-scale folded in.  dq_hat: (B, Hq, N_pad, d/G*); perms:
    (B, Hq, nq, d) → (B, Hq, N_pad, d) f32.  Shared by the single-device
    ``custom_vjp`` and the ring backward (distributed.ring_attention) so
    the estimator chain rule cannot diverge between them."""
    b, hq, n_pad, dg = dq_hat.shape
    d = perms.shape[-1]
    nq_blocks = n_pad // block_q
    sample_fn = (
        grouping.sample_columns if estimator == "sample"
        else grouping.mean_columns
    )
    blocks_ = (
        dq_hat.astype(jnp.float32).reshape(b, hq, nq_blocks, block_q, dg)
        * scale
    )
    (dq_blocks,) = jax.linear_transpose(
        lambda t: sample_fn(t, perms, group_size),
        jax.ShapeDtypeStruct(
            (b, hq, nq_blocks, block_q, d), jnp.float32
        ),
    )(blocks_)
    return dq_blocks.reshape(b, hq, n_pad, d)


def resolve_distr_bwd_blocks(cfg, *, d, n, dtype, causal, interpret):
    """Backward KV tiles ``(bk_dq, bk_dkv)`` for the distr kernels
    (mirrors ``_resolve_bwd_blocks``).  ``block_q`` is *never* resolved
    here: it is the LSH grouping granularity shared with the forward and
    the saved permutations, and stays pinned (asserted in
    ``Autotuner.resolve_distr_bwd``).  Explicit ``cfg.block_k_bwd`` wins;
    outside measure mode the fwd ``block_k`` carries over.  The one
    resolver for both the single-device custom_vjp (lazy, at
    backward-trace time) and the ring backward (eager, at dispatch, with
    ``n`` = the per-device shard)."""
    if cfg.block_k_bwd is not None:
        return cfg.block_k_bwd, cfg.block_k_bwd
    from repro.tune.autotune import get_autotuner, tune_mode

    if tune_mode() != "measure":
        return cfg.block_k, cfg.block_k
    tuner = get_autotuner()
    kw = dict(
        block_q=cfg.block_q, d=d, n=n, dtype=dtype, group_size=cfg.group_size,
        causal=causal, interpret=interpret, fwd_block_k=cfg.block_k,
    )
    return (
        tuner.resolve_distr_bwd("distr_dq", **kw)[1],
        tuner.resolve_distr_bwd("distr_dkv", **kw)[1],
    )


def _distr_vjp_bwd(cfg, causal, scale, interpret, res, do):
    q, k, v, o, lse, q_hat, perms = res
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    q_per_kv = hq // hkv
    g = cfg.group_size
    dg = d // g
    kv_len = k.shape[2]
    bk_dq, bk_dkv = resolve_distr_bwd_blocks(
        cfg, d=d, n=max(n, kv_len), dtype=_dtype_str(q), causal=causal,
        interpret=interpret,
    )

    dop, n_orig = _pad_seq(do.astype(q.dtype), cfg.block_q)
    op, _ = _pad_seq(o, cfg.block_q)
    n_pad = dop.shape[2]
    nq_blocks = n_pad // cfg.block_q

    def kv_side(block):
        kp, _ = _pad_seq(k, block)
        vp, _ = _pad_seq(v, block)
        return _flatten_heads(kp), _flatten_heads(vp)

    kf1, vf1 = kv_side(bk_dq)
    kf2, vf2 = (kf1, vf1) if bk_dkv == bk_dq else kv_side(bk_dkv)
    dof, of = _flatten_heads(dop), _flatten_heads(op)
    perm_f = perms.reshape(b * hq, nq_blocks, d)

    delta = bwd.delta_kernel_call(of, dof, block_q=cfg.block_q, interpret=interpret)
    dq_hat = bwd.distr_dq_kernel_call(
        q_hat, kf1, vf1, perm_f, dof, lse, delta,
        q_per_kv=q_per_kv, causal=causal, group_size=g,
        block_q=cfg.block_q, block_k=bk_dq, kv_len=kv_len,
        interpret=interpret,
    )
    dk_h, dv_h = bwd.distr_dkv_kernel_call(
        q_hat, kf2, vf2, perm_f, dof, lse, delta,
        q_per_kv=q_per_kv, causal=causal, group_size=g,
        block_q=cfg.block_q, block_k=bk_dkv, kv_len=kv_len,
        interpret=interpret,
    )

    dq_full = distr_dq_from_dq_hat(
        cfg.estimator, dq_hat.reshape(b, hq, n_pad, dg), perms,
        block_q=cfg.block_q, group_size=g, scale=scale,
    )
    dq = dq_full[:, :, :n_orig, :].astype(q.dtype)
    dk = _gqa_sum(dk_h, b, hkv, q_per_kv, kv_len).astype(k.dtype)
    dv = _gqa_sum(dv_h, b, hkv, q_per_kv, kv_len).astype(v.dtype)
    return dq, dk, dv


_distr_attention.defvjp(_distr_vjp_fwd, _distr_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("cfg", "causal", "scale", "interpret"))
def _distr_attention_jit(q, k, v, cfg, causal, scale, interpret):
    return _distr_attention(cfg, causal, scale, interpret, q, k, v)


def distr_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: DistrConfig = DistrConfig(),
    *,
    causal: bool = False,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """DistrAttention Pallas kernel (paper §3.3 + FA-2 integration),
    differentiable under straight-through permutations.

    Stage 1 (outside kernel, XLA): LSH permutations per Q block + Q sampling.
    Stage 2 (kernel): per-KV-block fusion + reduced-d flash attention.

    ``cfg.block_q``/``block_k`` may be None (auto): resolved here through
    the autotuner under the Pallas "distr" kind.
    """
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = default_interpret()
    cfg = cfg.resolved(
        q.shape[-1], max(q.shape[2], k.shape[2]), dtype=_dtype_str(q),
        causal=causal, xla=False, interpret=interpret,
    )
    return _distr_attention_jit(q, k, v, cfg, causal, scale, interpret)


# ---------------------------------------------------------------------------
# Flash-decoding (split-K) — the serve-path hot op
# ---------------------------------------------------------------------------


def _pack_gqa_rows(q: jnp.ndarray, hkv: int) -> tuple[jnp.ndarray, int]:
    """(B, Hq, q_len, d) → (B, Hkv, rows_pad, d): all query heads sharing a
    KV head (× q_len) packed into the kernel's row dimension, padded to the
    sublane width.  Returns (packed, rows_live)."""
    b, hq, q_len, d = q.shape
    rows_live = (hq // hkv) * q_len
    packed = q.reshape(b, hkv, rows_live, d)
    pad = (-rows_live) % decode_kernels.ROW_ALIGN
    if pad:
        packed = jnp.pad(packed, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return packed, rows_live


def _unpack_gqa_rows(o: jnp.ndarray, rows_live: int, hq: int) -> jnp.ndarray:
    """(B, Hkv, rows_pad, d) → (B, Hq, q_len, d)."""
    b, hkv, _, d = o.shape
    q_len = rows_live * hkv // hq
    return o[:, :, :rows_live, :].reshape(b, hq, q_len, d)


def _decode_impl(q_packed, k_score, v, lengths, *, hq, rows_live, scale,
                 block_k, q_len, interpret):
    nk = k_score.shape[2]
    block_k = min(block_k, nk)
    pad = (-nk) % block_k
    if pad:  # dead tail: clamped index maps keep it out of the KV stream
        k_score = jnp.pad(k_score, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    o, m, l = decode_kernels.decode_kernel_call(
        q_packed, k_score, v, lengths,
        scale=scale, block_k=block_k, q_len=q_len, interpret=interpret,
    )
    return _unpack_gqa_rows(
        decode_kernels.merge_splits(o, m, l), rows_live, hq
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "q_len", "interpret")
)
def _decode_attention_jit(q, k, v, lengths, scale, block_k, q_len, interpret):
    b, hq, _, d = q.shape
    hkv = k.shape[1]
    q_packed, rows_live = _pack_gqa_rows(q, hkv)
    out = _decode_impl(
        q_packed, k, v, lengths, hq=hq, rows_live=rows_live, scale=scale,
        block_k=block_k, q_len=q_len, interpret=interpret,
    )
    return out.astype(q.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "scale", "block_k", "q_len", "interpret"),
)
def _decode_attention_fused_jit(q, k_fused, v, perm, lengths, group_size,
                                scale, block_k, q_len, interpret):
    hq, hkv = q.shape[1], k_fused.shape[1]
    # Sample Q columns under the layer's static per-KV-head permutation —
    # decode has no per-Q-block LSH stage (serve.kv_cache.static_perms).
    q_s = grouping.sample_q_heads(q, perm, group_size)
    q_packed, rows_live = _pack_gqa_rows(q_s, hkv)
    out = _decode_impl(
        q_packed, k_fused, v, lengths, hq=hq, rows_live=rows_live,
        scale=scale, block_k=block_k, q_len=q_len, interpret=interpret,
    )
    return out.astype(q.dtype)


def _decode_lengths(lengths, b: int, nk: int) -> jnp.ndarray:
    if lengths is None:
        lengths = jnp.full((b,), nk, jnp.int32)
    return jnp.minimum(jnp.asarray(lengths, jnp.int32), nk)


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    lengths: jnp.ndarray | None = None,
    k_fused: jnp.ndarray | None = None,
    perm: jnp.ndarray | None = None,
    group_size: int = 1,
    scale: float | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Split-K flash-decoding over a KV cache (kernels/decode.py).

    q: (B, Hq, q_len, d) with q_len small (1, or a short speculative
    window); k, v: (B, Hkv, S, d) cache; ``lengths``: (B,) live token
    counts — the kernel grid only streams ``ceil(length/block_k)`` KV
    blocks per slot (None ⇒ all S live).

    Distr fused-K̂ variant: pass ``k_fused`` (B, Hkv, S, d/G*), the layer's
    static ``perm`` (Hkv, d) and ``group_size`` — the score stage streams
    the narrow fused cache (column-sampled Q), the value stage full V; raw
    ``k`` may be None (it stays cold on the serve path).  ``scale`` always
    refers to the full head dim (default 1/√d).  ``interpret=None``
    auto-detects the backend like every other op here.
    """
    d = v.shape[-1]
    q_len = q.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = default_interpret()
    if block_k is None:
        # Auto: tuned split length for this cache capacity (REPRO_TUNE).
        from repro.tune.autotune import resolve_decode_block

        nk_cache = (k_fused if k_fused is not None else k).shape[2]
        block_k = resolve_decode_block(
            d=d, n=nk_cache, dtype=_dtype_str(v),
            group_size=group_size if k_fused is not None else 1,
            interpret=interpret,
        )
    if k_fused is not None:
        if perm is None or group_size <= 1:
            raise ValueError("k_fused needs perm and group_size > 1")
        lengths = _decode_lengths(lengths, q.shape[0], k_fused.shape[2])
        return _decode_attention_fused_jit(
            q, k_fused, v, perm, lengths, group_size, scale, block_k, q_len,
            interpret,
        )
    lengths = _decode_lengths(lengths, q.shape[0], k.shape[2])
    return _decode_attention_jit(
        q, k, v, lengths, scale, block_k, q_len, interpret
    )


# ---------------------------------------------------------------------------
# Paged (block-table) flash-decoding — the paged serve-path hot op
# ---------------------------------------------------------------------------


def _paged_decode_impl(q_packed, k_pool, v_pool, block_tables, lengths,
                       layer, *, hq, rows_live, scale, q_len, interpret):
    o, m, l = paged_decode_kernels.paged_decode_kernel_call(
        q_packed, k_pool, v_pool, block_tables, lengths,
        scale=scale, q_len=q_len, layer=layer, interpret=interpret,
    )
    return _unpack_gqa_rows(
        decode_kernels.merge_splits(o, m, l), rows_live, hq
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "q_len", "interpret")
)
def _paged_decode_attention_jit(q, k_pool, v_pool, block_tables, lengths,
                                layer, scale, q_len, interpret):
    hq, hkv = q.shape[1], v_pool.shape[-1] // q.shape[-1]
    q_packed, rows_live = _pack_gqa_rows(q, hkv)
    out = _paged_decode_impl(
        q_packed, k_pool, v_pool, block_tables, lengths, layer, hq=hq,
        rows_live=rows_live, scale=scale, q_len=q_len, interpret=interpret,
    )
    return out.astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("group_size", "scale", "q_len", "interpret")
)
def _paged_decode_attention_fused_jit(q, k_fused_pool, v_pool, perm,
                                      block_tables, lengths, layer,
                                      group_size, scale, q_len, interpret):
    hq, hkv = q.shape[1], v_pool.shape[-1] // q.shape[-1]
    # Static per-KV-head permutation, same as the contiguous fused decode —
    # paged decode has no per-Q-block LSH stage (serve.kv_cache.static_perms).
    q_s = grouping.sample_q_heads(q, perm, group_size)
    q_packed, rows_live = _pack_gqa_rows(q_s, hkv)
    out = _paged_decode_impl(
        q_packed, k_fused_pool, v_pool, block_tables, lengths, layer, hq=hq,
        rows_live=rows_live, scale=scale, q_len=q_len, interpret=interpret,
    )
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray | None,
    v_pool: jnp.ndarray,
    *,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    k_fused_pool: jnp.ndarray | None = None,
    perm: jnp.ndarray | None = None,
    group_size: int = 1,
    scale: float | None = None,
    layer: jnp.ndarray | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Block-table split-K flash-decoding over a paged KV pool
    (kernels/paged_decode.py).

    q: (B, Hq, q_len, d) with q_len small (1, or a chunked-prefill window);
    k_pool, v_pool: (P, block_size, Hkv·d) shared token-major block pools
    (``kernels.paged_decode.token_major``), or the model's (L, P,
    block_size, Hkv·d) stacks with a traced ``layer``;
    ``block_tables``: (B, max_blocks) int32 physical block ids (logical
    block j of request b lives at ``block_tables[b, j]``); ``lengths``:
    (B,) live token counts — the kernel streams ``ceil(length/block_size)``
    pool blocks per request through scalar-prefetched, clamped index maps.

    Distr fused-K̂ variant: pass ``k_fused_pool`` (P, block_size,
    Hkv·d/G*), the layer's static ``perm`` (Hkv, d) and ``group_size`` — the
    score stage streams the narrow fused pool (column-sampled Q), the value
    stage full V; ``k_pool`` may be None (raw K stays cold on the paged
    serve path).  ``scale`` always refers to the full head dim (default
    1/√d).  ``interpret=None`` auto-detects the backend.
    """
    d = q.shape[-1]
    q_len = q.shape[2]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = default_interpret()
    block_size = v_pool.shape[-2]
    capacity = block_tables.shape[1] * block_size
    if lengths is None:
        # None ⇒ every table position live (contiguous-op convention).
        lengths = jnp.full((q.shape[0],), capacity, jnp.int32)
    else:
        # Deliberately NOT clamped to capacity: a padded chunked-prefill
        # window may overhang it (lengths = pos + w with the last rows
        # dead), and clamping would shift the LIVE rows' causal band
        # ``col < length − (q_len−1−i)`` downward — silently dropping
        # their most recent context.  The kernel is safe unclamped: the
        # index map's split id never exceeds the table width (jj ≤ j),
        # and live rows' bands always land within capacity.
        lengths = jnp.asarray(lengths, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if k_fused_pool is not None:
        if perm is None or group_size <= 1:
            raise ValueError("k_fused_pool needs perm and group_size > 1")
        return _paged_decode_attention_fused_jit(
            q, k_fused_pool, v_pool, perm, block_tables, lengths, layer,
            group_size, scale, q_len, interpret,
        )
    return _paged_decode_attention_jit(
        q, k_pool, v_pool, block_tables, lengths, layer, scale, q_len,
        interpret,
    )


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, a, b, c, chunk, interpret):
    bsz, n, h, p = x.shape
    g, s = b.shape[2], b.shape[3]
    heads_per_group = h // g
    pad = (-n) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_pad = x.shape[1]

    xr = x.transpose(0, 2, 1, 3).reshape(bsz * h, n_pad, p)
    ar = a.transpose(0, 2, 1).reshape(bsz * h, n_pad, 1)
    br = b.transpose(0, 2, 1, 3).reshape(bsz * g, n_pad, s)
    cr = c.transpose(0, 2, 1, 3).reshape(bsz * g, n_pad, s)

    y = ssd_kernel_call(
        xr, ar, br, cr, heads_per_group=heads_per_group, chunk=chunk,
        interpret=interpret,
    )
    y = y.reshape(bsz, h, n_pad, p).transpose(0, 2, 1, 3)
    return y[:, :n, :, :]


def ssd(
    x: jnp.ndarray,
    a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    *,
    chunk: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Mamba-2 SSD.  x: (B,N,H,P); a: (B,N,H); b,c: (B,N,G,S)."""
    if interpret is None:
        interpret = default_interpret()
    return _ssd_jit(x, a, b, c, chunk, interpret)


# ---------------------------------------------------------------------------
# Analytic cost models (benchmarks + roofline corrections).
# ---------------------------------------------------------------------------


def attention_cost(
    b: int,
    hq: int,
    n: int,
    nk: int,
    d: int,
    *,
    causal: bool = False,
    group_size: int = 1,
    block_q: int = 128,
) -> dict:
    """FLOPs / bytes model of (Distr)FlashAttention, forward AND backward.

    Forward keys model one fused forward pass: MXU matmul FLOPs, VPU fusion
    adds, and HBM bytes (bf16 in/out, the flash structure never materialises
    S/P).  ``bwd_*`` keys model the kernels/backward.py pass: the dQ kernel
    recomputes S and runs dP, dQ; the dK/dV kernel recomputes S and runs dP,
    dV, dK; plus the D = rowsum(dO ∘ O) precompute.  Score-space matmuls
    (S, dQ, dK) contract over d/G*; context-space ones (dP, dV) over the
    full d.  ``group_size=1`` = exact FA-2.
    """
    frac = 0.5 * (1 + 1 / max(nk // max(block_q, 1), 1)) if causal else 1.0
    d_eff = d // group_size
    score_mm = 2 * b * hq * n * nk * d_eff * frac  # one reduced-d matmul
    full_mm = 2 * b * hq * n * nk * d * frac  # one full-d matmul
    qk_flops = score_mm
    pv_flops = full_mm
    softmax_flops = 4 * b * hq * n * nk * frac  # exp, max, sum, scale
    # K fusion: for each (q-block, kv element) a d-length permuted add chain.
    fusion_adds = (
        b * hq * (n // max(block_q, 1)) * nk * d * frac if group_size > 1 else 0
    )
    lsh_flops = (
        2 * b * hq * (n // max(block_q, 1)) * lsh.N_PRIME * block_q * d
        if group_size > 1
        else 0
    )
    w = 2  # bf16
    io_bytes = w * (
        b * hq * n * ((d + d // group_size) if group_size > 1 else d)  # Q (+Q̂)
        # K̂ is (re)built inside the kernel and never leaves VMEM: 0 bytes.
        + 2 * b * hq * nk * d  # K, V read (per-head upper bound)
        + b * hq * n * d  # O write
    )

    # ---- backward (kernels/backward.py structure) ----------------------
    # dq kernel: S recompute (d_eff) + dP (d) + dQ (d_eff)
    # dkv kernel: S recompute (d_eff) + dP (d) + dV (d) + dK (d_eff)
    bwd_mxu_flops = 4 * score_mm + 3 * full_mm
    # P from saved LSE (exp) twice + dS = P∘(dP−D) twice + D precompute.
    bwd_vpu_flops = 6 * b * hq * n * nk * frac + 2 * b * hq * n * d
    # K̂ re-fused in both backward kernels; dK̂ replication adds back to d.
    bwd_fusion_adds = 3 * fusion_adds
    bwd_io_bytes = w * (
        2 * b * hq * n * ((d + d // group_size) if group_size > 1 else d)  # Q(+Q̂) ×2 kernels
        + 4 * b * hq * nk * d  # K, V read in both kernels
        + 4 * b * hq * n * d  # dO read ×2 kernels + O + dO reads (delta)
    ) + 4 * (
        # LSE + D are per-row f32 scalars in HBM: one write each (fwd kernel /
        # delta kernel) + one read each in both backward kernels = 6n.  The
        # implementation matches (kernels store (BHq, N) f32 and re-broadcast
        # in-kernel, DESIGN.md §Backward) — no lane-replication factor.
        6 * b * hq * n
        + b * hq * n * d  # dQ write, f32
        + 2 * b * hq * nk * d  # per-q-head dK, dV writes, f32
    )

    return {
        "qk_flops": qk_flops,
        "pv_flops": pv_flops,
        "softmax_flops": softmax_flops,
        "fusion_adds": fusion_adds,
        "lsh_flops": lsh_flops,
        "mxu_flops": qk_flops + pv_flops,
        "total_flops": qk_flops + pv_flops + softmax_flops + fusion_adds + lsh_flops,
        "hbm_bytes": io_bytes,
        "bwd_mxu_flops": bwd_mxu_flops,
        "bwd_total_flops": bwd_mxu_flops + bwd_vpu_flops + bwd_fusion_adds,
        "bwd_hbm_bytes": bwd_io_bytes,
        "fwd_bwd_mxu_flops": qk_flops + pv_flops + bwd_mxu_flops,
        "fwd_bwd_hbm_bytes": io_bytes + bwd_io_bytes,
    }


def ssd_cost(b: int, n: int, h: int, p: int, s: int, *, chunk: int = 64) -> dict:
    """FLOPs model of chunked SSD forward."""
    nc = n // chunk
    intra = 2 * b * h * nc * (chunk * chunk * s + chunk * chunk * p)
    inter = 2 * b * h * nc * (chunk * s * p * 2)
    return {"total_flops": intra + inter, "mxu_flops": intra + inter}
