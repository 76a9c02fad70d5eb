"""Unified attention dispatch — the framework-facing entry point.

``AttentionConfig`` selects the implementation:

  reference    — naive exact softmax oracle
  xla_flash    — FA-2 blockwise exact, pure JAX (XLA path)
  distr        — DistrAttention, pure JAX (XLA path; dry-run default)
  pallas_flash — Pallas TPU FA-2 kernel (interpret auto-detected per backend)
  pallas_distr — Pallas TPU DistrAttention kernel (interpret auto-detected)

Models call :func:`attend` and never touch implementations directly, so a
single config flag flips an architecture between exact and DistrAttention —
the paper's "flexibility" knob (speed vs accuracy via group_size).

The Pallas paths are differentiable (``kernels.ops`` wires ``custom_vjp``
to the fused FA-2-style backward kernels), so training under
``pallas_flash`` / ``pallas_distr`` runs the kernel path end-to-end instead
of the ``jax.checkpoint``-scan XLA fallback (DESIGN.md §Backward).

They also scale past one device's HBM: with ``context_axis`` set and an
active mesh carrying that axis, dispatch goes to
``distributed.ring_attention`` — ring sequence-parallel attention over the
same kernels (DESIGN.md §Context parallelism).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

from repro.core.distr_attention import DistrConfig, distr_attention
from repro.core.flash_reference import blockwise_flash_reference, reference_attention
# Leaf imports only (no repro deps): the resolver itself is imported lazily in
# resolve_attention_blocks to keep repro.core ↔ repro.tune import-order-free.
from repro.tune.block_sizes import BlockSizes
from repro.tune.cache import dtype_str as _dtype_str

IMPLS = ("reference", "xla_flash", "distr", "pallas_flash", "pallas_distr")

# Resolution kind per impl for the block-size autotuner (repro.tune); the
# XLA path is keyed separately from the Pallas kernel — same analytic
# search space, different measured optimum.  The distr impls are absent:
# their dispatch reads DistrConfig's blocks, resolved via
# DistrConfig.resolved (kinds "xla_distr" / "distr").
_TUNE_KIND = {
    "xla_flash": "xla_flash",
    "pallas_flash": "flash",
}


@dataclass(frozen=True)
class AttentionConfig:
    impl: str = "xla_flash"
    distr: DistrConfig = field(default_factory=DistrConfig)
    # Kernel block sizes for the exact paths (distr block sizes live in
    # DistrConfig so the paper's (l, m) study has one home).  ``None`` means
    # "auto": resolved at dispatch by the block-size autotuner according to
    # REPRO_TUNE (off → static 128, analytic → paper §3.3.1 rule, measure →
    # measured best from the persistent cache; DESIGN.md §Autotuning).
    block_q: int | None = None
    block_k: int | None = None
    # Decode split-K length — a separate knob from the fwd KV tile (pinning
    # prefill tiles must not override the decode split's own tuning).
    # None = auto (REPRO_TUNE, keyed per cache capacity).
    block_k_decode: int | None = None
    # Pallas interpret mode: None = auto (compiled on TPU, interpreter on
    # the CPU container); set explicitly only to force one mode.
    interpret: bool | None = None
    # Context parallelism: name of the mesh axis the sequence dimension is
    # ring-sharded over.  When set and the active mesh has that axis (size
    # > 1), the Pallas impls dispatch to distributed.ring_attention — Q/K/V
    # shard on the sequence axis, KV rotates hop-by-hop, partial (O, LSE)
    # merge online — so max sequence length scales with ring size instead
    # of HBM per chip.  Short sequences (< ring size × 128) stay on one
    # device: a ring hop is not worth its ppermute below a full lane tile.
    # For model-integrated use, name the mesh axis
    # distributed.sharding.CONTEXT_AXIS ("context"): the built-in sharding
    # rules special-case that literal to keep the batch dim off the ring.
    context_axis: str | None = None
    # Beyond-paper: serve-side fused-K̂ decode cache under a static
    # permutation (see serve.kv_cache); cuts K-cache read bytes by 1/G*.
    distr_decode: bool = False

    def with_impl(self, impl: str) -> "AttentionConfig":
        return replace(self, impl=impl)

    def degraded(self, group_size: int) -> "AttentionConfig":
        """The overload-degradation dial (serve.degrade): this config with
        prefill switched onto DistrAttention at grouping fraction
        1/``group_size``.  ``group_size ≤ 1`` returns the config unchanged
        (the engine's exact path — degradation is fully reversible).  The
        Pallas impls degrade to the Pallas distr kernel, the XLA paths to
        the pure-JAX distr implementation, so the backend family (and its
        interpret/tuning setup) is preserved; every other knob rides along
        via ``replace``."""
        if group_size <= 1:
            return self
        impl = "pallas_distr" if self.impl.startswith("pallas") else "distr"
        return replace(
            self, impl=impl, distr=replace(self.distr, group_size=group_size)
        )


def _active_context_mesh(context_axis: str | None):
    """The active mesh when it carries a >1-sized ``context_axis``, else
    None (no mesh set, axis missing, or trivially sized — the single-device
    paths apply)."""
    if not context_axis:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    if context_axis not in mesh.axis_names:
        return None
    return mesh if int(mesh.shape[context_axis]) > 1 else None


def _ring_dispatch(cfg: AttentionConfig, q, k, v, *, causal, scale, kv_mask):
    """Route to distributed.ring_attention when context parallelism applies;
    returns None to fall through to the single-device paths."""
    if cfg.impl not in ("pallas_flash", "pallas_distr") or kv_mask is not None:
        return None
    if q.shape[2] != k.shape[2]:  # ring is self-attention only (cross-attn
        return None  # keeps the single-device kernels)
    mesh = _active_context_mesh(cfg.context_axis)
    if mesh is None:
        return None
    from repro.distributed import ring_attention as ring

    p = int(mesh.shape[cfg.context_axis])
    if q.shape[2] < p * ring.MIN_RING_SHARD:
        return None
    if cfg.impl == "pallas_flash":
        blocks = None
        if cfg.block_q is not None or cfg.block_k is not None:
            blocks = BlockSizes.from_pair(cfg.block_q or 128, cfg.block_k or 128)
        return ring.ring_flash_attention(
            q, k, v, mesh, axis=cfg.context_axis, causal=causal, scale=scale,
            blocks=blocks, interpret=cfg.interpret,
        )
    return ring.ring_distr_attention(
        q, k, v, cfg.distr, mesh, axis=cfg.context_axis, causal=causal,
        scale=scale, interpret=cfg.interpret,
    )


def resolve_attention_blocks(
    cfg: AttentionConfig,
    *,
    d: int,
    n_q: int,
    n_k: int | None = None,
    dtype: str = "float32",
    causal: bool = False,
    bwd: bool = False,
) -> BlockSizes:
    """Concrete :class:`BlockSizes` for one dispatch site.

    Explicit ints in the config always win; both-``None`` resolves through
    the autotuner under the key (impl-kind, backend, dtype, d, G*,
    seq-bucket, causal).  A *partial* pin (one int, one None) uses the
    static default for the free dim — mixing a pinned dim into a tuned
    pair measured for a different combination would produce a tile the
    search never validated.  ``bwd=True`` (training warm-up) additionally
    resolves the backward dQ/dKV keys in measure mode; forward-only
    dispatch leaves them to resolve lazily at backward-trace time.
    Shape-only — safe to call while tracing.

    Under context parallelism (``cfg.context_axis`` naming an active mesh
    axis) the tuner key is *per-shard*: the sequence bucket is the length
    one ring device actually streams, ``context_shard_len(n, P)``, not the
    global N — matching what distributed.ring_attention resolves at
    dispatch.
    """
    n_k = n_k if n_k is not None else n_q
    mesh = _active_context_mesh(cfg.context_axis)
    if mesh is not None and cfg.impl.startswith("pallas") and n_q == n_k:
        # Mirror the _ring_dispatch guards (self-attention, long enough to
        # fill a shard per device): warming a bucket the dispatch will
        # never route to the ring would leave the *real* bucket cold and
        # the measure-mode sweep would fire inside the first jitted step.
        from repro.distributed.ring_attention import (
            MIN_RING_SHARD, context_shard_len,
        )

        p = int(mesh.shape[cfg.context_axis])
        if n_q >= p * MIN_RING_SHARD:
            n_q = n_k = context_shard_len(n_q, p)
    if cfg.impl in ("distr", "pallas_distr"):
        # The distr dispatch reads DistrConfig's blocks, not ours — resolve
        # (or pass through) those, so warm-up and launcher logs report the
        # blocks that actually execute.
        dcfg = cfg.distr.resolved(
            d, max(n_q, n_k), dtype=dtype, causal=causal,
            xla=(cfg.impl == "distr"), interpret=cfg.interpret,
        )
        if bwd and cfg.impl == "pallas_distr":
            # Training warm-up: pre-resolve (measure mode: sweep + persist)
            # the backward block_k keys too — block_q stays pinned as the
            # LSH grouping granularity.
            from repro.tune.autotune import get_autotuner, tune_mode

            if dcfg.block_k_bwd is None and tune_mode() == "measure":
                tuner = get_autotuner()
                kw = dict(
                    block_q=dcfg.block_q, d=d, n=max(n_q, n_k), dtype=dtype,
                    group_size=dcfg.group_size, causal=causal,
                    interpret=cfg.interpret, fwd_block_k=dcfg.block_k,
                )
                tuner.resolve_distr_bwd("distr_dq", **kw)
                tuner.resolve_distr_bwd("distr_dkv", **kw)
        return BlockSizes.from_pair(dcfg.block_q, dcfg.block_k)
    if cfg.block_q is not None or cfg.block_k is not None:
        # Fully pinned, or a partial pin (free dim → static default).
        return BlockSizes.from_pair(cfg.block_q or 128, cfg.block_k or 128)
    kind = _TUNE_KIND.get(cfg.impl)
    if kind is None:  # reference oracle: blocks unused
        return BlockSizes.from_pair(128, 128)
    interpret = cfg.interpret if cfg.impl.startswith("pallas") else False
    from repro.tune.autotune import resolve_block_sizes

    return resolve_block_sizes(
        kind, d=d, n=max(n_q, n_k), dtype=dtype, causal=causal,
        interpret=interpret, bwd=bwd,
    )


def attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: AttentionConfig,
    *,
    causal: bool = False,
    scale: float | None = None,
    kv_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Multi-head attention with the configured implementation.

    q: (B, Hq, N, d);  k, v: (B, Hkv, Nk, d).

    When ``cfg.context_axis`` names an axis of the active mesh, the Pallas
    impls run ring sequence-parallel (distributed.ring_attention): Q/K/V
    shard over the sequence axis, KV rotates around the ring, and partial
    (O, LSE) merge online — the same kernels, one shard per device.
    """
    ring_out = _ring_dispatch(
        cfg, q, k, v, causal=causal, scale=scale, kv_mask=kv_mask
    )
    if ring_out is not None:
        return ring_out
    if cfg.impl == "reference":
        return reference_attention(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask)
    if cfg.impl == "xla_flash":
        if kv_mask is not None:
            # Blockwise path has no kv_mask plumbing; the oracle handles it.
            return reference_attention(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask)
        bs = resolve_attention_blocks(
            cfg, d=q.shape[-1], n_q=q.shape[2], n_k=k.shape[2],
            dtype=_dtype_str(q), causal=causal,
        )
        # Ragged lengths stay blockwise: blockwise_flash_reference pads and
        # masks internally (no silent O(N²) dense fallback).
        return blockwise_flash_reference(
            q, k, v, block_q=bs.block_q, block_k=bs.block_k, causal=causal, scale=scale
        )
    if cfg.impl == "distr":
        return distr_attention(
            q, k, v, cfg.distr, causal=causal, scale=scale, kv_mask=kv_mask
        )
    if cfg.impl == "pallas_flash":
        if kv_mask is not None:
            # Kernels have no kv_mask plumbing; the oracle handles it.
            return reference_attention(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask)
        from repro.kernels import ops  # deferred: kernels are optional at import

        bs = resolve_attention_blocks(
            cfg, d=q.shape[-1], n_q=q.shape[2], n_k=k.shape[2],
            dtype=_dtype_str(q), causal=causal,
        )
        return ops.flash_attention(
            q, k, v, causal=causal, scale=scale, blocks=bs,
            interpret=cfg.interpret,
        )
    if cfg.impl == "pallas_distr":
        if kv_mask is not None:
            return reference_attention(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask)
        from repro.kernels import ops

        return ops.distr_attention(
            q, k, v, cfg.distr, causal=causal, scale=scale, interpret=cfg.interpret,
        )
    raise ValueError(f"unknown attention impl {cfg.impl!r}; choose from {IMPLS}")


def attend_decode(
    q: jnp.ndarray,
    k: jnp.ndarray | None,
    v: jnp.ndarray,
    cfg: AttentionConfig,
    *,
    lengths: jnp.ndarray | None = None,
    k_fused: jnp.ndarray | None = None,
    perm: jnp.ndarray | None = None,
    group_size: int = 1,
    scale: float | None = None,
    block_tables: jnp.ndarray | None = None,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Decode-path attention dispatch: one (or a few speculative /
    chunked-prefill) query tokens against a KV cache with per-slot live
    ``lengths``.

    Contiguous caches (``block_tables=None``): k/v are (B, Hkv, S, d)
    slabs; every impl except ``reference`` routes to the split-K
    flash-decoding Pallas op (``kernels.ops.decode_attention``) — per-token
    KV traffic then scales with the live length, not S.

    Paged caches (``block_tables`` (B, max_blocks) int32): k/v are shared
    token-major (P, block_size, Hkv·d) pools — or (L, P, block_size, Hkv·d)
    stacks read at the traced ``layer`` — and the KV stream goes through the
    scalar-prefetched block table (``kernels.ops.paged_decode_attention``);
    the ``reference`` oracle gathers the table into a contiguous cache
    first (the parity baseline in tests).  Multi-token ``q`` is banded —
    query token ``i`` of the window sees positions
    ``< length − (q_len − 1 − i)`` — which is what chunked prefill rides.

    ``reference`` keeps the pure-JAX masked-softmax oracle.  The fused-K̂
    variant is selected by passing ``k_fused`` + ``perm`` + ``group_size``
    (see serve.kv_cache); ``k`` may be None in that case.  ``scale`` always
    refers to the full head dim (default 1/√d, d from ``q``) on both paths.
    """
    if cfg.impl not in IMPLS:
        raise ValueError(
            f"unknown attention impl {cfg.impl!r}; choose from {IMPLS}"
        )
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if block_tables is not None:
        return _attend_decode_paged(
            q, k, v, cfg, lengths=lengths, k_fused=k_fused, perm=perm,
            group_size=group_size, scale=scale, block_tables=block_tables,
            layer=layer,
        )
    if cfg.impl == "reference":
        from repro.core import grouping

        nk = (k_fused if k_fused is not None else k).shape[2]
        kv_mask = (
            jnp.arange(nk)[None, :] < lengths[:, None]
            if lengths is not None
            else None
        )
        if k_fused is not None:
            q_s = grouping.sample_q_heads(q, perm, group_size)
            return reference_attention(
                q_s, k_fused.astype(q_s.dtype), v.astype(q_s.dtype),
                causal=False, scale=scale, kv_mask=kv_mask,
            )
        return reference_attention(
            q, k.astype(q.dtype), v.astype(q.dtype),
            causal=False, scale=scale, kv_mask=kv_mask,
        )
    from repro.kernels import ops  # deferred: kernels are optional at import

    return ops.decode_attention(
        q, k, v, lengths=lengths, k_fused=k_fused, perm=perm,
        group_size=group_size, scale=scale, block_k=cfg.block_k_decode,
        interpret=cfg.interpret,
    )


def _gather_paged(pool: jnp.ndarray, block_tables: jnp.ndarray,
                  hkv: int) -> jnp.ndarray:
    """(P, bs, Hkv·d) pool + (B, max_blocks) table → (B, Hkv, max_blocks·bs,
    d) contiguous per-request cache (the reference/oracle materialisation the
    kernel path exists to avoid)."""
    gathered = jnp.take(pool, block_tables, axis=0)  # (B, mb, bs, Hkv·d)
    b, mb, bs, width = gathered.shape
    return gathered.reshape(b, mb * bs, hkv, width // hkv).transpose(0, 2, 1, 3)


def _attend_decode_paged(q, k, v, cfg, *, lengths, k_fused, perm, group_size,
                         scale, block_tables, layer):
    if cfg.impl == "reference":
        from repro.core import grouping

        if layer is not None:
            k, v, k_fused = (
                None if x is None else x[layer] for x in (k, v, k_fused)
            )
        bs = v.shape[1]
        hkv = v.shape[2] // q.shape[-1]
        capacity = block_tables.shape[1] * bs
        # Like the kernel op, lengths are NOT clamped to capacity: a padded
        # window overhanging it must not shift live rows' causal bands.
        lengths = (
            jnp.asarray(lengths, jnp.int32)
            if lengths is not None
            else jnp.full((q.shape[0],), capacity, jnp.int32)
        )
        q_len = q.shape[2]
        nk = capacity
        col = jnp.arange(nk)[None, None, :]  # (1, 1, Nk)
        row = jnp.arange(q_len)[None, :, None]  # (1, q_len, 1)
        # Banded live window per query row (degenerate for q_len = 1).
        band = col < (lengths[:, None, None] - (q_len - 1 - row))
        v_c = _gather_paged(v, block_tables, hkv).astype(q.dtype)
        if k_fused is not None:
            q_r = grouping.sample_q_heads(q, perm, group_size)
            k_c = _gather_paged(k_fused, block_tables, hkv).astype(q.dtype)
        else:
            q_r = q
            k_c = _gather_paged(k, block_tables, hkv).astype(q.dtype)
        outs = [
            reference_attention(
                q_r[:, :, i : i + 1], k_c, v_c, causal=False, scale=scale,
                kv_mask=band[:, i],
            )
            for i in range(q_len)
        ]
        return jnp.concatenate(outs, axis=2) if q_len > 1 else outs[0]
    from repro.kernels import ops  # deferred: kernels are optional at import

    return ops.paged_decode_attention(
        q, k, v, block_tables=block_tables, lengths=lengths,
        k_fused_pool=k_fused, perm=perm, group_size=group_size, scale=scale,
        layer=layer, interpret=cfg.interpret,
    )
