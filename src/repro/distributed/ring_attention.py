"""Ring sequence-parallel ("context parallel") attention over the Pallas
kernel path.

The online-softmax merge that makes FlashAttention-2 associative over KV
*tiles* is equally associative over device-resident KV *shards*: partial
``(O, LSE)`` pairs merge as

    LSE = logaddexp(LSE_a, LSE_b)
    O   = O_a · exp(LSE_a − LSE) + O_b · exp(LSE_b − LSE)

so Q/K/V are sharded on the sequence axis under ``shard_map``, each device
runs the existing fused Pallas kernels (flash or distr) on its local Q tile
against whichever KV shard it currently holds, and KV rotates one hop around
the ICI ring with ``ppermute`` between kernel launches — IO-aware blocking
extended from VMEM tiles to ring hops.  Sequence length then scales with
device count instead of HBM per chip.

Schedule (P = ring size, device ``i`` owns Q/KV shard ``i``):

  hop 0:  every device attends its *own* shard — the causal diagonal, so
          this is the only hop that runs the causal kernel variant;
  hop h:  device ``i`` holds KV shard ``src = (i − h) mod P``.  Causal rings
          skip the hop when ``src > i`` (the shard is entirely in the
          future) — ~half the hops run; both modes skip hops whose KV shard
          holds no live tokens, and devices whose Q shard is all padding.
          Skips are real ``lax.cond`` branches, counted by an executed-hop
          probe (``return_hops=True``) so tests can assert dead hops never
          launch a kernel.

DistrAttention under the ring keeps the paper's grouping *shard-local*: each
device derives its per-Q-block LSH permutations from its own Q shard
(``block_q`` never crosses a shard boundary — shards are rounded to a
``block_q`` multiple), and the fused K̂ is rebuilt in-kernel from the raw
rotating K under those local permutations — K̂ cannot be rotated as state
because every destination fuses under *different* (Q-shard-local) perms.

The backward runs the same ring in reverse over the already-tuned dQ/dKV
kernels (``kernels.backward``): dQ accumulates locally across hops while
(K, V, dK, dV) rotate together; after P rotations the dK/dV accumulators are
back at their owner shard.  The merged (global) LSE and the local
Δ = rowsum(dO ∘ O) are row statistics of the *local* Q shard, so no
statistics ever cross the ring.

Everything here is a shard_map-level building block in the style of
``distributed.collectives``; ``core.api.attend`` dispatches to it when
``AttentionConfig.context_axis`` names an axis of the active mesh.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.distr_attention import DistrConfig
from repro.kernels import backward as bwd
from repro.kernels import ops
from repro.kernels.distr_attention import distr_attention_kernel_call
from repro.kernels.flash_attention import NEG_INF, flash_attention_kernel_call
from repro.tune.block_sizes import BlockSizes
from repro.tune.cache import dtype_str as _dtype_str

# A ring shard is only worth its ppermute overhead once it holds at least a
# full lane tile of tokens; below this the dispatch layer keeps the call on
# one device (serve-side short prompts).
MIN_RING_SHARD = 128


def context_shard_len(n: int, p: int, *, multiple: int = 128) -> int:
    """Per-device sequence shard for a ring of size ``p``: ceil(n/p) rounded
    up to ``multiple`` (the kernels' lane tile / LSH block granularity)."""
    per = -(-int(n) // int(p))
    return max(multiple, -(-per // multiple) * multiple)


def _fit_block(block: int, shard: int) -> int:
    """Clamp a tuned block size to one that tiles the shard exactly."""
    b = min(int(block), shard)
    return b if shard % b == 0 else 128


def _merge_partial(o, lse, o_h, lse_h):
    """Associative online-softmax merge of two (O, LSE) partials (f32)."""
    lse_new = jnp.logaddexp(lse, lse_h)
    w = jnp.exp(lse - lse_new)[..., None]
    w_h = jnp.exp(lse_h - lse_new)[..., None]
    return o * w + o_h.astype(jnp.float32) * w_h, lse_new


def _rotate(tree, axis: str, p: int):
    """One KV hop: every device sends its shard to the next ring position."""
    perm = [(j, (j + 1) % p) for j in range(p)]
    return jax.tree_util.tree_map(
        lambda x: jax.lax.ppermute(x, axis, perm), tree
    )


@dataclass(frozen=True)
class _RingMeta:
    """Static ring configuration riding through ``custom_vjp`` nondiff args."""

    axis: str
    size: int
    causal: bool
    scale: float
    interpret: bool
    n_live: int  # global live sequence length (pre-padding)
    shard: int  # per-device padded shard length
    blocks: BlockSizes  # flash tiles (fwd + bwd; distr reads dcfg instead)
    dcfg: DistrConfig | None = None  # distr mode when set (resolved blocks)
    bk_bwd_distr: tuple[int, int] | None = None  # distr bwd (bk_dq, bk_dkv)

    @property
    def tail_idx(self) -> int:
        """Index of the partially-live shard (−1 when none: the live length
        lands exactly on a shard boundary)."""
        return self.n_live // self.shard if self.n_live % self.shard else -1

    @property
    def tail_len(self) -> int:
        return self.n_live % self.shard


# -- fault injection (serve.faults catalog point "dead_ring_shard") --------
#
# Shards listed here model a dead host mid-ring: its KV shard never arrives
# at the other devices (the ppermute from that neighbor yields nothing), so
# every hop h > 0 whose source is a dead shard is skipped and the ring
# serves a degraded-but-finite result instead of hanging or NaN-ing.  Hop 0
# (a device's own local KV) always runs — it is resident, not rotated — so
# no Q row ever loses its softmax diagonal.  Read at trace time: apply the
# context manager around an untraced call (the chaos suite does), not
# around an already-jitted function.
_DEAD_SHARDS: frozenset[int] = frozenset()


@contextlib.contextmanager
def dead_shard_fault(shards):
    """Treat KV shards in ``shards`` as dead for ring sweeps traced inside
    the context (graceful-degradation fault injection; see serve.faults)."""
    global _DEAD_SHARDS
    prev = _DEAD_SHARDS
    _DEAD_SHARDS = frozenset(int(s) for s in shards)
    try:
        yield
    finally:
        _DEAD_SHARDS = prev


def _hop_schedule(meta: _RingMeta, idx, h: int):
    """(run, kernel_causal) for hop ``h`` on device ``idx``.

    ``run`` is the traced skip predicate: the hop launches no kernel when the
    held KV shard has no live tokens, when the device's own Q shard is all
    padding, or — causal rings — when the shard is entirely in the future
    (``src > idx``; the diagonal ``src == idx`` is always hop 0 under this
    rotation direction, so it alone runs the causal kernel variant).
    """
    p = meta.size
    src = (idx - h) % p if h else idx
    run = (src * meta.shard < meta.n_live) & (idx * meta.shard < meta.n_live)
    if meta.causal and h > 0:
        run = run & (src < idx)
    if _DEAD_SHARDS and h > 0:
        # Injected dead shards (dead_shard_fault): the rotated KV from a
        # dead source never arrives — skip the hop, keep serving.
        dead = jnp.asarray(sorted(_DEAD_SHARDS), jnp.int32)
        run = run & jnp.all(src != dead)
    return src, run, (meta.causal and h == 0)


def _hop_kv_variants(meta: _RingMeta, src, call):
    """Invoke ``call(kv_len)`` with the static live length of the held KV
    shard: full shards stream ``shard`` live columns, the single partial
    (tail) shard masks past ``tail_len``.  ``kv_len`` is static inside the
    kernels, so the choice is a two-branch ``lax.cond`` on the traced shard
    origin rather than a dynamic argument."""
    if meta.tail_idx < 0:
        return call(meta.shard)
    return jax.lax.cond(
        src == meta.tail_idx,
        lambda: call(meta.tail_len),
        lambda: call(meta.shard),
    )


def _live_row_mask(meta: _RingMeta, idx, n_rows: int):
    """(n_rows,) bool — rows of the local Q shard that are real tokens."""
    live = jnp.clip(meta.n_live - idx * meta.shard, 0, meta.shard)
    return jnp.arange(n_rows) < live


def _ring_hops(meta: _RingMeta, kv, carry, hop_body, *, post_hop=None):
    """The ring-loop scaffold shared by all four sweeps (flash/distr ×
    fwd/bwd): per hop, derive the schedule, run ``hop_body(src,
    kernel_causal, k_c, v_c, carry)`` under the skip predicate (a real
    ``lax.cond`` — skipped hops launch no kernel), apply ``post_hop`` to
    the carry *unconditionally* (the backwards rotate their dK/dV
    accumulators every hop, skipped or not, so they land back on the owner
    after P rotations), then rotate KV — except after the last hop.

    Keeping the skip/rotation ordering in one place is the point: it is
    the subtlest invariant of the ring and must not drift between the four
    sweeps."""
    idx = jax.lax.axis_index(meta.axis)
    for h in range(meta.size):
        src, run, kernel_causal = _hop_schedule(meta, idx, h)
        k_c, v_c = kv

        def compute(c, k_c=k_c, v_c=v_c, src=src, kc=kernel_causal):
            return hop_body(src, kc, k_c, v_c, c)

        carry = jax.lax.cond(run, compute, lambda c: c, carry)
        if post_hop is not None:
            carry = post_hop(carry)
        if h < meta.size - 1:
            kv = _rotate(kv, meta.axis, meta.size)
    return carry


# ---------------------------------------------------------------------------
# Exact flash ring
# ---------------------------------------------------------------------------


def _ring_flash_fwd_impl(meta: _RingMeta, q, k, v):
    b, hq, n_sh, d = q.shape
    hkv = k.shape[1]
    q_per_kv = hq // hkv
    bq, bk = meta.blocks.fwd()

    qf = q.reshape(b * hq, n_sh, d)
    kv = (k.reshape(b * hkv, n_sh, d), v.reshape(b * hkv, n_sh, d))

    o0 = jnp.zeros((b * hq, n_sh, d), jnp.float32)
    lse0 = jnp.full((b * hq, n_sh), NEG_INF, jnp.float32)

    def hop_body(src, kernel_causal, k_c, v_c, c):
        o, lse, hops = c

        def call(kv_len):
            return flash_attention_kernel_call(
                qf, k_c, v_c, q_per_kv=q_per_kv, scale=meta.scale,
                causal=kernel_causal, block_q=bq, block_k=bk,
                kv_len=kv_len, interpret=meta.interpret,
                return_residuals=True,
            )

        o_h, lse_h = _hop_kv_variants(meta, src, call)
        o, lse = _merge_partial(o, lse, o_h, lse_h)
        return o, lse, hops + 1

    o, lse, hops = _ring_hops(
        meta, kv, (o0, lse0, jnp.zeros((), jnp.int32)), hop_body
    )
    out = o.reshape(b, hq, n_sh, d).astype(q.dtype)
    return out, lse, jax.lax.psum(hops, meta.axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_flash_local(meta: _RingMeta, q, k, v):
    out, _, hops = _ring_flash_fwd_impl(meta, q, k, v)
    return out, hops


def _ring_flash_vjp_fwd(meta, q, k, v):
    out, lse, hops = _ring_flash_fwd_impl(meta, q, k, v)
    return (out, hops), (q, k, v, out, lse)


def _ring_flash_vjp_bwd(meta, res, cts):
    q, k, v, o, lse = res
    do, _ = cts  # the hop count is a probe: no cotangent flows through it
    b, hq, n_sh, d = q.shape
    hkv = k.shape[1]
    q_per_kv = hq // hkv
    idx = jax.lax.axis_index(meta.axis)
    # Backward tiles must tile the fixed local shard; a tuned tile that
    # doesn't fit falls back to the 128 lane tile (which tiles every shard
    # by construction).
    bq_dq, bk_dq = (_fit_block(x, n_sh) for x in meta.blocks.dq())
    bq_dkv, bk_dkv = (_fit_block(x, n_sh) for x in meta.blocks.dkv())

    qf = q.reshape(b * hq, n_sh, d)
    dof = do.astype(q.dtype).reshape(b * hq, n_sh, d)
    of = o.reshape(b * hq, n_sh, d)
    delta = bwd.delta_kernel_call(
        of, dof, block_q=bq_dq, interpret=meta.interpret
    )
    # Padded Q rows never carry cotangent (the public wrapper zero-pads dO),
    # but their LSE is garbage from the unmasked forward rows; pin it to
    # +big so P ≡ 0 and they contribute nothing to dK/dV.
    row_live = _live_row_mask(meta, idx, n_sh)[None, :]
    lse_b = jnp.where(row_live, lse, ops.LSE_PAD)

    kv = (k.reshape(b * hkv, n_sh, d), v.reshape(b * hkv, n_sh, d))
    state = (
        jnp.zeros((b * hq, n_sh, d), jnp.float32),
        jnp.zeros((b, hkv, n_sh, d), jnp.float32),
        jnp.zeros((b, hkv, n_sh, d), jnp.float32),
    )

    def hop_body(src, kernel_causal, k_c, v_c, c):
        dq, dk, dv = c

        def call(kv_len):
            dq_h = bwd.flash_dq_kernel_call(
                qf, k_c, v_c, dof, lse_b, delta,
                q_per_kv=q_per_kv, scale=meta.scale,
                causal=kernel_causal, block_q=bq_dq, block_k=bk_dq,
                kv_len=kv_len, interpret=meta.interpret,
            )
            dk_h, dv_h = bwd.flash_dkv_kernel_call(
                qf, k_c, v_c, dof, lse_b, delta,
                q_per_kv=q_per_kv, scale=meta.scale,
                causal=kernel_causal, block_q=bq_dkv, block_k=bk_dkv,
                kv_len=kv_len, interpret=meta.interpret,
            )
            return dq_h, dk_h, dv_h

        dq_h, dk_h, dv_h = _hop_kv_variants(meta, src, call)
        # GQA group-sum per hop: the rotating accumulator carries the
        # per-KV-head layout (P× less ring traffic than per-Q-head).
        dk = dk + ops._gqa_sum(dk_h, b, hkv, q_per_kv, n_sh)
        dv = dv + ops._gqa_sum(dv_h, b, hkv, q_per_kv, n_sh)
        return dq + dq_h, dk, dv

    def rotate_dkv(c):
        # dK/dV rotate *with* their KV shard every hop (P rotations total),
        # landing back on the owner; dQ stays local.
        dq, dk, dv = c
        dk, dv = _rotate((dk, dv), meta.axis, meta.size)
        return dq, dk, dv

    dq, dk, dv = _ring_hops(meta, kv, state, hop_body, post_hop=rotate_dkv)
    dq = dq.reshape(b, hq, n_sh, d).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash_local.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


# ---------------------------------------------------------------------------
# DistrAttention ring (shard-local LSH grouping)
# ---------------------------------------------------------------------------


def _distr_stage1(meta: _RingMeta, q, hkv: int):
    """The LSH stage (per-Q-block permutations + sampled Q̂), run as plain
    XLA *outside* the shard_map — the shared ``ops.distr_stage1``
    implementation, so the grouping decision cannot diverge from the
    single-device op.  Blocks never cross a shard boundary (shards are
    ``block_q``-aligned), so grouping is shard-local by construction and
    computing it on the global (GSPMD-sharded) array is bit-identical to a
    per-shard computation.  ``hkv`` enables the shared-KV-perm variant
    (one permutation per KV group from the group's mean query block,
    broadcast back to Hq) — still shard-local for the same reason."""
    return ops.distr_stage1(meta.dcfg, q, meta.scale, hkv=hkv)


def _ring_distr_local_fwd(meta: _RingMeta, q_hat, perms, k, v):
    """Shard-local ring forward: q_hat (b, hq, n_sh, dG), perms
    (b, hq, nq_local, d), k/v (b, hkv, n_sh, d)."""
    cfg = meta.dcfg
    b, hq, n_sh, dg = q_hat.shape
    hkv, d = k.shape[1], k.shape[-1]
    q_per_kv = hq // hkv
    g = cfg.group_size

    q_hat = q_hat.reshape(b * hq, n_sh, dg)
    nq_blocks = n_sh // cfg.block_q
    perm_f = perms.reshape(b * hq, nq_blocks, d)
    kv = (k.reshape(b * hkv, n_sh, d), v.reshape(b * hkv, n_sh, d))

    o0 = jnp.zeros((b * hq, n_sh, d), jnp.float32)
    lse0 = jnp.full((b * hq, n_sh), NEG_INF, jnp.float32)

    def hop_body(src, kernel_causal, k_c, v_c, c):
        o, lse, hops = c

        def call(kv_len):
            # K̂ is re-fused inside the kernel from the rotating raw K
            # under the *local* permutations — the shard-local grouping
            # invariant (it never rides the ring as state).
            return distr_attention_kernel_call(
                q_hat, k_c, v_c, perm_f, q_per_kv=q_per_kv,
                causal=kernel_causal, group_size=g,
                block_q=cfg.block_q, block_k=cfg.block_k, kv_len=kv_len,
                interpret=meta.interpret, return_residuals=True,
            )

        o_h, lse_h = _hop_kv_variants(meta, src, call)
        o, lse = _merge_partial(o, lse, o_h, lse_h)
        return o, lse, hops + 1

    o, lse, hops = _ring_hops(
        meta, kv, (o0, lse0, jnp.zeros((), jnp.int32)), hop_body
    )
    out = o.reshape(b, hq, n_sh, d).astype(k.dtype)
    return out, lse.reshape(b, hq, n_sh), jax.lax.psum(hops, meta.axis)


def _ring_distr_local_bwd(meta: _RingMeta, q_hat, perms, k, v, o, lse, do):
    """Shard-local ring backward.  All args shard-local; lse is the merged
    (global over KV hops) logsumexp of the local Q rows.  Returns
    (dq_hat, dk, dv) — dq_hat still in sampled space; the global wrapper
    transposes the sampling gather."""
    cfg = meta.dcfg
    b, hq, n_sh, dg = q_hat.shape
    hkv, d = k.shape[1], k.shape[-1]
    q_per_kv = hq // hkv
    g = cfg.group_size
    idx = jax.lax.axis_index(meta.axis)
    nq_blocks = n_sh // cfg.block_q

    q_hat = q_hat.reshape(b * hq, n_sh, dg)
    bk_dq, bk_dkv = meta.bk_bwd_distr or (cfg.block_k, cfg.block_k)
    bk_dq, bk_dkv = _fit_block(bk_dq, n_sh), _fit_block(bk_dkv, n_sh)

    dof = do.astype(k.dtype).reshape(b * hq, n_sh, d)
    of = o.reshape(b * hq, n_sh, d)
    perm_f = perms.reshape(b * hq, nq_blocks, d)
    delta = bwd.delta_kernel_call(
        of, dof, block_q=cfg.block_q, interpret=meta.interpret
    )
    row_live = _live_row_mask(meta, idx, n_sh)[None, :]
    lse_b = jnp.where(row_live, lse.reshape(b * hq, n_sh), ops.LSE_PAD)

    kv = (k.reshape(b * hkv, n_sh, d), v.reshape(b * hkv, n_sh, d))
    state = (
        jnp.zeros((b * hq, n_sh, dg), jnp.float32),
        jnp.zeros((b, hkv, n_sh, d), jnp.float32),
        jnp.zeros((b, hkv, n_sh, d), jnp.float32),
    )

    def hop_body(src, kernel_causal, k_c, v_c, c):
        dq_hat_acc, dk, dv = c

        def call(kv_len):
            dq_h = bwd.distr_dq_kernel_call(
                q_hat, k_c, v_c, perm_f, dof, lse_b, delta,
                q_per_kv=q_per_kv, causal=kernel_causal, group_size=g,
                block_q=cfg.block_q, block_k=bk_dq, kv_len=kv_len,
                interpret=meta.interpret,
            )
            dk_h, dv_h = bwd.distr_dkv_kernel_call(
                q_hat, k_c, v_c, perm_f, dof, lse_b, delta,
                q_per_kv=q_per_kv, causal=kernel_causal, group_size=g,
                block_q=cfg.block_q, block_k=bk_dkv, kv_len=kv_len,
                interpret=meta.interpret,
            )
            return dq_h, dk_h, dv_h

        dq_h, dk_h, dv_h = _hop_kv_variants(meta, src, call)
        dk = dk + ops._gqa_sum(dk_h, b, hkv, q_per_kv, n_sh)
        dv = dv + ops._gqa_sum(dv_h, b, hkv, q_per_kv, n_sh)
        return dq_hat_acc + dq_h, dk, dv

    def rotate_dkv(c):
        dq_hat_acc, dk, dv = c
        dk, dv = _rotate((dk, dv), meta.axis, meta.size)
        return dq_hat_acc, dk, dv

    dq_hat, dk, dv = _ring_hops(meta, kv, state, hop_body,
                                post_hop=rotate_dkv)
    return (
        dq_hat.reshape(b, hq, n_sh, dg),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


# Global-level custom_vjp: stage 1 (and its transpose) run as plain XLA on
# the GSPMD-sharded global arrays; only the hop loops live inside shard_map.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ring_distr(meta: _RingMeta, mesh, axis, q, k, v):
    (out, _, hops), _, _ = _ring_distr_fwd_global(meta, mesh, axis, q, k, v)
    return out, hops


def _ring_distr_fwd_global(meta, mesh, axis, q, k, v):
    q_hat, perms = _distr_stage1(meta, q, k.shape[1])
    qkv_spec, out_spec = _ring_specs(
        mesh, axis, q.shape[0], q.shape[1], k.shape[1]
    )
    res = jax.shard_map(
        functools.partial(_ring_distr_local_fwd, meta),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, qkv_spec),
        out_specs=(out_spec, P(*out_spec[:3]), P()),
        check_vma=False,
    )(q_hat, perms, k, v)
    return res, q_hat, perms


def _ring_distr_vjp_fwd(meta, mesh, axis, q, k, v):
    (out, lse, hops), q_hat, perms = _ring_distr_fwd_global(
        meta, mesh, axis, q, k, v
    )
    return (out, hops), (k, v, out, lse, q_hat, perms)


def _ring_distr_vjp_bwd(meta, mesh, axis, res, cts):
    cfg = meta.dcfg
    k, v, o, lse, q_hat, perms = res
    do, _ = cts
    b, hq = o.shape[0], o.shape[1]
    g = cfg.group_size

    qkv_spec, out_spec = _ring_specs(mesh, axis, b, hq, k.shape[1])
    dq_hat, dk, dv = jax.shard_map(
        functools.partial(_ring_distr_local_bwd, meta),
        mesh=mesh,
        in_specs=(qkv_spec,) * 7,
        out_specs=(qkv_spec, qkv_spec, qkv_spec),
        check_vma=False,
    )(q_hat, perms, k, v, o, lse[..., None], do)

    dq = ops.distr_dq_from_dq_hat(
        cfg.estimator, dq_hat, perms,
        block_q=cfg.block_q, group_size=g, scale=meta.scale,
    ).astype(k.dtype)
    return dq, dk, dv


_ring_distr.defvjp(_ring_distr_vjp_fwd, _ring_distr_vjp_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _ring_specs(mesh, axis: str, b: int, hq: int, hkv: int):
    """(qkv_spec, out_spec): seq over ``axis``; batch over whatever DP axes
    divide it; heads over "model" only when *both* head counts divide (a
    lopsided GQA split would break the kernels' q_per_kv mapping)."""
    batch = []
    prod = 1
    for a in mesh.axis_names:
        sz = int(mesh.shape[a])
        if a in ("model", axis) or sz == 1:
            continue
        if b % (prod * sz) == 0:
            batch.append(a)
            prod *= sz
    msize = int(mesh.shape.get("model", 1))
    head = "model" if msize > 1 and hq % msize == 0 and hkv % msize == 0 else None
    spec = P(tuple(batch) or None, head, axis, None)
    return spec, spec


def _pad_global(x, n_pad):
    pad = n_pad - x.shape[2]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _run_ring(local_fn, meta, q, k, v, mesh, axis):
    n = q.shape[2]
    n_pad = meta.size * meta.shard
    q, k, v = (_pad_global(x, n_pad) for x in (q, k, v))
    qkv_spec, out_spec = _ring_specs(
        mesh, axis, q.shape[0], q.shape[1], k.shape[1]
    )
    out, hops = jax.shard_map(
        functools.partial(local_fn, meta),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        # The executed-hop probe is psum'd over the ring inside the local
        # body — replicated by construction, which VMA can't infer.
        out_specs=(out_spec, P()),
        check_vma=False,
    )(q, k, v)
    return out[:, :, :n, :], hops


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh,
    *,
    axis: str = "context",
    causal: bool = False,
    scale: float | None = None,
    blocks: BlockSizes | None = None,
    interpret: bool | None = None,
    return_hops: bool = False,
):
    """Exact FA-2 ring attention.  q: (B, Hq, N, d); k, v: (B, Hkv, N, d)
    with N the *global* sequence length — sharded over ``mesh.shape[axis]``
    devices inside.  Differentiable (ring backward over the fused dQ/dKV
    kernels).  ``return_hops=True`` additionally returns the total number of
    ring hops that actually launched kernels (the causal/dead-shard skip
    probe)."""
    if q.shape[2] != k.shape[2]:
        raise ValueError(
            f"ring attention is self-attention only: N_q={q.shape[2]} != "
            f"N_k={k.shape[2]}"
        )
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = ops.default_interpret()
    p = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    if p == 1:
        out = ops.flash_attention(
            q, k, v, causal=causal, scale=scale, blocks=blocks,
            interpret=interpret,
        )
        return (out, jnp.asarray(1, jnp.int32)) if return_hops else out

    n = q.shape[2]
    if blocks is None:
        # Per-shard sequence bucket: the tuner key is the length one device
        # actually streams, not the global N (tune/ satellite).
        from repro.tune.autotune import resolve_block_sizes, tune_mode

        shard0 = context_shard_len(n, p)
        blocks = resolve_block_sizes(
            "flash", d=q.shape[-1], n=shard0, dtype=_dtype_str(q),
            causal=causal, interpret=interpret,
            bwd=(tune_mode() == "measure"),
        )
    from math import lcm

    shard = context_shard_len(n, p, multiple=lcm(128, blocks.block_q))
    blocks = blocks.with_(
        block_q=_fit_block(blocks.block_q, shard),
        block_k=_fit_block(blocks.block_k, shard),
    )
    meta = _RingMeta(
        axis=axis, size=p, causal=causal, scale=scale, interpret=interpret,
        n_live=n, shard=shard, blocks=blocks,
    )
    out, hops = _run_ring(_ring_flash_local, meta, q, k, v, mesh, axis)
    return (out, hops) if return_hops else out


def ring_distr_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: DistrConfig,
    mesh,
    *,
    axis: str = "context",
    causal: bool = False,
    scale: float | None = None,
    interpret: bool | None = None,
    return_hops: bool = False,
):
    """DistrAttention ring with shard-local LSH grouping: permutations and
    Q̂ sampling run on the local Q shard (``block_q`` never crosses a shard
    boundary); raw K/V rotate and K̂ is re-fused in-kernel per hop under the
    local permutations."""
    if q.shape[2] != k.shape[2]:
        raise ValueError(
            f"ring attention is self-attention only: N_q={q.shape[2]} != "
            f"N_k={k.shape[2]}"
        )
    scale = float(scale) if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = ops.default_interpret()
    p = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    if p == 1:
        out = ops.distr_attention(
            q, k, v, cfg, causal=causal, scale=scale, interpret=interpret
        )
        return (out, jnp.asarray(1, jnp.int32)) if return_hops else out

    n = q.shape[2]
    shard0 = context_shard_len(n, p)
    cfg = cfg.resolved(
        q.shape[-1], shard0, dtype=_dtype_str(q), causal=causal, xla=False,
        interpret=interpret,
    )
    # The grouping grain is sacrosanct: shards are rounded to a multiple of
    # lcm(block_q, 128), so the configured block_q always tiles the shard
    # exactly — the ring never silently regroups at a different granularity
    # than the single-device path.
    from math import lcm

    shard = context_shard_len(n, p, multiple=lcm(128, cfg.block_q))
    from dataclasses import replace as dc_replace

    cfg = dc_replace(cfg, block_k=_fit_block(cfg.block_k, shard))
    bk_bwd = _resolve_distr_bwd_pair(cfg, q, shard, causal, interpret)
    meta = _RingMeta(
        axis=axis, size=p, causal=causal, scale=scale, interpret=interpret,
        n_live=n, shard=shard, blocks=BlockSizes.from_pair(cfg.block_q, cfg.block_k),
        dcfg=cfg, bk_bwd_distr=bk_bwd,
    )
    n_pad = p * shard
    qp, kp, vp = (_pad_global(x, n_pad) for x in (q, k, v))
    out, hops = _ring_distr(meta, mesh, axis, qp, kp, vp)
    out = out[:, :, :n, :]
    return (out, hops) if return_hops else out


def _resolve_distr_bwd_pair(cfg, q, shard, causal, interpret):
    """Backward ``block_k`` for the distr ring via the shared resolver in
    ``kernels.ops`` (eager: the ring's static meta is fixed at
    forward-dispatch time, so the lazy backward-trace resolution the
    single-device op uses isn't available here; ``n`` is the per-device
    shard — the length one ring device actually streams)."""
    return ops.resolve_distr_bwd_blocks(
        cfg, d=q.shape[-1], n=shard, dtype=_dtype_str(q), causal=causal,
        interpret=interpret,
    )
