"""Paged KV-cache: a ref-counted block-pool allocator + the pooled arrays.

The slot engine reserves a contiguous ``max_len`` KV slab per slot, so HBM
is committed at admission for the *worst-case* sequence and short requests
strand most of it.  The paged subsystem (vLLM's PagedAttention model) cuts
KV into fixed-size **blocks** drawn from one shared pool:

  pools:        k, v  (L, P, block_size, Hkv·dh)   [+ k_fused (·, Hkv·dh/G*)]
  block table:  per request, logical block j → physical pool block ids[j]
  invariant:    block 0 is a reserved GARBAGE block — never allocated, the
                write target for dead/padded lanes so their stores can't
                corrupt live KV.

``BlockPool`` is pure host-side bookkeeping (free list + ref counts);
``PagedKVCache`` owns the device arrays and the per-request tables and
provides the engine-facing operations:

  * ``allocate_to(uid, n_tokens)`` — grow a table to cover ``n_tokens``
    (admission / chunked prefill / decode growth), failing cleanly with
    ``PoolExhausted`` so the scheduler can preempt;
  * ``free(uid)`` — return a finished request's blocks (ref-counted:
    prefix-shared blocks survive until their last holder frees);
  * ``evict_to_host(uid)`` / ``restore(uid)`` — whole-request preemption:
    the request's live KV is copied to host numpy, its blocks freed, and
    later re-allocated + copied back — continuations are bit-identical;
  * ``share_prefix(src_uid, dst_uid, n_tokens)`` — optional shared-prefix
    reuse: the *full* blocks covering a common prompt prefix are ref-
    bumped into the new table instead of recomputed (shared blocks are
    never written again — only whole blocks are shared, and the dst's own
    tokens land in fresh blocks).

The ``(L, ...)`` pool stacks ride the decode scan's carry and each layer
writes and reads its own index in place; block tables are shared across
layers.
The kernel side is kernels/paged_decode.py; policy is serve/scheduler.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_decode import GARBAGE_BLOCK


class PoolExhausted(Exception):
    """Raised when an allocation cannot be satisfied; the scheduler reacts
    by preempting (whole-request eviction to host), never by crashing."""


class BlockPool:
    """Ref-counted fixed-size block allocator (host-side free list).

    Block ids are indices into the pooled device arrays.  Block
    ``GARBAGE_BLOCK`` (0) is reserved and never handed out.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("pool needs ≥ 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() → low ids first
        self._refs = np.zeros((num_blocks,), np.int32)
        self._refs[GARBAGE_BLOCK] = 1  # permanently held

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> list[int]:
        """n fresh blocks (refcount 1) or ``PoolExhausted`` — all-or-nothing,
        so a partial grab never deadlocks two growing requests."""
        if n > len(self._free):
            raise PoolExhausted(f"need {n} blocks, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, block: int) -> None:
        if self._refs[block] <= 0:
            raise ValueError(f"incref of free block {block}")
        self._refs[block] += 1

    def free(self, block: int) -> None:
        if block == GARBAGE_BLOCK:
            return
        if self._refs[block] <= 0:
            raise ValueError(f"double free of block {block}")
        self._refs[block] -= 1
        if self._refs[block] == 0:
            self._free.append(block)

    def refcount(self, block: int) -> int:
        return int(self._refs[block])


# ---------------------------------------------------------------------------
# Pooled device arrays + per-request tables
# ---------------------------------------------------------------------------


def pool_struct(cfg, num_blocks: int, block_size: int, dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct tree for the paged pools.  GQA families only: the
    paged layout replaces the (L, B, Hkv, S, dh) ring slabs; MLA/SSM/hybrid/
    enc-dec keep the slot engine (serve.kv_cache)."""
    if cfg.family not in ("dense", "moe") or cfg.use_mla:
        raise NotImplementedError(
            f"paged KV covers GQA dense/moe caches; family={cfg.family!r} "
            f"use_mla={cfg.use_mla} keeps the slot engine"
        )
    f = jax.ShapeDtypeStruct
    l, hkv, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_
    # Token-major rows (kernels/paged_decode.py): all KV heads of one
    # token share the 128 TPU lanes.
    pools = {"v": f((l, num_blocks, block_size, hkv * dh), dtype)}
    # Mirror serve_step.make_paged_step's dispatch exactly: the fused path
    # only engages for dense — a moe config with distr_decode set still
    # runs (and pools) the raw-K path, like the slot engine's decode scan.
    if cfg.attention.distr_decode and cfg.family == "dense":
        # Fused-K̂ paged serving never reads OR writes raw K (chunked
        # prefill rides the fused decode kernel too), so unlike the slot
        # cache the raw K pool is dropped entirely — an extra
        # (1 − 1/G*)·½ of the *allocation*, not just the read stream.
        g = cfg.attention.distr.group_size
        pools["k_fused"] = f((l, num_blocks, block_size, hkv * (dh // g)), dtype)
    else:
        pools["k"] = f((l, num_blocks, block_size, hkv * dh), dtype)
    return pools


def init_pools(cfg, num_blocks: int, block_size: int, dtype=jnp.bfloat16) -> dict:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        pool_struct(cfg, num_blocks, block_size, dtype),
    )


@dataclass
class _Evicted:
    """Host copy of a preempted request's live KV (per pool key: numpy
    (L, width, bs, Hkv·dh*) gathered blocks in logical order, possibly
    garbage-padded to a fixed width — see evict_to_host)."""
    length: int
    blocks: dict = field(default_factory=dict)
    n_blocks: int = 0  # real (unpadded) table entries


class PagedKVCache:
    """Device pools + per-request block tables over a :class:`BlockPool`."""

    def __init__(self, cfg, num_blocks: int, block_size: int,
                 dtype=jnp.bfloat16):
        self.cfg = cfg
        self.pool = BlockPool(num_blocks, block_size)
        self.block_size = block_size
        self.pools = init_pools(cfg, num_blocks, block_size, dtype)
        self.tables: dict[int, list[int]] = {}  # uid → physical block ids
        self.evicted: dict[int, _Evicted] = {}
        # Shared (ref > 1 at share time) leading blocks are read-only for
        # their sharers; count per uid so eviction gathers only owned KV.
        self._shared_prefix: dict[int, int] = {}

    # -- allocation -----------------------------------------------------

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def allocate_to(self, uid: int, n_tokens: int) -> None:
        """Grow ``uid``'s table to cover ``n_tokens`` positions.  Raises
        ``PoolExhausted`` (table unchanged) when the pool can't satisfy it."""
        table = self.tables.setdefault(uid, [])
        need = self.blocks_for(n_tokens) - len(table)
        if need > 0:
            table.extend(self.pool.alloc(need))

    def free(self, uid: int) -> None:
        for b in self.tables.pop(uid, []):
            self.pool.free(b)
        self._shared_prefix.pop(uid, None)
        self.evicted.pop(uid, None)

    def table_array(self, uids, max_blocks: int) -> jnp.ndarray:
        """(len(uids), max_blocks) int32 padded block-table rows; absent /
        short tables pad with the garbage block."""
        out = np.full((len(uids), max_blocks), GARBAGE_BLOCK, np.int32)
        for i, uid in enumerate(uids):
            t = self.tables.get(uid, [])
            out[i, : len(t)] = t
        return jnp.asarray(out)

    # -- shared-prefix reuse -------------------------------------------

    def share_prefix(self, src_uid: int, dst_uid: int, n_tokens: int) -> int:
        """Seed ``dst``'s table with ``src``'s full blocks covering the first
        ``n_tokens`` positions (rounded *down* to whole blocks — partial
        blocks are still written by src's decode and are never shared).
        Returns the number of tokens actually covered; dst must start its
        prefill at that offset."""
        if self.tables.get(dst_uid):
            raise ValueError(f"dst {dst_uid} already has blocks")
        src = self.tables.get(src_uid, [])
        n_blocks = min(n_tokens // self.block_size, len(src))
        for b in src[:n_blocks]:
            self.pool.incref(b)
        self.tables[dst_uid] = list(src[:n_blocks])
        if n_blocks:
            self._shared_prefix[dst_uid] = n_blocks
        return n_blocks * self.block_size

    # -- preemption ----------------------------------------------------

    def evict_to_host(self, uid: int, length: int, *,
                      pad_to: int | None = None) -> None:
        """Copy ``uid``'s live blocks to host numpy and free them.  Every
        table entry is gathered (shared-prefix blocks included — restore
        simply writes them back as owned blocks).  ``pad_to`` pads the
        gather to a fixed table width with the garbage block so every
        evict/restore traces the SAME shapes — without it, each distinct
        block count jit-compiles a fresh gather/scatter pair (a visible
        first-preemption stall in serving)."""
        table = self.tables.get(uid)
        if not table:
            raise ValueError(f"uid {uid} holds no blocks")
        width = max(pad_to or 0, len(table))
        padded = table + [GARBAGE_BLOCK] * (width - len(table))
        idx = jnp.asarray(padded, jnp.int32)
        ev = _Evicted(length=length)
        ev.n_blocks = len(table)
        for key, pool in self.pools.items():
            # (L, width, bs, Hkv·dh*) in logical block order
            ev.blocks[key] = np.asarray(jnp.take(pool, idx, axis=1))
        self.evicted[uid] = ev
        for b in table:
            self.pool.free(b)
        del self.tables[uid]
        self._shared_prefix.pop(uid, None)

    def restore(self, uid: int) -> int:
        """Re-allocate and copy back an evicted request's KV; returns its
        live length.  Raises ``PoolExhausted`` with nothing allocated if the
        pool can't hold it yet.  Rows padded at eviction scatter back into
        the garbage block (content never read), keeping the write shape
        fixed too."""
        ev = self.evicted[uid]
        width = next(iter(ev.blocks.values())).shape[1]
        blocks = self.pool.alloc(ev.n_blocks)  # all-or-nothing
        padded = blocks + [GARBAGE_BLOCK] * (width - len(blocks))
        idx = jnp.asarray(padded, jnp.int32)
        for key in self.pools:
            self.pools[key] = self.pools[key].at[:, idx].set(
                jnp.asarray(ev.blocks[key], self.pools[key].dtype)
            )
        self.tables[uid] = blocks
        del self.evicted[uid]
        return ev.length
