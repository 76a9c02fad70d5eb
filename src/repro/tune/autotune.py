"""The empirical block-size autotuner.

Three-stage resolution for "auto" (``None``) block sizes, selected by the
``REPRO_TUNE`` env var:

  off       static defaults (128×128 / decode 128) — the pre-tuner behaviour.
  analytic  the paper's §3.3.1 rule (``core.block_size.select_block_sizes``),
            clamped to the sequence bucket.  Zero measurement cost.
  measure   enumerate candidates with the analytic model *as a pruner* (all
            VMEM-fitting tiles, ranked by the paper's max-l-then-m objective,
            top-K kept, default 128×128 always included), time each on the
            live backend, cache the winner in the persistent JSON cache.

Measurement runs at the key's sequence bucket (capped in interpret mode —
CPU-interpreter wall time at 4k tokens is pure overhead) on synthetic
inputs, with warmup and ``block_until_ready``; the timer is injectable so
tests are deterministic.  Resolutions are memoised per (mode, cache-path,
key), so a jitted train/serve step pays the sweep once per process and the
JSON cache makes later processes pay nothing.
"""
from __future__ import annotations

import functools
import os

from repro.core.block_size import enumerate_block_sizes, select_block_sizes
from repro.obs.trace import get_recorder
from repro.tune.block_sizes import BlockSizes
from repro.tune.cache import TuneCache, cache_key, seq_bucket
from repro.tune.measure import Timer, measure_candidates, wall_timer

MODES = ("off", "analytic", "measure")
DEFAULT_BLOCK = 128
TOP_K = 8
# Interpreter-mode measurement cap: beyond this the sweep cost dwarfs the
# information (the relative ordering is stable in the bucket); compiled
# backends measure the true bucket up to 2k.
MEASURE_SEQ_CAP_INTERPRET = 512
MEASURE_SEQ_CAP_COMPILED = 2048


def tune_mode() -> str:
    mode = os.environ.get("REPRO_TUNE", "off").lower()
    if mode not in MODES:
        raise ValueError(f"REPRO_TUNE={mode!r}; choose from {MODES}")
    return mode


def _default_interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"


def _backend_tag(interpret: bool) -> str:
    import jax

    return f"{jax.default_backend()}:{'interpret' if interpret else 'compiled'}"


# ---------------------------------------------------------------------------
# Candidate spaces — sourced from the analytic model (the pruner)
# ---------------------------------------------------------------------------


def pair_candidates(
    d: int,
    *,
    n: int,
    group_size: int = 1,
    top_k: int = TOP_K,
    max_block: int = 1024,
) -> list[tuple[int, int]]:
    """Top-K (l, m) candidates: every VMEM-fitting tile from
    ``enumerate_block_sizes``, ranked by the paper's objective (max l —
    minimum HBM I/O — then max m), clamped to the sequence bucket, deduped.
    The 128×128 default is always appended so a measured pick can never be
    *worse* than the static default on the measured axis."""
    nb = min(seq_bucket(n), max_block)
    legal = enumerate_block_sizes(
        d, group_size=group_size, max_l=max_block, max_m=max_block
    )
    ranked = sorted(legal, key=lambda t: (-t[0], -t[1]))
    cands: list[tuple[int, int]] = []
    for l, m, _ws in ranked:
        c = (min(l, nb), min(m, nb))
        if c not in cands:
            cands.append(c)
        if len(cands) >= top_k:
            break
    default = (min(DEFAULT_BLOCK, nb), min(DEFAULT_BLOCK, nb))
    if default not in cands:
        cands.append(default)
    return cands


def distr_bwd_candidates(
    d: int,
    *,
    block_q: int,
    n: int,
    group_size: int,
    top_k: int = TOP_K,
    max_block: int = 1024,
) -> list[int]:
    """``block_k`` candidates for the distr backward kernels with ``block_q``
    *pinned* (it is the LSH grouping granularity — never swept): the legal
    m values at l = block_q from the analytic VMEM model, largest first,
    clamped to the sequence bucket, 128 always included."""
    nb = min(seq_bucket(n), max_block)
    legal = enumerate_block_sizes(
        d, group_size=group_size, max_l=max_block, max_m=max_block
    )
    ms = sorted(
        {min(m, nb) for l, m, _ws in legal if l == block_q}, reverse=True
    )[:top_k]
    default = min(DEFAULT_BLOCK, nb)
    if default not in ms:
        ms.append(default)
    return ms or [default]


def decode_candidates(n: int, *, max_block: int = 1024) -> list[int]:
    """Split-K decode block_k candidates: power-of-two split lengths up to
    the cache capacity.  Fewer, longer splits amortise per-split overhead;
    more, shorter splits add parallelism — the right point is empirical."""
    nb = min(seq_bucket(n), max_block)
    cands = [bk for bk in (64, 128, 256, 512, 1024) if bk <= nb]
    return cands or [nb]


def paged_block_candidates(n: int, *, max_block: int = 512) -> list[int]:
    """Pool block-size candidates for the paged decode kernel.  The block
    size is simultaneously the DMA granularity (bigger amortises per-block
    overhead) and the allocator granularity (smaller wastes less of the
    last, part-filled block per request) — the right point is empirical,
    measured on the kernel side here; the fragmentation side is workload
    policy (serve/scheduler.py)."""
    nb = min(seq_bucket(n), max_block)
    cands = [bs for bs in (64, 128, 256, 512) if bs <= nb]
    return cands or [nb]


def _make_run_paged_decode(n, d, dtype, interpret, group_size):
    """Sweep runner: one request whose block table spans the whole capacity
    ``n`` — physical blocks deliberately shuffled so the measurement sees
    real (non-contiguous) table indirection."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.paged_decode import token_major

    dt = _np_dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    hkv, hq = 1, 2
    q = jax.random.normal(ks[0], (1, hq, 1, d), jnp.float32).astype(dt)
    if group_size > 1:
        from repro.core import grouping

        perm = jnp.broadcast_to(
            jax.random.permutation(jax.random.PRNGKey(1), d)[None], (hkv, d)
        ).astype(jnp.int32)

    def make_run(cand):
        bs = int(cand)
        mb = -(-n // bs)
        p = mb + 1  # + the reserved garbage block
        k_pool = jax.random.normal(
            ks[1], (p, hkv, bs, d), jnp.float32
        ).astype(dt)
        v_pool = jax.random.normal(
            ks[2], (p, hkv, bs, d), jnp.float32
        ).astype(dt)
        bt = jax.random.permutation(
            jax.random.PRNGKey(2), jnp.arange(1, p, dtype=jnp.int32)
        )[None, :]
        lengths = jnp.full((1,), n, jnp.int32)
        v_pool = token_major(v_pool)
        if group_size > 1:
            from repro.core import grouping

            k_fused = token_major(grouping.fuse_columns(
                k_pool.astype(jnp.float32), perm[None], group_size
            ).astype(dt))
            return lambda: ops.paged_decode_attention(
                q, None, v_pool, block_tables=bt, lengths=lengths,
                k_fused_pool=k_fused, perm=perm, group_size=group_size,
                interpret=interpret,
            )
        k_pool = token_major(k_pool)
        return lambda: ops.paged_decode_attention(
            q, k_pool, v_pool, block_tables=bt, lengths=lengths,
            interpret=interpret,
        )

    return make_run


def _analytic_pair(d: int, *, n: int, group_size: int) -> tuple[int, int]:
    nb = min(seq_bucket(n), 1024)
    l, m = select_block_sizes(d, group_size=group_size, max_l=nb, max_m=nb)
    return (min(l, nb), min(m, nb))


def _analytic_decode(n: int) -> int:
    # Aim for ~8 live splits (enough grid parallelism) but never below the
    # 128-lane tile; clamp to the capacity bucket.
    nb = min(seq_bucket(n), 1024)
    bk = 128
    while bk * 8 < nb:
        bk *= 2
    return min(bk, nb, 512)


# ---------------------------------------------------------------------------
# Measurement factories (one per kernel key)
# ---------------------------------------------------------------------------


def _np_dtype(dtype: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}.get(
        dtype, jnp.float32
    )


def _qkv(n: int, d: int, dtype: str, *, heads: int = 1):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (1, heads, n, d)
    dt = _np_dtype(dtype)
    return tuple(
        jax.random.normal(k, shape, jnp.float32).astype(dt) for k in ks
    )


def _pad_axis(x, block: int, axis: int, value: float = 0.0):
    import jax.numpy as jnp

    pad = (-x.shape[axis]) % block
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _make_run_flash_fwd(n, d, dtype, causal, interpret):
    from repro.kernels import ops

    q, k, v = _qkv(n, d, dtype)

    def make_run(cand):
        bq, bk = cand

        def run():
            return ops.flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                interpret=interpret,
            )

        return run

    return make_run


def _make_run_xla_flash(n, d, dtype, causal, interpret):
    del interpret  # pure-XLA path
    import jax

    from repro.core.flash_reference import blockwise_flash_reference

    q, k, v = _qkv(n, d, dtype)

    def make_run(cand):
        bq, bk = cand
        fn = jax.jit(
            functools.partial(
                blockwise_flash_reference, block_q=bq, block_k=bk,
                causal=causal,
            )
        )
        return lambda: fn(q, k, v)

    return make_run


def _make_run_distr(n, d, dtype, causal, interpret, group_size, *, xla: bool):
    from dataclasses import replace as dc_replace

    from repro.core.distr_attention import DistrConfig

    q, k, v = _qkv(n, d, dtype)
    base = DistrConfig(group_size=group_size)

    def make_run(cand):
        bq, bk = cand
        cfg = dc_replace(base, block_q=bq, block_k=bk)
        if xla:
            import jax

            from repro.core.distr_attention import distr_attention as core_distr

            fn = jax.jit(
                functools.partial(core_distr, cfg=cfg, causal=causal)
            )
            return lambda: fn(q, k, v)
        from repro.kernels import ops

        return lambda: ops.distr_attention(
            q, k, v, cfg, causal=causal, interpret=interpret
        )

    return make_run


def _flash_bwd_inputs(n, d, dtype, causal, interpret):
    """Shared residuals for the dQ/dKV sweeps: one fwd pass at the default
    blocks provides (O, LSE); Δ comes from the delta kernel."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import backward as bwd
    from repro.kernels import ops

    q, k, v = _qkv(n, d, dtype)
    scale = 1.0 / (d**0.5)
    out, lse = ops._flash_fwd_impl(  # noqa: SLF001 — same package family
        causal, scale, min(DEFAULT_BLOCK, n), min(DEFAULT_BLOCK, n),
        interpret, q, k, v, with_residuals=True,
    )
    do = jax.random.normal(jax.random.PRNGKey(7), out.shape, jnp.float32)
    qf = q.reshape(-1, n, d)
    kf = k.reshape(-1, n, d)
    vf = v.reshape(-1, n, d)
    dof = do.reshape(-1, n, d).astype(q.dtype)
    of = out.reshape(-1, n, d)
    delta = bwd.delta_kernel_call(
        of, dof, block_q=min(DEFAULT_BLOCK, n), interpret=interpret
    )
    return qf, kf, vf, dof, lse[:, :n], delta[:, :n], scale


def _make_run_flash_bwd(n, d, dtype, causal, interpret, *, which: str):
    import jax

    from repro.kernels import backward as bwd
    # Residual padding MUST be the production backward's own helpers
    # (ops._pad_rows / ops.LSE_PAD): the sweep times exactly the
    # computation the tuned blocks will run.
    from repro.kernels import ops

    qf, kf, vf, dof, lse, delta, scale = _flash_bwd_inputs(
        n, d, dtype, causal, interpret
    )

    def make_run(cand):
        bq, bk = cand
        qp = _pad_axis(qf, bq, 1)
        dop = _pad_axis(dof, bq, 1)
        lsep = ops._pad_rows(lse, bq, ops.LSE_PAD)
        deltap = ops._pad_rows(delta, bq)
        kp = _pad_axis(kf, bk, 1)
        vp = _pad_axis(vf, bk, 1)
        call = (
            bwd.flash_dq_kernel_call if which == "dq"
            else bwd.flash_dkv_kernel_call
        )
        fn = jax.jit(
            lambda a, b, c, e, f, g: call(
                a, b, c, e, f, g, q_per_kv=1, scale=scale, causal=causal,
                block_q=bq, block_k=bk, kv_len=n, interpret=interpret,
            )
        )
        return lambda: fn(qp, kp, vp, dop, lsep, deltap)

    return make_run


def _make_run_distr_bwd(n, d, dtype, causal, interpret, group_size, block_q,
                        *, which: str):
    """Sweep runner for the distr backward kernels: one fwd pass at the
    pinned block_q provides (O, LSE, Q̂, perms); only ``block_k`` varies."""
    from dataclasses import replace as dc_replace

    import jax
    import jax.numpy as jnp

    from repro.core.distr_attention import DistrConfig
    from repro.kernels import backward as bwd
    from repro.kernels import ops

    q, k, v = _qkv(n, d, dtype)
    scale = 1.0 / (d**0.5)
    cfg = dc_replace(
        DistrConfig(group_size=group_size), block_q=min(block_q, n),
        block_k=min(DEFAULT_BLOCK, n),
    )
    out, lse, q_hat, perms = ops._distr_fwd_impl(  # noqa: SLF001
        cfg, causal, scale, interpret, q, k, v, with_residuals=True,
    )
    do = jax.random.normal(jax.random.PRNGKey(7), out.shape, jnp.float32)
    dof = do.reshape(-1, n, d).astype(q.dtype)
    of = out.reshape(-1, n, d)
    kf = k.reshape(-1, n, d)
    vf = v.reshape(-1, n, d)
    perm_f = perms.reshape(1, -1, d)
    delta = bwd.delta_kernel_call(
        of, dof, block_q=cfg.block_q, interpret=interpret
    )

    def make_run(cand):
        bk = int(cand)
        kp = _pad_axis(kf, bk, 1)
        vp = _pad_axis(vf, bk, 1)
        kw = dict(
            q_per_kv=1, causal=causal, group_size=group_size,
            block_q=cfg.block_q, block_k=bk, kv_len=n, interpret=interpret,
        )
        if which == "dq":
            fn = jax.jit(lambda: bwd.distr_dq_kernel_call(
                q_hat, kp, vp, perm_f, dof, lse, delta, **kw
            ))
        else:
            fn = jax.jit(lambda: bwd.distr_dkv_kernel_call(
                q_hat, kp, vp, perm_f, dof, lse, delta, **kw
            ))
        return fn

    return make_run


def _make_run_decode(n, d, dtype, interpret, group_size):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.paged_decode import token_major

    dt = _np_dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    hkv, hq = 1, 2
    q = jax.random.normal(ks[0], (1, hq, 1, d), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (1, hkv, n, d), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (1, hkv, n, d), jnp.float32).astype(dt)
    if group_size > 1:
        # Fused-K̂ layout: narrow score stream, full-width V.
        perm = jnp.broadcast_to(
            jax.random.permutation(jax.random.PRNGKey(1), d)[None], (hkv, d)
        ).astype(jnp.int32)
        from repro.core import grouping

        k_fused = grouping.fuse_columns(
            k.astype(jnp.float32), perm[None], group_size
        ).astype(dt)
    lengths = jnp.full((1,), n, jnp.int32)

    def make_run(cand):
        bk = int(cand)
        if group_size > 1:
            return lambda: ops.decode_attention(
                q, None, v, lengths=lengths, k_fused=k_fused, perm=perm,
                group_size=group_size, block_k=bk, interpret=interpret,
            )
        return lambda: ops.decode_attention(
            q, k, v, lengths=lengths, block_k=bk, interpret=interpret
        )

    return make_run


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


class Autotuner:
    """Resolution + measurement + caching.  ``timer`` is injectable (tests
    pass a deterministic fake); ``cache`` defaults to the env-pointed JSON."""

    def __init__(
        self,
        cache: TuneCache | None = None,
        timer: Timer | None = None,
        *,
        top_k: int = TOP_K,
    ):
        self.cache = cache if cache is not None else TuneCache()
        self.timer = timer
        self.top_k = top_k
        self._memo: dict = {}

    # -- internals ----------------------------------------------------------

    def _timer(self) -> Timer:
        return self.timer if self.timer is not None else wall_timer()

    def _measure_seq(self, n: int, interpret: bool) -> int:
        cap = (
            MEASURE_SEQ_CAP_INTERPRET if interpret
            else MEASURE_SEQ_CAP_COMPILED
        )
        return max(128, min(seq_bucket(n), cap))

    def _resolve_measured(self, kernel, key, candidates, make_run_thunk) -> dict:
        """Cache lookup → sweep → persist.  Returns the cache entry.
        ``make_run_thunk()`` lazily builds the per-candidate runner factory so
        a cache hit never touches the backend."""
        entry = self.cache.get(key)
        if entry is not None:
            return entry
        # Sweeps ride the global recorder: the autotuner has no constructor
        # injection path, and --trace runs want tuned picks in the trace.
        rec = get_recorder()
        with rec.span("tune/measure", kernel=kernel,
                      n_candidates=len(candidates)):
            table = measure_candidates(
                make_run_thunk(), candidates, self._timer()
            )
        best = min(table, key=lambda c: table[c])
        rec.instant(
            "tune/pick", kernel=kernel,
            best=list(best) if isinstance(best, tuple) else int(best),
            seconds=table[best],
        )
        entry = {
            "kernel": kernel,
            "best": list(best) if isinstance(best, tuple) else int(best),
            "table": [
                {
                    "candidate": list(c) if isinstance(c, tuple) else int(c),
                    "seconds": s,
                }
                for c, s in sorted(table.items(), key=lambda kv: kv[1])
            ],
        }
        self.cache.put(key, entry)
        return entry

    def _pair_key_and_resolve(
        self, kernel, *, d, n, dtype, group_size, causal, interpret,
        make_run_for,
    ) -> tuple[int, int]:
        mode = tune_mode()
        memo_key = (
            mode, self.cache.path, kernel, d, seq_bucket(n), dtype,
            group_size, causal, interpret,
        )
        if memo_key in self._memo:
            return self._memo[memo_key]
        if mode == "off":
            nb = seq_bucket(n)
            pair = (min(DEFAULT_BLOCK, nb), min(DEFAULT_BLOCK, nb))
        elif mode == "analytic":
            pair = _analytic_pair(d, n=n, group_size=group_size)
        else:
            n_meas = self._measure_seq(n, interpret)
            cands = pair_candidates(
                d, n=n_meas, group_size=group_size, top_k=self.top_k
            )
            key = cache_key(
                kernel, backend=_backend_tag(interpret), dtype=dtype, d=d,
                group_size=group_size, n=n_meas, causal=causal,
            )
            entry = self._resolve_measured(
                kernel, key, cands, lambda: make_run_for(n_meas)
            )
            pair = tuple(entry["best"])
        self._memo[memo_key] = pair
        return pair

    # -- public resolution entry points -------------------------------------

    def resolve_pair(
        self,
        kernel: str,
        *,
        d: int,
        n: int,
        dtype: str = "float32",
        group_size: int = 1,
        causal: bool = False,
        interpret: bool | None = None,
    ) -> tuple[int, int]:
        """(block_q, block_k) for one forward/backward kernel key.  Kernels:
        flash_fwd | flash_dq | flash_dkv | xla_flash | distr_fwd | xla_distr.
        """
        if interpret is None:
            interpret = _default_interpret()

        def make_run_for(n_meas):
            if kernel == "flash_fwd":
                return _make_run_flash_fwd(n_meas, d, dtype, causal, interpret)
            if kernel == "xla_flash":
                return _make_run_xla_flash(n_meas, d, dtype, causal, interpret)
            if kernel in ("flash_dq", "flash_dkv"):
                return _make_run_flash_bwd(
                    n_meas, d, dtype, causal, interpret,
                    which=kernel.split("_")[1],
                )
            if kernel in ("distr_fwd", "xla_distr"):
                return _make_run_distr(
                    n_meas, d, dtype, causal, interpret, group_size,
                    xla=(kernel == "xla_distr"),
                )
            raise ValueError(f"unknown pair kernel {kernel!r}")

        return self._pair_key_and_resolve(
            kernel, d=d, n=n, dtype=dtype, group_size=group_size,
            causal=causal, interpret=interpret, make_run_for=make_run_for,
        )

    def resolve_distr_bwd(
        self,
        kernel: str,
        *,
        block_q: int,
        d: int,
        n: int,
        dtype: str = "float32",
        group_size: int = 2,
        causal: bool = False,
        interpret: bool | None = None,
        fwd_block_k: int | None = None,
    ) -> tuple[int, int]:
        """(block_q, block_k) for a distr *backward* kernel ("distr_dq" |
        "distr_dkv").  ``block_q`` is pinned by the caller — it is the LSH
        grouping granularity, shared with the forward and the saved
        permutations — and only ``block_k`` is resolved: the fwd pick (or
        128) outside measure mode, an independent sweep under it."""
        if kernel not in ("distr_dq", "distr_dkv"):
            raise ValueError(f"unknown distr bwd kernel {kernel!r}")
        if interpret is None:
            interpret = _default_interpret()
        mode = tune_mode()
        memo_key = (
            mode, self.cache.path, kernel, block_q, d, seq_bucket(n), dtype,
            group_size, causal, interpret, fwd_block_k,
        )
        if memo_key in self._memo:
            pair = self._memo[memo_key]
        elif mode != "measure":
            bk = (
                fwd_block_k if fwd_block_k is not None
                else min(DEFAULT_BLOCK, seq_bucket(n))
            )
            pair = (block_q, bk)
        else:
            n_meas = self._measure_seq(n, interpret)
            bq = min(block_q, n_meas)
            cands = distr_bwd_candidates(
                d, block_q=bq, n=n_meas, group_size=group_size,
                top_k=self.top_k,
            )
            # The grouping pin: the backward sweep varies block_k ONLY —
            # a refactor that starts sweeping (l, m) pairs here would
            # silently change which columns the saved permutations group.
            # Fail loudly on the candidate space and on the cache entry (a
            # pair-shaped `best` means a drifted writer poisoned the key).
            assert all(not isinstance(c, (tuple, list)) for c in cands), (
                "distr backward candidates must be block_k scalars; "
                "block_q is the LSH grouping granularity and stays pinned"
            )
            key = cache_key(
                f"{kernel}@l={block_q}", backend=_backend_tag(interpret),
                dtype=dtype, d=d, group_size=group_size, n=n_meas,
                causal=causal,
            )
            entry = self._resolve_measured(
                kernel, key, cands,
                lambda: _make_run_distr_bwd(
                    n_meas, d, dtype, causal, interpret, group_size, bq,
                    which=kernel.split("_")[1],
                ),
            )
            assert not isinstance(entry["best"], (tuple, list)), (
                f"distr backward cache entry for {key!r} holds a (l, m) "
                "pair — block_q must stay pinned to the LSH grouping "
                "granularity, only block_k is tuned"
            )
            pair = (block_q, int(entry["best"]))
        self._memo[memo_key] = pair
        return pair

    def resolve_decode(
        self,
        *,
        d: int,
        n: int,
        dtype: str = "bfloat16",
        group_size: int = 1,
        interpret: bool | None = None,
    ) -> int:
        """Split-K ``block_k`` for the decode kernel at cache capacity n."""
        if interpret is None:
            interpret = _default_interpret()
        mode = tune_mode()
        memo_key = (
            mode, self.cache.path, "decode", d, seq_bucket(n), dtype,
            group_size, interpret,
        )
        if memo_key in self._memo:
            return self._memo[memo_key]
        if mode == "off":
            bk = min(DEFAULT_BLOCK, seq_bucket(n))
        elif mode == "analytic":
            bk = _analytic_decode(n)
        else:
            n_meas = self._measure_seq(n, interpret)
            cands = decode_candidates(n_meas)
            key = cache_key(
                "decode", backend=_backend_tag(interpret), dtype=dtype, d=d,
                group_size=group_size, n=n_meas, causal=False,
            )
            entry = self._resolve_measured(
                "decode", key, cands,
                lambda: _make_run_decode(n_meas, d, dtype, interpret, group_size),
            )
            bk = int(entry["best"])
        self._memo[memo_key] = bk
        return bk

    def resolve_paged_decode(
        self,
        *,
        d: int,
        n: int,
        dtype: str = "bfloat16",
        group_size: int = 1,
        interpret: bool | None = None,
    ) -> int:
        """Pool block size for the *paged* decode kernel at per-request
        capacity ``n`` (kernels/paged_decode.py).  Unlike the contiguous
        split-K knob this is also the allocator granularity — the
        PagedServeEngine resolves it once at construction (its pools are
        shaped by it), which doubles as the warm-up: measure-mode sweeps
        run here, never inside a serving tick."""
        if interpret is None:
            interpret = _default_interpret()
        mode = tune_mode()
        memo_key = (
            mode, self.cache.path, "paged_decode", d, seq_bucket(n), dtype,
            group_size, interpret,
        )
        if memo_key in self._memo:
            return self._memo[memo_key]
        if mode == "off":
            bs = min(DEFAULT_BLOCK, seq_bucket(n))
        elif mode == "analytic":
            bs = _analytic_decode(n)
        else:
            n_meas = self._measure_seq(n, interpret)
            cands = paged_block_candidates(n_meas)
            key = cache_key(
                "paged_decode", backend=_backend_tag(interpret), dtype=dtype,
                d=d, group_size=group_size, n=n_meas, causal=False,
            )
            entry = self._resolve_measured(
                "paged_decode", key, cands,
                lambda: _make_run_paged_decode(
                    n_meas, d, dtype, interpret, group_size
                ),
            )
            bs = int(entry["best"])
        self._memo[memo_key] = bs
        return bs

    def resolve(
        self,
        kind: str,
        *,
        d: int,
        n: int,
        dtype: str = "float32",
        group_size: int = 1,
        causal: bool = False,
        interpret: bool | None = None,
        bwd: bool = False,
    ) -> BlockSizes:
        """Full BlockSizes record for an attention implementation kind:
        "flash" (Pallas), "xla_flash", "distr" (Pallas; block_q doubles as
        the LSH granularity so the bwd kernels keep the fwd pair), or
        "xla_distr".  For "flash", ``bwd=True`` eagerly resolves the
        backward dQ/dKV keys too (measure mode; training warm-up) — the
        default leaves them None, and ``ops._flash_vjp_bwd`` resolves them
        lazily when grad tracing first reaches the op, so forward-only
        dispatch never pays a backward sweep."""
        if kind == "flash":
            fwd = self.resolve_pair(
                "flash_fwd", d=d, n=n, dtype=dtype, causal=causal,
                interpret=interpret,
            )
            bs = BlockSizes.from_pair(*fwd)
            if bwd and tune_mode() == "measure":
                dq = self.resolve_pair(
                    "flash_dq", d=d, n=n, dtype=dtype, causal=causal,
                    interpret=interpret,
                )
                dkv = self.resolve_pair(
                    "flash_dkv", d=d, n=n, dtype=dtype, causal=causal,
                    interpret=interpret,
                )
                bs = bs.with_(
                    block_q_dq=dq[0], block_k_dq=dq[1],
                    block_q_dkv=dkv[0], block_k_dkv=dkv[1],
                )
            return bs
        if kind in ("xla_flash", "distr", "xla_distr"):
            kernel = {
                "xla_flash": "xla_flash",
                "distr": "distr_fwd",
                "xla_distr": "xla_distr",
            }[kind]
            fwd = self.resolve_pair(
                kernel, d=d, n=n, dtype=dtype, group_size=group_size,
                causal=causal, interpret=interpret,
            )
            return BlockSizes.from_pair(*fwd)
        raise ValueError(f"unknown resolution kind {kind!r}")


# ---------------------------------------------------------------------------
# Module-level singleton + convenience wrappers (the dispatch entry points)
# ---------------------------------------------------------------------------

_AUTOTUNER: Autotuner | None = None


def get_autotuner() -> Autotuner:
    global _AUTOTUNER
    if _AUTOTUNER is None:
        _AUTOTUNER = Autotuner()
    return _AUTOTUNER


def reset_autotuner(tuner: Autotuner | None = None) -> None:
    """Swap/clear the process-wide tuner (tests: inject fake timers/caches)."""
    global _AUTOTUNER
    _AUTOTUNER = tuner


def resolve_block_sizes(kind: str, **kw) -> BlockSizes:
    return get_autotuner().resolve(kind, **kw)


def resolve_decode_block(**kw) -> int:
    return get_autotuner().resolve_decode(**kw)


def resolve_paged_decode_block(**kw) -> int:
    return get_autotuner().resolve_paged_decode(**kw)


def warm_paged_engine(cfg, max_len: int, *, decode: bool = True,
                      mesh_prefill_buckets: bool = False,
                      buckets=(32, 64, 128, 256, 512, 1024,
                               2048, 4096)) -> dict:
    """Pre-resolve the block-size keys a PagedServeEngine will hit: the
    paged-decode pool block (which shapes the pools themselves, so it MUST
    resolve before construction).  Measure-mode sweeps run here, once —
    mirroring :func:`warm_engine` for the slot engine.  Returns
    {site: resolved} for logging.

    ``mesh_prefill_buckets`` additionally resolves the whole-prompt
    ring-prefill attend at each bucket ≤ max_len (the mesh engine's
    ``prefill_mesh_run`` buckets).  Call it with the engine's mesh ACTIVE
    (``maybe_set_mesh``): ``api.resolve_attention_blocks`` then keys each
    bucket by its per-ring-shard sequence length, so the tuned tile sizes
    match what each device actually runs — the same per-shard keying the
    slot engine's long-prompt path gets from :func:`warm_engine`."""
    out: dict = {}
    if cfg.attention.impl == "reference":
        return out
    g = (
        cfg.attention.distr.group_size if cfg.attention.distr_decode else 1
    )
    if decode:
        # Keyed by the KV-pool dtype (bf16, the serve default), like the
        # contiguous decode key.
        out["paged_decode"] = get_autotuner().resolve_paged_decode(
            d=cfg.head_dim_, n=max_len, dtype="bfloat16", group_size=g
        )
    if mesh_prefill_buckets:
        from repro.core import api

        dtype = (
            "bfloat16" if getattr(cfg, "compute_dtype", "") == "bfloat16"
            else "float32"
        )
        live = sorted({min(b, max_len) for b in buckets if b <= max_len}
                      | {max_len})
        for b in live:
            out[f"mesh_prefill/{b}"] = api.resolve_attention_blocks(
                cfg.attention, d=cfg.head_dim_, n_q=b, n_k=b, dtype=dtype,
                causal=True,
            )
    return out


def warm_engine(cfg, max_len: int, *, buckets=(32, 64, 128, 256, 512, 1024,
                                              2048, 4096)) -> dict:
    """Pre-resolve every block-size key a ServeEngine will hit: the prefill
    attend at each bucket ≤ max_len and the decode split-K block at the
    cache capacity.  In ``measure`` mode this runs (and persists) the sweeps
    up front so no serving step ever blocks on a timing run; in ``off`` /
    ``analytic`` it is effectively free.  Forward keys only: the backward
    dQ/dKV keys resolve lazily at backward-trace time, which a serving
    process never reaches.  Returns {site: resolved} for logging."""
    from repro.core import api

    acfg = cfg.attention
    out: dict = {}
    dtype = (
        "bfloat16" if getattr(cfg, "compute_dtype", "") == "bfloat16"
        else "float32"
    )
    d = cfg.head_dim_
    if acfg.impl != "reference":
        live = sorted({min(b, max_len) for b in buckets if b <= max_len}
                      | {max_len})
        for b in live:
            out[f"prefill/{b}"] = api.resolve_attention_blocks(
                acfg, d=d, n_q=b, n_k=b, dtype=dtype, causal=True
            )
        g = acfg.distr.group_size if acfg.distr_decode else 1
        # The decode key is keyed by the KV-cache dtype (bf16 — the
        # serve.kv_cache.init_cache default the engine uses), not the
        # compute dtype: decode_attention resolves from the cache arrays.
        bk = get_autotuner().resolve_decode(
            d=d, n=max_len, dtype="bfloat16", group_size=g
        )
        out["decode"] = BlockSizes(
            block_k_decode=bk, num_splits=-(-max_len // bk)
        )
    return out
