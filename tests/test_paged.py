"""Paged KV subsystem: block-table kernel parity, pool/scheduler
invariants, paged-vs-contiguous engine-path parity (ISSUE 5 acceptance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import grouping
from repro.core.api import attend_decode
from repro.kernels.paged_decode import token_major
from repro.models import lm
from repro.serve import paged
from repro.serve.engine import PagedServeEngine
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.serve.serve_step import make_decode_step, make_paged_step, make_prefill


def _random_pool_case(key, b, hkv, d, bs, mb, dtype=jnp.float32):
    """Token-major pools + a shuffled (non-contiguous) block table per
    request."""
    ks = jax.random.split(key, 3)
    p = 1 + b * mb  # + reserved garbage block 0
    k_pool = jax.random.normal(ks[0], (p, hkv, bs, d), jnp.float32).astype(dtype)
    v_pool = jax.random.normal(ks[1], (p, hkv, bs, d), jnp.float32).astype(dtype)
    ids = np.arange(1, p, dtype=np.int32)
    np.random.RandomState(0).shuffle(ids)
    bt = jnp.asarray(ids.reshape(b, mb))
    return token_major(k_pool), token_major(v_pool), bt, ks[2]


def _gather(pool, bt, hkv):
    g = jnp.take(pool, bt, axis=0)  # (B, mb, bs, Hkv·d)
    b, mb, bs, width = g.shape
    return g.reshape(b, mb * bs, hkv, width // hkv).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Kernel-level parity (ops.paged_decode_attention vs gathered oracle)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("q_per_kv", [1, 4])
def test_paged_kernel_matches_gathered_oracle(dtype, q_per_kv):
    """Ragged lengths (incl. block-boundary crossings and single-token) over
    shuffled physical blocks equal the contiguous decode oracle."""
    from repro.kernels import ops, ref

    b, hkv, d, bs, mb = 4, 2, 32, 8, 4
    k_pool, v_pool, bt, kq = _random_pool_case(
        jax.random.PRNGKey(0), b, hkv, d, bs, mb, dtype
    )
    q = jax.random.normal(kq, (b, hkv * q_per_kv, 1, d), jnp.float32).astype(dtype)
    # exact block multiple, mid-block, crossing, and single-token lengths
    lengths = jnp.asarray([16, 13, 25, 1], jnp.int32)
    out = ops.paged_decode_attention(
        q, k_pool, v_pool, block_tables=bt, lengths=lengths
    )
    want = ref.decode_attention_ref(
        q.astype(jnp.float32),
        _gather(k_pool, bt, hkv).astype(jnp.float32),
        _gather(v_pool, bt, hkv).astype(jnp.float32),
        lengths,
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=tol, atol=tol
    )


def test_paged_kernel_banded_window():
    """q_len > 1 (chunked prefill): row i sees positions
    < length − (q_len − 1 − i), matching the contiguous kernel's band."""
    from repro.kernels import ops

    b, hkv, d, bs, mb, ql = 2, 2, 32, 8, 4, 4
    k_pool, v_pool, bt, kq = _random_pool_case(
        jax.random.PRNGKey(1), b, hkv, d, bs, mb
    )
    q = jax.random.normal(kq, (b, 4, ql, d), jnp.float32)
    lengths = jnp.asarray([17, 9], jnp.int32)
    out = ops.paged_decode_attention(
        q, k_pool, v_pool, block_tables=bt, lengths=lengths
    )
    from repro.core.flash_reference import reference_attention

    k_c, v_c = _gather(k_pool, bt, hkv), _gather(v_pool, bt, hkv)
    for bi in range(b):
        for i in range(ql):
            mask = (
                jnp.arange(mb * bs)[None, :]
                < int(lengths[bi]) - (ql - 1 - i)
            )
            want = reference_attention(
                q[bi : bi + 1, :, i : i + 1], k_c[bi : bi + 1],
                v_c[bi : bi + 1], kv_mask=mask,
            )
            np.testing.assert_allclose(
                np.asarray(out[bi : bi + 1, :, i : i + 1]), np.asarray(want),
                rtol=2e-5, atol=2e-5,
            )


def test_paged_kernel_window_overhanging_capacity():
    """Regression: a padded chunk window whose lengths = pos + w overhangs
    the table capacity must NOT shift live rows' causal bands (a wholesale
    capacity clamp used to drop their most recent context — including
    their own token)."""
    from repro.kernels import ops
    from repro.core.flash_reference import reference_attention

    b, hkv, d, bs, mb, ql = 1, 2, 32, 8, 2, 4  # capacity 16
    k_pool, v_pool, bt, kq = _random_pool_case(
        jax.random.PRNGKey(5), b, hkv, d, bs, mb
    )
    q = jax.random.normal(kq, (b, 4, ql, d), jnp.float32)
    pos, live = 13, 2  # live rows at positions 13, 14; rows 2-3 padded
    lengths = jnp.asarray([pos + ql], jnp.int32)  # 17 > capacity
    out = ops.paged_decode_attention(
        q, k_pool, v_pool, block_tables=bt, lengths=lengths
    )
    k_c, v_c = _gather(k_pool, bt, hkv), _gather(v_pool, bt, hkv)
    for t in range(live):
        mask = jnp.arange(mb * bs)[None, :] < (pos + t + 1)  # own band
        want = reference_attention(
            q[:, :, t : t + 1], k_c, v_c, kv_mask=mask
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :, t : t + 1]), np.asarray(want),
            rtol=2e-5, atol=2e-5,
        )


def test_paged_kernel_fused_variant():
    """Fused-K̂ pool (d/G* score width) through the block table equals the
    reference dispatch on the gathered fused cache."""
    b, hkv, q_per_kv, d, g, bs, mb = 2, 2, 2, 32, 2, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    p = 1 + b * mb
    k_pool = jax.random.normal(ks[0], (p, hkv, bs, d), jnp.float32)
    v_pool = jax.random.normal(ks[1], (p, hkv, bs, d), jnp.float32)
    perm = jnp.stack([
        jax.random.permutation(jax.random.fold_in(ks[2], h), d)
        for h in range(hkv)
    ]).astype(jnp.int32)
    kf_pool = token_major(grouping.fuse_columns(k_pool, perm[None], g))
    v_pool = token_major(v_pool)
    ids = np.arange(1, p, dtype=np.int32)
    np.random.RandomState(1).shuffle(ids)
    bt = jnp.asarray(ids.reshape(b, mb))
    q = jax.random.normal(ks[3], (b, hkv * q_per_kv, 1, d), jnp.float32)
    lengths = jnp.asarray([11, 24], jnp.int32)
    scale = 1.0 / (d**0.5)

    from repro.core.api import AttentionConfig

    out = attend_decode(
        q, None, v_pool, AttentionConfig(impl="pallas_flash"),
        lengths=lengths, k_fused=kf_pool, perm=perm, group_size=g,
        scale=scale, block_tables=bt,
    )
    want = attend_decode(
        q, None, v_pool, AttentionConfig(impl="reference"),
        lengths=lengths, k_fused=kf_pool, perm=perm, group_size=g,
        scale=scale, block_tables=bt,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# Block pool + cache invariants
# ---------------------------------------------------------------------------


def test_block_pool_invariants():
    pool = paged.BlockPool(5, 8)  # 4 allocatable (block 0 reserved)
    assert pool.num_free == 4
    got = pool.alloc(4)
    assert 0 not in got and len(set(got)) == 4
    with pytest.raises(paged.PoolExhausted):
        pool.alloc(1)
    pool.free(got[0])
    assert pool.num_free == 1
    with pytest.raises(ValueError):
        pool.free(got[0])  # double free
    # refcounting: a shared block survives its first free
    pool.incref(got[1])
    pool.free(got[1])
    assert pool.refcount(got[1]) == 1 and pool.num_free == 1
    pool.free(got[1])
    assert pool.num_free == 2
    # the garbage block is never handed out and never freed
    pool.free(0)
    assert pool.refcount(0) == 1


def test_shared_prefix_blocks_are_reused_and_refcounted():
    cfg = get_config("minicpm-2b", reduced=True)
    cache = paged.PagedKVCache(cfg, 8, 8, dtype=jnp.float32)
    cache.allocate_to(0, 20)  # 3 blocks
    covered = cache.share_prefix(0, 1, 20)
    assert covered == 16  # whole blocks only (2×8), partial third not shared
    assert cache.tables[1] == cache.tables[0][:2]
    free_before = cache.pool.num_free
    cache.free(0)  # shared blocks stay alive through uid 1
    assert cache.pool.num_free == free_before + 1  # only the partial block
    cache.free(1)
    assert cache.pool.num_free == cache.pool.num_blocks - 1


def test_evict_restore_roundtrip_preserves_kv():
    cfg = get_config("minicpm-2b", reduced=True)
    cache = paged.PagedKVCache(cfg, 8, 8, dtype=jnp.float32)
    cache.allocate_to(7, 20)
    table = list(cache.tables[7])
    marker = jnp.arange(
        np.prod(cache.pools["k"].shape), dtype=jnp.float32
    ).reshape(cache.pools["k"].shape)
    cache.pools["k"] = marker
    want = np.asarray(jnp.take(marker, jnp.asarray(table), axis=1))
    cache.evict_to_host(7, 20)
    assert 7 not in cache.tables
    assert cache.pool.num_free == cache.pool.num_blocks - 1
    cache.restore(7)
    got = np.asarray(
        jnp.take(cache.pools["k"], jnp.asarray(cache.tables[7]), axis=1)
    )
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Scheduler invariants (fake engine: policy only, no model)
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, uid, n_prompt, max_new):
        self.uid = uid
        self.prompt = list(range(1, n_prompt + 1))
        self.max_new_tokens = max_new
        self.eos_id = None
        self.generated = []
        self.done = False


class _FakeEngine:
    """Implements the scheduler's primitive surface over a bare BlockPool —
    exercises admission/preemption/restore policy without touching a
    model (no jit, milliseconds per test)."""

    def __init__(self, num_blocks, block_size, max_batch, capacity_tokens):
        self.pool = paged.BlockPool(num_blocks, block_size)
        self.bs = block_size
        self.max_batch = max_batch
        self.capacity_tokens = capacity_tokens
        self.ids: dict[int, list[int]] = {}  # uid → held block ids
        self.evicted_uids: set[int] = set()
        self.scheduler = None
        self.first_token_order: list[int] = []

    def free_lane(self):
        return next(
            l for l in range(self.max_batch)
            if l not in self.scheduler.running
        )

    def alloc(self, entry, n_tokens):
        need = -(-n_tokens // self.bs) - len(self.ids.get(entry.uid, []))
        if need <= 0:
            return True
        try:
            got = self.pool.alloc(need)
        except paged.PoolExhausted:
            return False
        self.ids.setdefault(entry.uid, []).extend(got)
        return True

    def can_admit(self, entry):
        need = -(-min(len(entry.req.prompt) + 1, self.capacity_tokens)
                 // self.bs)
        return self.pool.num_free >= need

    def holds_blocks(self, entry):
        return bool(self.ids.get(entry.uid))

    def evict(self, entry):
        for b in self.ids.pop(entry.uid):
            self.pool.free(b)
        self.evicted_uids.add(entry.uid)

    def restore(self, entry):
        blocks = -(-max(entry.length, 1) // self.bs)
        try:
            self.ids[entry.uid] = self.pool.alloc(blocks)
        except paged.PoolExhausted:
            return False
        return True

    def release(self, entry):
        for b in self.ids.pop(entry.uid, []):
            self.pool.free(b)

    def sample_one(self, logits):
        self.first_token_order.append(int(logits))
        return 1

    def prefill_chunk_run(self, entry, chunk):
        return entry.uid  # "logits" = uid, recorded at first-token sampling

    def decode_tick(self, running):
        return np.full((self.max_batch,), 1, np.int64)


def _fake_engine(num_blocks, block_size, max_batch, capacity):
    return _FakeEngine(num_blocks, block_size, max_batch, capacity)


def test_scheduler_no_starvation_and_fcfs_first_tokens():
    """Many requests through a tight pool: everyone finishes, first tokens
    are produced in arrival order (FCFS), and no block is leaked."""
    eng = _fake_engine(num_blocks=7, block_size=8, max_batch=3, capacity=32)
    sched = Scheduler(
        SchedulerConfig(max_batch=3, prefill_chunk=8), clock=lambda: 0.0
    )
    eng.scheduler = sched
    for uid in range(8):
        sched.submit(_FakeReq(uid, n_prompt=10, max_new=5))
    for _ in range(400):
        sched.tick(eng)
        if not sched.has_work():
            break
    assert not sched.has_work(), "a request starved"
    assert len(sched.done) == 8
    assert all(len(e.req.generated) == 5 for e in sched.done)
    assert eng.first_token_order == sorted(eng.first_token_order)
    assert eng.pool.num_free == eng.pool.num_blocks - 1  # nothing leaked


def test_scheduler_lifo_self_preempts_newest_grower():
    """When the GROWING request is itself the newest block holder, LIFO
    preemption must evict it — never an older request's memory (the
    documented head-of-line guarantee)."""
    eng = _fake_engine(num_blocks=6, block_size=8, max_batch=2, capacity=40)
    sched = Scheduler(
        SchedulerConfig(max_batch=2, prefill_chunk=32), clock=lambda: 0.0
    )
    eng.scheduler = sched
    # old: 3 blocks, first growth (→ 4 blocks) only at its 8th decode tick
    sched.submit(_FakeReq(0, n_prompt=17, max_new=12))
    # new: 2 blocks, grows past 16 at its 7th tick — one tick EARLIER, with
    # zero free blocks and itself the newest holder
    sched.submit(_FakeReq(1, n_prompt=10, max_new=10))
    for _ in range(100):
        sched.tick(eng)
        if not sched.has_work():
            break
    assert len(sched.done) == 2
    assert all(len(e.req.generated) == e.req.max_new_tokens
               for e in sched.done)
    assert 0 not in eng.evicted_uids, "LIFO evicted the FCFS-oldest request"
    assert 1 in eng.evicted_uids, "the newest grower should self-preempt"
    assert eng.pool.num_free == eng.pool.num_blocks - 1


def test_scheduler_requeue_preserves_arrival_order():
    """A just-preempted runner must re-enter the queue at its uid (arrival)
    position — behind an older evicted request already waiting — so
    restores happen FCFS."""
    from repro.serve.scheduler import Entry

    sched = Scheduler(SchedulerConfig(), clock=lambda: 0.0)
    e0 = Entry(req=_FakeReq(0, 4, 4), evicted=True)
    e5 = Entry(req=_FakeReq(5, 4, 4))
    sched.waiting.extend([e0, e5])
    e2 = Entry(req=_FakeReq(2, 4, 4))
    sched._requeue(e2)
    assert [e.uid for e in sched.waiting] == [0, 2, 5]


def test_scheduler_preempts_and_resumes_under_pressure():
    """Pool holds ~2 live requests; 4 submitted: preemption must trigger,
    and preempted requests must finish with their full token count."""
    eng = _fake_engine(num_blocks=9, block_size=8, max_batch=4, capacity=32)
    sched = Scheduler(
        SchedulerConfig(max_batch=4, prefill_chunk=8), clock=lambda: 0.0
    )
    eng.scheduler = sched
    for uid in range(4):
        sched.submit(_FakeReq(uid, n_prompt=10, max_new=16))
    for _ in range(400):
        sched.tick(eng)
        if not sched.has_work():
            break
    assert len(sched.done) == 4
    assert all(len(e.req.generated) == 16 for e in sched.done)
    assert eng.evicted_uids, "pressure run never preempted"
    assert eng.pool.num_free == eng.pool.num_blocks - 1


def _span_parents(rec):
    """{(name, t): parent name} of a recorder's sync spans: the innermost
    span that encloses each one in time (None for a root)."""
    spans = [(e["t"], e["t"] + e["dur"], e["name"]) for e in rec.events
             if e["ph"] == "X"]
    out = {}
    for t0, t1, name in spans:
        outer = [s for s in spans if s[0] < t0 and t1 < s[1]]
        out[(name, t0)] = max(outer)[2] if outer else None
    return out


def test_scheduler_tick_spans_nest_by_phase():
    """One tick that runs a chunk, a final chunk and a decode records
    ``serve.tick`` around every phase, the chunks and the first token
    under ``serve.admit``, the decode step with its lane count; a tick that
    preempts records ``serve.kv.evict`` under ``serve.grow``."""
    import itertools

    from repro.obs import TraceRecorder

    rec = TraceRecorder(clock=itertools.count().__next__)
    eng = _fake_engine(num_blocks=5, block_size=8, max_batch=2, capacity=32)
    sched = Scheduler(SchedulerConfig(max_batch=2, prefill_chunk=8),
                      clock=lambda: 0.0, trace=rec)
    eng.scheduler = sched
    sched.submit(_FakeReq(0, n_prompt=12, max_new=6))
    sched.tick(eng)
    parents = _span_parents(rec)
    got = sorted((name, parent) for (name, _), parent in parents.items())
    assert got == sorted([
        ("serve.tick", None), ("serve.expire", "serve.tick"),
        ("serve.admit", "serve.tick"),
        ("serve.prefill_chunk", "serve.admit"),
        ("serve.prefill_chunk", "serve.admit"),
        ("serve.first_token", "serve.admit"),
        ("serve.grow", "serve.tick"), ("serve.decode_step", "serve.tick"),
        ("serve.emit", "serve.tick"),
    ])
    spans = [e for e in rec.events if e["ph"] == "X"]
    chunks = [e["args"] for e in spans if e["name"] == "serve.prefill_chunk"]
    assert chunks == [{"uid": 0, "start": 0, "n": 8},
                      {"uid": 0, "start": 8, "n": 4}]
    (step,) = [e for e in spans if e["name"] == "serve.decode_step"]
    assert step["args"] == {"n_lanes": 1}
    order = [e["name"] for e in sorted(spans, key=lambda e: e["t"])
             if parents[(e["name"], e["t"])] == "serve.tick"]
    assert order == ["serve.expire", "serve.admit", "serve.grow",
                     "serve.decode_step", "serve.emit"]

    # A second request fills the pool; growing the first past its third
    # block evicts the newest holder.
    sched.submit(_FakeReq(1, n_prompt=12, max_new=20))
    for _ in range(40):
        rec.events.clear()
        sched.tick(eng)
        if eng.evicted_uids:
            break
    assert eng.evicted_uids
    parents = _span_parents(rec)
    evicts = [p for (name, _), p in parents.items() if name == "serve.kv.evict"]
    assert evicts == ["serve.grow"]


# ---------------------------------------------------------------------------
# Engine-path parity + end-to-end (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------


def test_paged_decode_matches_contiguous_ring_path():
    """Acceptance: f32 logits allclose across ≥ 8 generated tokens vs the
    contiguous ring-cache decode, on a GQA config, with the request's KV
    spanning ≥ 3 pool blocks; plus a second, shorter (ragged) lane decoded
    in the same paged batch."""
    cfg = get_config("qwen2.5-32b", reduced=True)  # GQA: Hq > Hkv
    cfg = cfg.replace(attention=cfg.attention.with_impl("pallas_flash"))
    assert cfg.n_heads > cfg.n_kv_heads
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, cfg.vocab)
    toks_b = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, cfg.vocab)
    n_a, n_b = 12, 5  # ragged pair
    bs, mb = 8, 4  # request A spans 3 blocks by the end

    # contiguous ring path, one request at a time
    def contiguous_logits(tok_stream, n):
        _, cache = make_prefill(cfg, mb * bs)(params, tok_stream[:, :n])
        cache["length"] = jnp.asarray([n], jnp.int32)
        dec = make_decode_step(cfg)
        outs = []
        for i in range(n, n + 8):
            lg, cache = dec(params, tok_stream[:, i : i + 1], cache,
                            jnp.asarray([i], jnp.int32))
            outs.append(np.asarray(lg[:, 0], np.float32))
        return outs

    want_a = contiguous_logits(toks, n_a)
    want_b = contiguous_logits(toks_b, n_b)

    # paged path: chunked prefill then a 2-lane batched decode
    cache = paged.PagedKVCache(cfg, 1 + 2 * mb, bs, dtype=jnp.float32)
    chunk = make_paged_step(cfg, 8)
    dec = make_paged_step(cfg, 1)
    for uid, (stream, n) in enumerate(((toks, n_a), (toks_b, n_b))):
        done = 0
        while done < n:
            c = min(8, n - done)
            cache.allocate_to(uid, done + c)
            bt = cache.table_array([uid], mb)
            tk = np.zeros((1, 8), np.int32)
            tk[0, :c] = np.asarray(stream[0, done : done + c])
            _, cache.pools = chunk(
                params, jnp.asarray(tk), cache.pools, bt,
                jnp.asarray([done], jnp.int32), jnp.asarray([c], jnp.int32),
            )
            done += c
    lengths = [n_a, n_b]
    streams = [toks, toks_b]
    for step in range(8):
        pos = jnp.asarray([lengths[0] + step, lengths[1] + step], jnp.int32)
        cache.allocate_to(0, int(pos[0]) + 1)
        cache.allocate_to(1, int(pos[1]) + 1)
        bt = cache.table_array([0, 1], mb)
        tk = jnp.stack([
            streams[0][0, int(pos[0])], streams[1][0, int(pos[1])]
        ])[:, None]
        lg, cache.pools = dec(
            params, tk, cache.pools, bt, pos, jnp.asarray([1, 1], jnp.int32)
        )
        np.testing.assert_allclose(
            np.asarray(lg[0:1, 0], np.float32), want_a[step],
            rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(
            np.asarray(lg[1:2, 0], np.float32), want_b[step],
            rtol=1e-4, atol=1e-4,
        )
    assert len(cache.tables[0]) >= 3  # spanned ≥ 3 pool blocks


def test_paged_engine_continuous_batching_end_to_end():
    """More requests than lanes; mixed lengths; every request completes
    with full token counts and TTFT metrics recorded."""
    cfg = get_config("minicpm-2b", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedServeEngine(cfg, params, max_batch=3, max_len=64,
                           block_size=8, prefill_chunk=8)
    for i in range(5):
        eng.add_request(list(range(1 + i, 4 + 2 * i)), max_new_tokens=4)
    # max_new_tokens=1 finishes on the prefill-sampled token — exactly one
    # generated token, no decode tick (slot-engine contract).
    eng.add_request([9, 9, 9], max_new_tokens=1)
    done = eng.run_to_completion(max_steps=200)
    assert len(done) == 6
    by_new = sorted(len(r.generated) for r in done)
    assert by_new == [1, 4, 4, 4, 4, 4]
    m = eng.metrics()
    assert len(m) == 6 and all(x["ttft_s"] is not None for x in m)
    assert eng.cache.pool.num_free == eng.cache.pool.num_blocks - 1


@pytest.mark.slow
def test_paged_engine_preemption_identical_continuations():
    """A pool sized for ~2 live requests forces preemption; generations
    must equal the unpressured run token-for-token (whole-request host
    eviction + restore) and the pool must be fully reclaimed."""
    cfg = get_config("minicpm-2b", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)

    def run(num_blocks):
        eng = PagedServeEngine(
            cfg, params, max_batch=4, max_len=32, block_size=8,
            num_blocks=num_blocks, prefill_chunk=8,
        )
        for i in range(4):
            eng.add_request([2 + i] * 10, max_new_tokens=12)
        done = eng.run_to_completion(max_steps=300)
        return eng, {r.uid: r.generated for r in done}

    eng_tight, gen_tight = run(num_blocks=1 + 8)
    eng_roomy, gen_roomy = run(num_blocks=1 + 4 * 4)
    assert len(gen_tight) == 4
    assert gen_tight == gen_roomy
    assert sum(x["n_preemptions"] for x in eng_tight.metrics()) > 0
    assert eng_tight.cache.pool.num_free == eng_tight.cache.pool.num_blocks - 1


def test_paged_engine_rejects_overlong_prompt_and_bad_pool():
    cfg = get_config("minicpm-2b", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedServeEngine(cfg, params, max_batch=2, max_len=32,
                           block_size=8, prefill_chunk=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(list(range(40)))
    with pytest.raises(ValueError, match="full request"):
        PagedServeEngine(cfg, params, max_batch=2, max_len=32, block_size=8,
                         num_blocks=3, prefill_chunk=8)


# ---------------------------------------------------------------------------
# Windowed decode past capacity (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


def test_paged_windowed_decode_matches_slot_sliding_window():
    """Decode past the table capacity recycles the request's HEAD blocks in
    place (write at ``pos mod capacity``, attend the last ``capacity``
    tokens) — logits equal the slot engine's sliding-window decode
    (make_decode_step) with ``max_len == capacity``, step for step."""
    cfg = get_config("qwen2.5-32b", reduced=True)  # exact impl for parity
    cfg = cfg.replace(attention=cfg.attention.with_impl("pallas_flash"))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    bs, mb = 8, 2  # capacity 16
    n, steps = 10, 12  # decode positions 10..21 — wraps at 16
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (1, n + steps), 0, cfg.vocab
    )

    # slot path: contiguous ring cache of exactly `capacity` slots
    _, cache = make_prefill(cfg, mb * bs)(params, toks[:, :n])
    cache["length"] = jnp.asarray([n], jnp.int32)
    dec_slot = make_decode_step(cfg)
    want = []
    for i in range(n, n + steps):
        lg, cache = dec_slot(
            params, toks[:, i : i + 1], cache, jnp.asarray([i], jnp.int32)
        )
        want.append(np.asarray(lg[:, 0], np.float32))

    # paged path: same capacity through the block table, decoded past it
    pcache = paged.PagedKVCache(cfg, 1 + mb, bs, dtype=jnp.float32)
    chunk = make_paged_step(cfg, 8)
    done = 0
    while done < n:
        c = min(8, n - done)
        pcache.allocate_to(0, done + c)
        bt = pcache.table_array([0], mb)
        tk = np.zeros((1, 8), np.int32)
        tk[0, :c] = np.asarray(toks[0, done : done + c])
        _, pcache.pools = chunk(
            params, jnp.asarray(tk), pcache.pools, bt,
            jnp.asarray([done], jnp.int32), jnp.asarray([c], jnp.int32),
        )
        done += c
    pcache.allocate_to(0, mb * bs)  # full table; further growth is a no-op
    bt = pcache.table_array([0], mb)
    dec = make_paged_step(cfg, 1)
    for step in range(steps):
        lg, pcache.pools = dec(
            params, toks[:, n + step : n + step + 1], pcache.pools, bt,
            jnp.asarray([n + step], jnp.int32), jnp.asarray([1], jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32), want[step],
            rtol=1e-4, atol=1e-4,
        )


def test_paged_engine_decode_crosses_capacity():
    """``max_new_tokens`` may cross the table capacity: the request is
    accepted (only PROMPTS are capacity-bound) and decodes its full budget
    by recycling head blocks instead of being force-finished at the
    capacity bound."""
    cfg = get_config("minicpm-2b", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedServeEngine(cfg, params, max_batch=2, max_len=16,
                           block_size=8, prefill_chunk=8)
    assert eng.capacity_tokens == 16
    uid = eng.add_request([3, 1, 4, 1, 5, 9], max_new_tokens=20)  # 6+20 > 16
    done = eng.run_to_completion(max_steps=200)
    (req,) = done
    assert req.uid == uid
    assert len(req.generated) == 20
    assert eng.cache.pool.num_free == eng.cache.pool.num_blocks - 1


def test_paged_chunk_overhanging_capacity_matches_small_chunks():
    """Regression: the last chunked-prefill window of a prompt may overhang
    the table capacity with padded rows (here a 44-token tail in a 256-wide
    window at position 256, capacity 256 + 128).  Its live rows keep their
    full causal band, so greedy tokens equal those of small chunks that
    never overhang."""
    cfg = get_config("minicpm-2b", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.random.RandomState(0).randint(1, cfg.vocab, size=300).tolist()
    out = []
    for chunk in (256, 32):
        eng = PagedServeEngine(cfg, params, max_batch=1, max_len=364,
                               block_size=128, prefill_chunk=chunk,
                               cache_dtype=jnp.float32)
        assert eng.capacity_tokens == 384
        eng.add_request(prompt, max_new_tokens=3)
        out.append(eng.run_to_completion()[0].generated)
    assert out[0] == out[1], out
