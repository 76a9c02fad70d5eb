"""Chaos suite: overload-aware serving under injected faults.

Every test asserts the robustness contract (DESIGN.md §Robustness): each
request reaches exactly one terminal lifecycle status
(done | rejected | expired | cancelled | failed), pool blocks leak nothing
(free count returns to initial), and faults quarantine only the offending
request — concurrent unaffected requests produce bit-identical outputs
(greedy sampling + per-row decode independence make this deterministic).

Fast tests drive the scheduler through a fake engine (policy only, no
model); slow tests drive the real engines and the 8-device ring.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.serve import lifecycle, paged
from repro.serve.degrade import DegradationController, DegradeConfig
from repro.serve.faults import (
    NULL_INJECTOR, FaultInjector, FaultSpec, InjectedFault,
)
from repro.serve.lifecycle import IncompleteRun
from repro.serve.scheduler import Scheduler, SchedulerConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# Fault-injection plumbing (serve.faults)
# ---------------------------------------------------------------------------


def test_fault_spec_counted_window():
    """A spec fires exactly on hits [after, after + times); times=-1 fires
    forever — deterministic across runs by construction."""
    inj = FaultInjector([FaultSpec("stuck_step", after=2, times=3)])
    fired = [inj.fires("stuck_step") is not None for _ in range(8)]
    assert fired == [False, False, True, True, True, False, False, False]
    persistent = FaultInjector([FaultSpec("nan_logits", times=-1)])
    assert all(persistent.fires("nan_logits") is not None for _ in range(20))


def test_fault_spec_rejects_unknown_point():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultSpec("disk_on_fire")


def test_injector_uid_filter_and_dead_shards():
    inj = FaultInjector([
        FaultSpec("nan_logits", uid=7, times=-1),
        FaultSpec("dead_ring_shard", shards=(1, 3)),
        FaultSpec("dead_ring_shard", shards=(3, 5)),
    ])
    assert inj.fires("nan_logits", uid=3) is None
    assert inj.fires("nan_logits", uid=7) is not None
    assert inj.dead_shards() == frozenset({1, 3, 5})
    assert inj.raise_if("pool_exhausted", uid=7) is None  # no spec → no-op
    with pytest.raises(InjectedFault) as ei:
        FaultInjector([FaultSpec("stuck_step")]).raise_if("stuck_step", 4)
    assert ei.value.point == "stuck_step" and ei.value.uid == 4


def test_fault_spec_counts_hits_per_matching_uid():
    """A uid-filtered spec counts hits only on consultations that match:
    interleaved other-uid traffic must not advance its window."""
    inj = FaultInjector([FaultSpec("nan_logits", uid=1, after=2, times=1)])
    fired = []
    for _ in range(4):  # interleave uid 0 and uid 1 consultations
        inj.fires("nan_logits", uid=0)  # never matches, never counts
        fired.append(inj.fires("nan_logits", uid=1) is not None)
    # uid 1's own hits are 0,1,2,3 → fires exactly on its third hit.
    assert fired == [False, False, True, False]
    # An unrestricted spec, by contrast, counts every consultation.
    inj = FaultInjector([FaultSpec("nan_logits", after=2, times=1)])
    seen = [inj.fires("nan_logits", uid=u) is not None
            for u in (0, 1, 0, 1)]
    assert seen == [False, False, True, False]


def test_multiple_specs_on_one_point():
    """Several specs may watch one point: every matching spec counts the
    hit, the FIRST whose window covers it is returned — so staggered
    windows hand off deterministically and overlaps don't double-fire."""
    a = FaultSpec("stuck_step", after=0, times=2)
    b = FaultSpec("stuck_step", after=1, times=3)
    inj = FaultInjector([a, b])
    winners = []
    for _ in range(5):
        s = inj.fires("stuck_step")
        winners.append(None if s is None else ("a" if s is a else "b"))
    # hit 0: only a's window; hit 1: both → a (listed first); hits 2-3:
    # a exhausted → b; hit 4: both exhausted.
    assert winners == ["a", "a", "b", "b", None]
    # Exhaustion is permanent: further consultations stay quiet.
    assert inj.fires("stuck_step") is None
    # uid-filtered + unfiltered specs on one point: the filtered spec
    # only wins consultations it matches.
    u = FaultSpec("pool_exhausted", uid=5, times=-1)
    g = FaultSpec("pool_exhausted", after=1, times=-1)
    inj = FaultInjector([u, g])
    assert inj.fires("pool_exhausted", uid=3) is None  # g's hit 0 (after=1)
    assert inj.fires("pool_exhausted", uid=5) is u
    assert inj.fires("pool_exhausted", uid=3) is g


def test_replica_crash_point_in_catalog():
    """The cluster tier's fault point rides the same counted-trigger
    plumbing: uid carries the REPLICA id (serve.cluster consults it once
    per tick per replica)."""
    inj = FaultInjector([FaultSpec("replica_crash", uid=1, after=2)])
    assert inj.fires("replica_crash", uid=0) is None
    fired = [inj.fires("replica_crash", uid=1) is not None
             for _ in range(4)]
    assert fired == [False, False, True, False]


# ---------------------------------------------------------------------------
# Degradation controller (serve.degrade)
# ---------------------------------------------------------------------------


def test_degrade_config_validation():
    with pytest.raises(ValueError):
        DegradeConfig(group_sizes=())
    with pytest.raises(ValueError):
        DegradeConfig(group_sizes=(1, 4))
    with pytest.raises(ValueError):
        DegradeConfig(high_watermark=1, low_watermark=2)
    assert DegradeConfig(group_sizes=(2, 4, 8)).group_for(0) == 1
    assert DegradeConfig(group_sizes=(2, 4, 8)).group_for(3) == 8


def test_degrade_controller_hysteresis():
    """One level step per up_after (resp. down_after) CONSECUTIVE pressure
    (drain) ticks; a single calm tick resets the streak — no flapping on a
    bursty queue."""
    c = DegradationController(DegradeConfig(
        group_sizes=(2, 4), high_watermark=4, low_watermark=1,
        up_after=2, down_after=3,
    ))
    assert c.observe(10) == 0  # 1 hot tick — not yet
    assert c.observe(10) == 1  # 2 consecutive → up
    assert c.observe(10) == 1
    assert c.observe(2) == 1  # mid-band: neither hot nor cool
    assert c.observe(10) == 1  # streak was reset by the calm tick
    assert c.observe(10) == 2  # up again (max level)
    assert c.group_size == 4
    for _ in range(2):
        assert c.observe(0) == 2
    assert c.observe(0) == 1  # 3 consecutive cool → down
    assert c.observe(10) == 1  # pressure returns: drain streak resets


def test_degrade_return_bound_ticks():
    """Reversibility guarantee: from the deepest level, sustained drain
    returns to exact within down_after × max_level ticks."""
    cfg = DegradeConfig(group_sizes=(2, 4, 8), up_after=1, down_after=2)
    c = DegradationController(cfg)
    for _ in range(10):
        c.observe(100)
    assert c.level == cfg.max_level
    for t in range(cfg.return_bound_ticks()):
        if c.observe(0) == 0:
            break
    assert c.level == 0, (
        f"controller stuck at level {c.level} after "
        f"{cfg.return_bound_ticks()} drain ticks"
    )


# ---------------------------------------------------------------------------
# Scheduler chaos through a fake engine (policy only, no model)
# ---------------------------------------------------------------------------


class FakeReq:
    def __init__(self, uid, n_prompt=8, max_new=4, deadline_ttft=None,
                 deadline_e2e=None):
        self.uid = uid
        self.prompt = list(range(1, n_prompt + 1))
        self.max_new_tokens = max_new
        self.eos_id = None
        self.generated = []
        self.done = False
        self.status = lifecycle.QUEUED
        self.deadline_ttft = deadline_ttft
        self.deadline_e2e = deadline_e2e
        self.degrade_group = 1


class FakeEngine:
    """The scheduler's primitive surface over a bare BlockPool, consulting
    a FaultInjector at the same points the real paged engine does."""

    def __init__(self, num_blocks=16, block_size=8, max_batch=4,
                 capacity=64, faults=NULL_INJECTOR):
        self.pool = paged.BlockPool(num_blocks, block_size)
        self.bs = block_size
        self.max_batch = max_batch
        self.capacity_tokens = capacity
        self.faults = faults
        self.ids: dict[int, list[int]] = {}
        self.evicted_uids: set[int] = set()
        self.scheduler = None

    def free_lane(self):
        return next(l for l in range(self.max_batch)
                    if l not in self.scheduler.running)

    def alloc(self, entry, n_tokens):
        if self.faults.fires("pool_exhausted", entry.uid) is not None:
            return False
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
        self.faults.raise_if("restore_failure", entry.uid)
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
        return 1

    def prefill_chunk_run(self, entry, chunk):
        self.faults.raise_if("stuck_step", entry.uid)
        if self.faults.fires("nan_logits", entry.uid) is not None:
            return np.nan
        return entry.uid  # "logits" scalar

    def decode_tick(self, running):
        for e in running.values():
            self.faults.raise_if("stuck_step", e.uid)
        ok = np.ones((self.max_batch,), bool)
        for lane, e in running.items():
            if self.faults.fires("nan_logits", e.uid) is not None:
                ok[lane] = False
        return np.full((self.max_batch,), 1, np.int64), ok


class DegradedFakeEngine(FakeEngine):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.degraded_prompts: list[tuple[int, int]] = []

    def prefill_full_run(self, entry, group):
        self.faults.raise_if("stuck_step", entry.uid)
        self.degraded_prompts.append((entry.uid, group))
        return entry.uid


class MeshFakeEngine(FakeEngine):
    """FakeEngine + the mesh-admission surface (ISSUE 9): whole-prompt
    one-tick prefill, consulting the mesh_prefill fault point BEFORE any
    pool mutation — exactly like PagedServeEngine.prefill_mesh_run."""

    def __init__(self, threshold=8, **kw):
        super().__init__(**kw)
        self.threshold = threshold
        self.mesh_prompts: list[int] = []

    def mesh_prefill_ready(self, n):
        return n > self.threshold

    def prefill_mesh_run(self, entry):
        self.faults.raise_if("stuck_step", entry.uid)
        self.faults.raise_if("mesh_prefill", entry.uid)
        self.mesh_prompts.append(entry.uid)
        if self.faults.fires("nan_logits", entry.uid) is not None:
            return np.nan
        return entry.uid


class TickClock:
    """Injectable tick-domain clock: deadlines and TTFT in ticks."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def drive(sched, eng, clock=None, max_ticks=500):
    for _ in range(max_ticks):
        sched.tick(eng)
        if clock is not None:
            clock.t += 1
        if not sched.has_work():
            return
    raise AssertionError("scheduler did not drain within max_ticks")


def assert_all_terminal_and_clean(sched, eng, reqs):
    assert not sched.has_work()
    for r in reqs:
        assert lifecycle.is_terminal(r.status), (r.uid, r.status)
    assert eng.pool.num_free == eng.pool.num_blocks - 1, "blocks leaked"
    assert not eng.ids, "fake engine still maps uid → blocks"


def _sched(eng, *, max_batch=4, chunk=8, clock=None, **cfg_kw):
    s = Scheduler(
        SchedulerConfig(max_batch=max_batch, prefill_chunk=chunk, **cfg_kw),
        clock=clock or (lambda: 0.0),
        faults=eng.faults,
    )
    eng.scheduler = s
    return s


def test_shed_rejects_newest_when_queue_full():
    """Bounded waiting queue: the newest submissions are rejected at the
    gate with an immediate terminal status; accepted ones complete."""
    eng = FakeEngine()
    sched = _sched(eng, max_waiting=2)
    reqs = [FakeReq(uid) for uid in range(5)]
    entries = [sched.submit(r) for r in reqs]
    assert entries[0] is not None and entries[1] is not None
    assert entries[2] is None and entries[3] is None and entries[4] is None
    for shed in reqs[2:]:
        assert shed.status == lifecycle.REJECTED
    assert sched.counters["shed"] == 3
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert [r.status for r in reqs[:2]] == [lifecycle.DONE] * 2
    rows = {m["uid"]: m for m in sched.metrics()}
    assert rows[4]["status"] == lifecycle.REJECTED
    assert rows[0]["status"] == lifecycle.DONE


def test_cancel_frees_blocks_immediately():
    """cancel(uid) terminates a request wherever it is — waiting,
    mid-prefill, or running — and its blocks free in the call itself."""
    eng = FakeEngine()
    sched = _sched(eng, chunk=4)
    reqs = [FakeReq(uid, n_prompt=12, max_new=8) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    assert not sched.cancel(99, eng)  # unknown uid
    assert sched.cancel(2, eng)  # still waiting
    assert reqs[2].status == lifecycle.CANCELLED
    sched.tick(eng)  # uid 0 mid-prefill (chunk 4 < prompt 12) or running
    held_before = len(eng.ids.get(0, []))
    assert held_before > 0
    assert sched.cancel(0, eng)
    assert reqs[0].status == lifecycle.CANCELLED
    assert 0 not in eng.ids, "cancel left blocks allocated"
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[1].status == lifecycle.DONE
    assert sched.counters["cancelled"] == 2


def test_cancel_running_entry_mid_decode():
    eng = FakeEngine()
    sched = _sched(eng)
    reqs = [FakeReq(uid, max_new=32) for uid in range(2)]
    for r in reqs:
        sched.submit(r)
    for _ in range(3):
        sched.tick(eng)
    assert any(e.uid == 1 for e in sched.running.values())
    assert sched.cancel(1, eng)
    assert reqs[1].status == lifecycle.CANCELLED
    assert all(e.uid != 1 for e in sched.running.values())
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)


def test_ttft_deadline_expires_waiting_requests():
    """Tick-domain deadlines: a request whose TTFT deadline lapses while
    queued is expired at the next tick — running requests are untouched."""
    eng = FakeEngine(max_batch=1)
    clock = TickClock()
    sched = _sched(eng, max_batch=1, clock=clock)
    fast = FakeReq(0, max_new=16)
    tight = FakeReq(1, deadline_ttft=2)  # behind fast on 1 lane: starves
    loose = FakeReq(2, deadline_ttft=1000)
    for r in (fast, tight, loose):
        sched.submit(r)
    drive(sched, eng, clock=clock)
    assert_all_terminal_and_clean(sched, eng, [fast, tight, loose])
    assert fast.status == lifecycle.DONE
    assert tight.status == lifecycle.EXPIRED
    assert loose.status == lifecycle.DONE
    assert sched.counters["expired"] == 1


def test_e2e_deadline_expires_running_request():
    eng = FakeEngine()
    clock = TickClock()
    sched = _sched(eng, clock=clock)
    marathon = FakeReq(0, max_new=100, deadline_e2e=5)
    sprint = FakeReq(1, max_new=2)
    for r in (marathon, sprint):
        sched.submit(r)
    drive(sched, eng, clock=clock)
    assert_all_terminal_and_clean(sched, eng, [marathon, sprint])
    assert marathon.status == lifecycle.EXPIRED
    assert 0 < len(marathon.generated) < 100, "expiry never interrupted it"
    assert sprint.status == lifecycle.DONE


def test_slow_step_fault_ages_deadlines_without_sleeping():
    """The slow_step fault advances the scheduler's clock offset: deadline
    expiry is exercised with zero wall-clock sleep."""
    eng = FakeEngine(faults=FaultInjector(
        [FaultSpec("slow_step", after=1, delay=50.0)]
    ))
    clock = TickClock()
    sched = _sched(eng, clock=clock)
    doomed = FakeReq(0, max_new=100, deadline_e2e=20)
    safe = FakeReq(1, max_new=3, deadline_e2e=10_000)
    for r in (doomed, safe):
        sched.submit(r)
    drive(sched, eng, clock=clock)
    assert_all_terminal_and_clean(sched, eng, [doomed, safe])
    assert doomed.status == lifecycle.EXPIRED  # 50 » 20, after one tick
    assert safe.status == lifecycle.DONE


def test_stuck_prefill_transient_fault_recovers():
    """A fault shorter than the retry budget costs ticks, not the request."""
    eng = FakeEngine(faults=FaultInjector(
        [FaultSpec("stuck_step", uid=1, times=2)]  # budget is 2 retries
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert all(r.status == lifecycle.DONE for r in reqs)
    assert sched.counters["step_retries"] == 2


def test_stuck_prefill_persistent_fault_fails_culprit_only():
    eng = FakeEngine(faults=FaultInjector(
        [FaultSpec("stuck_step", uid=1, times=-1)]
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[1].status == lifecycle.FAILED
    assert reqs[0].status == lifecycle.DONE
    assert reqs[2].status == lifecycle.DONE
    assert sched.counters["failed_fault"] == 1


def test_stuck_decode_fails_culprit_only():
    """A decode-tick fault surfaces after the culprit reaches a lane; the
    other lanes lose the faulted ticks but finish untouched."""
    eng = FakeEngine(faults=FaultInjector(
        # after=1: first decode for uid 1 succeeds, then 3 raises exhaust
        # the 2-retry budget.
        [FaultSpec("stuck_step", uid=1, after=2, times=-1)]
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid, max_new=6) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[1].status == lifecycle.FAILED
    assert reqs[0].status == lifecycle.DONE
    assert len(reqs[0].generated) == 6
    assert reqs[2].status == lifecycle.DONE


def test_nan_prefill_quarantined_before_lane():
    eng = FakeEngine(faults=FaultInjector(
        [FaultSpec("nan_logits", uid=0, times=-1)]
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid) for uid in range(2)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[0].status == lifecycle.FAILED
    assert reqs[0].generated == [], "a poisoned prompt must not sample"
    assert reqs[1].status == lifecycle.DONE
    assert sched.counters["failed_numeric"] == 1


def test_nan_decode_quarantines_lane_only():
    eng = FakeEngine(faults=FaultInjector(
        [FaultSpec("nan_logits", uid=1, after=2, times=1)]
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid, max_new=6) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[1].status == lifecycle.FAILED
    assert reqs[0].status == lifecycle.DONE
    assert reqs[2].status == lifecycle.DONE
    assert len(reqs[0].generated) == 6 and len(reqs[2].generated) == 6


def test_restore_fault_backoff_then_fail():
    """A faulting restore retries with exponential backoff and bounded
    budget; a pool-capacity wait (False return) costs no retries."""
    eng = FakeEngine(num_blocks=9, block_size=8, faults=FaultInjector(
        [FaultSpec("restore_failure", uid=3, times=-1)]
    ))
    sched = _sched(eng, max_batch=4, restore_max_retries=3,
                   restore_backoff_ticks=1)
    # Tight pool (as the preempt-resume test in test_paged.py): uid 3 (the
    # newest) is the LIFO victim; its restore then faults forever.
    reqs = [FakeReq(uid, n_prompt=10, max_new=16) for uid in range(4)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert 3 in eng.evicted_uids, "pressure never preempted uid 3"
    assert reqs[3].status == lifecycle.FAILED
    assert sched.counters["restore_retries"] == 4  # 3 retries + final
    for r in reqs[:3]:
        assert r.status == lifecycle.DONE
        assert len(r.generated) == 16


def test_restore_transient_fault_recovers():
    eng = FakeEngine(num_blocks=9, block_size=8, faults=FaultInjector(
        [FaultSpec("restore_failure", uid=3, times=2)]
    ))
    sched = _sched(eng, max_batch=4)
    reqs = [FakeReq(uid, n_prompt=10, max_new=16) for uid in range(4)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert all(r.status == lifecycle.DONE for r in reqs)
    assert all(len(r.generated) == 16 for r in reqs)
    assert sched.counters["restore_retries"] == 2


def test_watchdog_fails_head_on_global_stall():
    """A persistently failing allocator wedges the FCFS head; the global
    watchdog fails it after watchdog_ticks of zero progress, unwedging the
    queue.  Per-entry timers would have shot the healthy waiters too."""
    eng = FakeEngine(faults=FaultInjector(
        [FaultSpec("pool_exhausted", uid=0, times=-1)]
    ))
    sched = _sched(eng, watchdog_ticks=6)
    reqs = [FakeReq(uid) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[0].status == lifecycle.FAILED
    assert sched.counters["watchdog_fails"] == 1
    assert reqs[1].status == lifecycle.DONE
    assert reqs[2].status == lifecycle.DONE


@pytest.mark.parametrize("point,kw", [
    ("pool_exhausted", dict(uid=1, times=-1)),
    ("nan_logits", dict(uid=1, times=-1)),
    ("stuck_step", dict(uid=1, times=-1)),
    ("restore_failure", dict(uid=1, times=-1)),
    ("slow_step", dict(delay=1.0, times=3)),
])
def test_every_fault_reaches_terminal_status(point, kw):
    """The blanket contract: under each injectable fault point, every
    request reaches a terminal status and the pool drains clean."""
    eng = FakeEngine(faults=FaultInjector([FaultSpec(point, **kw)]))
    sched = _sched(eng, watchdog_ticks=6)
    reqs = [FakeReq(uid) for uid in range(4)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)


def test_mesh_prefill_one_tick_admission():
    """A prompt longer than the mesh threshold admits whole in one tick
    (counted as a mesh_prefill); shorter prompts still take the chunked
    path — and both drain clean."""
    eng = MeshFakeEngine(threshold=8)
    sched = _sched(eng, chunk=4)
    long_reqs = [FakeReq(uid, n_prompt=16, max_new=3) for uid in (0, 1)]
    short = FakeReq(2, n_prompt=6, max_new=3)
    for r in long_reqs + [short]:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, long_reqs + [short])
    assert all(r.status == lifecycle.DONE for r in long_reqs + [short])
    assert eng.mesh_prompts == [0, 1]
    assert 2 not in eng.mesh_prompts, "short prompt took the mesh path"
    assert sched.counters["mesh_prefills"] == 2


def test_mesh_prefill_transient_fault_recovers():
    """A mesh_prefill fault within the retry budget costs ticks, not the
    request: it raises BEFORE pool mutation, so the retry re-runs against
    clean blocks."""
    eng = MeshFakeEngine(threshold=8, faults=FaultInjector(
        [FaultSpec("mesh_prefill", uid=1, times=2)]
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid, n_prompt=16, max_new=3) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert all(r.status == lifecycle.DONE for r in reqs)
    assert sched.counters["step_retries"] == 2
    assert sched.counters["mesh_prefills"] == 3


def test_mesh_prefill_persistent_fault_fails_culprit_only():
    eng = MeshFakeEngine(threshold=8, faults=FaultInjector(
        [FaultSpec("mesh_prefill", uid=1, times=-1)]
    ))
    sched = _sched(eng)
    reqs = [FakeReq(uid, n_prompt=16, max_new=3) for uid in range(3)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)
    assert reqs[1].status == lifecycle.FAILED
    assert reqs[0].status == lifecycle.DONE
    assert reqs[2].status == lifecycle.DONE
    assert sched.counters["failed_fault"] == 1
    assert 1 not in eng.mesh_prompts, "faulted prefill mutated the pool"


@pytest.mark.parametrize("point,kw", [
    ("mesh_prefill", dict(uid=1, times=-1)),
    ("nan_logits", dict(uid=1, times=-1)),
    ("stuck_step", dict(uid=1, times=-1)),
    ("pool_exhausted", dict(uid=1, times=-1)),
])
def test_every_fault_reaches_terminal_under_mesh_admission(point, kw):
    """The blanket terminal-status contract holds when admission goes
    through the mesh path too."""
    eng = MeshFakeEngine(threshold=4, faults=FaultInjector(
        [FaultSpec(point, **kw)]
    ))
    sched = _sched(eng, watchdog_ticks=6)
    reqs = [FakeReq(uid) for uid in range(4)]  # 8 > 4: all mesh-admitted
    for r in reqs:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, reqs)


def test_scheduler_degrades_under_pressure_and_recovers():
    """Tentpole integration at the policy layer: a flooded queue pushes the
    controller up, new prompts prefill degraded (whole-prompt, recorded on
    the request), and once pressure drains the dial returns to exact within
    the documented bound."""
    eng = DegradedFakeEngine(num_blocks=64, block_size=8, max_batch=2,
                             capacity=64)
    dcfg = DegradeConfig(group_sizes=(2, 4), high_watermark=3,
                         low_watermark=1, up_after=2, down_after=2)
    sched = Scheduler(
        SchedulerConfig(max_batch=2, prefill_chunk=8),
        clock=lambda: 0.0, degrade=dcfg,
    )
    eng.scheduler = sched
    flood = [FakeReq(uid, n_prompt=16, max_new=2) for uid in range(12)]
    for r in flood:
        sched.submit(r)
    drive(sched, eng)
    assert_all_terminal_and_clean(sched, eng, flood)
    assert all(r.status == lifecycle.DONE for r in flood)
    assert eng.degraded_prompts, "overload never triggered degraded prefill"
    degraded_uids = {uid for uid, _ in eng.degraded_prompts}
    for r in flood:
        if r.uid in degraded_uids:
            assert r.degrade_group > 1
        else:
            assert r.degrade_group == 1
    assert sched.counters["degraded_prefills"] == len(eng.degraded_prompts)
    # Reversibility: drained queue → exact within the bound.
    assert sched.degrade.level > 0 or sched.degrade.transitions, \
        "controller never moved"
    for _ in range(dcfg.return_bound_ticks() + dcfg.down_after):
        sched.tick(eng)
    assert sched.degrade.level == 0, "dial did not return to exact"
    late = FakeReq(100, n_prompt=16, max_new=2)
    sched.submit(late)
    drive(sched, eng)
    assert late.status == lifecycle.DONE
    assert late.degrade_group == 1, "post-drain prompt should be exact"


def test_metrics_rows_carry_status_and_degrade_group():
    eng = FakeEngine()
    sched = _sched(eng)
    r = FakeReq(0)
    sched.submit(r)
    drive(sched, eng)
    (row,) = sched.metrics()
    assert row["status"] == lifecycle.DONE
    assert row["degrade_group"] == 1
    assert row["n_generated"] == len(r.generated)


# ---------------------------------------------------------------------------
# Dead ring shard (distributed.ring_attention fault hook)
# ---------------------------------------------------------------------------


def test_hop_schedule_skips_dead_shards_keeps_diagonal():
    """The dead-shard predicate drops every h>0 hop sourced from a dead
    shard but never hop 0 (own resident KV): no Q row loses its softmax
    diagonal, so outputs stay finite."""
    from repro.distributed.ring_attention import (
        _RingMeta, _hop_schedule, dead_shard_fault,
    )
    from repro.tune.block_sizes import BlockSizes

    meta = _RingMeta(axis="context", size=4, causal=False, scale=1.0,
                     interpret=True, n_live=512, shard=128,
                     blocks=BlockSizes())

    def runs(idx):
        out = []
        for h in range(meta.size):
            src, run, _ = _hop_schedule(meta, idx, h)
            out.append((int(src), bool(run)))
        return out

    baseline = runs(idx=1)
    assert all(r for _, r in baseline)  # non-causal, all live: all hops run
    with dead_shard_fault({3}):
        faulted = runs(idx=1)
    assert faulted[0] == (1, True), "hop 0 (own shard) must always run"
    for src, run in faulted[1:]:
        assert run == (src != 3), (src, run)
    # context manager restores the healthy schedule
    assert runs(idx=1) == baseline
    # a dead device's own hop-0 still runs (it is resident, not rotated)
    with dead_shard_fault({3}):
        assert runs(idx=3)[0] == (3, True)


@pytest.mark.slow
def test_dead_ring_shard_degraded_but_finite_8dev():
    """8-device ring with a dead KV shard: the sweep skips the dead hops
    (hop probe), output stays finite everywhere, and rows whose causal
    window excludes the dead shard are bit-identical to the healthy run."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.ring_attention import (
            dead_shard_fault, ring_flash_attention,
        )
        ring = make_mesh((8,), ("context",))
        B, Hq, Hkv, N, D = 1, 2, 1, 1024, 32  # 8 shards of 128, all live
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, N, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.float32)
        healthy, hops0 = jax.jit(lambda q, k, v: ring_flash_attention(
            q, k, v, ring, causal=True, return_hops=True))(q, k, v)
        with dead_shard_fault({2}):
            degraded, hops1 = jax.jit(lambda q, k, v: ring_flash_attention(
                q, k, v, ring, causal=True, return_hops=True))(q, k, v)
        assert int(hops1) < int(hops0), (int(hops1), int(hops0))
        d = np.asarray(degraded)
        assert np.isfinite(d).all(), "dead shard produced non-finite output"
        h = np.asarray(healthy)
        # Rows at positions < 256 never attend shard 2 (causal): identical.
        np.testing.assert_array_equal(d[:, :, :256], h[:, :, :256])
        # Rows past the dead shard lost real context: they must differ.
        assert np.abs(d[:, :, 384:] - h[:, :, 384:]).max() > 0
        print("DEAD SHARD OK")
        """
        % SRC
    )
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"


# ---------------------------------------------------------------------------
# Real engines: regression satellites + chaos integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_lm():
    import jax as _jax

    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("minicpm-2b", reduced=True)
    params = lm.init_params(_jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged_engine(small_lm, **kw):
    from repro.serve.engine import PagedServeEngine

    cfg, params = small_lm
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return PagedServeEngine(cfg, params, **kw)


def _slot_engine(small_lm, **kw):
    from repro.serve.engine import ServeEngine

    cfg, params = small_lm
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    return ServeEngine(cfg, params, **kw)


def test_run_to_completion_raises_incomplete_run(small_lm):
    """Regression (satellite 1): max_steps exhaustion with requests still
    in flight must raise, not silently return partial results."""
    eng = _paged_engine(small_lm)
    uid = eng.add_request([1, 2, 3], max_new_tokens=30)
    with pytest.raises(IncompleteRun) as ei:
        eng.run_to_completion(max_steps=2)
    assert uid in ei.value.uids
    eng.run_to_completion()  # plenty of steps: drains fine now

    slot = _slot_engine(small_lm)
    uid2 = slot.add_request([1, 2, 3], max_new_tokens=30)
    with pytest.raises(IncompleteRun) as ei:
        slot.run_to_completion(max_steps=2)
    assert uid2 in ei.value.uids
    slot.run_to_completion()


def test_add_request_validation_parity(small_lm):
    """Satellite 2: both engines reject bad input identically through the
    shared helper — empty prompt, non-positive max_new_tokens, overlong
    prompt."""
    engines = [_paged_engine(small_lm), _slot_engine(small_lm)]
    for eng in engines:
        with pytest.raises(ValueError, match="at least one token"):
            eng.add_request([])
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request([1, 2], max_new_tokens=0)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request([1, 2], max_new_tokens=-3)
        with pytest.raises(ValueError, match="exceeds the engine"):
            eng.add_request(list(range(1, 200)))
    # paged engine additionally reserves one slot for the first decode
    # token: a prompt that fills capacity exactly must be rejected too.
    with pytest.raises(ValueError, match="capacity"):
        engines[0].add_request(list(range(1, 65)))


PROMPTS = [list(range(3, 11)), list(range(5, 17)), list(range(2, 8))]


def _run_paged(small_lm, faults=None, **kw):
    eng = _paged_engine(small_lm, faults=faults, **kw)
    free0 = eng.cache.pool.num_free
    uids = [eng.add_request(p, max_new_tokens=6) for p in PROMPTS]
    eng.run_to_completion(max_steps=300)
    by_uid = {r.uid: r for r in eng.finished}
    assert set(by_uid) == set(uids)
    for r in eng.finished:
        assert lifecycle.is_terminal(r.status), (r.uid, r.status)
    assert eng.cache.pool.num_free == free0, "pool blocks leaked"
    return eng, by_uid


@pytest.mark.slow
@pytest.mark.parametrize("point", ["nan_logits", "stuck_step"])
def test_real_paged_engine_fault_quarantine_bit_identical(small_lm, point):
    """Chaos acceptance on the real paged engine: fault uid 1, every
    request terminal, pool clean, and the unaffected requests' tokens are
    BIT-IDENTICAL to the fault-free run (greedy sampling + per-row decode
    independence)."""
    _, baseline = _run_paged(small_lm)
    assert all(r.status == lifecycle.DONE for r in baseline.values())
    faults = FaultInjector([FaultSpec(point, uid=1, after=1, times=-1)])
    eng, by_uid = _run_paged(small_lm, faults=faults)
    assert by_uid[1].status == lifecycle.FAILED
    for uid in (0, 2):
        assert by_uid[uid].status == lifecycle.DONE
        assert by_uid[uid].generated == baseline[uid].generated, (
            f"uid {uid} diverged under {point} fault on uid 1"
        )
    counters = eng.counters_snapshot()
    assert counters.get("failed_numeric", 0) + counters.get(
        "failed_fault", 0) == 1


@pytest.mark.slow
def test_real_paged_engine_watchdog_on_wedged_alloc(small_lm):
    """Persistent allocator failure for one uid: the watchdog fails it and
    the queue unwedges; everything terminal, pool clean."""
    faults = FaultInjector([FaultSpec("pool_exhausted", uid=1, times=-1)])
    eng, by_uid = _run_paged(small_lm, faults=faults)
    assert by_uid[1].status == lifecycle.FAILED
    assert eng.counters_snapshot()["watchdog_fails"] == 1
    assert by_uid[0].status == lifecycle.DONE
    assert by_uid[2].status == lifecycle.DONE


@pytest.mark.slow
def test_real_paged_engine_cancel_and_deadline(small_lm):
    eng = _paged_engine(small_lm, clock=TickClock())
    free0 = eng.cache.pool.num_free
    u0 = eng.add_request(PROMPTS[0], max_new_tokens=40)
    u1 = eng.add_request(PROMPTS[1], max_new_tokens=4, deadline_ttft=1000)
    eng.step()
    assert eng.cancel(u0)
    assert not eng.cancel(u0)  # already terminal
    eng.run_to_completion(max_steps=300)
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[u0].status == lifecycle.CANCELLED
    assert by_uid[u1].status == lifecycle.DONE
    assert eng.cache.pool.num_free == free0


@pytest.mark.slow
def test_real_paged_engine_degradation_reversible(small_lm):
    """Degradation on the real model: overload trips the controller, some
    prompts prefill through the whole-prompt DistrAttention path (recorded
    per request), everything completes, and the dial returns to exact."""
    dcfg = DegradeConfig(group_sizes=(2,), high_watermark=2,
                         low_watermark=1, up_after=1, down_after=2)
    eng = _paged_engine(small_lm, degrade=dcfg, max_batch=2, max_len=64)
    free0 = eng.cache.pool.num_free
    uids = [eng.add_request(list(range(2, 12)), max_new_tokens=3)
            for _ in range(8)]
    eng.run_to_completion(max_steps=400)
    by_uid = {r.uid: r for r in eng.finished}
    assert set(by_uid) == set(uids)
    assert all(r.status == lifecycle.DONE for r in by_uid.values())
    assert eng.cache.pool.num_free == free0
    degraded = [r for r in by_uid.values() if r.degrade_group > 1]
    assert degraded, "overload never tripped the degraded prefill path"
    assert eng.counters_snapshot()["degraded_prefills"] == len(degraded)
    # drained: the controller must be back at exact within its bound
    for _ in range(dcfg.return_bound_ticks() + dcfg.down_after):
        eng.step()
    assert eng.scheduler.degrade.level == 0
    late = eng.add_request(list(range(2, 12)), max_new_tokens=3)
    eng.run_to_completion(max_steps=100)
    late_req = next(r for r in eng.finished if r.uid == late)
    assert late_req.status == lifecycle.DONE
    assert late_req.degrade_group == 1


@pytest.mark.slow
def test_real_slot_engine_chaos(small_lm):
    """Slot-engine robustness: nan quarantine fails only the poisoned
    request (others bit-identical to fault-free), shedding and cancel
    produce their terminal statuses."""
    base = _slot_engine(small_lm)
    for p in PROMPTS:
        base.add_request(p, max_new_tokens=5)
    base.run_to_completion(max_steps=200)
    want = {r.uid: r.generated for r in base.finished}

    faults = FaultInjector([FaultSpec("nan_logits", uid=1, times=-1)])
    eng = _slot_engine(small_lm, faults=faults, max_waiting=4)
    uids = [eng.add_request(p, max_new_tokens=5) for p in PROMPTS]
    eng.run_to_completion(max_steps=200)
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[uids[1]].status == lifecycle.FAILED
    for i in (0, 2):
        assert by_uid[uids[i]].status == lifecycle.DONE
        assert by_uid[uids[i]].generated == want[i]

    # shedding + cancel on a fresh engine with a 1-deep waiting queue
    eng2 = _slot_engine(small_lm, max_slots=1, max_waiting=1)
    a = eng2.add_request(PROMPTS[0], max_new_tokens=4)
    eng2.step()  # a takes the single slot; the waiting queue is empty
    b = eng2.add_request(PROMPTS[1], max_new_tokens=4)  # queued
    c = eng2.add_request(PROMPTS[2], max_new_tokens=4)  # shed
    by_uid2 = {r.uid: r for r in eng2.finished}
    assert by_uid2[c].status == lifecycle.REJECTED
    assert eng2.cancel(b)
    eng2.run_to_completion(max_steps=200)
    by_uid2 = {r.uid: r for r in eng2.finished}
    assert by_uid2[a].status == lifecycle.DONE
    assert by_uid2[b].status == lifecycle.CANCELLED
    snap = eng2.counters_snapshot()
    assert snap["shed"] == 1 and snap["cancelled"] == 1
