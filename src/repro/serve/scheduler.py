"""Continuous-batching scheduler over the paged KV cache.

The slot engine admits a request only when a whole slot (a ``max_len`` KV
slab) frees up — admission is *slot*-bound.  This scheduler makes admission
*memory*-bound and *budget*-bound instead, deciding every tick:

  * **Token-budget admission.**  Each tick spends at most
    ``token_budget`` tokens of model work: one token per running decode
    lane plus chunked-prefill tokens for the head of the queue.  New work
    is admitted every step, not only when a sequence finishes.

  * **Chunked prefill interleaved with decode.**  Prompts are processed in
    ``prefill_chunk``-token windows that ride the *paged decode kernel*
    (banded multi-token windows — serve_step.make_paged_step), so a long
    prompt never stalls running decodes for its full length: each tick runs
    some prefill chunks AND the batched decode tick.

  * **FCFS with preempt-on-pool-exhaustion.**  Requests start in arrival
    order.  When the pool can't grow a *running* request for its next
    decode token, the latest-arrived block holder is preempted — its whole
    KV is evicted to host (serve.paged.evict_to_host), its blocks freed —
    and it resumes bit-identically later (the KV is copied back, not
    recomputed).  Admission and restores never preempt: they wait for
    genuinely free blocks (two restores evicting each other would thrash
    without a token of progress), so the oldest request always advances
    and nothing starves (the pool must hold ≥ one full-length request).

  * **Request lifecycle control** (serve.lifecycle).  Every request ends in
    exactly one terminal status: per-request deadlines (TTFT and
    end-to-end) are checked each tick against the injectable clock
    (``expired``); a bounded waiting queue sheds the *newest* arrival when
    full (``rejected``); ``cancel`` frees a request's blocks immediately
    (``cancelled``); numeric-health and fault failures quarantine exactly
    the offending request (``failed``) — the batch keeps running.

  * **Mesh one-tick admission.**  A mesh-capable paged engine
    (``PagedServeEngine(mesh=)``) exposes ``prefill_mesh_run`` +
    ``mesh_prefill_ready``: a long prompt's whole prefill runs as ONE
    exact ring sequence-parallel forward across the engine's mesh and its
    K/V lands in the (single-device) block pool in the same tick —
    replacing ceil(n/chunk) chunked ticks at no accuracy cost.  Short
    prompts keep chunked prefill (nothing to amortise).

  * **Graceful degradation** (serve.degrade).  An optional hysteresis
    controller watches queue depth (and optionally rolling p50 TTFT) and,
    under sustained overload, switches *new* prompts from exact chunked
    prefill onto one whole-prompt DistrAttention forward
    (``engine.prefill_full_run``) at a per-level grouping fraction — TTFT
    collapses to a single tick at a per-request-recorded accuracy cost —
    then dials back to exact within ``down_after × max_level`` ticks of the
    pressure draining.

  * **Fault containment** (serve.faults).  Engine primitives may raise
    :class:`~repro.serve.faults.InjectedFault` (or its real-world
    equivalents): a failing model step is retried ``step_max_retries``
    times before the culprit alone is failed; a failing ``restore`` backs
    off exponentially (``restore_backoff_ticks`` doubling) for
    ``restore_max_retries`` attempts.  A *global-stall* watchdog fails the
    queue head if nothing in the scheduler progressed for
    ``watchdog_ticks`` consecutive ticks with work present — per-entry
    watchdogs would shoot legitimately queued requests under overload.

  * **Per-request metrics.**  TTFT (submit → first sampled token) and TPOT
    (mean inter-token time after the first) from an injectable clock —
    the serving benchmark's P50/P99 comes from here — plus the terminal
    status and degradation level per request, and scheduler-level
    ``counters()`` (shed / expired / cancelled / failed / retries /
    degraded prefills).

The scheduler is pure policy: it talks to the engine through a small
primitive surface (``lane_*``, ``alloc``, ``prefill_chunk_run``,
``prefill_full_run``, ``decode_tick``, ``evict``/``restore``/``release``)
so the decision logic is unit-testable without a model (tests/test_paged.py
and tests/test_chaos.py fake the engine).
"""
from __future__ import annotations

from collections import Counter, deque
from dataclasses import dataclass, field

import numpy as np

from repro.obs.clock import resolve_clock
from repro.obs.trace import get_recorder
from repro.serve.degrade import DegradationController, DegradeConfig
from repro.serve.faults import NULL_INJECTOR, InjectedFault
from repro.serve import lifecycle


@dataclass
class SchedulerConfig:
    max_batch: int = 8  # concurrent decode lanes
    prefill_chunk: int = 32  # chunked-prefill window (one jit bucket)
    # Model tokens processed per tick (decode lanes + prefill chunks);
    # 0 → max_batch + 2·prefill_chunk (one decode tick + two chunks).
    token_budget: int = 0
    # Bounded waiting queue: submissions past this depth are shed
    # (rejected) instead of queued — reject-newest keeps every accepted
    # request's latency bounded.  None → unbounded (the historical
    # behaviour).
    max_waiting: int | None = None
    # Global-stall watchdog: ticks with work present but zero progress
    # anywhere (no chunk, token, restore, admission, or finish) before the
    # queue head is failed.  Must exceed the restore backoff horizon
    # (sum of restore_backoff_ticks · 2^k) or the watchdog would fire
    # mid-backoff.
    watchdog_ticks: int = 16
    # Bounded retry-with-backoff for a faulting ``restore`` (raise — a
    # False return is a capacity wait, not a fault, and costs no retry).
    restore_max_retries: int = 4
    restore_backoff_ticks: int = 1  # doubles per attempt
    # Bounded retry for a faulting model step (prefill chunk / full
    # prefill / decode tick raising InjectedFault).
    step_max_retries: int = 2

    def budget(self) -> int:
        return self.token_budget or (self.max_batch + 2 * self.prefill_chunk)


@dataclass
class RequestMetrics:
    t_submit: float = 0.0
    t_first_token: float | None = None
    t_done: float | None = None
    n_preemptions: int = 0

    @property
    def ttft(self) -> float | None:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    def tpot(self, n_generated: int) -> float | None:
        if self.t_done is None or self.t_first_token is None:
            return None
        if n_generated <= 1:
            return 0.0
        return (self.t_done - self.t_first_token) / (n_generated - 1)


@dataclass
class Entry:
    """Scheduler-side state for one request (engine's Request rides along)."""
    req: object  # serve.engine.Request
    prompt_done: int = 0  # prompt tokens prefilled so far
    length: int = 0  # live KV tokens in the pool
    next_token: int | None = None  # sampled, not yet fed to decode
    lane: int | None = None
    evicted: bool = False
    restore_tries: int = 0  # consecutive *faulting* restores (not waits)
    restore_next_tick: int = 0  # backoff: no restore attempt before this
    step_tries: int = 0  # consecutive faulting model steps
    metrics: RequestMetrics = field(default_factory=RequestMetrics)

    @property
    def uid(self) -> int:
        return self.req.uid


class Scheduler:
    """FCFS continuous batching with chunked prefill and preemption."""

    def __init__(self, cfg: SchedulerConfig, *, clock=None,
                 degrade: DegradeConfig | DegradationController | None = None,
                 faults=NULL_INJECTOR, trace=None):
        self.cfg = cfg
        self.clock = resolve_clock(clock)
        if isinstance(degrade, DegradeConfig):
            degrade = DegradationController(degrade)
        self.degrade = degrade
        self.faults = faults
        self.trace = trace if trace is not None else get_recorder()
        self._tns = self.trace.ns()  # async-span id namespace (obs.trace)
        self.waiting: deque[Entry] = deque()
        self.running: dict[int, Entry] = {}  # lane → entry
        self.done: list[Entry] = []
        self.counters: Counter = Counter()
        self._tick = 0
        # The slow_step fault (and nothing else) advances this: deadline
        # checks see submit-relative time self.clock() + offset, so a
        # straggling step expires requests without wall-clock sleeps.
        self._clock_offset = 0.0
        self._stall_ticks = 0
        self._level = 0  # degradation level chosen this tick
        self._last_level = 0  # last level a degrade_level instant recorded

    def _now(self) -> float:
        return self.clock() + self._clock_offset

    # -- queue ----------------------------------------------------------

    def submit(self, req) -> Entry | None:
        """Queue a request — or shed it (status ``rejected``, returns None)
        when the bounded waiting queue is full.  Reject-newest: accepted
        requests keep their FCFS position and latency bound; the caller
        learns the verdict immediately from ``req.status``."""
        e = Entry(req=req)
        e.metrics.t_submit = self._now()
        self.trace.begin("request", f"{self._tns}:{e.uid}", uid=e.uid,
                         prompt_len=len(req.prompt),
                         max_new=req.max_new_tokens)
        if (self.cfg.max_waiting is not None
                and len(self.waiting) >= self.cfg.max_waiting):
            self.counters["shed"] += 1
            self.trace.instant("shed", uid=e.uid)
            e.metrics.t_done = e.metrics.t_submit
            req.status = lifecycle.REJECTED
            self.done.append(e)
            self.trace.end("request", f"{self._tns}:{e.uid}",
                           **self._metric_row(e))
            return None
        req.status = lifecycle.QUEUED
        self.waiting.append(e)
        return e

    def cancel(self, uid: int, engine) -> bool:
        """Terminate ``uid`` now, wherever it is (waiting, mid-prefill,
        running, or evicted): its blocks / lane / host copy are freed this
        call, not at the next tick.  Returns False for unknown or already-
        terminal uids."""
        for e in list(self.waiting):
            if e.uid == uid:
                self.waiting.remove(e)
                self._finalize(e, engine, lifecycle.CANCELLED)
                self.counters["cancelled"] += 1
                return True
        for e in list(self.running.values()):
            if e.uid == uid:
                self._finalize(e, engine, lifecycle.CANCELLED)
                self.counters["cancelled"] += 1
                return True
        return False

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def _metric_row(self, e: Entry) -> dict:
        """The per-request metrics row.  ONE builder feeds both metrics()
        and the trace's async end-event args, so the exported trace is
        bit-consistent with metrics() by construction."""
        return {
            "uid": e.uid,
            "ttft_s": e.metrics.ttft,
            "tpot_s": e.metrics.tpot(len(e.req.generated)),
            "n_generated": len(e.req.generated),
            "n_preemptions": e.metrics.n_preemptions,
            "status": getattr(e.req, "status", lifecycle.DONE),
            "degrade_group": getattr(e.req, "degrade_group", 1),
        }

    def metrics(self) -> list[dict]:
        return [self._metric_row(e) for e in self.done]

    # -- termination ----------------------------------------------------

    def _finalize(self, e: Entry, engine, status: str) -> None:
        """Move an entry to its terminal status, freeing whatever it holds
        (lane, pool blocks, host copy — ``release`` covers all three)."""
        if e.lane is not None:
            self.running.pop(e.lane, None)
            e.lane = None
        if e.evicted or engine.holds_blocks(e):
            engine.release(e)
            e.evicted = False
        e.req.status = status
        e.metrics.t_done = self._now()
        self.done.append(e)
        self.trace.end("request", f"{self._tns}:{e.uid}",
                       **self._metric_row(e))

    def _fail(self, e: Entry, engine, kind: str, finished: list) -> None:
        self._finalize(e, engine, lifecycle.FAILED)
        self.counters[kind] += 1
        finished.append(e.req)

    def _expire_pass(self, engine, finished: list) -> bool:
        """Deadline sweep: TTFT deadlines apply until the first token
        (entries still waiting / mid-prefill); end-to-end deadlines apply
        for the whole request.  Running entries always hold a first token
        (lanes are only assigned after it), so only e2e applies there."""
        now = self._now()
        progressed = False
        for e in list(self.waiting):
            r = e.req
            d_ttft = getattr(r, "deadline_ttft", None)
            d_e2e = getattr(r, "deadline_e2e", None)
            waited = now - e.metrics.t_submit
            if (d_ttft is not None and e.metrics.t_first_token is None
                    and waited > d_ttft) or (d_e2e is not None
                                             and waited > d_e2e):
                self.waiting.remove(e)
                self._finalize(e, engine, lifecycle.EXPIRED)
                self.counters["expired"] += 1
                finished.append(r)
                progressed = True
        for e in list(self.running.values()):
            d_e2e = getattr(e.req, "deadline_e2e", None)
            if d_e2e is not None and now - e.metrics.t_submit > d_e2e:
                self._finalize(e, engine, lifecycle.EXPIRED)
                self.counters["expired"] += 1
                finished.append(e.req)
                progressed = True
        return progressed

    def _ttft_p50(self) -> float | None:
        """Rolling p50 TTFT over the last 32 finished requests (degrade
        controller signal; None until one finishes)."""
        vals = [e.metrics.ttft for e in self.done[-32:]
                if e.metrics.ttft is not None]
        if not vals:
            return None
        return float(np.median(vals))

    def counters_snapshot(self) -> dict:
        """Frozen to ``lifecycle.COUNTER_KEYS`` (zero-filled): the schema
        the cluster router's health model reads — see lifecycle.py."""
        return lifecycle.counters_view(self.counters)

    # -- preemption -----------------------------------------------------

    def _requeue(self, victim: Entry) -> None:
        """Put a preempted entry back into the waiting queue at its ARRIVAL
        position (by uid).  The queue is always uid-sorted — new arrivals
        append in uid order and re-insertions bisect — so a just-evicted
        runner can never jump ahead of an older evicted request already
        waiting for its restore."""
        idx = 0
        for e in self.waiting:
            if e.uid > victim.uid:
                break
            idx += 1
        self.waiting.insert(idx, victim)

    def _preempt_newest_holder(self, engine, grower: Entry) -> bool:
        """Evict the latest-arrived request holding pool blocks (vLLM's
        LIFO victim: the oldest keeps its memory, guaranteeing head-of-line
        progress) — *including* ``grower`` itself: when the growing request
        is the newest holder, LIFO demands it self-preempts rather than
        stealing an older request's memory.  Candidates are the running
        set plus partially-prefilled waiters (they hold blocks too).
        Returns True when an eviction freed memory the grower may retry
        with; False when the grower itself was evicted (stop growing it)
        or nothing holds blocks."""
        cands = list(self.running.values()) + [
            e for e in self.waiting
            if not e.evicted and engine.holds_blocks(e)
        ]
        if not cands:
            return False
        victim = max(cands, key=lambda e: e.uid)
        with self.trace.span("serve.kv.evict", uid=victim.uid):
            engine.evict(victim)
        victim.evicted = True
        victim.req.status = lifecycle.PREEMPTED
        victim.metrics.n_preemptions += 1
        self.trace.instant("preempt", uid=victim.uid)
        if victim.lane is not None:
            del self.running[victim.lane]
            victim.lane = None
            self._requeue(victim)
        # else: a partially-prefilled waiter — already queued in uid order.
        return victim is not grower

    def _alloc_or_preempt(self, engine, entry: Entry, n_tokens: int) -> bool:
        """Cover ``n_tokens`` positions for a RUNNING ``entry``, preempting
        newest block holders until it fits.  Only decode growth preempts:
        admission and restores wait for genuinely free blocks instead —
        evicting a runner to admit (or re-admit) another would let two
        restores thrash evicting each other within one tick, with no token
        of progress in between.  Returns False when the entry itself got
        evicted (it was the newest holder) — the caller must skip it."""
        while not engine.alloc(entry, n_tokens):
            if not self._preempt_newest_holder(engine, grower=entry):
                return False
        return True

    # -- prompt completion ----------------------------------------------

    def _finish_prompt(self, engine, head: Entry, logits_row,
                       finished: list) -> None:
        """Prompt fully prefilled: health-check the last-position logits,
        sample the first token, and either finish (max_new_tokens=1 / eos)
        or move to a decode lane.  ``serve.first_token`` covers the logits
        pull, where the host waits for the final chunk."""
        with self.trace.span("serve.first_token", uid=head.uid):
            row = np.asarray(logits_row, np.float32)
            if not np.isfinite(row).all():
                # Numeric quarantine: a non-finite distribution poisons only
                # this request — its blocks free now, the batch keeps running.
                self._fail(head, engine, "failed_numeric", finished)
                return
            tok = engine.sample_one(logits_row)
            head.req.generated.append(tok)
            head.next_token = tok
            head.metrics.t_first_token = self._now()
            self.trace.instant("first_token", uid=head.uid)
            # The first token may already satisfy the stop conditions
            # (max_new_tokens=1 / eos): finish without a decode tick —
            # the slot engine's contract, and one saved decode.
            if (len(head.req.generated) >= head.req.max_new_tokens
                    or (head.req.eos_id is not None
                        and tok == head.req.eos_id)):
                head.req.done = True
                self._finalize(head, engine, lifecycle.DONE)
                finished.append(head.req)
                return
            head.req.status = lifecycle.RUNNING
            head.lane = engine.free_lane()
            self.running[head.lane] = head

    def _step_fault(self, engine, e: Entry, finished: list) -> bool:
        """Bounded retry for a faulting model step.  Returns True when the
        entry was failed (budget exhausted), False when it should retry."""
        e.step_tries += 1
        self.counters["step_retries"] += 1
        if e.step_tries > self.cfg.step_max_retries:
            self._fail(e, engine, "failed_fault", finished)
            return True
        return False

    # -- the tick -------------------------------------------------------

    def tick(self, engine) -> list:
        """One scheduling step.  Returns newly *terminal* Requests — done,
        expired, cancelled-by-deadline, or failed this tick (rejected and
        explicitly cancelled requests terminate inside submit()/cancel()).

        Each phase runs under a span (``serve.tick`` around them all), so
        a trace charges the host's time, and the device's idle time, to
        the phase that spent it."""
        with self.trace.span("serve.tick"):
            self._tick += 1
            finished: list = []

            # A straggling step: the injected delay ages every in-flight
            # deadline before the sweep below.
            spec = self.faults.fires("slow_step")
            if spec is not None:
                self._clock_offset += spec.delay

            with self.trace.span("serve.expire"):
                progressed = self._expire_pass(engine, finished)

            if self.degrade is not None:
                self._level = self.degrade.observe(
                    len(self.waiting), self._ttft_p50()
                )
                if self._level != self._last_level:
                    self.trace.instant("degrade_level", level=self._level)
                    self._last_level = self._level

            # The decode phase is reserved first.
            budget = self.cfg.budget() - len(self.running)
            with self.trace.span("serve.admit"):
                progressed |= self._admit_pass(engine, budget, finished)
            if self.running:
                with self.trace.span("serve.grow"):
                    self._grow_pass(engine)
            if self.running:
                progressed |= self._decode_pass(engine, finished)
            self._watchdog(engine, progressed, finished)
            return finished

    def _admit_pass(self, engine, budget: int, finished: list) -> bool:
        """Admission and chunked prefill, FCFS from the head of the queue,
        within ``budget`` tokens.  Returns whether anything progressed.

        The head is POPPED before any allocation: preemption pushes
        victims onto the queue front mid-allocation, so indexing the
        queue while holding the head would pop the wrong entry.  Any
        path that leaves the head unfinished puts it back in front
        (it is the oldest entry, so FCFS order is preserved)."""
        progressed = False
        while (budget > 0 and self.waiting
               and len(self.running) < self.cfg.max_batch):
            head = self.waiting.popleft()
            if head.evicted:
                if head.restore_next_tick > self._tick:
                    # Backing off after a faulting restore: hold the FCFS
                    # head (younger entries would jump it) — decode lanes
                    # keep draining meanwhile.
                    self.waiting.appendleft(head)
                    break
                # Whole-request restore: needs its full block count back,
                # from genuinely FREE blocks (no preemption — see
                # _alloc_or_preempt).  Until then the head waits; running
                # lanes keep finishing and freeing.
                try:
                    with self.trace.span("serve.kv.restore", uid=head.uid):
                        restored = engine.restore(head)
                except InjectedFault:
                    # A raise is a FAULT (host↔device copy failure) and
                    # spends retry budget; a False return is a capacity
                    # wait and never does.
                    head.restore_tries += 1
                    self.counters["restore_retries"] += 1
                    if head.restore_tries > self.cfg.restore_max_retries:
                        self._fail(head, engine, "failed_fault", finished)
                        progressed = True
                        continue
                    head.restore_next_tick = self._tick + (
                        self.cfg.restore_backoff_ticks
                        << (head.restore_tries - 1)
                    )
                    self.waiting.appendleft(head)
                    break
                if not restored:
                    self.waiting.appendleft(head)
                    break
                head.evicted = False
                head.restore_tries = 0
                progressed = True
                self.trace.instant("restore", uid=head.uid)
                if head.prompt_done == len(head.req.prompt):
                    head.req.status = lifecycle.RUNNING
                    head.lane = engine.free_lane()
                    self.running[head.lane] = head
                else:
                    # Preempted mid-prefill: back in front — the next
                    # iteration resumes its chunked prefill.
                    head.req.status = lifecycle.PREFILL
                    self.waiting.appendleft(head)
                continue
            if head.prompt_done == 0 and not engine.can_admit(head):
                # Admission watermark (vLLM-style): don't start a prompt
                # unless its whole prefill + one decode-growth block fits
                # in FREE memory now — admitting on a chunk-by-chunk
                # basis over-commits the pool and forces later decode-
                # growth preemptions (evict + restore round-trips that
                # cost far more than the wait).
                self.waiting.appendleft(head)
                break
            if (head.prompt_done == 0
                    and hasattr(engine, "prefill_mesh_run")
                    and engine.mesh_prefill_ready(len(head.req.prompt))):
                # Mesh admission: one whole-prompt EXACT prefill across the
                # engine's context-parallel ring replaces ceil(n/chunk)
                # chunks — the long prompt's TTFT collapses to a single
                # tick with no accuracy cost (the degraded branch below
                # stays the overload valve for non-mesh engines).
                n = len(head.req.prompt)
                if not engine.alloc(head, n):
                    self.waiting.appendleft(head)
                    break
                head.req.status = lifecycle.PREFILL
                try:
                    row = engine.prefill_mesh_run(head)
                except InjectedFault:
                    # mesh_prefill / stuck_step raise BEFORE any pool
                    # write, so the retry re-runs against clean blocks.
                    if self._step_fault(engine, head, finished):
                        progressed = True
                    else:
                        self.waiting.appendleft(head)
                    break
                head.step_tries = 0
                head.prompt_done = n
                head.length = n
                self.counters["mesh_prefills"] += 1
                self.trace.instant("mesh_prefill", uid=head.uid, n=n)
                budget -= n
                progressed = True
                self._finish_prompt(engine, head, row, finished)
                continue
            if (self._level > 0 and head.prompt_done == 0
                    and hasattr(engine, "prefill_full_run")):
                # Degraded admission: one whole-prompt DistrAttention
                # forward instead of ceil(n/chunk) exact chunks — TTFT
                # under overload collapses to a single tick, the accuracy
                # cost is recorded on the request (degrade_group).
                n = len(head.req.prompt)
                if not engine.alloc(head, n):
                    self.waiting.appendleft(head)
                    break
                group = self.degrade.group_size
                head.req.status = lifecycle.PREFILL
                try:
                    row = engine.prefill_full_run(head, group)
                except InjectedFault:
                    if self._step_fault(engine, head, finished):
                        progressed = True
                    else:
                        self.waiting.appendleft(head)
                    break
                head.step_tries = 0
                head.prompt_done = n
                head.length = n
                head.req.degrade_group = group
                self.counters["degraded_prefills"] += 1
                self.trace.instant("degraded_prefill", uid=head.uid,
                                   group=group)
                budget -= n
                progressed = True
                self._finish_prompt(engine, head, row, finished)
                continue
            chunk = min(
                self.cfg.prefill_chunk,
                len(head.req.prompt) - head.prompt_done,
                budget,
            )
            if chunk <= 0:
                self.waiting.appendleft(head)
                break
            if not engine.alloc(head, head.prompt_done + chunk):
                # Admission waits for free blocks rather than preempting.
                self.waiting.appendleft(head)
                break
            head.req.status = lifecycle.PREFILL
            try:
                with self.trace.span("serve.prefill_chunk", uid=head.uid,
                                     start=head.prompt_done, n=chunk):
                    logits_last = engine.prefill_chunk_run(head, chunk)
            except InjectedFault:
                if self._step_fault(engine, head, finished):
                    progressed = True
                else:
                    self.waiting.appendleft(head)
                break
            head.step_tries = 0
            head.prompt_done += chunk
            head.length = head.prompt_done
            budget -= chunk
            progressed = True
            if head.prompt_done == len(head.req.prompt):
                # Prompt complete: the final chunk's last live row is the
                # exact last-position distribution → first token now.
                self._finish_prompt(engine, head, logits_last, finished)
            else:
                # Partial prefill: back to the front; the loop (or the
                # next tick) continues this prompt's chunks first.
                self.waiting.appendleft(head)
        return progressed

    def _grow_pass(self, engine) -> None:
        """Decode writes one token at position `length` per lane: make
        sure every lane's table covers it (preempting if needed)."""
        for lane in sorted(self.running):
            e = self.running.get(lane)
            if e is None:
                continue
            if not self._alloc_or_preempt(engine, e, e.length + 1):
                if e.evicted:
                    # The grower was the newest holder and self-
                    # preempted (LIFO): it decodes after a restore.
                    continue
                # Oldest request alone can't grow: capacity bug — the
                # constructor guarantees one full request fits.
                raise RuntimeError(
                    f"request {e.uid} cannot grow to {e.length + 1} "
                    "tokens with an empty pool"
                )

    def _decode_pass(self, engine, finished: list) -> bool:
        """One batched decode tick over all running lanes, then each lane's
        token appended (``serve.emit``).  Returns whether anything
        progressed."""
        try:
            with self.trace.span("serve.decode_step",
                                 n_lanes=len(self.running)):
                out = engine.decode_tick(self.running)
            # Engines return (tokens, ok_mask); legacy fakes returning
            # bare tokens get an all-healthy mask.
            if isinstance(out, tuple):
                toks, ok = out
            else:
                toks, ok = out, np.ones((len(out),), bool)
        except InjectedFault as f:
            # The whole batched step is lost (nothing was written: engines
            # raise before mutating pools) but only the culprit spends
            # retry budget; everyone else just loses one tick, bounded by
            # step_max_retries.
            culprit = next(
                (x for x in self.running.values() if x.uid == f.uid), None,
            )
            return culprit is not None and self._step_fault(
                engine, culprit, finished)
        progressed = False
        now = self._now()
        with self.trace.span("serve.emit"):
            for lane, e in list(self.running.items()):
                if not ok[lane]:
                    # Numeric quarantine: only the offending lane dies;
                    # the other lanes' KV and tokens are untouched
                    # (per-row independence).
                    self._fail(e, engine, "failed_numeric", finished)
                    progressed = True
                    continue
                e.step_tries = 0
                t = int(toks[lane])
                e.req.generated.append(t)
                e.next_token = t
                e.length += 1
                progressed = True
                limit = len(e.req.generated) >= e.req.max_new_tokens
                hit_eos = e.req.eos_id is not None and t == e.req.eos_id
                # Window-decoding engines slide past the table bound
                # (head-block recycling) — only engines without the
                # ring-write invariant force-finish at capacity.
                full = (
                    not getattr(engine, "window_decode", False)
                    and e.length >= engine.capacity_tokens - 1
                )
                if limit or hit_eos or full:
                    e.req.done = True
                    self._finalize(e, engine, lifecycle.DONE)
                    finished.append(e.req)
        return progressed

    def _watchdog(self, engine, progressed: bool, finished: list) -> None:
        """Global-stall watchdog.  Per-entry no-progress timers would shoot
        legitimately queued requests under overload; the global form only
        fires when NOTHING moved — a wedged allocator / dead engine — and
        then fails the FCFS head (the entry the whole queue is stuck
        behind).  Failing it is itself progress, so the counter resets and
        termination stays bounded."""
        if progressed or not self.has_work():
            self._stall_ticks = 0
        else:
            self._stall_ticks += 1
            if self._stall_ticks >= self.cfg.watchdog_ticks:
                if self.waiting:
                    victim = self.waiting.popleft()
                else:
                    victim = min(self.running.values(), key=lambda x: x.uid)
                self.trace.instant("watchdog", uid=victim.uid)
                self._fail(victim, engine, "watchdog_fails", finished)
                self._stall_ticks = 0
