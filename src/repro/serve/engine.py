"""Serving engines: the slot engine (contiguous ring caches) and the paged
engine (block-pool KV + continuous-batching scheduler).

``ServeEngine`` is slot-based: the decode cache holds ``max_slots``
sequences with a contiguous ``max_len`` slab each — admission is
slot-bound.  ``PagedServeEngine`` replaces the slabs with a shared block
pool and delegates every step to ``serve.scheduler`` (token-budget
admission, chunked prefill, preempt-to-host) — admission is memory-bound,
so mixed-length workloads pack more concurrent decode lanes into the same
HBM (DESIGN.md §Paged serving, benchmarks/serving.py).

Slot engine: requests are
prefilled one at a time (bucketed prompt padding bounds recompiles) and their
caches inserted into free slots; every ``step()`` advances *all* active slots
by one token in a single jitted decode.  Finished sequences free their slot
immediately — the vLLM-style continuous batching pattern at step granularity.

Long generations: for ring-layout caches (GQA ``length``-tracked) decoding
continues *past* ``max_len`` with sliding-window eviction — the ring write
(``pos mod S``) overwrites the oldest token and the kernels attend over the
live window ``min(length, max_len)``, so a slot serves arbitrarily long
outputs at bounded memory.  Families without the ring invariant (MLA / SSM /
hybrid / enc-dec) still finish before wrap.

Construction also warms the block-size autotuner (``repro.tune``) for every
prefill bucket and the decode split — under ``REPRO_TUNE=measure`` the
timing sweeps run once here, never inside a serving step.
"""
from __future__ import annotations

import itertools
from collections import Counter
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.clock import resolve_clock
from repro.obs.trace import get_recorder
from repro.serve import kv_cache, lifecycle
from repro.serve.degrade import DegradationController, DegradeConfig
from repro.serve.faults import NULL_INJECTOR
from repro.serve.lifecycle import IncompleteRun
from repro.serve.sampler import sample
from repro.serve.serve_step import make_decode_step, make_prefill
from repro.tune.autotune import warm_engine
from repro.distributed.sharding import maybe_set_mesh


def _validate_request(prompt, limit: int, max_new_tokens: int,
                      what: str = "max_len") -> None:
    """Shared submission-time validation for both engines: a prompt longer
    than the cache would otherwise shape-error (or silently corrupt KV)
    deep inside admission, and a non-positive ``max_new_tokens`` would
    decode forever (the ≥-limit stop can never trip)."""
    if len(prompt) > limit:
        raise ValueError(
            f"prompt length {len(prompt)} exceeds the engine's "
            f"{what}={limit}; truncate the prompt or build the engine "
            "with a larger cache"
        )
    if not prompt:
        raise ValueError("prompt must hold at least one token")
    if max_new_tokens <= 0:
        raise ValueError(
            f"max_new_tokens must be ≥ 1, got {max_new_tokens}"
        )


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 4095) // 4096) * 4096


@dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int | None = None
    generated: list[int] = field(default_factory=list)
    done: bool = False  # completed *successfully* (status == "done")
    # Lifecycle status (serve.lifecycle): every request terminates in
    # exactly one terminal status; non-terminals are observability.
    status: str = lifecycle.QUEUED
    # Deadlines in clock units (seconds for the default wall clock; ticks
    # for an injected tick clock), relative to submission.  None → none.
    deadline_ttft: float | None = None
    deadline_e2e: float | None = None
    # Grouping fraction G* the prefill actually ran at (1 = exact; > 1 =
    # degraded under overload — serve.degrade attributes the accuracy cost).
    degrade_group: int = 1


class ServeEngine:
    def __init__(self, cfg, params, *, max_slots: int = 8, max_len: int = 512,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0, mesh=None, clock=None, max_waiting=None,
                 degrade: DegradeConfig | None = None, faults=None,
                 trace=None):
        """``mesh``: optional device mesh.  When it carries the axis named
        by ``cfg.attention.context_axis``, long-prompt prefill (sequence ≥
        ring size × 128) runs ring sequence-parallel attention
        (distributed.ring_attention) — prompt length then scales with ring
        size instead of one device's HBM.  Decode stays single-device: a
        one-token query never fills a ring shard."""
        if cfg.family == "encdec":
            raise NotImplementedError(
                "engine drives decoder-only archs; use serve_step directly "
                "for enc-dec"
            )
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.mesh = mesh
        self.clock = resolve_clock(clock)
        self.max_waiting = max_waiting
        if isinstance(degrade, DegradeConfig):
            degrade = DegradationController(degrade)
        self.degrade = degrade
        self.faults = faults or NULL_INJECTOR
        self.trace = trace if trace is not None else get_recorder()
        self._tns = self.trace.ns()  # async-span id namespace (obs.trace)
        self._last_degrade_level = 0
        self.counters: Counter = Counter()
        self._clock_offset = 0.0  # advanced only by the slow_step fault
        self._step_tries: dict[int, int] = {}  # uid → faulting-step retries
        self._uid = itertools.count()
        self._rng = jax.random.PRNGKey(seed)

        # Resolve every block-size key this engine's steps will hit (prefill
        # buckets + decode split) before the first request arrives; under
        # REPRO_TUNE=measure the sweeps run and persist here, once.  The
        # mesh context keys long-prompt buckets per ring shard.
        with maybe_set_mesh(mesh):
            self.tuned_blocks = warm_engine(cfg, max_len)

        self.cache = kv_cache.init_cache(cfg, max_slots, max_len)
        self.pos = jnp.zeros((max_slots,), jnp.int32)
        self.tokens = jnp.zeros((max_slots, 1), jnp.int32)
        self.active: dict[int, Request] = {}  # slot -> request
        self.pending: list[Request] = []
        self.finished: list[Request] = []

        self._decode = jax.jit(make_decode_step(cfg))
        self._prefills: dict[int, object] = {}
        # Wall-clock per request (submit / first token) so the serving
        # benchmark compares TTFT against the paged engine's scheduler-
        # tracked metrics on equal terms.  In-flight timings are folded
        # into _metric_records (and dropped from these dicts) when a
        # request finishes, so they track active requests, not history.
        self._t_submit: dict[int, float] = {}
        self._t_first: dict[int, float] = {}
        self._metric_records: dict[int, dict] = {}

    # ------------------------------------------------------------------
    def _now(self) -> float:
        return self.clock() + self._clock_offset

    def add_request(self, prompt: list[int], *, max_new_tokens: int = 32,
                    eos_id: int | None = None, deadline_ttft=None,
                    deadline_e2e=None) -> int:
        # Regression guard: a prompt longer than the cache used to
        # shape-error inside _admit (`toks[0, :n] = prompt` against the
        # clamped max_len bucket); fail cleanly at submission instead.
        _validate_request(prompt, self.max_len, max_new_tokens)
        req = Request(next(self._uid), list(prompt), max_new_tokens, eos_id,
                      deadline_ttft=deadline_ttft, deadline_e2e=deadline_e2e)
        now = self._now()
        self.trace.begin("request", f"{self._tns}:{req.uid}", uid=req.uid,
                         prompt_len=len(req.prompt), max_new=max_new_tokens)
        if (self.max_waiting is not None
                and len(self.pending) >= self.max_waiting):
            # Load shedding, reject-newest: accepted requests keep their
            # latency bound; the verdict is immediate (req.status).
            self.counters["shed"] += 1
            self.trace.instant("shed", uid=req.uid)
            self._terminal(req, lifecycle.REJECTED, now, t_submit=now)
            return req.uid
        self.pending.append(req)
        self._t_submit[req.uid] = now
        return req.uid

    def cancel(self, uid: int) -> bool:
        """Terminate ``uid`` immediately, freeing its slot if it holds one.
        False for unknown / already-terminal uids."""
        for req in self.pending:
            if req.uid == uid:
                self.pending.remove(req)
                self.counters["cancelled"] += 1
                self._terminal(req, lifecycle.CANCELLED, self._now())
                return True
        for slot, req in list(self.active.items()):
            if req.uid == uid:
                self._release_slot(slot)
                self.counters["cancelled"] += 1
                self._terminal(req, lifecycle.CANCELLED, self._now())
                return True
        return False

    def _terminal(self, req: Request, status: str, now: float, *,
                  t_submit: float | None = None) -> None:
        """Move a request to a terminal status and record its metrics row."""
        req.status = status
        if t_submit is not None:
            self._t_submit.setdefault(req.uid, t_submit)
        self._finish_metrics(req, now)
        # End-event args ARE the metrics row: the trace reconstructs the
        # terminal status / timings bit-consistently with metrics().
        self.trace.end("request", f"{self._tns}:{req.uid}",
                       **self._metric_records[req.uid])
        self.finished.append(req)

    def _release_slot(self, slot: int) -> None:
        """Free a slot mid-flight: pin its garbage decode to one KV block
        (same reset as natural completion)."""
        del self.active[slot]
        self.pos = self.pos.at[slot].set(0)
        if "length" in self.cache:
            self.cache["length"] = self.cache["length"].at[slot].set(0)

    def _expire_pass(self, done_now: list) -> None:
        """Deadline sweep: TTFT deadlines apply while a request waits for
        admission (its first token lands on the first step after); e2e
        deadlines apply everywhere."""
        now = self._now()
        for req in list(self.pending):
            waited = now - self._t_submit.get(req.uid, now)
            if ((req.deadline_ttft is not None and waited > req.deadline_ttft)
                    or (req.deadline_e2e is not None
                        and waited > req.deadline_e2e)):
                self.pending.remove(req)
                self.counters["expired"] += 1
                self._terminal(req, lifecycle.EXPIRED, now)
                done_now.append(req)
        for slot, req in list(self.active.items()):
            waited = now - self._t_submit.get(req.uid, now)
            if req.deadline_e2e is not None and waited > req.deadline_e2e:
                self._release_slot(slot)
                self.counters["expired"] += 1
                self._terminal(req, lifecycle.EXPIRED, now)
                done_now.append(req)

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.max_slots) if s not in self.active]

    def _prefill_fn(self, bucket: int, group: int = 1):
        """Jitted prefill keyed by (bucket, G*): G* = 1 is the engine's
        exact path; G* > 1 runs the backbone under the degraded attention
        config (serve.degrade) while the cache layout stays the engine's
        own (make_prefill backbone_cfg)."""
        key = (bucket, group)
        if key not in self._prefills:
            bcfg = None
            if group > 1:
                bcfg = self.cfg.replace(
                    attention=self.cfg.attention.degraded(group)
                )
            self._prefills[key] = jax.jit(
                make_prefill(self.cfg, self.max_len, backbone_cfg=bcfg)
            )
        return self._prefills[key]

    def _admit(self, done_now: list) -> None:
        group = 1
        if self.degrade is not None:
            # Pressure signal = backlog depth; one observe per step (admit
            # runs once per step) keeps the hysteresis tick-domain.
            level = self.degrade.observe(len(self.pending))
            group = self.degrade.cfg.group_for(level)
            if level != self._last_degrade_level:
                self.trace.instant("degrade_level", level=level, group=group)
                self._last_degrade_level = level
        for slot in self._free_slots():
            if not self.pending:
                break
            req = self.pending.pop(0)
            if self.faults.fires("stuck_step", req.uid) is not None:
                # Bounded retry: the prefill "raised"; requeue at the front
                # and retry next step, then quarantine just this request.
                tries = self._step_tries.get(req.uid, 0) + 1
                self._step_tries[req.uid] = tries
                self.counters["step_retries"] += 1
                if tries > 2:
                    self._step_tries.pop(req.uid, None)
                    self.counters["failed_fault"] += 1
                    self._terminal(req, lifecycle.FAILED, self._now())
                    done_now.append(req)
                else:
                    self.pending.insert(0, req)
                    break
                continue
            self._step_tries.pop(req.uid, None)
            n = len(req.prompt)
            bucket = min(_bucket(n), self.max_len)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = req.prompt
            # Long-prompt prefill rides the context-parallel ring when the
            # engine has a mesh (trace-time dispatch in core.api.attend).
            with self.trace.span("prefill", uid=req.uid, bucket=bucket,
                                 group=group), maybe_set_mesh(self.mesh):
                logits, cache1 = self._prefill_fn(bucket, group)(
                    self.params, jnp.asarray(toks)
                )
            # Numeric health guard: a non-finite last-position row means
            # this prompt's forward blew up — quarantine the request BEFORE
            # its cache touches the slot; the other slots never notice.
            row = np.asarray(logits[0, -1], np.float32)
            if (self.faults.fires("nan_logits", req.uid) is not None
                    or not np.isfinite(row).all()):
                self.counters["failed_numeric"] += 1
                self._terminal(req, lifecycle.FAILED, self._now())
                done_now.append(req)
                continue
            req.degrade_group = group
            if group > 1:
                self.counters["degraded_prefills"] += 1
            req.status = lifecycle.RUNNING
            # NOTE: right-padding shifts the "last" logit for padded prompts;
            # re-read the true last-position logits from position n-1 by
            # decoding from position n with the prompt's last token instead.
            self.cache = {
                key: self._insert_slot(self.cache[key], cache1[key], slot,
                                       self._slot_axis(key))
                for key in self.cache
            }
            if "length" in self.cache:
                # Bucketed prefill right-pads the prompt; only the true n
                # tokens are live — every decode step's KV walk (and the
                # kernel grid) is bounded by this, not by max_len.
                self.cache["length"] = self.cache["length"].at[slot].set(n)
            self.pos = self.pos.at[slot].set(n - 1)
            self.tokens = self.tokens.at[slot, 0].set(req.prompt[-1])
            self.active[slot] = req

    @staticmethod
    def _slot_axis(key: str) -> int:
        """Batch/slot axis per cache layout (serve.kv_cache docstring)."""
        if key in ("cross_len", "length"):
            return 0
        if key.startswith("groups_"):
            return 2  # (G, per_group, B, ...)
        return 1  # (L_or_G, B, ...)

    @staticmethod
    def _insert_slot(full: jnp.ndarray, one: jnp.ndarray, slot: int,
                     axis: int) -> jnp.ndarray:
        # pad seq dims that differ (prefill bucket < max_len)
        for ax2 in range(full.ndim):
            if ax2 != axis and one.shape[ax2] != full.shape[ax2]:
                widths = [(0, 0)] * full.ndim
                widths[ax2] = (0, full.shape[ax2] - one.shape[ax2])
                one = jnp.pad(one, widths)
        idx = [slice(None)] * full.ndim
        idx[axis] = slice(slot, slot + 1)
        return full.at[tuple(idx)].set(one.astype(full.dtype))

    def step(self) -> list[Request]:
        """Admit pending, decode one token for all active slots; returns
        newly *terminal* requests (done, expired, or failed this step)."""
        done_now: list[Request] = []
        spec = self.faults.fires("slow_step")
        if spec is not None:
            # A straggling step ages every in-flight deadline (no wall-
            # clock sleep needed — the offset rides the injectable clock).
            self._clock_offset += spec.delay
        self._expire_pass(done_now)
        self._admit(done_now)
        if not self.active:
            return done_now
        # advance positions: decode writes at pos+1 (pos = last filled index).
        # Idle slots stay pinned at 0 so their garbage decode keeps walking
        # one KV block instead of growing back toward max_len (serve_step
        # stores length = max(length, pos+1)).
        occupied = np.zeros((self.max_slots,), bool)
        for s in self.active:
            occupied[s] = True
        step_pos = jnp.where(jnp.asarray(occupied), self.pos + 1, 0)
        for slot, req in list(self.active.items()):
            if self.faults.fires("stuck_step", req.uid) is not None:
                # The whole batched decode "raised": retry the step next
                # call, spending retry budget only on the culprit.
                tries = self._step_tries.get(req.uid, 0) + 1
                self._step_tries[req.uid] = tries
                self.counters["step_retries"] += 1
                if tries > 2:
                    self._step_tries.pop(req.uid, None)
                    self._release_slot(slot)
                    self.counters["failed_fault"] += 1
                    self._terminal(req, lifecycle.FAILED, self._now())
                    done_now.append(req)
                return done_now
        self._rng, sub = jax.random.split(self._rng)
        with self.trace.span("decode", n_active=len(self.active)):
            logits, self.cache = self._decode(
                self.params, self.tokens, self.cache, step_pos
            )
        # Per-slot numeric health guard: one device-side reduce + a tiny
        # host transfer; a non-finite row quarantines exactly that slot.
        nan_slots = {
            slot for slot, req in self.active.items()
            if self.faults.fires("nan_logits", req.uid) is not None
        }
        if nan_slots:
            logits = logits.at[np.array(sorted(nan_slots)), -1].set(jnp.nan)
        row_ok = np.asarray(jnp.isfinite(logits[:, -1]).all(axis=-1))
        next_tokens = sample(
            logits, rng=sub, temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p,
        )
        self.pos = step_pos
        self.tokens = next_tokens[:, None]

        toks = np.asarray(next_tokens)
        now = self._now()
        # Ring caches (GQA, length-tracked) slide past max_len: the ring
        # write evicts the oldest token and the kernels see the live window
        # min(length, max_len).  Other cache layouts (MLA/SSM/hybrid/encdec)
        # have no ring invariant, so their sequences finish before wrap.
        sliding = "length" in self.cache
        for slot, req in list(self.active.items()):
            if not row_ok[slot]:
                # Quarantine: the offending slot alone dies; every other
                # slot's cache row and token are untouched.
                self._release_slot(slot)
                self.counters["failed_numeric"] += 1
                self._terminal(req, lifecycle.FAILED, now)
                done_now.append(req)
                continue
            self._step_tries.pop(req.uid, None)
            t = int(toks[slot])
            req.generated.append(t)
            if len(req.generated) == 1:
                self._t_first[req.uid] = now
                self.trace.instant("first_token", uid=req.uid)
            limit = len(req.generated) >= req.max_new_tokens
            hit_eos = req.eos_id is not None and t == req.eos_id
            full = (not sliding) and int(self.pos[slot]) >= self.max_len - 2
            if limit or hit_eos or full:
                req.done = True
                # Reset the freed slot so its (garbage) decode walks one KV
                # block, not the dead sequence's full live window.
                self._release_slot(slot)
                self._terminal(req, lifecycle.DONE, now)
                done_now.append(req)
        return done_now

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            self.step()
            if not self.active and not self.pending:
                return self.finished
        # Steps exhausted with work in flight: a silent return here let a
        # hung engine masquerade as success (requests vanished without a
        # terminal status).
        raise IncompleteRun(
            sorted([r.uid for r in self.active.values()]
                   + [r.uid for r in self.pending]),
            max_steps,
        )

    def _finish_metrics(self, req: Request, now: float) -> None:
        t0 = self._t_submit.pop(req.uid, None)
        t1 = self._t_first.pop(req.uid, None)
        n = len(req.generated)
        self._metric_records[req.uid] = {
            "uid": req.uid,
            "ttft_s": None if t0 is None or t1 is None else t1 - t0,
            "tpot_s": None if t1 is None else (now - t1) / max(n - 1, 1),
            "n_generated": n,
            "n_preemptions": 0,
            "status": req.status,
            "degrade_group": req.degrade_group,
        }

    def has_work(self) -> bool:
        return bool(self.active or self.pending)

    def queue_depth(self) -> int:
        """Requests waiting for admission (cluster-router health signal)."""
        return len(self.pending)

    def degrade_level(self) -> int:
        """Current degradation-controller level (0 = exact / no controller)."""
        return 0 if self.degrade is None else self.degrade.level

    def counters_snapshot(self) -> dict:
        """Robustness counters, frozen to ``lifecycle.COUNTER_KEYS`` (zero-
        filled) — the exact key set the paged engine and scheduler report,
        so the cluster router's health model can diff snapshots blindly."""
        return lifecycle.counters_view(self.counters)

    @property
    def max_prompt_len(self) -> int:
        """Longest prompt ``add_request`` accepts — the replica *capability*
        the cluster router steers on (serve.cluster.EngineReplica)."""
        return self.max_len

    def metrics(self) -> list[dict]:
        """Per-request TTFT / TPOT (same shape as PagedServeEngine.metrics,
        so benchmarks/serving.py compares the engines on equal terms).
        Records live exactly as long as ``finished`` does."""
        return [
            self._metric_records[req.uid]
            for req in self.finished
            if req.uid in self._metric_records
        ]


# ---------------------------------------------------------------------------
# Paged engine: block-pool KV + continuous-batching scheduler
# ---------------------------------------------------------------------------


class PagedServeEngine:
    """Serving engine over the paged KV subsystem (serve.paged +
    serve.scheduler + kernels/paged_decode.py).

    Replaces the per-slot contiguous ``max_len`` slab with a shared block
    pool: HBM is committed per *live token* (rounded to ``block_size``),
    not per worst-case sequence, so at equal memory budget a mixed-length
    workload runs far more concurrent decode lanes.  Every ``step()``
    delegates to the continuous-batching :class:`~repro.serve.scheduler.
    Scheduler`: token-budget admission each tick, chunked prefill riding
    the paged decode kernel (banded multi-token windows — exact attention,
    unlike the slot engine's approximate distr prefill when
    ``impl='distr'``), FCFS with whole-request preemption to host when the
    pool runs dry.

    Scope: GQA dense/moe families (the pools mirror the ring k/v cache
    layout; fused-K̂ pools under ``attention.distr_decode``).  A request's
    *prompt* is bounded by ``max_len`` (the block-table width); decode
    slides past it — once the table is full the write position wraps
    (``pos mod capacity``) and new tokens recycle the request's head
    blocks in place, the paged analog of the slot engine's ring-cache
    eviction, so ``max_new_tokens`` is never capacity-bound.

    ``mesh``: optional device mesh.  When it carries the axis named by
    ``cfg.attention.context_axis``, whole-prompt prefill of long prompts
    runs ring sequence-parallel attention across the mesh and scatters the
    resulting per-layer K/V into this engine's (single-device) block pool
    in ONE scheduler tick (``prefill_mesh_run``) — prefill compute scales
    with ring size, decode-side KV residency stays paged and local.

    Construction resolves the pool block size through the autotuner
    (``repro.tune`` kernel key ``paged_decode``) — under
    ``REPRO_TUNE=measure`` the sweep runs once here, never in a tick.
    With a mesh it also pre-resolves the ring-prefill attention buckets
    (keyed per ring shard) so no serving tick blocks on a timing run.
    """

    #: Decode slides past capacity by recycling head blocks (the scheduler
    #: consults this before force-finishing a request at the table bound).
    window_decode = True

    def __init__(self, cfg, params, *, max_batch: int = 8, max_len: int = 512,
                 block_size: int | None = None, num_blocks: int | None = None,
                 prefill_chunk: int = 32, token_budget: int = 0,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0, cache_dtype=jnp.bfloat16, clock=None,
                 max_waiting=None, degrade: DegradeConfig | None = None,
                 faults=None, mesh=None, trace=None):
        from repro.serve import paged
        from repro.serve.scheduler import Scheduler, SchedulerConfig
        from repro.serve.serve_step import make_paged_step
        from repro.tune.autotune import warm_paged_engine

        if cfg.family not in ("dense", "moe") or cfg.use_mla:
            raise NotImplementedError(
                "paged serving covers GQA dense/moe; use ServeEngine for "
                f"family={cfg.family!r} use_mla={cfg.use_mla}"
            )
        if getattr(cfg, "frontend", None):
            raise NotImplementedError(
                "chunked prefill drives token prompts; patch/frame "
                "frontends keep the slot engine"
            )
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.mesh = mesh
        self._uid = itertools.count()
        self._rng = jax.random.PRNGKey(seed)

        # Pool block size doubles as allocator granularity: resolve it
        # (tuned under REPRO_TUNE) before the pools are shaped by it.  An
        # explicit block_size skips the decode warm-up — a measure-mode
        # sweep whose result would be discarded is pure construction-time
        # waste.  A mesh engine additionally warms the ring-prefill
        # attention buckets under the mesh (per-shard tuner keys).
        want_decode = block_size is None
        if want_decode or mesh is not None:
            with maybe_set_mesh(mesh):
                self.tuned_blocks = warm_paged_engine(
                    cfg, max_len, decode=want_decode,
                    mesh_prefill_buckets=mesh is not None,
                )
        else:
            self.tuned_blocks = {}
        if block_size is None:
            block_size = self.tuned_blocks.get("paged_decode", 128)
        self.block_size = min(block_size, max_len)
        self.max_blocks = -(-max_len // self.block_size)
        self.capacity_tokens = self.max_blocks * self.block_size
        if num_blocks is None:
            # Memory-pressure-free default: every lane can hold max_len.
            num_blocks = 1 + max_batch * self.max_blocks
        if num_blocks - 1 < self.max_blocks:
            raise ValueError(
                f"pool of {num_blocks} blocks (1 reserved) cannot hold one "
                f"full request ({self.max_blocks} blocks of "
                f"{self.block_size}); preemption could not guarantee "
                "progress"
            )
        self.cache = paged.PagedKVCache(
            cfg, num_blocks, self.block_size, dtype=cache_dtype
        )
        # Decode runs Pallas kernels outside any shard_map, so the pools
        # live on one device whatever mesh the prefill uses.
        (self._pool_device,) = self.cache.pools["v"].devices()
        self.prefill_chunk = min(prefill_chunk, max_len)
        self.faults = faults or NULL_INJECTOR
        self.trace = trace if trace is not None else get_recorder()
        self.scheduler = Scheduler(
            SchedulerConfig(
                max_batch=max_batch, prefill_chunk=self.prefill_chunk,
                token_budget=token_budget, max_waiting=max_waiting,
            ),
            degrade=degrade, faults=self.faults, trace=self.trace,
            **({"clock": clock} if clock is not None else {}),
        )
        # The pools are donated to every step that rewrites them, so the
        # device holds one copy of the KV (serve_step.make_paged_step).
        self._decode = jax.jit(make_paged_step(cfg, 1), donate_argnums=(2,))
        self._chunk = jax.jit(
            make_paged_step(cfg, self.prefill_chunk), donate_argnums=(2,)
        )
        self._degraded: dict[tuple[int, int], object] = {}
        self._mesh_prefills: dict = {}
        self.finished: list[Request] = []

    # -- public API (mirrors ServeEngine) --------------------------------

    def add_request(self, prompt: list[int], *, max_new_tokens: int = 32,
                    eos_id: int | None = None, deadline_ttft=None,
                    deadline_e2e=None) -> int:
        # The first decode token writes at position len(prompt): a request
        # must leave at least one block-table slot for it (a clamped write
        # at capacity would land inside the LAST live block).  Only the
        # PROMPT is capacity-bound: max_new_tokens may cross capacity
        # freely — decode slides by recycling head blocks (window_decode).
        _validate_request(
            prompt, min(self.max_len, self.capacity_tokens - 1),
            max_new_tokens, what="max_len (capacity − 1)",
        )
        req = Request(next(self._uid), list(prompt), max_new_tokens, eos_id,
                      deadline_ttft=deadline_ttft, deadline_e2e=deadline_e2e)
        if self.scheduler.submit(req) is None:
            self.finished.append(req)  # shed at the gate (status rejected)
        return req.uid

    def cancel(self, uid: int) -> bool:
        """Terminate ``uid`` now; its blocks / lane / host copy free in this
        call, not at the next tick.  False for unknown / terminal uids."""
        if self.scheduler.cancel(uid, self):
            e = next(x for x in reversed(self.scheduler.done)
                     if x.uid == uid)
            self.finished.append(e.req)
            return True
        return False

    def step(self) -> list[Request]:
        """One scheduler tick: admission + chunked prefill + batched decode
        (serve.scheduler.Scheduler.tick)."""
        done = self.scheduler.tick(self)
        self.finished.extend(done)
        return done

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            self.step()
            if not self.scheduler.has_work():
                return self.finished
        # A silent return here let a hung scheduler masquerade as success.
        raise IncompleteRun(
            sorted([e.uid for e in self.scheduler.waiting]
                   + [e.uid for e in self.scheduler.running.values()]),
            max_steps,
        )

    def metrics(self) -> list[dict]:
        """Per-request TTFT / TPOT / preemption counts / terminal status /
        degradation level (scheduler-tracked)."""
        return self.scheduler.metrics()

    def counters_snapshot(self) -> dict:
        """Robustness counters, frozen to ``lifecycle.COUNTER_KEYS`` (the
        slot engine reports the identical key set)."""
        return self.scheduler.counters_snapshot()

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def queue_depth(self) -> int:
        """Requests waiting for admission (cluster-router health signal)."""
        return len(self.scheduler.waiting)

    def degrade_level(self) -> int:
        """Current degradation-controller level (0 = exact / no controller)."""
        d = self.scheduler.degrade
        return 0 if d is None else d.level

    @property
    def max_prompt_len(self) -> int:
        """Longest prompt ``add_request`` accepts — the replica *capability*
        the cluster router steers on (serve.cluster.EngineReplica).  A
        mesh-backed engine is built with a large ``max_len`` (ring prefill
        makes it affordable); this property is how it advertises that."""
        return min(self.max_len, self.capacity_tokens - 1)

    # -- scheduler primitives --------------------------------------------

    def free_lane(self) -> int:
        for lane in range(self.max_batch):
            if lane not in self.scheduler.running:
                return lane
        raise RuntimeError("no free lane (scheduler admitted past max_batch)")

    def alloc(self, entry, n_tokens: int) -> bool:
        from repro.serve.paged import PoolExhausted

        if self.faults.fires("pool_exhausted", entry.uid) is not None:
            # Injected allocator failure presents exactly like the real
            # one: False — the scheduler waits / preempts / watchdogs.
            return False
        try:
            self.cache.allocate_to(entry.uid, min(n_tokens, self.capacity_tokens))
            return True
        except PoolExhausted:
            return False

    def can_admit(self, entry) -> bool:
        """Admission watermark: the whole prompt plus one decode-growth
        block must fit in free blocks before the first chunk runs."""
        need = self.cache.blocks_for(
            min(len(entry.req.prompt) + 1, self.capacity_tokens)
        )
        return self.cache.pool.num_free >= need

    def evict(self, entry) -> None:
        self.cache.evict_to_host(entry.uid, entry.length,
                                 pad_to=self.max_blocks)

    def restore(self, entry) -> bool:
        from repro.serve.paged import PoolExhausted

        # A raise is a restore FAULT (host↔device copy failure — bounded
        # retry with backoff); a False return is a capacity wait (free
        # blocks will appear) and costs no retry budget.
        self.faults.raise_if("restore_failure", entry.uid)
        try:
            self.cache.restore(entry.uid)
            return True
        except PoolExhausted:
            return False

    def release(self, entry) -> None:
        self.cache.free(entry.uid)

    def holds_blocks(self, entry) -> bool:
        return bool(self.cache.tables.get(entry.uid))

    def sample_one(self, logits_row: jnp.ndarray) -> int:
        self._rng, sub = jax.random.split(self._rng)
        tok = sample(
            logits_row[None], rng=sub, temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p,
        )
        return int(tok[0])

    def prefill_chunk_run(self, entry, chunk: int) -> jnp.ndarray:
        """One chunked-prefill window for ``entry`` (B = 1 jit bucket);
        returns the last *live* row's logits (exact last-position
        distribution once the prompt completes)."""
        # Raised BEFORE any pool mutation: a retried chunk re-runs cleanly.
        self.faults.raise_if("stuck_step", entry.uid)
        start = entry.prompt_done
        with self.trace.span("serve.chunk.inputs"):
            toks = np.zeros((1, self.prefill_chunk), np.int32)
            toks[0, :chunk] = entry.req.prompt[start : start + chunk]
            bt = self.cache.table_array([entry.uid], self.max_blocks)
            toks_d = jnp.asarray(toks)
            start_d = jnp.asarray([start], jnp.int32)
            n_d = jnp.asarray([chunk], jnp.int32)
        with self.trace.span("serve.chunk.launch"):
            logits, self.cache.pools = self._chunk(
                self.params, toks_d, self.cache.pools, bt, start_d, n_d,
            )
            row = logits[0, chunk - 1]
        if self.faults.fires("nan_logits", entry.uid) is not None:
            row = jnp.full_like(row, jnp.nan)
        return row

    def _degraded_prefill_fn(self, bucket: int, group: int):
        from repro.serve.serve_step import make_degraded_paged_prefill

        key = (bucket, group)
        if key not in self._degraded:
            self._degraded[key] = jax.jit(
                make_degraded_paged_prefill(self.cfg, bucket, group),
                donate_argnums=(3,),
            )
        return self._degraded[key]

    def prefill_full_run(self, entry, group: int) -> jnp.ndarray:
        """Whole-prompt *degraded* prefill (serve.degrade): one forward
        under DistrAttention grouping 1/``group`` replaces every exact
        chunk, scattering the prompt's K/V into the already-allocated
        blocks; returns the last live row's logits."""
        self.faults.raise_if("stuck_step", entry.uid)
        n = len(entry.req.prompt)
        bucket = min(_bucket(n), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = entry.req.prompt
        bt = self.cache.table_array([entry.uid], self.max_blocks)
        row, self.cache.pools = self._degraded_prefill_fn(bucket, group)(
            self.params, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
            self.cache.pools, bt,
        )
        if self.faults.fires("nan_logits", entry.uid) is not None:
            row = jnp.full_like(row, jnp.nan)
        return row

    def mesh_prefill_ready(self, n: int) -> bool:
        """Scheduler consult: admit an ``n``-token prompt as ONE whole-
        prompt ring-prefill tick instead of chunked prefill?  Requires a
        mesh, and a prompt longer than one chunk — a one-chunk prompt
        already admits in a single tick, with no collective to amortise."""
        return self.mesh is not None and n > self.prefill_chunk

    def _mesh_prefill_fn(self, bucket: int, dead=frozenset()):
        from repro.serve.serve_step import make_mesh_paged_prefill

        # Keyed by the dead-shard set too: dead_shard_fault rewires the
        # ring at TRACE time, so a degraded ring needs its own jit entry.
        key = (bucket, tuple(sorted(dead)))
        if key not in self._mesh_prefills:
            self._mesh_prefills[key] = jax.jit(
                make_mesh_paged_prefill(self.cfg, bucket), donate_argnums=(3,)
            )
        return self._mesh_prefills[key]

    def prefill_mesh_run(self, entry) -> jnp.ndarray:
        """Whole-prompt *exact* prefill across the context-parallel ring
        (serve_step.make_mesh_paged_prefill): one forward under the
        engine's mesh replaces every chunk, scattering the prompt's
        per-layer K/V into the already-allocated blocks of THIS device's
        pool; returns the last live row's logits.  Faults fire before any
        pool mutation, so a failed collective never poisons the blocks."""
        self.faults.raise_if("stuck_step", entry.uid)
        self.faults.raise_if("mesh_prefill", entry.uid)
        from repro.distributed.ring_attention import dead_shard_fault

        n = len(entry.req.prompt)
        bucket = min(_bucket(n), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = entry.req.prompt
        bt = self.cache.table_array([entry.uid], self.max_blocks)
        dead = self.faults.dead_shards()
        replicated = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )
        with maybe_set_mesh(self.mesh), dead_shard_fault(dead):
            row, pools = self._mesh_prefill_fn(bucket, dead)(
                self.params, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
                jax.device_put(self.cache.pools, replicated), bt,
            )
        # The ring hands the pools back replicated over the mesh; the
        # engine keeps its own device's copy.
        self.cache.pools = jax.device_put(pools, self._pool_device)
        if self.faults.fires("nan_logits", entry.uid) is not None:
            row = jnp.full_like(row, jnp.nan)
        return row

    def decode_tick(self, running: dict) -> tuple[np.ndarray, np.ndarray]:
        """One batched decode over all running lanes; returns
        ``(tokens, ok)`` — (max_batch,) sampled tokens (garbage on idle
        lanes — the scheduler only reads occupied ones) and the numeric
        health mask (False = that lane's logits went non-finite; the
        scheduler quarantines exactly that request).

        Spans: ``serve.decode.inputs`` (lane arrays, block table, host→
        device copies), ``serve.decode.launch`` (the jitted step),
        ``serve.decode.health`` (the finite-logits pull, the first wait on
        the device) and ``serve.decode.sample`` (sampling and the token
        pull)."""
        for e in running.values():
            # Raised BEFORE the model call (no pool mutated): the retried
            # tick re-runs cleanly and only the culprit spends budget.
            self.faults.raise_if("stuck_step", e.uid)
        with self.trace.span("serve.decode.inputs"):
            occupied = np.zeros((self.max_batch,), bool)
            pos = np.zeros((self.max_batch,), np.int32)
            toks = np.zeros((self.max_batch, 1), np.int32)
            uids = [-1] * self.max_batch
            for lane, e in running.items():
                occupied[lane] = True
                pos[lane] = e.length
                toks[lane, 0] = e.next_token
                uids[lane] = e.uid
            bt = self.cache.table_array(uids, self.max_blocks)
            toks_d, pos_d = jnp.asarray(toks), jnp.asarray(pos)
            count = jnp.asarray(occupied.astype(np.int32))
        with self.trace.span("serve.decode.launch"):
            logits, self.cache.pools = self._decode(
                self.params, toks_d, self.cache.pools, bt, pos_d, count,
            )
        with self.trace.span("serve.decode.health"):
            nan_lanes = [lane for lane, e in running.items()
                         if self.faults.fires("nan_logits", e.uid) is not None]
            if nan_lanes:
                logits = logits.at[np.array(sorted(nan_lanes)), -1].set(jnp.nan)
            # Numeric health guard: one device-side reduce, one tiny
            # transfer.  Only occupied lanes count (idle lanes decode
            # garbage by design).
            ok = np.asarray(jnp.isfinite(logits[:, -1]).all(axis=-1)) | ~occupied
        with self.trace.span("serve.decode.sample"):
            self._rng, sub = jax.random.split(self._rng)
            next_tokens = sample(
                logits[:, -1], rng=sub, temperature=self.temperature,
                top_k=self.top_k, top_p=self.top_p,
            )
            return np.asarray(next_tokens), ok
