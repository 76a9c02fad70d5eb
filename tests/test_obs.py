"""Observability layer: tracing, typed metrics, validators, clock audit.

The contracts under test (DESIGN.md §Observability):

  * trace determinism — on an injected fake clock, span timestamps and
    durations are bit-deterministic; ring eviction never corrupts a span
    that is still open; Chrome export round-trips through JSON and the
    structural validator.
  * metrics ↔ counters bit-consistency — every frozen counter key
    (lifecycle / router / train.elastic schemas) appears in its registry
    exactly once, with values equal to ``counters_snapshot()`` verbatim;
    a request's trace end-event args equal its ``metrics()`` row after a
    JSON round-trip (the "reconstruct terminal status + timing from the
    trace" acceptance).
  * tracing off = free — the NullRecorder's per-call overhead is bounded
    by a benchmark assertion, so leaving instrumentation sites
    unconditional costs nothing measurable.
  * clock audit — no serve/train module reads wall time directly; every
    time read flows through the injectable obs.clock discipline.
  * the profiler's clock — a recorder's spans and instants reach an active
    ``jax.profiler`` trace with their args, and JAX's compiles become
    ``compile`` instants on the installed recorder.
"""
import json
import os
import time

import pytest

from repro.obs import (
    NULL_RECORDER,
    MetricsRegistry,
    NullRecorder,
    TraceRecorder,
    achieved_fraction,
    get_recorder,
    perf_clock,
    resolve_clock,
    roofline_lower_bound_s,
    router_registry,
    serving_registry,
    set_recorder,
    train_registry,
    use_recorder,
    utilization_columns,
)
from repro.obs.validate import validate_chrome_trace, validate_metrics_snapshot
from repro.serve import lifecycle
from repro.serve.cluster import ROUTER_COUNTER_KEYS
from repro.serve.lifecycle import COUNTER_KEYS, METRIC_KEYS
from repro.train.elastic import COUNTER_KEYS as TRAIN_COUNTER_KEYS

# Reuse the chaos/cluster/train fakes — the obs layer binds duck-typed to
# the same public surfaces, so the fakes exercise the identical code paths.
from test_chaos import FakeEngine, FakeReq, TickClock, _sched, drive
from test_cluster import FakeReplicaClient, PROMPTS, _drive, _mk_router
from test_train_chaos import FakeTrainer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class StepClock:
    """Fake clock that advances by a fixed step on every read — makes
    span begin/end timestamps bit-deterministic."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        t, self.t = self.t, self.t + self.step
        return t


# ---------------------------------------------------------------------------
# obs.clock: the injectable-clock discipline
# ---------------------------------------------------------------------------


def test_resolve_clock_defaults_to_perf_clock():
    assert resolve_clock(None) is perf_clock
    tick = TickClock()
    assert resolve_clock(tick) is tick


def test_clock_audit_serve_train_never_read_time_directly():
    """Grep-enforced: no module under src/repro/serve or src/repro/train
    (or obs itself, outside obs/clock.py) calls time.time/perf_counter/
    monotonic or even imports time — all time reads must flow through the
    injectable clock so chaos tests stay tick-deterministic."""
    roots = [os.path.join(SRC, "repro", d) for d in ("serve", "train", "obs")]
    whitelist = {os.path.join(SRC, "repro", "obs", "clock.py")}
    needles = ("import time", "time.time(", "time.perf_counter",
               "time.monotonic")
    offenders = []
    for root in roots:
        for dirpath, _, files in os.walk(root):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                if path in whitelist:
                    continue
                with open(path) as f:
                    src = f.read()
                for needle in needles:
                    if needle in src:
                        offenders.append((os.path.relpath(path, SRC), needle))
    assert not offenders, (
        f"direct wall-time reads outside obs/clock.py: {offenders}"
    )


# ---------------------------------------------------------------------------
# obs.trace: recorder semantics
# ---------------------------------------------------------------------------


def test_span_nesting_deterministic_on_fake_clock():
    """Nested sync spans on a step clock produce exact, repeatable
    timestamps: inner closes first (LIFO), outer's duration covers it."""
    def build():
        rec = TraceRecorder(clock=StepClock())
        with rec.span("outer", step=1):
            with rec.span("inner"):
                pass
        return list(rec.events)

    evs = build()
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    # StepClock reads: outer t0=0, inner t0=1, inner end=2, outer end=3.
    assert (inner["t"], inner["dur"]) == (1.0, 1.0)
    assert (outer["t"], outer["dur"]) == (0.0, 3.0)
    assert outer["args"] == {"step": 1}
    assert build() == evs  # bit-deterministic across runs


def test_ring_eviction_never_corrupts_open_spans():
    """Flooding the ring past maxlen while a span is open evicts completed
    events (counted in .dropped) but the open span still closes intact."""
    rec = TraceRecorder(clock=StepClock(), maxlen=4)
    with rec.span("long_lived"):
        for i in range(10):
            rec.instant("flood", i=i)
        # mid-flight: the open span exports as an unclosed "B" event
        doc = rec.to_chrome()
        assert [e for e in doc["traceEvents"] if e["ph"] == "B"]
    # 10 instants through a 4-slot ring drop 6; the span's own completion
    # event displaces a 7th — but the span itself survives (it lived on
    # the open stack, not in the ring, until it closed).
    assert rec.dropped == 7
    names = [e["name"] for e in rec.events]
    assert "long_lived" in names, "open span lost to ring eviction"
    assert not rec._open


def test_async_spans_namespaced_ids():
    """ns() hands each component a distinct namespace so engine-local uid
    counters cannot collide across replicas."""
    rec = TraceRecorder(clock=StepClock())
    ns_a, ns_b = rec.ns(), rec.ns()
    assert ns_a != ns_b
    rec.begin("request", f"{ns_a}:0", uid=0)
    rec.begin("request", f"{ns_b}:0", uid=0)
    rec.end("request", f"{ns_a}:0", status="done")
    rec.end("request", f"{ns_b}:0", status="failed")
    ids = [e["id"] for e in rec.events]
    assert len(set(ids)) == 2
    assert validate_chrome_trace(rec.to_chrome()) == []


def test_chrome_export_round_trips_and_validates(tmp_path):
    """save() → json.load → structural validator: every event taxonomy
    (sync X, async b/e, instant i, still-open B) conforms."""
    rec = TraceRecorder(clock=StepClock())
    ns = rec.ns()
    rec.begin("request", f"{ns}:7", uid=7)
    with rec.span("prefill", uid=7):
        rec.instant("first_token", uid=7)
    rec.end("request", f"{ns}:7", uid=7, status="done")
    open_span = rec.span("decode")
    open_span.__enter__()  # deliberately left open
    path = tmp_path / "trace.json"
    rec.save(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == []
    phases = sorted(e["ph"] for e in doc["traceEvents"])
    assert phases == ["B", "X", "b", "e", "i"]
    ts_units = {e["name"]: e["ts"] for e in doc["traceEvents"]}
    assert ts_units["prefill"] == 1e6  # seconds → microseconds
    assert doc["otherData"]["dropped_events"] == 0
    open_span.__exit__(None, None, None)


def test_trace_validator_rejects_malformed_docs():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{}]}) != []
    # end without a begin for the same (name, id)
    orphan = {"traceEvents": [
        {"name": "r", "ph": "e", "ts": 0, "pid": 0, "tid": 0,
         "id": "1:1", "cat": "async"},
    ]}
    assert any("end without begin" in p for p in validate_chrome_trace(orphan))
    # complete event lacking dur
    no_dur = {"traceEvents": [
        {"name": "s", "ph": "X", "ts": 0, "pid": 0, "tid": 0},
    ]}
    assert any("dur" in p for p in validate_chrome_trace(no_dur))


def test_global_recorder_install_and_scoping():
    assert get_recorder() is NULL_RECORDER
    rec = TraceRecorder(clock=StepClock())
    with use_recorder(rec):
        assert get_recorder() is rec
        with use_recorder(None):
            assert get_recorder() is NULL_RECORDER
        assert get_recorder() is rec
    assert get_recorder() is NULL_RECORDER
    set_recorder(rec)
    try:
        assert get_recorder() is rec
    finally:
        set_recorder(None)
    assert get_recorder() is NULL_RECORDER


def test_null_recorder_is_inert():
    n = NullRecorder()
    assert n.enabled is False and NULL_RECORDER.enabled is False
    with n.span("anything", big=list(range(10))):
        n.begin("r", "1:1", uid=1)
        n.end("r", "1:1", uid=1)
        n.instant("x")
    assert n.to_chrome()["traceEvents"] == []


def test_null_recorder_overhead_unmeasurable():
    """Acceptance: tracing disabled by default at zero measurable
    overhead.  The disabled path (one method call returning a shared
    no-op context manager) must stay within a generous per-call budget —
    catches anyone adding allocation or formatting to the hot path."""
    n = NULL_RECORDER
    iters = 50_000
    t0 = time.perf_counter()
    for i in range(iters):
        with n.span("decode", n_active=4):
            pass
        n.instant("tick", i=i)
    per_call_us = (time.perf_counter() - t0) / (2 * iters) * 1e6
    assert per_call_us < 25.0, (
        f"NullRecorder costs {per_call_us:.2f}us/call — no longer free"
    )


def _host_events(trace_dir):
    """Host events of the one ``.xplane.pb`` written under ``trace_dir``,
    by name."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.glob("**/*.xplane.pb")
    return {e.name: e for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_spans_and_instants_reach_the_profiler_with_their_args(tmp_path):
    """Under an active ``jax.profiler`` trace a recorder's spans and
    instants come back as host events of the same names, with their args
    and the spans' nesting, while the ring buffer records as before."""
    import jax

    rec = TraceRecorder(clock=StepClock())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("serve.tick"):
            with rec.span("serve.prefill_chunk", uid=3, start=256, n=128):
                pass
            rec.instant("compile", fun_name="jit_step", seconds=0.5)
    finally:
        jax.profiler.stop_trace()
    host = _host_events(tmp_path)
    tick, chunk, comp = (host["serve.tick"], host["serve.prefill_chunk"],
                         host["compile"])
    assert dict(chunk.stats) == {"uid": 3, "start": 256, "n": 128}
    assert dict(comp.stats) == {"fun_name": "jit_step", "seconds": 0.5}
    assert tick.start_ns <= chunk.start_ns
    assert chunk.start_ns + chunk.duration_ns <= comp.start_ns
    assert comp.start_ns + comp.duration_ns <= tick.start_ns + tick.duration_ns
    assert [e["name"] for e in rec.events] == [
        "serve.prefill_chunk", "compile", "serve.tick"]
    assert rec.events[0]["args"] == {"uid": 3, "start": 256, "n": 128}


def test_importing_obs_does_not_import_jax():
    """The recorder imports ``jax`` on its first span, not on import."""
    import subprocess
    import sys

    code = "import sys, repro.obs; sys.exit('jax' in sys.modules)"
    env = dict(os.environ, PYTHONPATH=SRC)
    assert subprocess.run([sys.executable, "-c", code], env=env).returncode == 0


def test_compile_instants_on_the_installed_recorder_only():
    """Jitting a new shape under an installed recorder records exactly one
    ``compile`` instant naming the function; under the NullRecorder the
    same listener records nowhere."""
    import jax
    import numpy as np

    def double_plus_one(x):
        return x * 2 + 1

    rec = TraceRecorder()
    with use_recorder(rec):
        np.asarray(jax.jit(double_plus_one)(np.zeros((3, 17), np.float32)))
    compiles = [e for e in rec.events if e["name"] == "compile"]
    assert len(compiles) == 1, compiles
    assert compiles[0]["ph"] == "i"
    assert compiles[0]["args"]["fun_name"] == "jit(double_plus_one)"
    assert compiles[0]["args"]["seconds"] >= 0
    assert get_recorder() is NULL_RECORDER
    n = len(rec.events)
    np.asarray(jax.jit(double_plus_one)(np.zeros((5, 19), np.float32)))
    assert len(rec.events) == n


# ---------------------------------------------------------------------------
# obs.metrics: registry semantics + Prometheus/JSON export
# ---------------------------------------------------------------------------


def test_registry_type_and_name_validation():
    reg = MetricsRegistry()
    reg.counter("requests", "total requests")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("requests")
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("c").inc(-1)
    with pytest.raises(ValueError, match="ascend"):
        reg.histogram("h", buckets=(2.0, 1.0))
    # a bound schema cannot collide with an existing typed metric
    reg.counter("eng_shed")
    with pytest.raises(ValueError, match="already registered"):
        reg.bind_counters("eng", lambda: {"shed": 0})


def test_histogram_observe_and_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 5.0))
    for v in (0.5, 0.7, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [2, 1, 1]
    assert h.cumulative() == [2, 3, 4]
    assert h.count == 4 and h.sum == pytest.approx(104.2)


def test_prometheus_text_golden():
    """Exact text exposition: HELP/TYPE lines, cumulative buckets with a
    +Inf terminal, _sum/_count — byte-for-byte."""
    reg = MetricsRegistry()
    reg.bind_counters("eng", lambda: {"shed": 3}, help="frozen")
    reg.counter("rows", "rows emitted").inc(2)
    reg.gauge("depth", "queue depth").set(1.5)
    h = reg.histogram("ttft_s", "time to first token", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(7.0)
    assert reg.to_prometheus() == (
        "# HELP eng_shed frozen\n"
        "# TYPE eng_shed counter\n"
        "eng_shed 3\n"
        "# HELP rows rows emitted\n"
        "# TYPE rows counter\n"
        "rows 2\n"
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 1.5\n"
        "# HELP ttft_s time to first token\n"
        "# TYPE ttft_s histogram\n"
        'ttft_s_bucket{le="0.5"} 1\n'
        'ttft_s_bucket{le="2"} 1\n'
        'ttft_s_bucket{le="+Inf"} 2\n'
        "ttft_s_sum 7.1\n"
        "ttft_s_count 2\n"
    )


def test_snapshot_schema_validates_and_pulls_live():
    reg = MetricsRegistry()
    source = {"shed": 0}
    reg.bind_counters("eng", lambda: dict(source))
    reg.histogram("lat", buckets=(1.0,)).observe(0.2)
    source["shed"] = 5  # bound counters re-pull at export time
    snap = reg.snapshot()
    assert validate_metrics_snapshot(snap) == []
    assert snap["counters"]["eng_shed"] == 5.0
    assert validate_metrics_snapshot({"schema": 2}) != []
    bad = reg.snapshot()
    bad["histograms"]["lat"]["count"] = 99
    assert any("count != sum" in p for p in validate_metrics_snapshot(bad))


# ---------------------------------------------------------------------------
# Frozen-schema consistency: registries over engines / router / trainer
# ---------------------------------------------------------------------------


def _counter_names(reg, prefix):
    return [n for n, _, _ in reg._bound_samples() if n.startswith(prefix)]


def test_scheduler_registry_every_frozen_key_exactly_once():
    """serving_registry over the paged scheduler path: every
    lifecycle.COUNTER_KEYS key appears exactly once, valued verbatim from
    counters_snapshot() — and a second bind of the same schema raises."""
    eng = FakeEngine()
    sched = _sched(eng, max_waiting=2)
    for r in [FakeReq(uid) for uid in range(4)]:
        sched.submit(r)
    drive(sched, eng)

    class _Surface:  # scheduler + the gauges serving_registry expects
        counters_snapshot = sched.counters_snapshot
        metrics = sched.metrics

        @staticmethod
        def queue_depth():
            return len(sched.waiting)

        @staticmethod
        def degrade_level():
            return 0

    reg = serving_registry(_Surface)
    names = _counter_names(reg, "serve_")
    assert sorted(names) == sorted(f"serve_{k}" for k in COUNTER_KEYS)
    assert len(names) == len(set(names)), "a frozen key bound twice"
    snap = reg.snapshot()
    counters = sched.counters_snapshot()
    for k in COUNTER_KEYS:
        assert snap["counters"][f"serve_{k}"] == float(counters[k])
    assert snap["counters"]["serve_shed"] == 2.0
    with pytest.raises(ValueError, match="already registered"):
        reg.bind_counters("serve", sched.counters_snapshot)


def test_router_registry_every_frozen_key_exactly_once():
    router, _ = _mk_router(n=2)
    for p in PROMPTS[:4]:
        router.add_request(p, max_new_tokens=3)
    _drive(router)
    reg = router_registry(router)
    rnames = _counter_names(reg, "router_")
    cnames = _counter_names(reg, "cluster_")
    assert sorted(rnames) == sorted(f"router_{k}" for k in ROUTER_COUNTER_KEYS)
    assert sorted(cnames) == sorted(f"cluster_{k}" for k in COUNTER_KEYS)
    snap = reg.snapshot()
    assert validate_metrics_snapshot(snap) == []
    for k, v in router.counters_snapshot().items():
        assert snap["counters"][f"router_{k}"] == float(v)
    for k, v in router.cluster_counters().items():
        assert snap["counters"][f"cluster_{k}"] == float(v)
    assert snap["counters"]["router_routed"] == 4.0
    # completed requests landed in the TTFT histogram
    assert snap["histograms"]["cluster_ttft_s"]["count"] == 4


class SnapFakeTrainer(FakeTrainer):
    """FakeTrainer + the counters_snapshot surface the real Trainer has
    (the supervisor provides its own when it wraps one)."""

    def counters_snapshot(self):
        from repro.train.elastic import counters_view

        return counters_view(self.counters)


def test_train_registry_every_frozen_key_exactly_once():
    ft = SnapFakeTrainer()
    ft.counters["nan_skips"] = 2
    for _ in range(3):
        ft.step_once()
    ft.history[-1]["sec"] = 0.1
    reg = train_registry(ft)
    names = _counter_names(reg, "train_")
    assert sorted(names) == sorted(f"train_{k}" for k in TRAIN_COUNTER_KEYS)
    snap = reg.snapshot()
    assert validate_metrics_snapshot(snap) == []
    for k, v in ft.counters_snapshot().items():
        assert snap["counters"][f"train_{k}"] == float(v)
    assert snap["gauges"]["train_step"] == 3
    assert snap["histograms"]["train_step_time_s"]["count"] == 1


def test_train_registry_over_supervisor_merges_counters():
    from repro.train.supervisor import TrainSupervisor

    ft = FakeTrainer()
    sup = TrainSupervisor(ft, num_workers=2)
    sup.run(4)
    reg = train_registry(sup)
    snap = reg.snapshot()
    for k, v in sup.counters_snapshot().items():
        assert snap["counters"][f"train_{k}"] == float(v)
    assert snap["gauges"]["train_step"] == 4  # gauge reads the inner trainer


# ---------------------------------------------------------------------------
# Acceptance: trace ↔ metrics bit-consistency through the serve stack
# ---------------------------------------------------------------------------


def _request_ends(rec, name="request"):
    """Terminal async end-events from a recorder, keyed by uid, after a
    JSON round-trip (what a trace consumer actually reads)."""
    doc = json.loads(json.dumps(rec.to_chrome()))
    return {
        e["args"]["uid"]: e["args"]
        for e in doc["traceEvents"]
        if e["ph"] == "e" and e["name"] == name
    }


def test_scheduler_trace_reconstructs_metrics_rows_bit_exact():
    """A chaos-style run with tracing on: every request's lifecycle span
    closes with args equal to its metrics() row — terminal status and
    per-phase timing reconstruct from the trace alone, bit-consistently
    (both built by the same _metric_row builder)."""
    clock = TickClock()
    eng = FakeEngine()
    rec = TraceRecorder(clock=clock)
    with use_recorder(rec):
        sched = _sched(eng, max_waiting=3, clock=clock)
    reqs = [FakeReq(uid, deadline_e2e=100) for uid in range(5)]
    for r in reqs:
        sched.submit(r)
    drive(sched, eng, clock=clock)
    rows = {m["uid"]: m for m in sched.metrics()}
    ends = _request_ends(rec)
    assert set(ends) == set(rows) == set(range(5))
    for uid, row in rows.items():
        assert ends[uid] == json.loads(json.dumps(row)), (
            f"trace end-args diverge from metrics() for uid {uid}"
        )
        assert set(row) == set(METRIC_KEYS)
    # the shed requests (bounded queue of 3) are terminal in the trace too
    shed = [u for u, r in rows.items() if r["status"] == lifecycle.REJECTED]
    assert len(shed) == 2
    # every request span opened exactly once and closed exactly once
    doc = rec.to_chrome()
    begins = [e for e in doc["traceEvents"]
              if e["ph"] == "b" and e["name"] == "request"]
    assert len(begins) == 5
    assert validate_chrome_trace(doc) == []


def test_cluster_trace_reconstructs_metrics_rows_bit_exact():
    """Same acceptance at the cluster tier: crequest end-events equal the
    router's metrics() rows (which add rid/redeliveries) bit-exactly."""
    clock = TickClock()
    rec = TraceRecorder(clock=clock)
    clients = [FakeReplicaClient() for _ in range(2)]
    from repro.serve.cluster import ClusterRouter

    router = ClusterRouter(clients, clock=clock, trace=rec)
    for p in PROMPTS[:5]:
        router.add_request(p, max_new_tokens=3)
    _drive(router, clock=clock)
    rows = {m["uid"]: m for m in router.metrics()}
    ends = _request_ends(rec, name="crequest")
    assert set(ends) == set(rows)
    for uid, row in rows.items():
        assert ends[uid] == json.loads(json.dumps(row))
        assert {"rid", "redeliveries"} <= set(row)
    assert validate_chrome_trace(rec.to_chrome()) == []


def test_trainer_step_spans_on_fake_trainer_clock():
    """Trainer-side spans: supervisor remesh instants ride the recorder
    the supervisor was constructed with."""
    from repro.faults import FaultInjector, FaultSpec
    from repro.train.supervisor import TrainSupervisor

    rec = TraceRecorder(clock=StepClock())
    inj = FaultInjector([FaultSpec("worker_loss", uid=1, after=3, times=-1)])
    sup = TrainSupervisor(FakeTrainer(), num_workers=3, max_missed=2,
                          faults=inj, trace=rec)
    sup.run(8)
    names = [e["name"] for e in rec.events]
    assert "worker_loss" in names and "remesh" in names
    assert validate_chrome_trace(rec.to_chrome()) == []


# ---------------------------------------------------------------------------
# obs.utilization: measured-vs-roofline columns
# ---------------------------------------------------------------------------


def test_roofline_lower_bound_is_max_of_compute_and_memory():
    # compute-bound: flops term dominates
    assert roofline_lower_bound_s(1e12, 1.0, peak_flops=1e12, hbm_bw=1e12) \
        == pytest.approx(1.0)
    # memory-bound: bytes term dominates
    assert roofline_lower_bound_s(1.0, 1e12, peak_flops=1e12, hbm_bw=1e12) \
        == pytest.approx(1.0)


def test_achieved_fraction_bounds_and_validation():
    lb = roofline_lower_bound_s(2e12, 1.0, peak_flops=1e12, hbm_bw=1e12)
    assert achieved_fraction(lb, 2e12, 1.0, peak_flops=1e12, hbm_bw=1e12) \
        == pytest.approx(1.0)  # measured == bound → util 1.0
    assert achieved_fraction(2 * lb, 2e12, 1.0, peak_flops=1e12,
                             hbm_bw=1e12) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        achieved_fraction(0.0, 1.0, 1.0)


def test_utilization_columns_from_cost_model():
    from repro.roofline.analysis import decode_attention_cost

    cost = decode_attention_cost(4, 8, 2, 64, 512, 64, block_k=64)
    cols = utilization_columns(cost, 1000.0)  # 1ms measured
    assert set(cols) == {"roofline_flops", "roofline_hbm_bytes",
                        "roofline_lower_bound_us", "roofline_util"}
    assert 0.0 < cols["roofline_util"] <= 1.0
    assert cols["roofline_lower_bound_us"] < 1000.0


# ---------------------------------------------------------------------------
# Regress gate: tolerance bands + per-backend keying (benchmarks/regress.py)
# ---------------------------------------------------------------------------


def _regress():
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import regress

    return regress


def test_regress_ceiling_band_and_backend_keying():
    regress = _regress()
    records = [
        {"live_length": 64, "roofline_util": 0.5, "backend": "cpu"},
        {"live_length": 64, "roofline_util": 2.0, "backend": "tpu"},
    ]
    band = regress.Bound(path="B.json", kind=None, metric="roofline_util",
                         floor=1e-9, ceiling=1.0,
                         match=(("live_length", 64),), backend="cpu")
    assert regress.check_bound(records, band) == []
    # the impossible tpu row is invisible to the cpu-keyed bound...
    tpu = regress.Bound(path="B.json", kind=None, metric="roofline_util",
                        floor=1e-9, ceiling=1.0,
                        match=(("live_length", 64),), backend="tpu")
    (msg,) = regress.check_bound(records, tpu)
    assert "> ceiling 1.000" in msg
    # ...and a selector with no matching backend reports it
    gpu = regress.Bound(path="B.json", kind=None, metric="roofline_util",
                        floor=0.0, backend="gpu")
    (msg,) = regress.check_bound(records, gpu)
    assert "no kind=None record" in msg and "'backend': 'gpu'" in msg


def test_regress_kind_none_matches_unkinded_rows():
    regress = _regress()
    records = [{"devices": 8, "hops": 36, "backend": "cpu"},
               {"kind": "summary", "ratio": 2.0, "backend": "cpu"}]
    b = regress.Bound(path="B.json", kind=None, metric="hops",
                      floor=8.0, ceiling=36.0, match=(("devices", 8),),
                      backend="cpu")
    assert regress.check_bound(records, b) == []
    # schema-stamp style bound: kind=None + empty match covers every row
    stamp = regress.Bound(path="B.json", kind=None, metric="hops", floor=1.0)
    (msg,) = regress.check_bound(records, stamp)
    assert "lacks" in msg  # the summary row has no hops field


def test_regress_committed_bounds_include_utilization_band():
    """At least one committed bound is a per-backend utilization band on
    BENCH_decode.json (floor > 0, ceiling ≤ 1) — the acceptance that the
    regress gate now bounds measured-vs-roofline achieved fraction."""
    regress = _regress()
    util = [b for b in regress.BOUNDS
            if b.metric == "roofline_util" and b.path == "BENCH_decode.json"]
    assert util, "no utilization bound committed"
    for b in util:
        assert b.floor > 0 and b.ceiling is not None and b.ceiling <= 1.0
        assert b.backend == "cpu"
    files = {b.path for b in regress.BOUNDS}
    assert files == {
        "BENCH_attention_bwd.json", "BENCH_autotune.json",
        "BENCH_cluster.json", "BENCH_decode.json", "BENCH_mesh.json",
        "BENCH_ring.json", "BENCH_serving.json", "BENCH_train_chaos.json",
    }, "regress gate must cover every committed BENCH family"
