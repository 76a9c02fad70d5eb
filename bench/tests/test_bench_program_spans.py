"""Device idle charged to the program's spans (bench/program_spans.py), and
the trace reduction it sits beside left as it was."""
import gzip
import json
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
SPANS_FIXTURE = DATA / "serve_spans.xplane.pb.gz"
NAMES = ("decode_host_idle_ms", "chunk_host_idle_ms", "sched_host_idle_ms",
         "compiles_in_window")


def synthetic():
    """Window 0–100 ns; device busy 0–6, 12–28, 48–52 and 62–80, so idle
    6–12, 28–48, 52–62 and 80–100.  A tick (``bench.tick`` around
    ``serve.tick``) admits a chunk and a first token, then runs a decode
    step whose engine spans sit inside the harness's ``bench.decode_step``;
    ``bench.poll`` follows, then nothing."""
    ops = [(0, 6, "%fusion.1 = f32[4] fusion()"), (12, 28, "%fusion.2 = f32[4] fusion()"),
           (48, 52, "%copy.3 = f32[4] copy()"), (62, 80, "%fusion.4 = f32[4] fusion()")]
    host = [(0, 100, tr.WINDOW_SPAN), (0, 58, "bench.tick"), (1, 57, "serve.tick"),
            (2, 20, "serve.admit"), (3, 10, "serve.prefill_chunk"),
            (10, 14, "serve.first_token"), (22, 56, "serve.decode_step"),
            (23, 55, "bench.decode_step"), (24, 27, "serve.decode.inputs"),
            (27, 30, "serve.decode.launch"), (30, 44, "serve.decode.health"),
            (44, 54, "serve.decode.sample"), (58, 66, "bench.poll"),
            (70, 70, "compile")]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": sorted(host)}


def test_idle_is_charged_by_exact_overlap_to_the_innermost_span():
    out = ps.reduce(synthetic(), step_module="jit_paged_step")
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["idle_s"] == pytest.approx(56e-9)
    # The gap 52–62 is split across six spans, where its midpoint (57)
    # would give it all to one; the engine's spans are charged ahead of
    # the harness's span around them.
    want = {"serve.prefill_chunk": 4, "serve.first_token": 2,
            "serve.decode.launch": 2, "serve.decode.health": 14,
            "serve.decode.sample": 6, "bench.decode_step": 1,
            "serve.decode_step": 1, "serve.tick": 1, "bench.tick": 1,
            "bench.poll": 4, ps.NO_SPAN: 20}
    assert out["by_span"] == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(out["by_span"].values()) == pytest.approx(out["idle_s"])
    paths = {tuple(p): s for p, s in out["paths"]}
    assert paths[("bench.tick", "serve.tick", "serve.decode_step", "bench.decode_step",
                  "serve.decode.health")] == pytest.approx(14e-9)
    assert out["spans"] == {"serve.tick": 1, "serve.decode_step": 1,
                            "serve.prefill_chunk": 1}


def test_readings_read_only_idle_charged_to_serve_spans():
    out = ps.metrics(ps.reduce(synthetic(), step_module="jit_paged_step"), compiles=0)
    # Decode: launch 2 + health 14 + sample 6 + the step itself 1; the
    # 1 ns left to bench.decode_step is no engine span's.
    assert out == {"decode_host_idle_ms": pytest.approx(23e-6),
                   "chunk_host_idle_ms": pytest.approx(6e-6),
                   "sched_host_idle_ms": pytest.approx(1e-6),
                   "compiles_in_window": 0}


def test_a_span_open_alone_or_none_open():
    gaps = [(0, 10), (20, 30)]
    assert ps.charge(gaps, []) == {(): 20}
    assert ps.charge(gaps, [(5, 25, "serve.tick")]) == {
        (): 10, ("serve.tick",): 10}
    # Two spans that start together: the shorter is inside.
    assert ps.charge([(0, 4)], [(0, 4, "serve.tick"), (0, 2, "serve.expire")]) == {
        ("serve.tick", "serve.expire"): 2, ("serve.tick",): 2}


def test_without_program_spans_every_reading_is_none():
    ev = synthetic()
    ev["host"] = [h for h in ev["host"] if not h[2].startswith(ps.SERVE)]
    reduced = ps.reduce(ev, step_module="jit_paged_step")
    assert sum(reduced["by_span"].values()) == pytest.approx(reduced["idle_s"])
    for got in (ps.metrics(reduced), ps.metrics(reduced, compiles=3),
                ps.metrics({}), ps.metrics(None)):
        assert got == dict.fromkeys(NAMES)
    ev["host"] = [h for h in ev["host"] if h[2] != tr.WINDOW_SPAN]
    assert ps.reduce(ev, step_module="jit_paged_step") == {}


def test_compiles_are_counted_inside_the_window():
    recorded = [{"name": "compile", "ph": "i", "t": t} for t in (1.0, 2.0, 3.0)]
    recorded.append({"name": "serve.tick", "ph": "X", "t": 2.5})
    assert ps.compiles_in(recorded, 2.0, 3.0) == 1
    assert ps.compiles_in(recorded, 0.0, 9.0) == 3


def test_trace_reduce_reads_the_old_fixture_as_before():
    """The reduction of the v5e fixture of three ticks, as it read when
    the program had no spans of its own."""
    out = tr.reduce(tr.read(DATA / "paged_steps.xplane.pb"), step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32)
    want = json.loads((DATA / "paged_steps.reduced.json").read_text())
    assert json.loads(json.dumps(out)) == want


def test_recorded_program_spans(tmp_path):
    """Recorded on a v5e (gzipped): the tiny configuration
    (``bench/tests/data/tiny*.json``) served by the paged engine with a
    ``TraceRecorder`` installed, under ``harness.CallLog``: two ticks, the
    first with a one-chunk prompt's prefill and first token, both with a
    decode step of its lanes."""
    path = tmp_path / "serve_spans.xplane.pb"
    path.write_bytes(gzip.decompress(SPANS_FIXTURE.read_bytes()))
    events = ps.read(path)
    host = events["host"]
    names = {h[2] for h in host}
    engine = {"serve.decode.inputs", "serve.decode.launch", "serve.decode.health",
              "serve.decode.sample"}
    assert engine | {"serve.tick", "serve.admit", "serve.first_token", "serve.grow",
                     "serve.decode_step", "serve.emit", "serve.chunk.inputs",
                     "serve.chunk.launch"} <= names
    for inner, outer in ((engine, "serve.decode_step"),
                         ({"serve.chunk.inputs", "serve.chunk.launch"},
                          "serve.prefill_chunk")):
        spans = [h for h in host if h[2] == outer]
        for s, e, n in host:
            if n in inner:
                assert any(a <= s and e <= b for a, b, _ in spans), (s, e, n)
    out = ps.reduce(events, step_module="jit_paged_step")
    assert out["spans"] == {"serve.tick": 2, "serve.decode_step": 2,
                            "serve.prefill_chunk": 1}
    assert sum(out["by_span"].values()) == pytest.approx(out["idle_s"])
    charged = sum(out["by_span"].get(n, 0.0) for n in engine)
    assert 0 < charged <= out["idle_s"]
    # The window's idle is trace_reduce's, on the same clock.
    base = tr.reduce(tr.read(path), step_module="jit_paged_step",
                     kernel="paged_decode_splitk", chunk_rows=32)
    assert base["steps"]["decode"]["count"] == 2
    assert out["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"])
    readings = ps.metrics(out)
    assert readings["decode_host_idle_ms"] > 0 and readings["chunk_host_idle_ms"] > 0
    assert readings["compiles_in_window"] is None
