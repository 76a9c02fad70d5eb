"""The reduction from a profiler trace to busy time, steps and kernels."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "paged_steps.xplane.pb"

KERN_DECODE = "%paged_decode_splitk.1 = (f32[4,4,2,8,64]{4,3,2,1,0}, f32[4,4,2,1,8]) custom-call()"
KERN_CHUNK = "%paged_decode_splitk.2 = (f32[1,4,2,32,64]{4,3,2,1,0}, f32[1,4,2,1,32]) custom-call()"


def synthetic():
    """Window 0–100 ns.  A decode step 10–30 holding a loop 10–30 that
    encloses a fusion 12–18 and the kernel 20–28; a chunk step 40–70 with
    its kernel 45–65; a stray op 95–110 cut by the window's end."""
    ops = [(10, 30, "%while.1 = (f32[2]) while()"),
           (12, 18, "%fusion.3 = f32[4] fusion()"),
           (20, 28, KERN_DECODE),
           (45, 65, KERN_CHUNK),
           (95, 110, "%copy.7 = f32[4] copy()")]
    modules = [(10, 30, "jit_paged_step(1)"), (40, 70, "jit_paged_step(2)"),
               (95, 110, "jit_argmax(3)")]
    host = [(0, 100, tr.WINDOW_SPAN), (5, 35, "bench.decode_step"),
            (35, 80, "bench.prefill_chunk"), (80, 100, "bench.wait_for_arrival")]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": sorted(host)}


def test_busy_is_the_union_clipped_to_the_window():
    out = tr.reduce(synthetic(), step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32)
    # 10–30, 45–65 and 95–100: 45 ns of 100
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(45e-9)


def test_steps_are_classed_by_their_kernel_rows():
    out = tr.reduce(synthetic(), step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32)
    assert out["steps"]["decode"] == {"count": 1, "device_s": pytest.approx(20e-9),
                                      "kernel_s": pytest.approx(8e-9)}
    assert out["steps"]["chunk"] == {"count": 1, "device_s": pytest.approx(30e-9),
                                     "kernel_s": pytest.approx(20e-9)}


def test_device_ops_count_self_time():
    out = tr.reduce(synthetic(), step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32)
    ops = dict(out["device_ops"])
    assert ops["decode/while"] == pytest.approx(6e-9)  # 20 minus 6 and 8 nested
    assert ops["decode/fusion"] == pytest.approx(6e-9)
    assert ops["chunk/paged_decode_splitk"] == pytest.approx(20e-9)
    assert ops["jit_argmax/copy"] == pytest.approx(5e-9)
    assert sum(ops.values()) == pytest.approx(45e-9)
    assert out["op_s"] == ops


def test_op_s_keeps_every_op_past_the_top():
    """``device_ops`` is cut to ``top``; ``op_s`` keeps every op, so a
    kernel's time is there whatever its rank."""
    out = tr.reduce(synthetic(), step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32, top=2)
    assert [k for k, _ in out["device_ops"]] == ["chunk/paged_decode_splitk",
                                                 "decode/paged_decode_splitk"]
    assert set(out["op_s"]) == {"decode/while", "decode/fusion",
                                "decode/paged_decode_splitk",
                                "chunk/paged_decode_splitk", "jit_argmax/copy"}
    assert out["op_s"]["decode/while"] == pytest.approx(6e-9)


def test_idle_gaps_are_named_by_the_open_host_span():
    out = tr.reduce(synthetic(), step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32)
    gaps = dict(out["idle_gaps"])
    # Gaps 0–10, 30–45 and 65–95 have midpoints 5, 37.5 and 80.
    assert gaps == {"bench.decode_step": pytest.approx(10e-9),
                    "bench.prefill_chunk": pytest.approx(15e-9),
                    "bench.wait_for_arrival": pytest.approx(30e-9)}


def test_no_window_span_reduces_to_nothing():
    ev = synthetic()
    ev["host"] = [h for h in ev["host"] if h[2] != tr.WINDOW_SPAN]
    assert tr.reduce(ev, step_module="jit_paged_step",
                     kernel="paged_decode_splitk", chunk_rows=32) == {}


def test_recorded_trace():
    """A trace recorded on a v5e: three ticks, each a 32-row chunk step and
    a 4-lane decode step (both jitted as ``paged_step`` around the paged
    kernel), then a 3 ms sleep in ``bench.wait_for_arrival``."""
    from jax.profiler import ProfileData

    events = tr.read(FIXTURE)
    out = tr.reduce(events, step_module="jit_paged_step",
                    kernel="paged_decode_splitk", chunk_rows=32)
    assert out["steps"]["decode"]["count"] == out["steps"]["chunk"]["count"] == 3
    # Kernel seconds are the sum of the kernel's events, read here directly.
    kern = 0
    for plane in ProfileData.from_file(str(FIXTURE)).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    kern += sum(e.duration_ns for e in line.events
                                if e.name.startswith("%paged_decode_splitk"))
    got = out["steps"]["decode"]["kernel_s"] + out["steps"]["chunk"]["kernel_s"]
    assert got == pytest.approx(kern / 1e9)
    assert 0 < out["busy_s"] < out["window_s"]
    # The sleeps take most of the idle time, and every idle second is named.
    gaps = dict(out["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench.wait_for_arrival"
    assert gaps["bench.wait_for_arrival"] > 0.009
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    # The device clock runs about a millisecond behind the host's here.
    assert 0.5e-3 < out["clock_offset_s"]["/device:TPU:0"] < 3e-3
