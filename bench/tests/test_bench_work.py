"""The llama work counts (bench/architectures/llama.py) against counts made
by hand at a tiny shape.

Shape: 2 layers, d 8, 2 query heads and 1 key/value head of 4, d_ff 16,
vocab 10, bfloat16 weights and cache.  Per layer the matmul weights are
8·(8 + 2·4) + 8·8 + 3·8·16 = 576; all weights are 2·(576·2 + 2·8·4 norm
bytes) + 8·2 final norm + 10·8·2 head = 2,608 bytes; one token's keys and
values over both layers are 2·2·1·4·2 = 32 bytes.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, manifest, work
from bench.architectures import llama

GOLDEN = json.loads((Path(__file__).resolve().parent / "data" / "golden_work.json").read_text())

S = llama.Shape(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
                vocab=10, qkv_bias=False)


def test_shape_totals():
    assert S.layer_matmul_params == 576
    assert S.weight_bytes_total == 2608
    assert S.kv_bytes_per_token == 32


def test_decode_step_counts_occupied_lanes_and_live_keys():
    w = llama.decode_step(S, [3, 5])
    # linear 2·2·576·2 + attention 2·4·2·4·(3+5) + head 2·8·10·2
    assert w.flops == 4608 + 512 + 320
    # weights + keys read (8 − 2 new)·32 + new keys 2·32 + 2 embedding rows
    # + 2 logits rows in float32
    assert w.bytes == 2608 + 192 + 64 + 32 + 80


def test_chunk_step_counts_the_causal_band_and_used_logits_only():
    # rows 4, 5, 6 see 5, 6, 7 keys: 18
    final = llama.chunk_step(S, 4, 3, True)
    assert final.flops == 2 * 2 * 576 * 3 + 2 * 4 * 2 * 4 * 18 + 2 * 8 * 10
    assert final.bytes == 2608 + 7 * 32 + 3 * 8 * 2 + 10 * 4
    middle = llama.chunk_step(S, 4, 3, False)
    assert middle.flops == final.flops - 2 * 8 * 10
    assert middle.bytes == final.bytes - 10 * 8 * 2 - 10 * 4


def test_kernel_counts():
    d = llama.decode_kernel(S, [3, 5])
    assert (d.flops, d.bytes) == (512, 8 * 32 + 2 * 2 * 2 * 2 * 4 * 2)
    c = llama.chunk_kernel(S, 4, 3)
    assert (c.flops, c.bytes) == (1152, 7 * 32 + 2 * 3 * 2 * 2 * 4 * 2)


def test_least_time_names_the_binding_term():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.Work(1000.0, 50.0).seconds(peaks) == (10.0, "flops")
    assert work.Work(100.0, 50.0).seconds(peaks) == (5.0, "bytes")


def test_each_lane_adds_only_its_own_live_keys():
    one, two = llama.decode_kernel(S, [3]), llama.decode_kernel(S, [3, 3])
    assert (two.flops, two.bytes) == (2 * one.flops, 2 * one.bytes)
    # A second lane adds its token's matmuls, attention and logits row; the
    # weights are read once per step whatever the lanes.
    step1, step2 = llama.decode_step(S, [3]), llama.decode_step(S, [3, 3])
    assert step2.flops == 2 * step1.flops
    assert step2.bytes - step1.bytes == step1.bytes - S.weight_bytes_total


def log_calls(calls):
    """``CallLog`` around a fake engine, fed ``calls`` through the wrapped
    step primitives as the engine would make them."""
    eng = SimpleNamespace(decode_tick=lambda running: None,
                          prefill_chunk_run=lambda entry, n: None)
    log = harness.CallLog(eng)
    log.on = True
    for c in calls:
        if c["kind"] == "decode":
            eng.decode_tick({i: SimpleNamespace(length=n - 1)
                             for i, n in enumerate(c["lengths"])})
        else:
            size = c["start"] + c["n"] + (0 if c["final"] else 1)
            eng.prefill_chunk_run(SimpleNamespace(prompt_done=c["start"],
                                                  req=SimpleNamespace(prompt=[1] * size)),
                                  c["n"])
    assert log.calls == calls
    return log


@pytest.mark.parametrize("name", sorted(GOLDEN["configs"]))
def test_llama_reproduces_the_golden_counts(name):
    """The counts at both committed configurations, call by call and summed
    by ``CallLog``, equal those the harness gave when it counted every
    configuration as llama (``data/golden_work.json``)."""
    conf = manifest.read_config(manifest.load(), name)
    want = GOLDEN["configs"][name]
    arch = manifest.architecture(conf)
    assert arch is llama and arch.kv_bytes_per_token(conf) == want["kv_bytes_per_token"]
    for call, counts in zip(GOLDEN["calls"], want["per_call"], strict=True):
        got = {k: vars(f(conf, call)) for k, f in llama.WORK.items()
               if f(conf, call) is not None}
        assert got == counts, call
    assert log_calls(GOLDEN["calls"]).work(conf) == want["call_log"]
