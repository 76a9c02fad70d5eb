"""bench/work.py against counts made by hand at a tiny shape.

Shape: 2 layers, d 8, 2 query heads and 1 key/value head of 4, d_ff 16,
vocab 10, bfloat16 weights and cache.  Per layer the matmul weights are
8·(8 + 2·4) + 8·8 + 3·8·16 = 576; all weights are 2·(576·2 + 2·8·4 norm
bytes) + 8·2 final norm + 10·8·2 head = 2,608 bytes; one token's keys and
values over both layers are 2·2·1·4·2 = 32 bytes.
"""
from bench import work

S = work.Shape(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
               vocab=10, qkv_bias=False)


def test_shape_totals():
    assert S.layer_matmul_params == 576
    assert S.weight_bytes_total == 2608
    assert S.kv_bytes_per_token == 32


def test_decode_step_counts_occupied_lanes_and_live_keys():
    w = work.decode_step(S, [3, 5])
    # linear 2·2·576·2 + attention 2·4·2·4·(3+5) + head 2·8·10·2
    assert w.flops == 4608 + 512 + 320
    # weights + keys read (8 − 2 new)·32 + new keys 2·32 + 2 embedding rows
    # + 2 logits rows in float32
    assert w.bytes == 2608 + 192 + 64 + 32 + 80


def test_chunk_step_counts_the_causal_band_and_used_logits_only():
    # rows 4, 5, 6 see 5, 6, 7 keys: 18
    final = work.chunk_step(S, 4, 3, True)
    assert final.flops == 2 * 2 * 576 * 3 + 2 * 4 * 2 * 4 * 18 + 2 * 8 * 10
    assert final.bytes == 2608 + 7 * 32 + 3 * 8 * 2 + 10 * 4
    middle = work.chunk_step(S, 4, 3, False)
    assert middle.flops == final.flops - 2 * 8 * 10
    assert middle.bytes == final.bytes - 10 * 8 * 2 - 10 * 4


def test_kernel_counts():
    d = work.decode_kernel(S, [3, 5])
    assert (d.flops, d.bytes) == (512, 8 * 32 + 2 * 2 * 2 * 2 * 4 * 2)
    c = work.chunk_kernel(S, 4, 3)
    assert (c.flops, c.bytes) == (1152, 7 * 32 + 2 * 3 * 2 * 2 * 4 * 2)


def test_least_time_names_the_binding_term():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.Work(1000.0, 50.0).seconds(peaks) == (10.0, "flops")
    assert work.Work(100.0, 50.0).seconds(peaks) == (5.0, "bytes")


def test_each_lane_adds_only_its_own_live_keys():
    one, two = work.decode_kernel(S, [3]), work.decode_kernel(S, [3, 3])
    assert (two.flops, two.bytes) == (2 * one.flops, 2 * one.bytes)
    # A second lane adds its token's matmuls, attention and logits row; the
    # weights are read once per step whatever the lanes.
    step1, step2 = work.decode_step(S, [3]), work.decode_step(S, [3, 3])
    assert step2.flops == 2 * step1.flops
    assert step2.bytes - step1.bytes == step1.bytes - S.weight_bytes_total
