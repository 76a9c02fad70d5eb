"""The request generator: determinism from the seed and length statistics."""
import itertools
from collections import Counter

import numpy as np
import pytest

from bench import traffic

SECONDS = 8.0  # one set of the open loop: 16 requests at 2/s
MIX = {"loop": "open", "rate_per_s": 2.0,
       "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.9, "min": 64, "max": 2048},
       "output": {"dist": "uniform", "min": 16, "max": 512},
       "engine": {"max_batch": 4, "max_len": 2688, "prefill_chunk": 256}}


def take(seed, n, mix=MIX):
    return list(itertools.islice(traffic.stream(mix, seed, 1000, SECONDS), n))


def test_same_seed_same_requests():
    assert take(7, 40) == take(7, 40)


def test_seeds_past_32_bits():
    a, b = take(2**31 + 11, 20), take(2**31 + 12, 20)
    assert a != b and a == take(2**31 + 11, 20)


def test_every_block_holds_the_same_sizes_in_another_order():
    a, b = take(1, 64), take(2, 64)
    assert traffic.set_size(MIX, SECONDS) == 16
    for blk in range(4):
        sa, sb = a[16 * blk:16 * blk + 16], b[16 * blk:16 * blk + 16]
        assert Counter(len(r.prompt) for r in sa) == Counter(len(r.prompt) for r in sb)
        assert Counter(r.max_new_tokens for r in sa) == Counter(r.max_new_tokens for r in sb)
        # Each set spans one window exactly: the same number of arrivals.
        assert b[16 * blk].due_s == pytest.approx(SECONDS * blk)
    assert [len(r.prompt) for r in a[:16]] != [len(r.prompt) for r in b[:16]]
    # The seed orders the gaps too: arrivals differ, the set of gaps does not.
    assert [r.due_s for r in a] != [r.due_s for r in b]
    gaps = [np.diff([r.due_s for r in x[:17]]) for x in (a, b)]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))


def test_length_statistics():
    lens = np.array([len(r.prompt) for r in take(3, 16)])
    assert lens.min() >= 64 and lens.max() <= 2048
    assert np.median(lens) == pytest.approx(384, rel=0.1)
    outs = np.array([r.max_new_tokens for r in take(3, 16)])
    assert outs.min() >= 16 and outs.max() <= 512
    assert outs.mean() == pytest.approx((16 + 512) / 2, rel=0.05)


def test_open_loop_rate():
    reqs = take(4, 161)
    # 10 sets of 16 gaps at 2/s, each spanning 8 s.
    assert reqs[-1].due_s == pytest.approx(80.0)
    assert all(b.due_s >= a.due_s for a, b in zip(reqs, reqs[1:]))
    gaps = np.diff([r.due_s for r in reqs[:17]])
    # Exponential gaps: bursts (gaps under a tenth of the mean) and lulls.
    assert gaps.min() < 0.05 < 1.0 < gaps.max()


def test_closed_loop_has_no_schedule():
    mix = dict(MIX, loop="closed", clients=4, set_size=16)
    assert {r.due_s for r in take(5, 32, mix)} == {0.0}


def test_token_ids_are_in_vocab():
    toks = np.concatenate([r.prompt for r in take(6, 16)])
    assert toks.min() >= 1 and toks.max() < 1000


@pytest.mark.parametrize("name", ["chat", "docs"])
def test_committed_mixes_are_valid(name):
    mix = traffic.load_mix(name)
    assert take(1, 4, mix)


def test_a_mix_whose_requests_overflow_the_table_is_refused():
    bad = dict(MIX, engine=dict(MIX["engine"], max_len=2048))
    with pytest.raises(ValueError):
        traffic.validate_mix(bad)


def test_the_preroll_is_a_set_of_its_own():
    mix = dict(MIX, preroll_s=3.0)  # 6 requests at 2/s, then sets of 16
    a, b = take(1, 22, mix), take(2, 22, mix)
    for x in (a, b):
        assert x[6].due_s == pytest.approx(3.0) and x[21].due_s < 3.0 + SECONDS
    assert Counter(len(r.prompt) for r in a[:6]) == Counter(len(r.prompt) for r in b[:6])
    assert Counter(r.max_new_tokens for r in a[:6]) == Counter(r.max_new_tokens for r in b[:6])
    assert [len(r.prompt) for r in a[:6]] != [len(r.prompt) for r in b[:6]]
    # The window's set that follows is the same set as without a pre-roll.
    assert Counter(len(r.prompt) for r in a[6:22]) == Counter(len(r.prompt) for r in take(1, 16))
