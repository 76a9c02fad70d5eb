"""A whole run of the harness on the CPU at a tiny size, skipping only the
look for a chip: the served tokens pass the reference comparison, and a
run whose timed path alters a token where it is produced does not."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, manifest, run

DATA = Path(__file__).resolve().parent / "data"
CELL = {"name": "minicpm-2b.chat", "config": "tiny", "traffic": "tiny", "chips": 1}


@pytest.fixture
def tiny(monkeypatch):
    """The program's own reduced minicpm-2b preset, at the tiny file's
    vocabulary and depth."""
    import repro.configs as configs

    conf = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((DATA / "tiny_mix.json").read_text())
    real = configs.get_config
    monkeypatch.setattr(configs, "get_config", lambda name, reduced=False: real(
        name, reduced=True).replace(vocab=conf["vocab_size"],
                                    n_layers=conf["num_hidden_layers"]))
    return conf, mix


def run_tiny(conf, mix, seed):
    return run.run_cell(manifest.load(), CELL, seed, 1.5, False,
                        dev={"platform": "cpu"}, peaks=None, conf=conf, mix=mix)


def test_served_tokens_match_the_reference(tiny):
    out = run_tiny(*tiny, seed=2**31 + 3)
    assert out["correct"], out["checks"]
    assert out["checks"]["requests_compared"]["value"] >= 1
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_an_altered_token_is_not_correct(tiny, monkeypatch):
    from repro.serve.engine import PagedServeEngine

    decode = PagedServeEngine.decode_tick
    vocab = tiny[0]["vocab_size"]

    def altered(self, running):
        toks, ok = decode(self, running)
        return (np.asarray(toks) + 1) % vocab, ok

    monkeypatch.setattr(PagedServeEngine, "decode_tick", altered)
    out = run_tiny(*tiny, seed=5)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


def control_readings(tiny):
    conf, mix = tiny
    mix = dict(mix, check={"min_served_tokens": 120, "max_requests": 12})
    return conf["check"]["limits"], control.readings(conf, mix, 3, 3.0, True)


def test_the_fp8_control_reads_above_the_limit(tiny):
    """The control, at a size a test can hold: the program's readings stay
    under the tiny file's limits and the fp8 reference's widest gap reads
    above its limit, so the runs' own verdict fails it."""
    limits, out = control_readings(tiny)
    fp8 = out["controls"]["fp8"]
    assert out["served"]["logit_gap"] <= limits["logit_gap"] < fp8["logit_gap"], out
    assert out["correct"] and not fp8["correct"], out


def test_the_int8_control_is_not_correct(tiny):
    """The int8 reference in the program's place fails the runs' own
    verdict on its mean gap, which separates it from the program."""
    limits, out = control_readings(tiny)
    int8 = out["controls"]["int8"]
    assert (out["served"]["logit_gap_mean"] <= limits["logit_gap_mean"]
            < int8["logit_gap_mean"]), out
    assert not int8["correct"], out


GOLDEN = json.loads((DATA / "golden_compare.json").read_text())


@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_compare_reads_as_the_llama_only_harness_did(tiny, seed):
    """The reference through the configuration's architecture module gives
    the readings the harness gave when its reference was llama's alone
    (``data/golden_compare.json``): served tokens and both controls, on
    hand-made requests over the tiny file's weights. Counts are exact; gaps
    agree to 1e-6 relative, far inside any control's distance, so that the
    CPU's code generation on another host or version does not fail it."""
    from bench import harness, reference

    conf, mix = tiny
    params = harness.make_weights(harness.program_config(conf), int(seed))
    finished = GOLDEN[seed]["finished"]
    got = harness.compare(params, conf, finished, mix, controls=reference.QUANTS)
    want = GOLDEN[seed]["readings"]
    assert (got["requests"], got["tokens"]) == (want["requests"], want["tokens"])
    groups = {"served": got["served"], **got["controls"]}
    assert groups.keys() == {"served", *want["controls"]}
    for name, gaps in groups.items():
        expected = want["served"] if name == "served" else want["controls"][name]
        assert gaps == pytest.approx(expected, rel=1e-6), name


def test_the_verdict_needs_every_reading_under_its_limit():
    from bench import harness

    limits = {"logit_gap": 0.25, "logit_gap_mean": 0.01}
    ok, checks = harness.verdict({"logit_gap": 0.1, "logit_gap_mean": 0.001}, 3, limits)
    assert ok and list(checks) == ["logit_gap", "logit_gap_mean", "requests_compared"]
    assert not harness.verdict({"logit_gap": 0.1, "logit_gap_mean": 0.02}, 3, limits)[0]
    assert not harness.verdict({"logit_gap": 0.3, "logit_gap_mean": 0.001}, 3, limits)[0]
    assert not harness.verdict({"logit_gap": 0.1, "logit_gap_mean": 0.001}, 0, limits)[0]
    assert not harness.verdict({"logit_gap": float("nan"),
                                "logit_gap_mean": float("nan")}, 1, limits)[0]
