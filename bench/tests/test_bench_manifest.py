"""BENCHMARK.json against the benchmark's contract, and each name's files."""
import copy
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import manifest, traffic

DATA = Path(__file__).resolve().parent / "data"

MAN = manifest.load()
CELLS = [c["name"] for c in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_paths():
    assert set(MAN) == KEYS
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51 and isinstance(MAN["run_seconds"], int)
    for word in MAN["command"]:
        assert not word.startswith("/") and ".." not in word


def test_free_text_fits():
    texts = ([c["why"] for c in MAN["configs"] + MAN["workloads"]]
             + [c["source"] for c in MAN["configs"]]
             + [m["layer"] for m in MAN["per_layer"]])
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    assert len(manifest.MANIFEST.read_bytes()) <= 64 * 1024
    for c in MAN["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert manifest.NAME_RE.match(key)
            assert not key.endswith(("_dim", "_rank", "_size")), key


def test_names_and_units():
    names = [c["name"] for c in MAN["configs"]] + CELLS + [m["name"] for m in METRICS]
    for name in names:
        assert manifest.NAME_RE.match(name), name
    for sub in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in MAN[sub]]
        assert len(got) == len(set(got)), sub
    for m in METRICS:
        assert manifest.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in manifest.SOURCES_END_TO_END
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert m["source"] in manifest.SOURCES
        assert "\n" not in m["layer"] and "bound" not in m


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = manifest.workload(MAN, cell)
    conf = manifest.read_config(MAN, c["config"])
    assert conf["serving"]["chips"] == c["chips"] == 1
    assert traffic.load_mix(c["traffic"])["engine"]
    conf_entry = manifest.configuration(MAN, c["config"])
    assert conf_entry["file"] == f"bench/configs/{c['config']}.json"
    assert conf_entry["reduced"] == conf["reduced"]
    assert conf_entry["source"] == conf["source"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer(cell):
    e2e = [m["name"] for m in manifest.metrics_for(MAN, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(MAN, cell, True)


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_per_layer_cells_report_what_it_moves(metric):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    moved = next(x for x in MAN["end_to_end"] if x["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_modules_declare_what_the_manifest_says(metric):
    entry = next(x for x in METRICS if x["name"] == metric)
    manifest.check_metric_module(entry, manifest.metric_module(metric))


def test_shares_are_named_as_shares():
    for m in MAN["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_a_new_cell_needs_only_new_entries(tmp_path):
    """A cell over an existing configuration and mix is one manifest entry:
    its files are found and its metrics are selected by name alone."""
    man = copy.deepcopy(MAN)
    man["workloads"].append({"name": "qwen1.5-4b.chat", "config": "qwen1.5-4b",
                             "traffic": "chat", "chips": 1, "why": "x"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and "minicpm-2b.chat" in m["workloads"]:
            m["workloads"].append("qwen1.5-4b.chat")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(man))
    man = manifest.load(path)
    cell = manifest.workload(man, "qwen1.5-4b.chat")
    assert manifest.read_config(man, cell["config"])["arch"] == "qwen1.5-4b"
    assert traffic.load_mix(cell["traffic"])["loop"] == "open"
    names = {m["name"] for m in manifest.metrics_for(man, cell["name"], True)}
    assert "decode_step_mfu.itl" in names
    for n in names:
        assert manifest.metric_module(n).NAME == n


@pytest.mark.parametrize("config", [c["name"] for c in MAN["configs"]])
def test_every_configuration_names_an_architecture_module(config):
    conf = manifest.read_config(MAN, config)
    assert (manifest.BENCH / "architectures" / f"{conf['architecture']}.py").is_file()
    arch = manifest.architecture(conf)
    for name in ("spec", "logits", "kv_bytes_per_token"):
        assert callable(getattr(arch, name)), name
    assert arch.WORK and all(callable(f) for f in arch.WORK.values())
    assert arch.kv_bytes_per_token(conf) > 0 and arch.spec(conf)


def test_an_architecture_has_no_default():
    conf = manifest.read_config(MAN, "minicpm-2b")
    del conf["architecture"]
    with pytest.raises(KeyError):
        manifest.architecture(conf)
    with pytest.raises(ValueError):
        manifest.architecture(dict(conf, architecture="../llama"))


TOY = {"architecture": "toy_hybrid", "vocab_size": 64, "hidden_size": 32,
       "num_key_value_heads": 4, "head_dim": 32,
       "layer_types": ["state", "attention", "state", "state", "attention"],
       "serving": {"chips": 1, "pool_bytes": 1024 * 128 * 6}}


@pytest.fixture
def toy(monkeypatch):
    """``bench/tests/data/toy_hybrid.py`` found by name, as it would be
    under ``bench/architectures/``: the data directory joins the package's
    path for the test."""
    from bench import architectures

    name = "bench.architectures.toy_hybrid"
    monkeypatch.setattr(architectures, "__path__", [*architectures.__path__, str(DATA)])
    monkeypatch.delitem(sys.modules, name, raising=False)
    yield copy.deepcopy(TOY)
    sys.modules.pop(name, None)


def test_a_new_architecture_needs_only_new_files(toy, tmp_path):
    """A configuration of another architecture, with a module of its own
    (toy reference, pool bytes of its attention layers only, a per-lane
    state in its step counts and a kernel of its own), goes through the
    manifest, the reference comparison, the pool check, the call log and a
    metric over ``op_s`` with no harness file changed."""
    import jax
    import numpy as np

    from bench import harness, reference

    conf_file = tmp_path / "toy.json"
    conf_file.write_text(json.dumps(toy))
    man = copy.deepcopy(MAN)
    man["configs"].append({"name": "toy", "source": "x", "file": str(conf_file),
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "toy.chat", "config": "toy", "traffic": "chat",
                             "chips": 1, "why": "x"})
    conf = manifest.read_config(man, manifest.workload(man, "toy.chat")["config"])
    arch = manifest.architecture(conf)
    assert arch.__name__ == "bench.architectures.toy_hybrid"

    # compare: greedy tokens of the toy reference read no gap, an altered
    # one does.
    mix = traffic.load_mix("chat")
    mix = dict(mix, prompt=dict(mix["prompt"], max=40), output=dict(mix["output"], max=8))
    table = jax.random.normal(jax.random.PRNGKey(0), (64, 32), "float32")
    params = {"embed": {"table": table}}
    spec, length = arch.spec(conf), harness.reference_length(mix)
    prompt = list(np.random.default_rng(1).integers(1, 64, 20))
    served = []
    for _ in range(6):
        tokens = prompt + served
        out = reference.logits(arch, params, spec, tokens, [len(tokens) - 1],
                               length=length, n_rows=8)
        served.append(int(out[0].argmax()))
    got = harness.compare(params, conf, [(prompt, served)], mix, controls=reference.QUANTS)
    assert got["tokens"] == 6 and got["served"]["logit_gap"] < 1e-5
    assert set(got["controls"]) == set(reference.QUANTS)
    altered = served[:3] + [(served[3] + 1) % 64] + served[4:]
    assert harness.compare(params, conf, [(prompt, altered)], mix)["served"]["logit_gap"] > 1e-3

    # the pool check: the program's pool of two attention layers is what
    # the toy counts, and three would not be.
    import repro.configs as configs

    cfg = configs.get_config("minicpm-2b", reduced=True).replace(vocab=64, n_layers=2)
    blocks, block = harness.pool_blocks(cfg, conf, 128)
    assert blocks == 1024 * 128 * 6 // (block * 1024) + 1
    with pytest.raises(SystemExit, match="configuration says 1536"):
        harness.pool_blocks(cfg, dict(conf, layer_types=["attention"] * 3), 128)

    # the call log counts every entry of the toy's WORK, its state term
    # once a lane.
    log = harness.CallLog(SimpleNamespace(decode_tick=None, prefill_chunk_run=None))
    log.calls = [{"kind": "decode", "lengths": [10, 30]},
                 {"kind": "chunk", "start": 0, "n": 16, "final": True},
                 {"kind": "decode", "lengths": [11, 31, 5]}]
    work = log.work(conf)
    assert set(work) == {"counts", "decode_step", "chunk_step", "scan_kernel"}
    assert work["counts"] == {"decode": 2, "chunk": 1}
    state = arch.STATE_BYTES
    assert work["scan_kernel"] == {"flops": 2 * state * (2 + 16 + 3),
                                   "bytes": 2 * state * (2 + 1 + 3)}
    weights, kv = 64 * 32 * 2, arch.kv_bytes_per_token(conf)
    assert work["decode_step"]["bytes"] == (2 * weights + (40 + 47) * kv
                                            + 5 * 2 * state + 5 * 64 * 4)

    # a metric file over op_s and a WORK key.
    path = DATA / "toy_scan_roofline.py"
    metric = importlib.util.module_from_spec(
        importlib.util.spec_from_file_location("toy_scan_roofline", path))
    metric.__spec__.loader.exec_module(metric)
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    record = {"work": work, "peaks": peaks,
              "trace": {"op_s": {"decode/toy_scan": 2e-5, "chunk/toy_scan": 1e-5,
                                 "decode/fusion": 1.0}}}
    least = max(work["scan_kernel"]["flops"] / 1e12, work["scan_kernel"]["bytes"] / 1e9)
    assert metric.compute(record) == pytest.approx(100 * least / 3e-5)
    assert metric.compute(dict(record, trace={"op_s": {"decode/fusion": 1.0}})) is None
    assert metric.compute({}) is None


def test_engine_arguments_come_from_the_mix():
    """A mix sets any of the engine's keyword arguments by name, a mesh and
    a degradation policy included, with no edit of the harness."""
    from bench import harness
    from repro.serve.degrade import DegradeConfig

    mix = traffic.load_mix("docs")
    assert harness.engine_kwargs(mix) == mix["engine"]
    mix = dict(mix, engine=dict(mix["engine"], token_budget=512,
                                degrade={"group_sizes": [2, 4], "high_watermark": 8},
                                mesh={"shape": [1], "axes": ["context"]}))
    kw = harness.engine_kwargs(mix)
    assert kw["degrade"] == DegradeConfig(group_sizes=(2, 4), high_watermark=8)
    assert kw["mesh"].axis_names == ("context",) and kw["token_budget"] == 512


@pytest.mark.parametrize("cell", CELLS)
def test_trace_names_come_from_the_mix(cell):
    mix = traffic.load_mix(manifest.workload(MAN, cell)["traffic"])
    assert set(mix["trace"]) == {"seconds", "step_module", "kernel"}
