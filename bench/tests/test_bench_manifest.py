"""BENCHMARK.json against the benchmark's contract, and each name's files."""
import copy
import json

import pytest

from bench import manifest, traffic

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
