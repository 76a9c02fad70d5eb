"""The benchmark's manifest (``BENCHMARK.json``) and the files it names.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by the name the manifest gives it:

- configuration ``<c>``: ``bench/configs/<c>.json``;
- traffic mix ``<t>``: ``bench/mixes/<t>.json``;
- architecture ``<a>`` (the configuration file's ``"architecture"``, which
  has no default): ``bench/architectures/<a>.py``, a module that declares
  ``spec(conf)`` (what its reference needs of the file),
  ``logits(params, spec, tokens, rows, *, quant=None)`` (the plain float32
  forward pass over padded tokens, ``bench/reference.py``),
  ``kv_bytes_per_token(conf)`` (checked against the program's paged pool)
  and ``WORK`` (name -> ``f(conf, call) -> Work | None``, the operations and
  bytes of one logged model-step call, ``harness.CallLog``);
- metric ``<m>``: ``bench/metrics/<m>.py``, a module that declares ``NAME``,
  ``UNIT``, ``BETTER``, ``SOURCE``, ``LAYER`` (per-layer metrics) and
  ``MOVES`` (the end-to-end metric it should move, ``None`` for an
  end-to-end metric), and computes the value from a run's record with
  ``compute(record) -> float | None``; ``None`` leaves it out of the line.
  A kernel's roofline share reads its device time from the trace's
  ``op_s`` and its work from a ``WORK`` key (``metrics/_common.op_share``).

So a new cell, mix or metric is new files plus new entries, and no edit.
A configuration of a new architecture brings, and edits nothing:

- ``bench/configs/<c>.json`` with ``"architecture": "<a>"``;
- ``bench/architectures/<a>.py``, its reference and work counts, with the
  step and kernel entries its metrics read (a recurrent state's bytes in
  the step counts, a new kernel's own entry);
- a mix ``bench/mixes/<t>.json`` where no mix fits;
- a metric file for each new reading, such as a new kernel's roofline;
- entries in ``BENCHMARK.json``: the configuration, its cells, and the
  cells added to the ``workloads`` of the metrics they report.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"

ARCH_RE = re.compile(r"^[a-z][a-z0-9_]{0,63}$")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_END_TO_END = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(path: Path = MANIFEST) -> dict:
    return json.loads(Path(path).read_text())


def workload(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in manifest["workloads"]]
    raise SystemExit(f"unknown workload {name!r}; the manifest has {known}")


def configuration(manifest: dict, name: str) -> dict:
    for conf in manifest["configs"]:
        if conf["name"] == name:
            return conf
    raise SystemExit(f"unknown configuration {name!r}")


def read_config(manifest: dict, name: str) -> dict:
    return json.loads((ROOT / configuration(manifest, name)["file"]).read_text())


def architecture(conf: dict):
    """The configuration's architecture module (``bench/architectures/``)."""
    name = conf["architecture"]
    if not ARCH_RE.match(name):
        raise ValueError(f"architecture {name!r} is not a module name")
    return importlib.import_module(f"bench.architectures.{name}")


def metrics_for(manifest: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer metrics
    (``trace`` true): those whose ``workloads`` list the cell, or that have
    no such list."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_module(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_metric_module(entry: dict, mod) -> None:
    """The module declares what the manifest says of the metric."""
    want = {"NAME": entry["name"], "UNIT": entry["unit"],
            "BETTER": entry["better"], "SOURCE": entry["source"],
            "LAYER": entry.get("layer"), "MOVES": entry.get("moves")}
    got = {k: getattr(mod, k, None) for k in want}
    if got != want:
        raise ValueError(f"metric {entry['name']}: module declares {got}, "
                         f"manifest says {want}")
