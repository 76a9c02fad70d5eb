#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's and the
controls'.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
      [--control-seeds 1,2,3]

For each seed, in one process: draw the weights, build the engine as
``bench/run.py`` does, drive the mix's pre-roll and a window of
``--seconds`` at the cell's own load, free the engine, and read on the
sampled finished requests what every run compares with its limits:

- ``logit_gap``: the widest gap of a served token's logit below the best
  logit of the float32 reference;
- ``logit_gap_mean``: the mean of that gap over every compared token.

For seeds in ``--control-seeds`` the same two readings for the tokens that
each control puts first at the same positions: the reference with every
matrix product in fp8 (e4m3) or in int8, weights scaled per output channel
and activations per row.  Each control then goes through the runs' own
verdict (``harness.verdict``) against the configuration's limits, which it
has to fail.

One JSON line per seed on stdout.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, manifest, reference, traffic  # noqa: E402


def readings(conf: dict, mix: dict, seed: int, seconds: float, control: bool) -> dict:
    """One seed's readings of the served tokens and, with ``control``, of
    each control, each with the verdict that the limits give it."""
    import jax

    cfg = harness.program_config(conf)
    params = harness.make_weights(cfg, seed)
    eng = harness.build_engine(cfg, params, conf, mix, seed)
    harness.warm_up(eng, mix, conf["vocab_size"], seed)
    stream = traffic.stream(mix, seed, conf["vocab_size"], seconds)
    window = harness.Window(eng, mix, stream)
    window.run(seconds)
    jax.block_until_ready(eng.cache.pools)
    record = window.record()
    served = {t.req.index: (t.req.prompt, list(t.entry.req.generated))
              for t in window.all if t.status == "done"}
    harness.free_engine(eng)
    del eng, window
    picked = harness.sample_finished(record, served, seed, mix["check"])
    cmp = harness.compare(params, conf, picked, mix,
                          controls=reference.QUANTS if control else ())
    del params
    limits = conf["check"]["limits"]
    out = {"seed": seed, "requests": cmp["requests"], "tokens": cmp["tokens"],
           "served": cmp["served"], "controls": {}}
    if all(v is not None for v in limits.values()):
        out["correct"] = harness.verdict(cmp["served"], cmp["requests"], limits)[0]
    for quant, r in cmp["controls"].items():
        out["controls"][quant] = dict(r)
        if "correct" in out:
            out["controls"][quant]["correct"] = harness.verdict(r, cmp["requests"], limits)[0]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    man = manifest.load()
    cell = manifest.workload(man, args.workload)
    conf = manifest.read_config(man, cell["config"])
    mix = traffic.load_mix(cell["traffic"])

    import jax
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(conf, mix, seed, args.seconds, seed in controls)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
