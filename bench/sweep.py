#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the system
sustains without a growing backlog.

  python3 bench/sweep.py --workload <cell> --seconds <s> --rates 0.5,1,1.5

One engine in one process, built as ``bench/run.py`` builds it; for each
rate the cell's mix at that rate through its pre-roll and a window of
``--seconds``, as a run drives it, then every request still in flight is
cancelled before the next rate.  One JSON line per rate: requests due and
requests that ended in the window, the waiting queue and the pool's share
in use at the window's opening and close, time to first token (median,
mean, 90th percentile), the 95th percentile of the gaps between tokens,
tokens per second and preemptions.  The cell's mix keeps a fixed rate, set once
from this sweep at about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, manifest, traffic  # noqa: E402
from bench.metrics._common import ttfts  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    man = manifest.load()
    cell = manifest.workload(man, args.workload)
    conf = manifest.read_config(man, cell["config"])
    mix = traffic.load_mix(cell["traffic"])

    import jax
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    cfg = harness.program_config(conf)
    params = harness.make_weights(cfg, args.seed)
    eng = harness.build_engine(cfg, params, conf, mix, args.seed)
    harness.warm_up(eng, mix, conf["vocab_size"], args.seed)
    print(json.dumps({"memory": jax.devices()[0].memory_stats()}), flush=True)
    metric = {n: manifest.metric_module(n)
              for n in ("ttft_mean_s.itl", "ttft_p90_s", "itl_p95_ms", "tokens_per_s")}
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        stream = traffic.stream(m, args.seed, conf["vocab_size"], args.seconds)
        win = harness.Window(eng, m, stream)
        win.run(args.seconds)
        rec = win.record()
        ttft = sorted(ttfts(rec))
        ticks = rec["ticks"]
        row = {"rate": rate,
               "due": sum(r["in_window"] for r in rec["requests"]),
               "ended": sum(r["ended"] is not None and r["ended"] >= 0
                            for r in rec["requests"]),
               "waiting": [ticks[0]["waiting"], ticks[-1]["waiting"]],
               "pool_share": [ticks[0]["blocks_used_share"],
                              sum(k["blocks_used_share"] for k in ticks) / len(ticks),
                              ticks[-1]["blocks_used_share"]],
               "preemptions": rec["preemptions"],
               "ttft_p50_s": ttft[len(ttft) // 2] if ttft else None}
        row.update({n: mod.compute(rec) for n, mod in metric.items()})
        print(json.dumps(row), flush=True)
        for t in win.live:
            eng.cancel(t.entry.uid)
    return 0


if __name__ == "__main__":
    sys.exit(main())
