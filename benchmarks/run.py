"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Results are also persisted as
JSON under benchmarks/results/ for EXPERIMENTS.md.

  T1/Fig9  attention_time   — Flash2 vs DistrAttention compute time
  §Bwd     attention_bwd    — fwd+bwd: scan path vs kernel custom_vjp path
  T2       blocksize        — (l, m): analytic vs measured best vs default
  §Tune    autotune         — tuned-vs-default blocks per kernel
                              (BENCH_autotune.json)
  T3/T4    errors           — Ŝ error vs block size / sampling rate
  T5/T7/T8 compare          — ours vs Hydra/Flatten/Primal/Hyper fidelity+time
  T6       llama_ttft       — LM prefill TTFT, exact vs distr
  T9       multidevice      — sharded attention on 1/2/4/8 devices
  Fig8     accuracy_train   — training-loss trajectories exact vs distr
  §4.8     lsh_grouping     — LSH grouping share of attention time
  extra    distr_decode     — beyond-paper fused-K̂ decode cache
  §Decode  decode           — split-K flash-decoding: tokens/s + per-token
                              KV bytes vs live length (BENCH_decode.json)
  §Paged   serving          — slot engine vs paged continuous batching at
                              equal HBM: tokens/s + P50/P99 TTFT
                              (BENCH_serving.json)
  §Cluster cluster          — multi-replica router: routing policies +
                              goodput retention under a mid-run replica
                              kill vs drain (BENCH_cluster.json; floors
                              gated by benchmarks/regress.py)
  §Train   train_chaos      — checkpoint-resume goodput under a mid-run
                              kill, with the latest checkpoint healthy vs
                              torn, plus bit-exact resume-loss match
                              (BENCH_train_chaos.json; floors gated by
                              benchmarks/regress.py)
  §Mesh    mesh_serving     — ring-prefill-into-paged-decode TTFT vs
                              chunked single-device prefill on the same
                              engine (BENCH_mesh.json; floor gated by
                              benchmarks/regress.py)

``--smoke`` runs every benchmark at one tiny shape (interpret mode on this
container) without touching the persisted JSON results — a CI-grade check
that no benchmark has silently rotted.

``--trace PATH`` installs a global :class:`repro.obs.trace.TraceRecorder`
for the run (autotune measurement spans ride it) and writes a Chrome
trace_event JSON; ``--metrics-out PATH`` writes a typed metrics snapshot
of the run itself (rows emitted, failures, per-row latency histogram).
Both artifacts conform to ``python -m repro.obs.validate``.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import traceback

BENCHES = [
    "errors",
    "blocksize",
    "autotune",
    "attention_time",
    "attention_bwd",
    "compare",
    "llama_ttft",
    "lsh_grouping",
    "accuracy_train",
    "multidevice",
    "distr_decode",
    "decode",
    "serving",
    "cluster",
    "train_chaos",
    "mesh_serving",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of {BENCHES}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape pass over every benchmark; no JSON "
                         "results are written")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(load in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a typed metrics snapshot of the run")
    args = ap.parse_args()
    names = args.only or BENCHES

    from repro.utils.compile_cache import use_compile_cache

    print(f"[bench] compile cache: {use_compile_cache()}", file=sys.stderr)

    from repro.obs import (
        NULL_RECORDER, MetricsRegistry, TraceRecorder, set_recorder,
    )

    rec = NULL_RECORDER
    if args.trace:
        rec = TraceRecorder()
        set_recorder(rec)
    reg = MetricsRegistry()
    c_rows = reg.counter("bench_rows", "CSV rows emitted across benchmarks")
    c_fail = reg.counter("bench_failures", "benchmark modules that raised")
    h_row = reg.histogram(
        "bench_row_us", "per-row us_per_call",
        buckets=(10.0, 100.0, 1e3, 1e4, 1e5, 1e6),
    )

    print("name,us_per_call,derived")
    failures = []
    for name in names:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            with rec.span("bench", bench=name):
                if args.smoke:
                    if "smoke" not in inspect.signature(mod.run).parameters:
                        raise TypeError(f"{name}.run() lacks a smoke=... param")
                    rows = mod.run(smoke=True)
                else:
                    rows = mod.run()
            for row_name, us, derived in rows:
                print(f"{row_name},{us:.1f},{derived}")
                c_rows.inc()
                h_row.observe(float(us))
            sys.stdout.flush()
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            c_fail.inc()
            traceback.print_exc()
    if args.trace:
        rec.save(args.trace)
        print(f"[bench] trace: {args.trace} "
              f"({len(rec.events)} events, {rec.dropped} dropped)",
              file=sys.stderr)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(reg.snapshot(), f, indent=1)
        print(f"[bench] metrics: {args.metrics_out}", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
