"""Serving launcher: continuous-batching engine over a model.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b --reduced \
      --requests 8 --max-new 16

Weights are held in the config's compute dtype (bf16 at published widths).

``--trace PATH`` records per-request lifecycle spans (admit → prefill →
decode → terminal) as a Chrome trace_event JSON loadable in Perfetto;
``--metrics-out PATH`` writes the typed metrics snapshot
(``repro.obs.metrics.serving_registry``) over the engine's frozen
counter schema plus TTFT/TPOT histograms.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.models import lm
from repro.obs import TraceRecorder, perf_clock, serving_registry
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as ckpt
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default=None, help="checkpoint dir to serve")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a typed metrics snapshot of the run")
    args = ap.parse_args()
    print(f"[serve] compile cache: {use_compile_cache()}")

    cfg = get_config(args.arch, reduced=args.reduced)
    key = jax.random.PRNGKey(0)
    if args.ckpt:
        template = jax.eval_shape(lambda k: lm.init_params(k, cfg), key)
        _, params, _, _ = ckpt.load_checkpoint(args.ckpt, template)
        params = jax.tree_util.tree_map(
            lambda x: x.astype(cfg.compute_dtype), params
        )
    else:
        params = lm.init_serving_params(key, cfg)

    rec = TraceRecorder() if args.trace else None
    eng = ServeEngine(cfg, params, max_slots=args.max_slots,
                      max_len=args.max_len, temperature=args.temperature,
                      trace=rec)
    rng = np.random.default_rng(0)
    t0 = perf_clock()
    for _ in range(args.requests):
        prompt = rng.integers(1, cfg.vocab, size=rng.integers(4, 17)).tolist()
        eng.add_request(prompt, max_new_tokens=args.max_new)
    done = eng.run_to_completion()
    dt = perf_clock() - t0
    total_tokens = sum(len(r.generated) for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.uid}: {r.generated[:12]}")
    if rec is not None:
        rec.save(args.trace)
        print(f"[serve] trace: {args.trace} ({len(rec.events)} events)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(serving_registry(eng).snapshot(), f, indent=1)
        print(f"[serve] metrics: {args.metrics_out}")


if __name__ == "__main__":
    main()
