"""Serve a small model with batched requests through the continuous-batching
engine (prefill → slot insert → lockstep decode → early slot recycling).

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import ServeEngine

cfg = get_config("qwen2.5-32b", reduced=True)
params = lm.init_serving_params(jax.random.PRNGKey(0), cfg)
engine = ServeEngine(cfg, params, max_slots=4, max_len=128, temperature=0.7)

rng = np.random.default_rng(0)
t0 = time.perf_counter()
for i in range(10):
    prompt = rng.integers(1, cfg.vocab, size=int(rng.integers(4, 20))).tolist()
    engine.add_request(prompt, max_new_tokens=int(rng.integers(4, 12)))

done = engine.run_to_completion()
dt = time.perf_counter() - t0
tokens = sum(len(r.generated) for r in done)
print(f"served {len(done)} requests / {tokens} tokens in {dt:.2f}s "
      f"({tokens / dt:.1f} tok/s, 4 slots, continuous batching)")
for r in done[:5]:
    print(f"  req {r.uid:2d} prompt_len={len(r.prompt):2d} → {r.generated}")
