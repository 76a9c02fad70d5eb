"""Mesh serving benchmark: ring-prefill-into-paged-decode TTFT vs chunked
single-device prefill (ISSUE 9 acceptance).

Two ``PagedServeEngine`` configurations serve the same long prompt:

  chunked_1dev    — no mesh: the prompt admits through chunked prefill,
                    one ``prefill_chunk`` slice per scheduler tick, so
                    TTFT is ~ceil(n / chunk) ticks;
  ring_into_paged — ``mesh=`` a context ring: the scheduler's mesh
                    admission prefills the WHOLE prompt across the ring in
                    one tick and lands the KV in the block pool, so TTFT
                    is ~1 tick.

TTFT is measured in the tick domain (injected clock, one tick per
scheduler step) so the structural claim — whole-prompt admission
collapses time-to-first-token — is deterministic and backend-independent.
Wall-clock rows ride along, labelled via ``backend_info`` (CPU-interpret
wall time is not TPU time; an 8-host-device ring adds collective overhead
the tick metric deliberately ignores).

Emits ``BENCH_mesh.json`` at the repo root (floor gated by
benchmarks/regress.py) and ``benchmarks/results/mesh_serving.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import save_result

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_mesh.json")

_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, {src!r})
from dataclasses import replace as dc_replace
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serve.engine import PagedServeEngine
from benchmarks.common import backend_info

class TickClock:
    t = 0.0
    def __call__(self):
        return self.t

cfg = get_config("qwen1.5-4b", reduced=True)
cfg = cfg.replace(attention=dc_replace(
    cfg.attention, impl="pallas_flash", context_axis="context"))
params = lm.init_params(jax.random.PRNGKey(0), cfg)
n, max_len, ndev = {n}, {max_len}, {ndev}
prompt = list(np.random.RandomState(0).randint(0, cfg.vocab, size=n))
ring = make_mesh((ndev,), ("context",))

out = []
for mode, mesh in (("chunked_1dev", None), ("ring_into_paged", ring)):
    c = cfg if mesh is not None else cfg.replace(
        attention=dc_replace(cfg.attention, context_axis=None))
    clock = TickClock()
    eng = PagedServeEngine(
        c, params, max_batch=2, max_len=max_len, block_size=128,
        prefill_chunk=32, cache_dtype=jnp.float32, clock=clock, mesh=mesh)
    eng.add_request(prompt, max_new_tokens=2)  # warm every jit path
    eng.run_to_completion()
    eng.finished = []
    eng.scheduler.done = []
    t0 = time.perf_counter()
    eng.add_request(prompt, max_new_tokens=2)
    while eng.scheduler.has_work():
        eng.step()
        clock.t += 1
    wall = time.perf_counter() - t0
    (row,) = eng.metrics()
    out.append(dict(
        mode=mode, prompt_len=n, prefill_chunk=32, max_len=max_len,
        devices=1 if mesh is None else ndev,
        ttft_ticks=float(row["ttft_s"]), wall_s=wall,
        mesh_prefills=eng.counters_snapshot()["mesh_prefills"],
        **backend_info(),
    ))
assert out[1]["mesh_prefills"] >= 1, "ring engine never took the mesh path"
print("MESHJSON:" + json.dumps(out))
"""


def _run_sub(script: str):
    """Run the child on 8 simulated host devices.  The child is pinned to
    the CPU, so it never contends with a parent that holds an accelerator;
    a failed child raises."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=1100, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"mesh_serving child failed:\n{res.stderr[-2000:]}")
    return json.loads(res.stdout.split("MESHJSON:")[1])


def run(smoke: bool = False) -> list[tuple]:
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    rows: list[tuple] = []
    records = _run_sub(
        textwrap.dedent(_SCRIPT).format(
            src=src,
            # smoke: bucket 256 = 2 × 128 still engages the ring
            n=160 if smoke else 300,
            max_len=256 if smoke else 512,
            ndev=2,
        ),
    )

    by_mode = {r["mode"]: r for r in records}
    ratio = (by_mode["chunked_1dev"]["ttft_ticks"]
             / max(by_mode["ring_into_paged"]["ttft_ticks"], 1.0))
    summary = dict(
        kind="summary", chunked_over_mesh_ttft_ticks=ratio,
        prompt_len=by_mode["ring_into_paged"]["prompt_len"],
        **{k: v for k, v in by_mode["ring_into_paged"].items()
           if k in ("backend", "interpret")},
    )
    records = records + [summary]
    for r in records[:-1]:
        mode = "interpret" if r["interpret"] else "compiled"
        rows.append((
            f"mesh_serving/{r['mode']}", r["wall_s"] * 1e6,
            f"ttft={r['ttft_ticks']:.0f}ticks devices={r['devices']} "
            f"mesh_prefills={r['mesh_prefills']} "
            f"backend={r['backend']}:{mode}",
        ))
    rows.append((
        "mesh_serving/ttft_collapse", 0.0,
        f"chunked/mesh TTFT = {ratio:.1f}x in ticks "
        f"(whole-prompt ring admission)",
    ))
    if not smoke:
        save_result("mesh_serving", records)
        with open(os.path.abspath(BENCH_PATH), "w") as f:
            json.dump(records, f, indent=1)
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
