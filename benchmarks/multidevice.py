"""Paper Table 9: multi-device attention, Flash2 vs DistrAttention.

Runs in a subprocess with 8 forced host devices; the attention workload is
sharded over a data mesh of 1/2/4/8 devices (paper: 1/2/4 GPUs) and timed.

Beyond the paper, a second subprocess times **ring sequence-parallel
attention** (distributed.ring_attention) — flash and distr — on context
rings of 1/2/4/8 devices against the single-device kernels, emitting
``BENCH_ring.json`` at the repo root.  On this CPU container the rows are
interpret-mode (labelled via ``backend_info``): the point is exercising the
ring schedule end-to-end and tracking the hop/merge overhead trend, not
absolute kernel speed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import save_result

BENCH_RING_PATH = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_ring.json"
)

_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, functools, time
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import attend, AttentionConfig, DistrConfig
from benchmarks.common import timeit

B, H, N, D = 8, 8, {n}, 128
q = jax.random.normal(jax.random.PRNGKey(0), (B, H, N, D), jnp.float32)
k = jax.random.normal(jax.random.PRNGKey(1), (B, H, N, D), jnp.float32)
v = jax.random.normal(jax.random.PRNGKey(2), (B, H, N, D), jnp.float32)

flash = functools.partial(
    attend, cfg=AttentionConfig(impl="xla_flash"), causal=True)
distr = functools.partial(
    attend,
    cfg=AttentionConfig(impl="distr", distr=DistrConfig(group_size=2)),
    causal=True)

out = []
for ndev in {ndevs}:
    mesh = jax.sharding.Mesh(jax.devices()[:ndev], ("data",))
    sh = NamedSharding(mesh, P("data"))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    with jax.sharding.set_mesh(mesh):
        t_f = timeit(jax.jit(flash), qs, ks, vs, warmup=1, iters=3)
        t_d = timeit(jax.jit(distr), qs, ks, vs, warmup=1, iters=3)
    out.append(dict(devices=ndev, flash_us=t_f, distr_us=t_d,
                    speedup=t_f / t_d))
print("JSON:" + json.dumps(out))
"""


_RING_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, functools
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.core.distr_attention import DistrConfig
from repro.distributed.ring_attention import (
    ring_distr_attention, ring_flash_attention,
)
from repro.kernels import ops
from benchmarks.common import backend_info, timeit

B, Hq, Hkv, N, D = 1, 4, 2, {n}, 64
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, Hq, N, D), jnp.float32)
k = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.float32)
v = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.float32)
dcfg = DistrConfig(group_size=2)

t_flash1 = timeit(jax.jit(functools.partial(ops.flash_attention, causal=True)),
                  q, k, v, warmup=1, iters=3)
t_distr1 = timeit(
    jax.jit(lambda q, k, v: ops.distr_attention(q, k, v, dcfg, causal=True)),
    q, k, v, warmup=1, iters=3)
out = []
for ndev in {ndevs}:
    mesh = jax.sharding.Mesh(jax.devices()[:ndev], ("context",))
    _, hops = ring_flash_attention(q, k, v, mesh, causal=True,
                                   return_hops=True)
    t_f = timeit(
        jax.jit(lambda q, k, v: ring_flash_attention(
            q, k, v, mesh, causal=True)), q, k, v, warmup=1, iters=3)
    t_d = timeit(
        jax.jit(lambda q, k, v: ring_distr_attention(
            q, k, v, dcfg, mesh, causal=True)), q, k, v, warmup=1, iters=3)
    out.append(dict(devices=ndev, seq=N, causal=True, hops=int(hops),
                    ring_flash_us=t_f, ring_distr_us=t_d,
                    single_flash_us=t_flash1, single_distr_us=t_distr1,
                    **backend_info()))
print("RINGJSON:" + json.dumps(out))
"""


def _run_sub(script: str, marker: str, label: str):
    """Run one child on 8 simulated host devices.  The child is pinned to
    the CPU, so it never contends with a parent that holds an accelerator;
    a failed child raises."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=1100, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"{label} child failed:\n{res.stderr[-2000:]}")
    return json.loads(res.stdout.split(marker)[1])


def run(smoke: bool = False) -> list[tuple]:
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    rows: list[tuple] = []
    records = _run_sub(
        textwrap.dedent(_SCRIPT).format(
            src=src,
            n=256 if smoke else 2048,
            ndevs=(1, 2) if smoke else (1, 2, 4, 8),
        ),
        "JSON:", "multidevice",
    )
    if not smoke:
        save_result("multidevice", records)
    for r in records:
        rows.append((
            f"multidevice/devices={r['devices']}", r["distr_us"],
            f"flash={r['flash_us']:.0f}us speedup={r['speedup']:.2f}x",
        ))

    ring = _run_sub(
        textwrap.dedent(_RING_SCRIPT).format(
            src=src,
            n=256 if smoke else 1024,
            ndevs=(1, 2) if smoke else (1, 2, 4, 8),
        ),
        "RINGJSON:", "multidevice/ring",
    )
    if not smoke:
        save_result("ring", ring)
        with open(os.path.abspath(BENCH_RING_PATH), "w") as f:
            json.dump(ring, f, indent=1)
    for r in ring:
        mode = "interpret" if r["interpret"] else "compiled"
        rows.append((
            f"multidevice/ring/devices={r['devices']}",
            r["ring_flash_us"],
            f"distr={r['ring_distr_us']:.0f}us "
            f"single_flash={r['single_flash_us']:.0f}us "
            f"hops={r['hops']} backend={r['backend']}:{mode}",
        ))
    return rows
