#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU.

  python3 chip_smoke.py [--seed N]          # one chip
  python3 chip_smoke.py --ring [--seed N]   # four chips: ring prefill only

One process; every phase raises on failure, so the script then exits
non-zero before its last line.

  (a) device check: platform, device kind and count; the platform must be
      ``tpu``.
  (b) kernel parity: every Pallas kernel, compiled (``interpret=False``),
      at the minicpm-2b head layout (36 heads × 64, bf16) against
      ``kernels/ref.py``; max error printed beside its tolerance.
  (c) serving: minicpm-2b at its published widths (40 layers, d_model 2304,
      vocab 122753) with bf16 weights drawn from ``--seed``, on
      ``PagedServeEngine(max_batch=8, max_len=2048, prefill_chunk=256)``:
      8 greedy requests with 128–1024-token prompts and 16 new tokens each
      must all end ``done``; one request's first-token logits must match an
      f32 ``impl="reference"`` forward of the same weights; the decode
      step's compiled program must hold the Pallas kernel
      (``tpu_custom_call``).  Compile seconds, wall seconds, tokens and
      peak device memory are printed as set-up facts, not metrics.

``--ring`` runs only the context-parallel path: a 4-device ``context`` ring
engine (``impl="pallas_flash"``) prefills a 2048-token prompt into its paged
pool, and its greedy tokens must equal those of a mesh-less engine on one
chip in the same process.

The last stdout line is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.  The compile cache is ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "minicpm-2b"
PUBLISHED = dict(n_layers=40, d_model=2304, vocab=122753)
KERNEL_TOL = 3e-2  # max |kernel − ref| / max |ref|, bf16 inputs
LOGITS_TOL = 5e-2  # max |engine − f32 ref| / max |f32 ref| of first-token logits
RING_MIN_PEAK = 64 << 20  # bytes: a prompt shard's K/V and activations alone


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def check(name: str, err: float, tol: float) -> None:
    print(f"[parity] {name}: max rel err {err:.3e} (tol {tol:.0e})", flush=True)
    if not err <= tol:
        raise AssertionError(f"{name}: error {err} above tolerance {tol}")


# -- (a) -------------------------------------------------------------------


def device_check(want_count: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {dev['platform']}")
    if dev["count"] < want_count:
        raise SystemExit(f"need {want_count} chips, found {dev['count']}")
    return dev


# -- (b) -------------------------------------------------------------------


def kernel_parity(seed: int, *, hq: int = 36, d: int = 64, n: int = 512) -> None:
    from repro.core.api import AttentionConfig, attend_decode
    from repro.core.distr_attention import DistrConfig
    from repro.core.distr_attention import distr_attention as core_distr
    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention_kernel_call
    from repro.kernels.paged_decode import token_major

    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    bf = jnp.bfloat16
    q, k, v = (jax.random.normal(kk, (1, hq, n, d)).astype(bf) for kk in ks[:3])
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def grads(attn):
        def loss(q, k, v):
            return (attn(q, k, v).astype(jnp.float32) * w).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    flash = lambda q, k, v: ops.flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=False)
    exact = lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True)  # noqa: E731
    check("flash fwd", rel_err(jax.jit(flash)(q, k, v), exact(f32(q), f32(k), f32(v))),
          KERNEL_TOL)

    _, lse = flash_attention_kernel_call(
        q.reshape(hq, n, d), k.reshape(hq, n, d), v.reshape(hq, n, d),
        q_per_kv=1, scale=d**-0.5, causal=True, block_q=128, block_k=128,
        kv_len=n, interpret=False, return_residuals=True,
    )
    s = jnp.einsum("hqd,hkd->hqk", f32(q[0]), f32(k[0])) * d**-0.5
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    check("flash fwd LSE", rel_err(lse, jax.nn.logsumexp(s, axis=-1)), KERNEL_TOL)

    got = grads(flash)(q, k, v)
    want = grads(exact)(f32(q), f32(k), f32(v))
    check("flash bwd", max(map(rel_err, got, want)), KERNEL_TOL)

    cfg = DistrConfig(group_size=2)
    distr = lambda q, k, v: ops.distr_attention(  # noqa: E731
        q, k, v, cfg, causal=True, interpret=False)
    core = lambda q, k, v: core_distr(q, k, v, cfg, causal=True)  # noqa: E731
    check("distr fwd", rel_err(jax.jit(distr)(q, k, v), jax.jit(core)(q, k, v)),
          KERNEL_TOL)
    got, want = grads(distr)(q, k, v), grads(core)(q, k, v)
    check("distr bwd", max(map(rel_err, got, want)), KERNEL_TOL)

    b, s_len = 8, 2048
    qd = jax.random.normal(ks[3], (b, hq, 1, d)).astype(bf)
    kc, vc = (jax.random.normal(kk, (b, hq, s_len, d)).astype(bf) for kk in ks[4:6])
    lengths = jnp.asarray(np.linspace(1, s_len, b).astype(np.int32))
    out = ops.decode_attention(qd, kc, vc, lengths=lengths, interpret=False)
    check("split-K decode", rel_err(out, ref.decode_attention_ref(
        f32(qd), f32(kc), f32(vc), lengths)), KERNEL_TOL)

    # Paged decode over a shuffled 129-block pool: 8-lane token decode and
    # one 256-token chunked-prefill window (banded).
    blocks, bs, table = 129, 128, 16
    kp, vp = (token_major(jax.random.normal(kk, (blocks, hq, bs, d)).astype(bf))
              for kk in ks[6:8])
    ids = np.random.RandomState(seed).permutation(np.arange(1, blocks))
    ref_cfg = AttentionConfig(impl="reference")
    for q_len, lanes, key in ((1, 8, ks[8]), (256, 1, ks[9])):
        tables = jnp.asarray(ids[: lanes * table].reshape(lanes, table), jnp.int32)
        lens = jnp.asarray(np.linspace(300, table * bs, lanes).astype(np.int32))
        qp = jax.random.normal(key, (lanes, hq, q_len, d)).astype(bf)
        got = ops.paged_decode_attention(
            qp, kp, vp, block_tables=tables, lengths=lens, interpret=False)
        want = jax.jit(lambda q, kp, vp, t, ln: attend_decode(
            q, kp, vp, ref_cfg, lengths=ln, block_tables=t))(
                f32(qp), f32(kp), f32(vp), tables, lens)
        check(f"paged decode q_len={q_len}", rel_err(got, want), KERNEL_TOL)


# -- (c) -------------------------------------------------------------------


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, as reported by
    its own monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def _engine_class():
    from repro.serve.engine import PagedServeEngine

    class FirstLogitsEngine(PagedServeEngine):
        """The paged engine, keeping each prompt's last-position logits
        (the distribution its first token is sampled from)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.first_logits = {}

        def prefill_chunk_run(self, entry, chunk):
            row = super().prefill_chunk_run(entry, chunk)
            if entry.prompt_done + chunk == len(entry.req.prompt):
                self.first_logits[entry.uid] = row
            return row

    return FirstLogitsEngine


def reference_logits(params, cfg, prompt) -> np.ndarray:
    """Last-position logits of an f32, ``impl="reference"`` forward of the
    same (bf16-held) weights."""
    from repro.models import lm

    rcfg = cfg.replace(
        compute_dtype="float32", attention=replace(cfg.attention, impl="reference")
    )

    @jax.jit
    def fwd(params, tokens):
        hidden, _, _, _ = lm.backbone(params, rcfg, tokens)
        return lm.logits_fn(params, rcfg, hidden[:, -1:])[0, 0]

    with jax.default_matmul_precision("highest"):
        out = fwd(params, jnp.asarray([prompt], jnp.int32))
    return np.asarray(out, np.float32)[: cfg.vocab]


def serve(cfg, seed: int, *, requests: int = 8, prompt_range=(128, 1024),
          max_new: int = 16, max_len: int = 2048, chunk: int = 256,
          max_batch: int = 8) -> dict:
    from repro.kernels import ops
    from repro.models import lm
    from repro.serve import lifecycle

    if ops.default_interpret() and jax.default_backend() == "tpu":
        raise AssertionError("Pallas kernels would run interpreted on the TPU")
    clock = _CompileClock()
    t0 = time.perf_counter()
    params = lm.init_serving_params(jax.random.PRNGKey(seed), cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}, {n_params / 1e9:.3f} B params in "
          f"{jax.tree_util.tree_leaves(params)[0].dtype}", flush=True)
    eng = _engine_class()(cfg, params, max_batch=max_batch, max_len=max_len,
                          prefill_chunk=chunk)
    print(f"[serve] pool: {eng.cache.pool.num_blocks} blocks of "
          f"{eng.block_size} tokens", flush=True)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=int(rng.integers(
        prompt_range[0], prompt_range[1] + 1))).tolist() for _ in range(requests)]
    uids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    done = eng.run_to_completion()
    jax.block_until_ready(eng.cache.pools)
    wall = time.perf_counter() - t0
    statuses = {r.uid: r.status for r in done}
    if sorted(statuses) != sorted(uids) or set(statuses.values()) != {lifecycle.DONE}:
        raise AssertionError(f"requests did not all finish done: {statuses}")
    tokens = sum(len(r.generated) for r in done)
    if tokens != requests * max_new:
        raise AssertionError(f"{tokens} tokens generated, want {requests * max_new}")

    # The decode step the engine ran holds the compiled Pallas kernel.
    lanes = eng.max_batch
    args = (params, jnp.zeros((lanes, 1), jnp.int32), eng.cache.pools,
            jnp.zeros((lanes, eng.max_blocks), jnp.int32),
            jnp.zeros((lanes,), jnp.int32), jnp.zeros((lanes,), jnp.int32))
    decode_text = eng._decode.lower(*args).compile().as_text()  # noqa: SLF001
    has_kernel = "tpu_custom_call" in decode_text
    del args
    if jax.default_backend() == "tpu" and not has_kernel:
        raise AssertionError("decode step holds no tpu_custom_call")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    first = np.asarray(eng.first_logits[uids[0]], np.float32)[: cfg.vocab]
    print(f"[serve] {len(done)} requests done, {tokens} tokens, "
          f"compile {clock.seconds:.1f} s, wall {wall:.1f} s (compile "
          f"included), peak_bytes_in_use {peak}, decode kernel "
          f"tpu_custom_call={has_kernel}", flush=True)

    del eng, done
    gc.collect()
    want = reference_logits(params, cfg, prompts[0])
    check(f"first-token logits vs f32 reference ({len(prompts[0])}-token "
          "prompt)", rel_err(first, want), LOGITS_TOL)
    return {"tokens": tokens, "peak_bytes_in_use": peak}


# -- --ring ------------------------------------------------------------------


def ring_prefill(cfg, seed: int, *, prompt_len: int = 2048, max_new: int = 8,
                 ring: int = 4) -> None:
    """Ring prefill across ``ring`` chips into the paged pool, against the
    mesh-less engine on one chip.  Both run f32 activations and KV under
    highest matmul precision, so a greedy token can only differ when the
    two paths disagree, not by bf16 rounding."""
    from repro.launch.mesh import make_mesh
    from repro.models import lm
    from repro.serve.engine import PagedServeEngine

    cfg = cfg.replace(compute_dtype="float32", attention=replace(
        cfg.attention, impl="pallas_flash", context_axis="context"))
    params = lm.init_serving_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, cfg.vocab, size=prompt_len).tolist()
    mesh = make_mesh((ring,), ("context",))
    kw = dict(max_batch=1, max_len=prompt_len + 64, prefill_chunk=256,
              cache_dtype=jnp.float32)

    def generate(eng):
        t = time.perf_counter()
        eng.add_request(prompt, max_new_tokens=max_new)
        (req,) = eng.run_to_completion()
        print(f"[ring] {'mesh' if eng.mesh is not None else 'one chip'}: "
              f"{req.status}, tokens {req.generated}, "
              f"{time.perf_counter() - t:.1f} s (compile included)", flush=True)
        return req.generated

    with jax.default_matmul_precision("highest"):
        ring_eng = PagedServeEngine(cfg, params, mesh=mesh, **kw)
        got = generate(ring_eng)
        counters = ring_eng.counters_snapshot()
        pool_devices = sorted(d.id for d in ring_eng.cache.pools["v"].devices())
        del ring_eng
        one = cfg.replace(attention=replace(cfg.attention, context_axis=None))
        want = generate(PagedServeEngine(one, params, **kw))
    # The ring must have run on every ring chip, not only on device 0.
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in mesh.devices.flat]
    print(f"[ring] mesh_prefills={counters['mesh_prefills']}, pool on devices "
          f"{pool_devices}, peak_bytes_in_use per ring device {peaks}",
          flush=True)
    if jax.default_backend() == "tpu" and min(peaks) < RING_MIN_PEAK:
        raise AssertionError(f"a ring device did no work: peaks {peaks}")
    if counters["mesh_prefills"] < 1:
        raise AssertionError("the ring engine never took the mesh prefill path")
    if got != want:
        raise AssertionError(f"ring tokens {got} != one-chip tokens {want}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ring", action="store_true",
                    help="run only the 4-chip ring prefill and its one-chip "
                         "comparison")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.utils.compile_cache import use_compile_cache

    print(f"[cache] compile cache: {use_compile_cache()}", flush=True)
    dev = device_check(4 if args.ring else 1)
    cfg = get_config(ARCH)
    got = {k: getattr(cfg, k) for k in PUBLISHED}
    if got != PUBLISHED:
        raise AssertionError(f"{ARCH} is not at its published widths: {got}")
    if args.ring:
        ring_prefill(cfg, args.seed)
    else:
        kernel_parity(args.seed)
        serve(cfg, args.seed)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
