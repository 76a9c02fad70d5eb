"""Distributed runtime tests.

Multi-device cases run in a subprocess with 8 forced host devices (the main
test process keeps 1 device so everything else stays fast)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.distributed import sharding as shd

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(body: str):
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        from jax import shard_map
        from jax.sharding import get_abstract_mesh, set_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        """
        % SRC
    ) + textwrap.dedent(body)
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


# ---------------------------------------------------------------------------
# single-process logic
# ---------------------------------------------------------------------------


def test_param_specs_rules():
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    axes = {"w": (None, "mlp"), "e": ("experts", None, None), "s": (None,)}
    shapes = {
        "w": jax.ShapeDtypeStruct((4096, 8192), jnp.float32),
        "e": jax.ShapeDtypeStruct((16, 64, 64), jnp.float32),
        "s": jax.ShapeDtypeStruct((64,), jnp.float32),
    }
    specs = shd.param_pspecs(axes, shapes, mesh, fsdp=True)
    assert specs["w"][1] == "model"
    assert specs["w"][0] == "data"  # FSDP on the large unsharded dim
    assert specs["e"][0] == "experts" or specs["e"][0] == "model"
    assert specs["s"] == jax.sharding.PartitionSpec(None)


def test_param_specs_divisibility_guard():
    import jax.numpy as jnp

    # 16-way axes in the production mesh wouldn't divide 3352 — simulate via
    # rule check with a fake mesh of size 1 (always divides) plus direct call
    spec = shd._spec_for((None, "mlp"), (768, 3352), FakeMesh(), fsdp=False,
                         stacked=False)
    assert spec[1] is None  # dropped: 3352 % 16 != 0


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_dp_axes_for_divisibility():
    mesh = FakeMesh()
    assert shd.dp_axes_for(mesh, 256) == ("data",)
    assert shd.dp_axes_for(mesh, 1) is None


class FakeContextMesh:
    axis_names = ("data", "context", "model")
    shape = {"data": 2, "context": 4, "model": 2}


def test_dp_axes_exclude_context():
    """The batch dim must never shard over the ring axis: each context
    device holds a sequence shard of the *same* batch."""
    assert shd.dp_axes(FakeContextMesh()) == ("data",)


def test_context_shard_len():
    from repro.distributed.ring_attention import context_shard_len

    assert context_shard_len(1024, 8) == 128
    assert context_shard_len(300, 8) == 128  # ceil(300/8)=38 → lane tile
    assert context_shard_len(2048, 8) == 256
    assert context_shard_len(3000, 8) == 384  # 375 → next 128-multiple
    assert context_shard_len(3000, 8, multiple=256) == 512


def test_ring_merge_algebra():
    """The (O, LSE) merge is the online-softmax combine: merging per-shard
    partials must equal the softmax over the concatenated KV."""
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.ring_attention import _merge_partial

    rng = np.random.RandomState(0)
    s1 = jnp.asarray(rng.randn(4, 8) * 3)  # scores vs shard 1 / shard 2
    s2 = jnp.asarray(rng.randn(4, 8) * 3)
    v1 = jnp.asarray(rng.randn(8, 5))
    v2 = jnp.asarray(rng.randn(8, 5))

    def partial(s, v):
        m = s.max(axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=1, keepdims=True)
        return (p @ v) / l, (m + jnp.log(l))[:, 0]

    o1, lse1 = partial(s1, v1)
    o2, lse2 = partial(s2, v2)
    o, lse = _merge_partial(o1[None], lse1[None], o2[None], lse2[None])

    p_full = jax.nn.softmax(jnp.concatenate([s1, s2], axis=1), axis=-1)
    want = p_full @ jnp.concatenate([v1, v2], axis=0)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want), atol=1e-6)
    want_lse = jax.scipy.special.logsumexp(
        jnp.concatenate([s1, s2], axis=1), axis=1
    )
    np.testing.assert_allclose(np.asarray(lse[0]), np.asarray(want_lse),
                               atol=1e-6)
    # merging against an empty partial (init carry) is the identity
    import repro.distributed.ring_attention as ra

    o_id, lse_id = _merge_partial(
        jnp.zeros_like(o1)[None], jnp.full_like(lse1, ra.NEG_INF)[None],
        o1[None], lse1[None],
    )
    np.testing.assert_allclose(np.asarray(o_id[0]), np.asarray(o1), atol=1e-6)


def test_ring_single_device_fallback():
    """A trivial ring (P=1) must collapse to the plain kernel call."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distr_attention import DistrConfig
    from repro.distributed.ring_attention import (
        ring_distr_attention, ring_flash_attention,
    )
    from repro.kernels import ops

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("context",))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 160, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1, 160, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1, 160, 32), jnp.float32)
    out, hops = ring_flash_attention(q, k, v, mesh, causal=True,
                                     return_hops=True)
    ref = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    assert int(hops) == 1
    dcfg = DistrConfig(group_size=2)
    outd = ring_distr_attention(q, k, v, dcfg, mesh, causal=True)
    refd = ops.distr_attention(q, k, v, dcfg, causal=True)
    np.testing.assert_allclose(np.asarray(outd), np.asarray(refd), atol=1e-6)


# ---------------------------------------------------------------------------
# 8-device subprocess integration
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    _run_subprocess(
        """
        from repro.configs import get_config
        from repro.models import lm
        from repro.train.optimizer import OptimizerConfig, adamw_init
        from repro.train.train_step import make_train_step
        from repro.distributed import sharding as shd

        cfg = get_config("qwen1.5-4b", reduced=True)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab),
        }
        opt_cfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=0, total_steps=10)
        step = make_train_step(cfg, opt_cfg)

        # single-device reference
        p1, _, m1 = jax.jit(step)(params, opt, batch, jnp.asarray(0))

        # sharded
        p_shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(0))
        shardings = shd.param_shardings(lm.param_axes(cfg), p_shapes, mesh, fsdp=True)
        params_s = jax.tree_util.tree_map(jax.device_put, params, shardings)
        batch_s = {k: jax.device_put(v, NamedSharding(mesh, P("data"))) for k, v in batch.items()}
        with set_mesh(mesh):
            p2, _, m2 = jax.jit(step)(params_s, jax.tree_util.tree_map(jnp.asarray, opt), batch_s, jnp.asarray(0))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (m1["loss"], m2["loss"])
        d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)).max()), p1, p2)
        worst = max(jax.tree_util.tree_leaves(d))
        assert worst < 5e-3, worst
        print("SHARDED OK", worst)
        """
    )


@pytest.mark.slow
def test_moe_ep_paths_match_dense():
    _run_subprocess(
        """
        from repro.configs import get_config
        from repro.models import moe, lm

        cfg = get_config("llama4-scout-17b-a16e", reduced=True)
        cfg = cfg.replace(capacity_factor=8.0)  # no drops: paths comparable
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

        y_ref, aux_ref = moe._moe_dense_onehot(params, x, cfg)
        with set_mesh(mesh):
            am = get_abstract_mesh()
            y_a2a, aux_a2a = jax.jit(lambda p, xx: moe._moe_ep_a2a(p, xx, cfg, am))(params, x)
            y_psum, aux_psum = jax.jit(lambda p, xx: moe._moe_ep_psum(p, xx, cfg, am))(params, x)
        e1 = float(jnp.abs(y_ref - y_a2a).max())
        e2 = float(jnp.abs(y_ref - y_psum).max())
        assert e1 < 1e-3, e1
        assert e2 < 1e-3, e2
        print("MOE OK", e1, e2)
        """
    )


@pytest.mark.slow
def test_pipeline_matches_sequential():
    _run_subprocess(
        """
        from repro.distributed.pipeline import pipeline_apply, stage_split
        mesh2 = make_mesh((4, 2), ("pod", "model"))
        L, D = 8, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.2

        def stage_fn(stage_params, x):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, stage_params)
            return y

        x = jax.random.normal(jax.random.PRNGKey(1), (6, 4, D))  # 6 microbatches
        stages = stage_split(ws, 4)  # (4, 2, D, D)
        with set_mesh(mesh2):
            out = pipeline_apply(stage_fn, stages, x, mesh2, axis="pod")
        want = jax.vmap(lambda mb: stage_fn(ws, mb))(x)
        err = float(jnp.abs(out - want).max())
        assert err < 1e-5, err
        print("PIPELINE OK", err)
        """
    )


@pytest.mark.slow
def test_ring_allgather_matmul_and_psum_scatter():
    _run_subprocess(
        """
        from repro.distributed.collectives import (
            psum_scatter_matmul, ring_allgather_matmul,
        )

        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        with set_mesh(mesh):
            y = ring_allgather_matmul(x, w, mesh, axis="model")
            y2 = psum_scatter_matmul(x, w, mesh, axis="model")
        err = float(jnp.abs(y - x @ w).max())
        assert err < 1e-4, err
        err2 = float(jnp.abs(jnp.asarray(y2) - x @ w).max())
        assert err2 < 1e-4, err2
        print("COLLECTIVES OK", err, err2)
        """
    )


@pytest.mark.slow
def test_ring_flash_parity_8dev():
    """Ring flash == single-device kernel (fwd + grads) on 8 virtual
    devices, f32 + bf16, causal + non-causal, ragged length; the hop probe
    confirms causal rings and dead shards skip kernel launches."""
    _run_subprocess(
        """
        from repro.distributed.ring_attention import ring_flash_attention
        from repro.kernels import ops
        ring = make_mesh((8,), ("context",))
        B, Hq, Hkv, N, D = 2, 4, 2, 300, 64  # ragged: 3 live shards of 128
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        qf = jax.random.normal(ks[0], (B, Hq, N, D), jnp.float32)
        kf = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.float32)
        vf = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.float32)
        w = jax.random.normal(ks[3], (B, Hq, N, D), jnp.float32)
        for dtype, ftol, gtol in ((jnp.float32, 2e-5, 5e-5),
                                  (jnp.bfloat16, 2e-2, 2e-1)):
            q, k, v = (x.astype(dtype) for x in (qf, kf, vf))
            for causal in (False, True):
                out, hops = jax.jit(lambda q, k, v: ring_flash_attention(
                    q, k, v, ring, causal=causal, return_hops=True))(q, k, v)
                ref = ops.flash_attention(q, k, v, causal=causal)
                err = float(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32)).max())
                assert err < ftol, (dtype, causal, err)
                # N=300 → live shards {0,1,2}: non-causal runs 3×3 hops,
                # causal 1+2+3; both far below the naive 8×8.
                assert int(hops) == (6 if causal else 9), (causal, int(hops))
                gr = jax.jit(jax.grad(
                    lambda q, k, v: (ring_flash_attention(
                        q, k, v, ring, causal=causal
                    ).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)
                ))(q, k, v)
                gs = jax.grad(
                    lambda q, k, v: (ops.flash_attention(
                        q, k, v, causal=causal
                    ).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)
                )(q, k, v)
                gerr = max(float(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32)).max())
                           for a, b in zip(gr, gs))
                assert gerr < gtol, (dtype, causal, gerr)
        print("RING FLASH OK")
        """
    )


@pytest.mark.slow
def test_ring_distr_parity_8dev():
    """Ring DistrAttention == single-device distr kernel: shard-local LSH
    grouping derives identical permutations when shards are block-aligned,
    so outputs (and straight-through grads) match."""
    _run_subprocess(
        """
        from repro.core.distr_attention import DistrConfig
        from repro.distributed.ring_attention import ring_distr_attention
        from repro.kernels import ops
        ring = make_mesh((8,), ("context",))
        B, Hq, Hkv, N, D = 2, 4, 2, 300, 64
        dcfg = DistrConfig(group_size=2)  # block_q=128: the grouping grain
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        qf = jax.random.normal(ks[0], (B, Hq, N, D), jnp.float32)
        kf = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.float32)
        vf = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.float32)
        w = jax.random.normal(ks[3], (B, Hq, N, D), jnp.float32)
        for dtype, ftol, gtol in ((jnp.float32, 2e-5, 5e-5),
                                  (jnp.bfloat16, 2e-2, 2e-1)):
            q, k, v = (x.astype(dtype) for x in (qf, kf, vf))
            for causal in (False, True):
                out, hops = jax.jit(lambda q, k, v: ring_distr_attention(
                    q, k, v, dcfg, ring, causal=causal, return_hops=True
                ))(q, k, v)
                ref = ops.distr_attention(q, k, v, dcfg, causal=causal)
                err = float(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32)).max())
                assert err < ftol, (dtype, causal, err)
                assert int(hops) == (6 if causal else 9), (causal, int(hops))
                gr = jax.jit(jax.grad(
                    lambda q, k, v: (ring_distr_attention(
                        q, k, v, dcfg, ring, causal=causal
                    ).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)
                ))(q, k, v)
                gs = jax.grad(
                    lambda q, k, v: (ops.distr_attention(
                        q, k, v, dcfg, causal=causal
                    ).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)
                )(q, k, v)
                gerr = max(float(jnp.abs(a.astype(jnp.float32)
                                         - b.astype(jnp.float32)).max())
                           for a, b in zip(gr, gs))
                assert gerr < gtol, (dtype, causal, gerr)
        print("RING DISTR OK")
        """
    )


@pytest.mark.slow
def test_ring_distr_shared_kv_perm_parity_8dev():
    """Ring DistrAttention with shared_kv_perm (one permutation per KV
    group, derived from the group's mean query block) == the single-device
    kernel, fwd + grads.  This used to raise NotImplementedError under the
    ring; stage 1 now runs the shared ops.distr_stage1 outside shard_map,
    so the variant composes for free."""
    _run_subprocess(
        """
        from repro.core.distr_attention import DistrConfig
        from repro.distributed.ring_attention import ring_distr_attention
        from repro.kernels import ops
        ring = make_mesh((8,), ("context",))
        B, Hq, Hkv, N, D = 2, 4, 2, 300, 64
        dcfg = DistrConfig(group_size=2, shared_kv_perm=True)
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, Hq, N, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.float32)
        w = jax.random.normal(ks[3], (B, Hq, N, D), jnp.float32)
        for causal in (False, True):
            out, hops = jax.jit(lambda q, k, v: ring_distr_attention(
                q, k, v, dcfg, ring, causal=causal, return_hops=True
            ))(q, k, v)
            ref = ops.distr_attention(q, k, v, dcfg, causal=causal)
            err = float(jnp.abs(out - ref).max())
            assert err < 2e-5, (causal, err)
            assert int(hops) == (6 if causal else 9), (causal, int(hops))
            gr = jax.jit(jax.grad(
                lambda q, k, v: (ring_distr_attention(
                    q, k, v, dcfg, ring, causal=causal
                ) * w).sum(), argnums=(0, 1, 2)
            ))(q, k, v)
            gs = jax.grad(
                lambda q, k, v: (ops.distr_attention(
                    q, k, v, dcfg, causal=causal
                ) * w).sum(), argnums=(0, 1, 2)
            )(q, k, v)
            gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(gr, gs))
            assert gerr < 5e-5, (causal, gerr)
        print("RING SHARED PERM OK")
        """
    )


@pytest.mark.slow
def test_attend_context_axis_dispatch_8dev():
    """core.api.attend routes to the ring under an active mesh with the
    configured context axis — including a mixed (data, context, model) mesh
    where batch and heads shard over their own axes — and falls back to the
    single-device kernel for short sequences."""
    _run_subprocess(
        """
        from repro.core import attend, AttentionConfig, DistrConfig
        ring = make_mesh((2, 2, 2), ("data", "context", "model"))
        B, Hq, Hkv, N, D = 2, 4, 2, 512, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, N, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, Hkv, N, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, Hkv, N, D), jnp.float32)
        cfg = AttentionConfig(impl="pallas_flash", context_axis="context")
        ref = attend(q, k, v, cfg.with_impl("pallas_flash"), causal=True)
        with set_mesh(ring):
            out = jax.jit(lambda q, k, v: attend(q, k, v, cfg, causal=True))(
                q, k, v)
        err = float(jnp.abs(out - ref).max())
        assert err < 2e-5, err
        # short sequence: below ring_size × 128 the dispatch stays local
        qs, ks_, vs = q[:, :, :96], k[:, :, :96], v[:, :, :96]
        with set_mesh(ring):
            outs = jax.jit(lambda q, k, v: attend(q, k, v, cfg, causal=True))(
                qs, ks_, vs)
        refs = attend(qs, ks_, vs, cfg.with_impl("pallas_flash"), causal=True)
        errs = float(jnp.abs(outs - refs).max())
        assert errs < 2e-5, errs
        print("ATTEND DISPATCH OK", err, errs)
        """
    )


@pytest.mark.slow
def test_pipeline_drain_ticks_inject_zeros():
    """Regression (drain-tick re-injection): every stage must see each
    microbatch exactly once — stage 0 used to re-inject microbatch M-1 on
    every drain tick, so stages recomputed it S-1 extra times.  Microbatch
    identity is encoded in the data (constant value m+1) and an identity
    stage_fn records what each stage actually processes."""
    _run_subprocess(
        """
        from collections import Counter
        from repro.distributed.pipeline import pipeline_apply
        mesh2 = make_mesh((4, 1), ("pod", "model"))
        S, M, MB, D = 4, 6, 2, 8
        seen = []
        def record(stage, val):
            seen.append((int(stage), round(float(val), 3)))
        def stage_fn(params, x):
            jax.debug.callback(record, jax.lax.axis_index("pod"), x[0, 0])
            return x
        x = jnp.broadcast_to(
            (jnp.arange(M, dtype=jnp.float32) + 1.0)[:, None, None], (M, MB, D)
        )
        ws = jnp.zeros((S, 1))
        with set_mesh(mesh2):
            out = pipeline_apply(stage_fn, ws, x, mesh2, axis="pod")
        jax.effects_barrier()
        err = float(jnp.abs(out - x).max())
        assert err < 1e-6, err
        counts = Counter((s, v) for s, v in seen if v != 0.0)
        assert len(counts) == S * M, sorted(counts)
        dupes = {k: c for k, c in counts.items() if c != 1}
        assert not dupes, f"stage saw a microbatch more than once: {dupes}"
        print("PIPELINE DRAIN OK")
        """
    )


@pytest.mark.slow
def test_serve_engine_ring_prefill_matches_single_device():
    """ServeEngine(mesh=...) long-prompt prefill rides the context ring;
    the generated (greedy) tokens must match a mesh-less engine, and the
    ring-produced KV cache must interoperate with the single-device decode
    step."""
    _run_subprocess(
        """
        from dataclasses import replace as dc_replace
        from repro.configs import get_config
        from repro.models import lm
        from repro.serve.engine import ServeEngine

        cfg = get_config("qwen1.5-4b", reduced=True)
        cfg = cfg.replace(attention=dc_replace(
            cfg.attention, impl="pallas_flash", context_axis="context"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        ring = make_mesh((2,), ("context",))
        prompt = list(np.random.RandomState(0).randint(
            0, cfg.vocab, size=300))  # bucket 512 ≥ ring×128 → ring prefill

        eng = ServeEngine(cfg, params, max_slots=2, max_len=512, mesh=ring)
        eng.add_request(prompt, max_new_tokens=3)
        got = eng.run_to_completion()[0].generated

        cfg0 = cfg.replace(attention=dc_replace(
            cfg.attention, context_axis=None))
        eng0 = ServeEngine(cfg0, params, max_slots=2, max_len=512)
        eng0.add_request(prompt, max_new_tokens=3)
        want = eng0.run_to_completion()[0].generated
        assert got == want, (got, want)
        print("SERVE RING OK", got)
        """
    )


@pytest.mark.slow
def test_paged_engine_mesh_prefill_matches_single_device():
    """ISSUE 9 acceptance: a long prompt on PagedServeEngine(mesh=...)
    prefills whole across the context ring in one tick (mesh_prefills
    counter), lands its KV in the block pool spanning ≥ 3 blocks, and the
    paged greedy decode matches a mesh-less engine token for token.  The
    cluster router steers the long prompt to the mesh-capable replica and
    away from a short-cache one."""
    _run_subprocess(
        """
        from dataclasses import replace as dc_replace
        from repro.configs import get_config
        from repro.models import lm
        from repro.serve.cluster import ClusterRouter
        from repro.serve.engine import PagedServeEngine
        from repro.serve import lifecycle

        cfg = get_config("qwen1.5-4b", reduced=True)
        cfg = cfg.replace(attention=dc_replace(
            cfg.attention, impl="pallas_flash", context_axis="context"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        ring = make_mesh((2,), ("context",))
        prompt = list(np.random.RandomState(0).randint(
            0, cfg.vocab, size=300))  # bucket 512 ≥ ring×128 → ring prefill

        eng = PagedServeEngine(
            cfg, params, max_batch=2, max_len=512, block_size=128,
            prefill_chunk=32, cache_dtype=jnp.float32, mesh=ring)
        assert eng.cache.blocks_for(len(prompt)) >= 3  # spans ≥ 3 blocks
        eng.add_request(prompt, max_new_tokens=3)
        got = eng.run_to_completion()[0].generated
        assert eng.counters_snapshot()["mesh_prefills"] == 1

        cfg0 = cfg.replace(attention=dc_replace(
            cfg.attention, context_axis=None))
        eng0 = PagedServeEngine(
            cfg0, params, max_batch=2, max_len=512, block_size=128,
            prefill_chunk=32, cache_dtype=jnp.float32)
        eng0.add_request(prompt, max_new_tokens=3)
        want = eng0.run_to_completion()[0].generated
        assert got == want, (got, want)

        # capability routing: a short-cache replica never sees the prompt
        short = PagedServeEngine(
            cfg0, params, max_batch=2, max_len=64, block_size=64,
            prefill_chunk=32, cache_dtype=jnp.float32)
        router = ClusterRouter([short, eng], policy="round_robin")
        uid = router.add_request(prompt, max_new_tokens=3)
        assert router.request(uid).rid == 1, "long prompt missed the mesh replica"
        router.run_to_completion(max_ticks=600)
        creq = router.request(uid)
        assert creq.status == lifecycle.DONE
        assert creq.emitted == want, (creq.emitted, want)
        print("MESH PAGED OK", got)
        """
    )


@pytest.mark.slow
def test_context_parallel_train_step_matches_single_device():
    """End-to-end train wiring: a Pallas-attention train step under a
    (data, context) mesh — ring attention inside the jitted loss/grads —
    matches the single-device step."""
    _run_subprocess(
        """
        from dataclasses import replace as dc_replace
        from repro.configs import get_config
        from repro.models import lm
        from repro.train.optimizer import OptimizerConfig, adamw_init
        from repro.train.train_step import make_train_step

        cfg = get_config("qwen1.5-4b", reduced=True)
        cfg = cfg.replace(attention=dc_replace(
            cfg.attention, impl="pallas_flash"))
        seq = 512  # ≥ ring size × 128 so the ring engages
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        batch = {
            "tokens": jax.random.randint(
                jax.random.PRNGKey(1), (2, seq), 0, cfg.vocab),
            "labels": jax.random.randint(
                jax.random.PRNGKey(2), (2, seq), 0, cfg.vocab),
        }
        opt_cfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=0, total_steps=10)
        step = make_train_step(cfg, opt_cfg)
        p1, _, m1 = jax.jit(step)(params, opt, batch, jnp.asarray(0))

        cfg_cp = cfg.replace(attention=dc_replace(
            cfg.attention, context_axis="context"))
        step_cp = make_train_step(cfg_cp, opt_cfg)
        mesh_cp = make_mesh((2, 4), ("data", "context"))
        with set_mesh(mesh_cp):
            p2, _, m2 = jax.jit(step_cp)(
                params, jax.tree_util.tree_map(jnp.asarray, opt), batch,
                jnp.asarray(0))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (
            m1["loss"], m2["loss"])
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(jnp.asarray(a, jnp.float32)
                                       - jnp.asarray(b, jnp.float32)).max()),
            p1, p2)
        worst = max(jax.tree_util.tree_leaves(d))
        assert worst < 5e-3, worst
        print("CONTEXT TRAIN OK", float(m1["loss"]), worst)
        """
    )


@pytest.mark.slow
def test_ef_pmean_compressed_allreduce():
    _run_subprocess(
        """
        from repro.train.compression import ef_pmean

        g = jax.random.normal(jax.random.PRNGKey(2), (2, 16))

        def local(gl):
            mean, new_r = ef_pmean({"g": gl}, {"g": jnp.zeros_like(gl)}, "data")
            return mean["g"], new_r["g"]

        gs = jax.device_put(g, NamedSharding(mesh, P("data", None)))
        with set_mesh(mesh):
            mean_g, _ = jax.jit(shard_map(
                local, mesh=mesh,
                in_specs=P("data", None),
                out_specs=(P("data", None), P("data", None)),
            ))(gs)
        exact = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
        # int8 quantization error bound: scale/2 per shard
        err = float(jnp.abs(jnp.asarray(mean_g) - exact).max())
        assert err < float(jnp.abs(g).max()) / 127 + 1e-5, err
        print("EF PMEAN OK", err)
        """
    )
