"""Every Pallas kernel of the serving and training paths compiles for a
TPU v5e at the benchmark models' head layouts.

The v5e topology is *described* (``jax.experimental.topologies``), not
attached: XLA's TPU compiler runs here and refuses what the chip would
refuse — block shapes off the (8, 128) tiling, VMEM overflows — which
interpret mode cannot see.  Nothing runs.  The topology is built in a
module fixture (never at import), so every test worker collects the same
tests and only the worker that runs this file loads the TPU library.

Layouts: minicpm-2b (36 × 64 MHA) and qwen2.5-32b (40:8 × 128 GQA).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.distr_attention import DistrConfig
from repro.kernels import ops
from repro.kernels.paged_decode import heads_per_step

LAYOUTS = {"36x64": (36, 36, 64), "40:8x128": (40, 8, 128)}
N = 512  # prefill / training sequence
POOL_BLOCKS, BLOCK, TABLE = 129, 128, 16  # paged pool of the serving engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(sharding, layout, n=N):
    hq, hkv, d = LAYOUTS[layout]
    q = jax.ShapeDtypeStruct((1, hq, n, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, hkv, n, d), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


def _grads(attn):
    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, interpret=False)


def _distr(q, k, v):
    return ops.distr_attention(
        q, k, v, DistrConfig(), causal=True, interpret=False
    )


@pytest.mark.parametrize("layout", LAYOUTS)
def test_flash_forward_with_residuals_compiles(one_chip, layout):
    d = LAYOUTS[layout][2]

    def fwd(q, k, v):
        return ops._flash_fwd_impl(  # noqa: SLF001 — the LSE-emitting path
            True, d**-0.5, 128, 128, False, q, k, v, with_residuals=True
        )

    _compile(fwd, *_qkv(one_chip, layout))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_flash_backward_compiles(one_chip, layout):
    _compile(_grads(_flash), *_qkv(one_chip, layout))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_distr_forward_compiles(one_chip, layout):
    _compile(_distr, *_qkv(one_chip, layout))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_distr_backward_compiles(one_chip, layout):
    _compile(_grads(_distr), *_qkv(one_chip, layout))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_split_k_decode_compiles(one_chip, layout):
    hq, hkv, d = LAYOUTS[layout]
    b, s = 8, 2048

    def dec(q, k, v, lengths):
        return ops.decode_attention(
            q, k, v, lengths=lengths, block_k=512, interpret=False
        )

    f = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    _compile(dec, f((b, hq, 1, d)), f((b, hkv, s, d)), f((b, hkv, s, d)),
             f((b,), jnp.int32))


@pytest.mark.parametrize("q_len", [1, 256])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_paged_decode_compiles(one_chip, layout, q_len):
    """Token decode (8 lanes) and one 256-token chunked-prefill window over
    the serving engine's (L, P, bs, Hkv·d) pool stack, read at a traced
    layer."""
    hq, hkv, d = LAYOUTS[layout]
    b = 8 if q_len == 1 else 1

    def dec(q, k_pool, v_pool, tables, lengths, layer):
        return ops.paged_decode_attention(
            q, k_pool, v_pool, block_tables=tables, lengths=lengths,
            layer=layer, interpret=False,
        )

    f = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip
    )
    pool = f((4, POOL_BLOCKS, BLOCK, hkv * d))
    _compile(dec, f((b, hq, q_len, d)), pool, pool, f((b, TABLE), jnp.int32),
             f((b,), jnp.int32), f((), jnp.int32))


def test_paged_heads_per_step_fill_lanes():
    assert heads_per_step(36, 64, 64) == 2
    assert heads_per_step(36, 32, 64) == 4  # fused K̂ at G* = 2
    assert heads_per_step(8, 128, 128) == 1
    assert heads_per_step(2, 32, 32) == 2  # narrower than a tile: whole row


def test_ring_flash_forward_compiles_on_four_chips(topo):
    """The context ring over the four chips of a v5e 2×2: per-shard flash
    kernels with LSE residuals, merged across ``ppermute`` hops."""
    from repro.distributed.ring_attention import ring_flash_attention

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("context",))
    seq = NamedSharding(mesh, P(None, None, "context", None))
    q, k, v = _qkv(seq, "36x64", n=2048)

    def ring(q, k, v):
        return ring_flash_attention(
            q, k, v, mesh, axis="context", causal=True, interpret=False
        )

    compiled = _compile(ring, q, k, v)
    assert "collective-permute" in compiled.as_text()
