"""Plain reference of the served models, and the comparison behind `correct`.

A llama-style decoder written from the published description in plain
``jax.numpy``, in float32 under ``highest`` matmul precision, with no
kernel, cache or batching: pre-norm RMSNorm blocks, rotary positions,
multi-head attention with grouped key/value heads and optional q/k/v
biases, a SwiGLU feed-forward, a final RMSNorm and a tied or untied LM
head.  It imports nothing of the program; it reads the weights the
benchmark drew (``bench/weights.py``) by their names.

Rotary positions rotate adjacent feature pairs ``(2i, 2i+1)`` (the original
LLaMA layout).  The Hugging Face checkpoints rotate the halves
``(i, i + d/2)`` instead; the two are the same model under a fixed
permutation of each head's query and key columns, which random weights do
not see.

Attention runs in blocks of query rows, each against the keys up to its
own end, so a long prompt fits on the chip.  The layer loop is a scan over
the stacked weights, each layer cast to float32 inside the loop.

``quant`` gives the controls, the steps down from bfloat16 that a later
change would be tempted to take: every matrix product takes its weights
and its activations in ``"fp8"`` (float8 e4m3) or ``"int8"``, the weights
scaled per output channel and the activations per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


QUANTS = ("fp8", "int8")


def _quantize(x, axis, quant):
    """``x`` rounded to ``quant`` with one scale per slice along ``axis``,
    and back to float32."""
    top = 448.0 if quant == "fp8" else 127.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "fp8":
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q = jnp.clip(jnp.round(x / scale), -127, 127)
    return q * scale


def _matmul(x, w, quant):
    if quant is not None:
        x, w = _quantize(x, -1, quant), _quantize(w, 0, quant)
    return x @ w


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (N, H, d); rotate pairs (2i, 2i+1) by position · theta^(−2i/d)."""
    n, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _attention(q, k, v):
    """Causal attention, q: (N, H, d); k, v: (N, Hkv, d) → (N, H·d)."""
    n, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    out = []
    for start in range(0, n, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, n)
        s = jnp.einsum("qhd,khd->hqk", q[start:stop], k[:stop]) * d**-0.5
        row = jnp.arange(start, stop)[:, None]
        s = jnp.where(jnp.arange(stop)[None, :] <= row, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:stop]))
    return jnp.concatenate(out, axis=0).reshape(n, h * d)


def _layer(x, lp, spec, quant):
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    eps, dh = spec["norm_eps"], spec["head_dim"]
    att = lp["attn"]

    def proj(h, name):
        y = _matmul(h, att[name]["w"], quant)
        return y + att[name]["b"] if "b" in att[name] else y

    h = _rms(x, lp["norm1"]["scale"], eps)
    n = x.shape[0]
    q = proj(h, "wq").reshape(n, spec["n_heads"], dh)
    k = proj(h, "wk").reshape(n, spec["n_kv_heads"], dh)
    v = proj(h, "wv").reshape(n, spec["n_kv_heads"], dh)
    q, k = _rope(q, spec["rope_theta"]), _rope(k, spec["rope_theta"])
    x = x + _matmul(_attention(q, k, v), att["wo"]["w"], quant)
    h = _rms(x, lp["norm2"]["scale"], eps)
    ffn = lp["ffn"]
    gate = _matmul(h, ffn["gate"]["w"], quant)
    up = _matmul(h, ffn["up"]["w"], quant)
    return x + _matmul(jax.nn.silu(gate) * up, ffn["down"]["w"], quant)


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _logits(params, tokens, rows, *, spec_items, quant):
    spec = dict(spec_items)
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, spec, quant), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    h = _rms(jnp.take(x, rows, axis=0),
             params["final_norm"]["scale"].astype(jnp.float32), spec["norm_eps"])
    if "lm_head" in params:
        head = params["lm_head"]["w"].astype(jnp.float32)
    else:
        head = table.astype(jnp.float32).T
    return _matmul(h, head[:, : spec["vocab"]], quant)


def logits(params, spec: dict, tokens, rows, *, length: int, n_rows: int,
           quant=None) -> np.ndarray:
    """float32 logits ``(len(rows), vocab)`` at positions ``rows`` of
    ``tokens``.  Tokens are right-padded to ``length`` and rows to
    ``n_rows``, so one program serves every request of a run."""
    if len(tokens) > length or len(rows) > n_rows:
        raise ValueError(f"{len(tokens)} tokens / {len(rows)} rows exceed "
                         f"the padded {length} / {n_rows}")
    padded = np.zeros((length,), np.int32)
    padded[: len(tokens)] = tokens
    at = np.full((n_rows,), rows[-1], np.int32)
    at[: len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        out = _logits(params, jnp.asarray(padded), jnp.asarray(at),
                      spec_items=tuple(sorted(spec.items())), quant=quant)
    return np.asarray(out, np.float32)[: len(rows)]


def served_gaps(ref_logits: np.ndarray, served) -> np.ndarray:
    """For each position, how far the served token's reference logit lies
    below the reference's best logit there (0 where they agree)."""
    served = np.asarray(served)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(served)), served]


def check_request(params, spec, prompt, generated, *, length: int,
                  n_rows: int, controls=()) -> dict:
    """The gaps of one finished request's served tokens under the
    reference, teacher-forced on prompt + served tokens: the widest
    (``gap``) and their sum (``gap_sum``) over ``tokens`` served tokens.
    For each quantisation in ``controls`` the same two readings for the
    tokens that the quantised reference puts first at the same positions
    (``<quant>_gap``, ``<quant>_gap_sum``)."""
    tokens = list(prompt) + list(generated[:-1])
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref = logits(params, spec, tokens, rows, length=length, n_rows=n_rows)
    gaps = {"": served_gaps(ref, generated)}
    for quant in controls:
        low = logits(params, spec, tokens, rows, length=length, n_rows=n_rows,
                     quant=quant)
        gaps[f"{quant}_"] = served_gaps(ref, low.argmax(axis=-1))
    out = {"tokens": len(generated)}
    for pre, g in gaps.items():
        out[f"{pre}gap"] = float(g.max())
        out[f"{pre}gap_sum"] = float(g.sum())
    return out
