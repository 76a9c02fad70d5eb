"""The comparison behind `correct`, and what every architecture's plain
reference shares.

Each architecture's reference is a module of its own
(``bench/architectures/<name>.py``, named by the configuration file), whose
``logits(params, spec, tokens, rows, *, quant=None)`` is the plain forward
pass in float32.  This module pads its inputs, runs it under ``highest``
matmul precision, and compares what the program served with it.

``quant`` gives the controls, the steps down from bfloat16 that a later
change would be tempted to take: every matrix product (``matmul``) takes
its weights and its activations in ``"fp8"`` (float8 e4m3) or ``"int8"``,
the weights scaled per output channel and the activations per row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Tokens are padded to a multiple of this many rows: the query block of the
# references' attention.
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


def matmul(x, w, quant):
    """``x @ w``, both rounded to ``quant`` first where it is given."""
    if quant is not None:
        x, w = _quantize(x, -1, quant), _quantize(w, 0, quant)
    return x @ w


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def logits(arch, params, spec: dict, tokens, rows, *, length: int, n_rows: int,
           quant=None) -> np.ndarray:
    """float32 logits ``(len(rows), vocab)`` of architecture module ``arch``
    at positions ``rows`` of ``tokens``.  Tokens are right-padded to
    ``length`` and rows to ``n_rows``, so one program serves every request
    of a run."""
    if len(tokens) > length or len(rows) > n_rows:
        raise ValueError(f"{len(tokens)} tokens / {len(rows)} rows exceed "
                         f"the padded {length} / {n_rows}")
    padded = np.zeros((length,), np.int32)
    padded[: len(tokens)] = tokens
    at = np.full((n_rows,), rows[-1], np.int32)
    at[: len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        out = arch.logits(params, spec, jnp.asarray(padded), jnp.asarray(at), quant=quant)
    return np.asarray(out, np.float32)[: len(rows)]


def served_gaps(ref_logits: np.ndarray, served) -> np.ndarray:
    """For each position, how far the served token's reference logit lies
    below the reference's best logit there (0 where they agree)."""
    served = np.asarray(served)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(served)), served]


def check_request(arch, params, spec, prompt, generated, *, length: int,
                  n_rows: int, controls=()) -> dict:
    """The gaps of one finished request's served tokens under the
    reference, teacher-forced on prompt + served tokens: the widest
    (``gap``) and their sum (``gap_sum``) over ``tokens`` served tokens.
    For each quantisation in ``controls`` the same two readings for the
    tokens that the quantised reference puts first at the same positions
    (``<quant>_gap``, ``<quant>_gap_sum``)."""
    tokens = list(prompt) + list(generated[:-1])
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref = logits(arch, params, spec, tokens, rows, length=length, n_rows=n_rows)
    gaps = {"": served_gaps(ref, generated)}
    for quant in controls:
        low = logits(arch, params, spec, tokens, rows, length=length, n_rows=n_rows,
                     quant=quant)
        gaps[f"{quant}_"] = served_gaps(ref, low.argmax(axis=-1))
    out = {"tokens": len(generated)}
    for pre, g in gaps.items():
        out[f"{pre}gap"] = float(g.max())
        out[f"{pre}gap_sum"] = float(g.sum())
    return out
