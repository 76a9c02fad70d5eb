"""The llama-style decoder: its plain reference and its work counts.

**Reference.** Written from the published description in plain
``jax.numpy``, in float32 (``bench/reference.py`` runs it under
``highest`` matmul precision), with no kernel, cache or batching: pre-norm
RMSNorm blocks, rotary positions, multi-head attention with grouped
key/value heads and optional q/k/v biases, a SwiGLU feed-forward, a final
RMSNorm and a tied or untied LM head.  It imports nothing of the program;
it reads the weights the benchmark drew (``bench/weights.py``) by their
names.

Rotary positions rotate adjacent feature pairs ``(2i, 2i+1)`` (the original
LLaMA layout).  The Hugging Face checkpoints rotate the halves
``(i, i + d/2)`` instead; the two are the same model under a fixed
permutation of each head's query and key columns, which random weights do
not see.

Attention runs in blocks of query rows, each against the keys up to its
own end, so a long prompt fits on the chip.  The layer loop is a scan over
the stacked weights, each layer cast to float32 inside the loop.

**Work.** Every layer is attention plus a SwiGLU MLP, and every layer
keeps keys and values in the paged pool; counted by the rules of
``bench/work.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from bench.reference import QUERY_BLOCK, matmul, rms
from bench.work import F32, Work, causal_keys

# -- reference -----------------------------------------------------------------


def spec(conf: dict) -> dict:
    """What the reference needs of a configuration file."""
    return {
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["derived"]["head_dim"],
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf["rms_norm_eps"]),
        "vocab": conf["vocab_size"],
    }


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
        y = matmul(h, att[name]["w"], quant)
        return y + att[name]["b"] if "b" in att[name] else y

    h = rms(x, lp["norm1"]["scale"], eps)
    n = x.shape[0]
    q = proj(h, "wq").reshape(n, spec["n_heads"], dh)
    k = proj(h, "wk").reshape(n, spec["n_kv_heads"], dh)
    v = proj(h, "wv").reshape(n, spec["n_kv_heads"], dh)
    q, k = _rope(q, spec["rope_theta"]), _rope(k, spec["rope_theta"])
    x = x + matmul(_attention(q, k, v), att["wo"]["w"], quant)
    h = rms(x, lp["norm2"]["scale"], eps)
    ffn = lp["ffn"]
    gate = matmul(h, ffn["gate"]["w"], quant)
    up = matmul(h, ffn["up"]["w"], quant)
    return x + matmul(jax.nn.silu(gate) * up, ffn["down"]["w"], quant)


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _logits(params, tokens, rows, *, spec_items, quant):
    spec = dict(spec_items)
    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, spec, quant), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    h = rms(jnp.take(x, rows, axis=0),
             params["final_norm"]["scale"].astype(jnp.float32), spec["norm_eps"])
    if "lm_head" in params:
        head = params["lm_head"]["w"].astype(jnp.float32)
    else:
        head = table.astype(jnp.float32).T
    return matmul(h, head[:, : spec["vocab"]], quant)


def logits(params, spec: dict, tokens, rows, *, quant=None):
    """float32 logits ``(len(rows), vocab)`` at positions ``rows`` of the
    padded ``tokens``."""
    return _logits(params, tokens, rows, spec_items=tuple(sorted(spec.items())),
                   quant=quant)


# -- work ------------------------------------------------------------------------


@dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    weight_bytes: int = 2  # bfloat16
    kv_bytes: int = 2  # bfloat16

    @classmethod
    def from_config(cls, conf: dict) -> "Shape":
        return cls(
            layers=conf["num_hidden_layers"], d=conf["hidden_size"],
            heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"],
            head_dim=conf["derived"]["head_dim"],
            d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
            qkv_bias=conf["derived"]["qkv_bias"],
        )

    # -- per token -----------------------------------------------------

    @property
    def layer_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        mats = self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.d_ff
        return mats + (q + 2 * kv if self.qkv_bias else 0)

    @property
    def layer_matmul_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.d_ff

    @property
    def kv_bytes_per_token(self) -> int:
        """Keys and values of one token over all layers."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes

    @property
    def weight_bytes_total(self) -> int:
        """Layer weights, norm gains (float32 in the layers, as served),
        final norm and one vocab × d matrix for the head (tied or not)."""
        norms = 2 * self.d * F32
        return (self.layers * (self.layer_params * self.weight_bytes + norms)
                + self.d * self.weight_bytes + self.vocab * self.d * self.weight_bytes)

    def attn_flops(self, keys: int) -> int:
        """One query row against ``keys`` keys, all layers: QKᵀ and PV."""
        return self.layers * 4 * self.heads * self.head_dim * keys

    def head_flops(self, rows: int) -> int:
        return 2 * self.d * self.vocab * rows

    def linear_flops(self, rows: int) -> int:
        return 2 * self.layers * self.layer_matmul_params * rows


def kv_bytes_per_token(conf: dict) -> int:
    return Shape.from_config(conf).kv_bytes_per_token


def decode_step(s: Shape, lengths) -> Work:
    """A decode step over the occupied lanes; ``lengths`` are their live
    key counts including the token decoded now."""
    lanes = len(lengths)
    keys = sum(lengths)
    flops = s.linear_flops(lanes) + sum(s.attn_flops(n) for n in lengths) + s.head_flops(lanes)
    kv_read = (keys - lanes) * s.kv_bytes_per_token
    kv_write = lanes * s.kv_bytes_per_token
    emb = lanes * s.d * s.weight_bytes
    logits = lanes * s.vocab * F32
    return Work(flops, s.weight_bytes_total + kv_read + kv_write + emb + logits)


def chunk_step(s: Shape, start: int, n: int, final: bool) -> Work:
    """One chunked-prefill window: rows ``start .. start+n−1`` of a prompt;
    ``final`` when it completes the prompt, so its last row's logits are
    used."""
    head_rows = 1 if final else 0
    flops = (s.linear_flops(n) + s.attn_flops(causal_keys(start, n))
             + s.head_flops(head_rows))
    weights = s.weight_bytes_total - (0 if final else s.vocab * s.d * s.weight_bytes)
    kv = (start + n) * s.kv_bytes_per_token  # prefix read, new rows written
    emb = n * s.d * s.weight_bytes
    return Work(flops, weights + kv + emb + head_rows * s.vocab * F32)


def _kernel_io(s: Shape, rows: int) -> int:
    """Queries read and outputs written, bfloat16, all layers."""
    return 2 * rows * s.layers * s.heads * s.head_dim * s.kv_bytes


def decode_kernel(s: Shape, lengths) -> Work:
    """The paged attention kernel at q_len 1, all layers: live keys and
    values read once per lane."""
    flops = sum(s.attn_flops(n) for n in lengths)
    kv = sum(lengths) * s.kv_bytes_per_token
    return Work(flops, kv + _kernel_io(s, len(lengths)))


def chunk_kernel(s: Shape, start: int, n: int) -> Work:
    """The banded paged kernel for one prefill window, all layers."""
    kv = (start + n) * s.kv_bytes_per_token
    return Work(s.attn_flops(causal_keys(start, n)), kv + _kernel_io(s, n))


def _entry(kind: str, count, *keys):
    """A ``WORK`` entry: ``count`` of a logged call of ``kind``, given the
    call's ``keys``; None for a call of the other kind."""
    def work_of(conf: dict, call: dict) -> Work | None:
        if call["kind"] != kind:
            return None
        return count(Shape.from_config(conf), *(call[k] for k in keys))

    return work_of


WORK = {
    "decode_step": _entry("decode", decode_step, "lengths"),
    "chunk_step": _entry("chunk", chunk_step, "start", "n", "final"),
    "decode_kernel": _entry("decode", decode_kernel, "lengths"),
    "chunk_kernel": _entry("chunk", chunk_kernel, "start", "n"),
}
