"""Operations and bytes that the requests need, from shapes and live lengths.

Counts follow the work, not an implementation of it:

- only occupied decode lanes, never idle ones;
- only live keys: a query row at absolute position ``p`` attends ``p + 1``
  keys, whatever blocks, splits or padding a kernel walks;
- only the logits rows that are used: a decode lane's row, and a prefill
  chunk's last row when it completes the prompt;
- attention at its exact width, so the count does not change with the
  mechanism that computes it.

Operations are the matrix products' multiply-adds times two (the chip's
peak counts those).  Bytes are the least that must cross HBM: every weight
read once per step, the live keys and values read once per request and
layer, the new keys and values written, the embedding rows gathered and the
used logits rows written (float32).  Activations between layers are left
out (they can stay on the chip).
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


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


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, other: "Work") -> "Work":
        self.flops += other.flops
        self.bytes += other.bytes
        return self

    def seconds(self, peaks: dict) -> tuple[float, str]:
        """Least time on the chip, and which term binds."""
        t_f = self.flops / peaks["flops_bf16"]
        t_b = self.bytes / peaks["hbm_bytes_per_s"]
        return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def _causal_keys(start: int, n: int) -> int:
    """Keys seen by rows start .. start+n−1 (row p sees p + 1 keys)."""
    return n * start + n * (n + 1) // 2


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
    flops = (s.linear_flops(n) + s.attn_flops(_causal_keys(start, n))
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
    return Work(s.attn_flops(_causal_keys(start, n)), kv + _kernel_io(s, n))
