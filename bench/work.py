"""Operations and bytes that the requests need, from shapes and live lengths.

Each architecture counts its own (``WORK`` in
``bench/architectures/<name>.py``), by the same rules:

- only occupied decode lanes, never idle ones;
- only live keys: a query row at absolute position ``p`` attends ``p + 1``
  keys, whatever blocks, splits or padding a kernel walks;
- only the logits rows that are used: a decode lane's row, and a prefill
  chunk's last row when it completes the prompt;
- attention at its exact width, so the count does not change with the
  mechanism that computes it.

Operations are the matrix products' multiply-adds times two (the chip's
peak counts those).  Bytes are the least that must cross HBM: every weight
read once per step, the live keys and values (and any recurrent state) read
once per request and layer, the new keys and values written, the embedding
rows gathered and the used logits rows written (float32).  Activations
between layers are left out (they can stay on the chip).
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


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


def causal_keys(start: int, n: int) -> int:
    """Keys seen by rows start .. start+n−1 (row p sees p + 1 keys)."""
    return n * start + n * (n + 1) // 2
