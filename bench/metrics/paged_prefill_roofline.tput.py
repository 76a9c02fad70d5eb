"""The paged attention kernel at q_len prefill_chunk, banded (inside prefill
chunk steps): least time for its live keys at the chip's peaks over its
device time in the trace."""
from bench.metrics._common import step_share

NAME, UNIT, BETTER, SOURCE = "paged_prefill_roofline.tput", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def compute(record):
    return step_share(record, "chunk_kernel", "chunk", "kernel_s")
