"""The paged attention kernel at q_len 1 (inside decode steps): least time
for its live keys at the chip's peaks over its device time in the trace."""
from bench.metrics._common import step_share

NAME, UNIT, BETTER, SOURCE = "paged_decode_roofline.itl", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "itl_p95_ms"


def compute(record):
    return step_share(record, "decode_kernel", "decode", "kernel_s")
