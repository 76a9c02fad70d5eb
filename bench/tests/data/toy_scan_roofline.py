"""A metric file for the toy architecture's scan kernel: its logged work
at the chip's peaks over its time in ``op_s``, in decode and chunk steps."""
from bench.metrics._common import op_share

NAME, UNIT, BETTER, SOURCE = "toy_scan_roofline.itl", "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "itl_p95_ms"


def compute(record):
    return op_share(record, "scan_kernel", "decode/toy_scan", "chunk/toy_scan")
