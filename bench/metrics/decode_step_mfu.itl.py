"""Decode steps (the paged step at width 1): the least time the work of
their occupied lanes needs at the chip's peaks (larger of the FLOP and the
HBM-byte term, bench/work.py) over the steps' device time in the trace."""
from bench.metrics._common import step_share

NAME, UNIT, BETTER, SOURCE = "decode_step_mfu.itl", "%", "higher", "device_trace"
LAYER, MOVES = "model step", "itl_p95_ms"


def compute(record):
    return step_share(record, "decode_step", "decode", "device_s")
