"""Prefill chunk steps (the paged step at width prefill_chunk): the least
time the work of their live rows needs at the chip's peaks (bench/work.py)
over the steps' device time in the trace."""
from bench.metrics._common import step_share

NAME, UNIT, BETTER, SOURCE = "prefill_step_mfu.tput", "%", "higher", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s"


def compute(record):
    return step_share(record, "chunk_step", "chunk", "device_s")
