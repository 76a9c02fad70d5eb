"""Mean time to first token over every request due in the window, timed
from when it was due (open loop: a stall delays later requests too); a
request still without a token at the window's end counts its wait so far.
A per-layer metric: over the 27 requests of a chat window even the mean
swings from seed to seed by more than the largest bound allows, since the
order in which long prompts arrive decides how long the scheduler's
first-come chunk budget holds the others back (PERF.md).  The same budget
puts prefill chunks between decode steps, so it moves the gaps between
tokens too."""
from bench.metrics._common import ttfts

NAME, UNIT, BETTER, SOURCE = "ttft_mean_s.itl", "s", "lower", "host_clock"
LAYER, MOVES = "scheduler and KV pool", "itl_p95_ms"


def compute(record):
    values = ttfts(record)
    return sum(values) / len(values) if values else None
