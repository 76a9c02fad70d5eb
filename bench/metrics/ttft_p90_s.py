"""90th percentile of time to first token over every request due in the
window, timed from when it was due; a request still without a token at the
window's end counts its wait so far.  The longest prompts' chunks, which
share each tick's token budget, set it; over the 27 requests of a chat
window it swings too far from run to run to carry a bound."""
from bench.metrics._common import percentile, ttfts

NAME, UNIT, BETTER, SOURCE = "ttft_p90_s", "s", "lower", "host_clock"
LAYER, MOVES = "scheduler and KV pool", "itl_p95_ms"


def compute(record):
    return percentile(ttfts(record), 90)
