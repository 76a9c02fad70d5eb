"""95th percentile of every gap between two consecutive output tokens of a
request, over all requests, inside the window: both tokens of a gap are
stamped after the window opened.  Tokens are stamped when the scheduler
tick that produced them returns."""
import numpy as np
from bench.metrics._common import percentile

NAME, UNIT, BETTER, SOURCE, LAYER, MOVES = "itl_p95_ms", "ms", "lower", "host_clock", None, None


def compute(record):
    gaps = [g for r in record["requests"]
            for g in np.diff([t for t in r["token_times"] if t >= 0])]
    v = percentile(gaps, 95)
    return None if v is None else 1000.0 * v
