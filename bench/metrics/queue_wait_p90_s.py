"""90th percentile of the wait from a request's due time to its first
prefill chunk, polled after each scheduler tick; a request not yet started
counts its wait so far."""
from bench.metrics._common import due_in_window, percentile

NAME, UNIT, BETTER, SOURCE = "queue_wait_p90_s", "s", "lower", "host_clock"
LAYER, MOVES = "scheduler and KV pool", "itl_p95_ms"


def compute(record):
    end = record["window_s"]
    return percentile([(end if r["prefill_started"] is None else r["prefill_started"])
                       - r["due"] for r in due_in_window(record)], 90)
