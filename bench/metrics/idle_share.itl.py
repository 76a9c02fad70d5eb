"""Share of the traced window in which no operation ran on the device."""
from bench.metrics._common import idle_share

NAME, UNIT, BETTER, SOURCE = "idle_share.itl", "%", "lower", "device_trace"
LAYER, MOVES = "device", "itl_p95_ms"


def compute(record):
    return idle_share(record)
