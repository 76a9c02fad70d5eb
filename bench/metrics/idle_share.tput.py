"""Share of the traced window in which no operation ran on the device."""
from bench.metrics._common import idle_share

NAME, UNIT, BETTER, SOURCE = "idle_share.tput", "%", "lower", "device_trace"
LAYER, MOVES = "device", "tokens_per_s"


def compute(record):
    return idle_share(record)
