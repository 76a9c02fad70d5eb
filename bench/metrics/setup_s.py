"""Seconds from the process's start to the window's first request: imports,
device start, weights drawn on the device, pool, and the warm-up of every
program the window runs (compiles, or loads from the compile cache)."""
NAME, UNIT, BETTER, SOURCE, LAYER, MOVES = "setup_s", "s", "lower", "host_clock", None, None


def compute(record):
    return record["setup_s"]
