"""Prompt tokens prefilled plus tokens generated inside the window, over the
window's seconds."""
NAME, UNIT, BETTER, SOURCE, LAYER, MOVES = "tokens_per_s", "tokens/s", "higher", "host_clock", None, None


def compute(record):
    return (record["prefilled_tokens"] + record["generated_tokens"]) / record["window_s"]
