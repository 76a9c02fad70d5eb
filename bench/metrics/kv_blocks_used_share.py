"""Share of the KV pool's blocks in use (the pool's own free-block count),
read after each scheduler tick and averaged over the window's ticks."""
NAME, UNIT, BETTER, SOURCE = "kv_blocks_used_share", "%", "higher", "program_counter"
LAYER, MOVES = "scheduler and KV pool", "tokens_per_s"


def compute(record):
    ticks = record["ticks"]
    return 100.0 * sum(t["blocks_used_share"] for t in ticks) / len(ticks) if ticks else None
