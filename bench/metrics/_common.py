"""Helpers shared by the metric readers (not a metric: no file of a metric
starts with an underscore)."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile (0–100), linear between order statistics;
    None for no values."""
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def ttfts(record: dict) -> list[float]:
    """Time to first token of every request due in the window, from when
    it was due; a request with no token yet counts its wait so far."""
    end = record["window_s"]
    return [(r["token_times"][0] if r["token_times"] else end) - r["due"]
            for r in due_in_window(record)]


def due_in_window(record: dict) -> list[dict]:
    return [r for r in record["requests"] if r["in_window"]]


def step_share(record: dict, work_key: str, step: str, time_key: str):
    """Least time for the logged work at the chip's peaks over the device
    time the trace gives it, in percent; None when the trace has none."""
    steps = (record.get("trace") or {}).get("steps", {})
    if step not in steps or steps[step][time_key] <= 0:
        return None
    w = record["work"][work_key]
    peaks = record["peaks"]
    least = max(w["flops"] / peaks["flops_bf16"], w["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / steps[step][time_key]


def idle_share(record: dict):
    tr = record.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
