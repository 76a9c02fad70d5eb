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
    return 100.0 * least_s(record, work_key) / steps[step][time_key]


def op_share(record: dict, work_key: str, *ops: str):
    """Least time for the logged work under ``work_key`` (an entry of the
    architecture's ``WORK``) at the chip's peaks over the device self time
    of ``ops`` (each ``"<step kind>/<op>"``) in the trace's ``op_s``, in
    percent; None when the trace has no time for them."""
    op_s = (record.get("trace") or {}).get("op_s", {})
    seconds = sum(op_s.get(op, 0.0) for op in ops)
    if seconds <= 0:
        return None
    return 100.0 * least_s(record, work_key) / seconds


def least_s(record: dict, work_key: str) -> float:
    """Least seconds the logged work under ``work_key`` needs at the chip's
    peaks: the larger of the FLOP and the HBM-byte term."""
    w = record["work"][work_key]
    peaks = record["peaks"]
    return max(w["flops"] / peaks["flops_bf16"], w["bytes"] / peaks["hbm_bytes_per_s"])


def idle_share(record: dict):
    tr = record.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
