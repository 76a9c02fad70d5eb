"""Request generator: one general generator for every traffic mix.

A mix is a JSON file under ``bench/mixes/`` (see ``load_mix``).  The
generator turns it and a seed into a stream of requests, in sets.  Every
set holds the same stratified quantiles of the mix's prompt and output
lengths and, for an open loop, of its exponential inter-arrival gaps; the
seed orders each of the three independently within the set and draws the
token ids.  An open loop's stream starts with the pre-roll's set, which
spans the mix's ``preroll_s``, and goes on in sets that each span exactly
one measured window: every seed offers the same sizes and the same number
of arrivals in the pre-roll and in a window, and the gaps, in random order,
give Poisson bursts and lulls.  A closed loop's sets hold ``set_size``
requests.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIXES = Path(__file__).resolve().parent / "mixes"
LOOPS = ("open", "closed")


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # open loop: seconds after the stream starts; closed: 0
    prompt: tuple[int, ...]
    max_new_tokens: int


def load_mix(name: str) -> dict:
    mix = json.loads((MIXES / f"{name}.json").read_text())
    validate_mix(mix)
    return mix


def validate_mix(mix: dict) -> None:
    if mix["loop"] not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}: {mix['loop']!r}")
    if mix["loop"] == "open" and not mix["rate_per_s"] > 0:
        raise ValueError("an open loop needs rate_per_s > 0")
    if mix["loop"] == "closed" and not (mix["clients"] >= 1 and mix["set_size"] >= 1):
        raise ValueError("a closed loop needs clients >= 1 and set_size >= 1")
    if mix.get("preroll_s", 0) < 0:
        raise ValueError("preroll_s must be >= 0")
    eng = mix["engine"]
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    if longest >= eng["max_len"]:
        # Decode past the block table would slide the window and drop the
        # oldest keys, which the full-attention reference never does.
        raise ValueError(f"prompt + output up to {longest} does not fit "
                         f"below max_len {eng['max_len']}")


def set_size(mix: dict, seconds: float) -> int:
    """Requests in one set: an open loop's arrivals in ``seconds`` at its
    rate; a closed loop's ``set_size``."""
    if mix["loop"] == "open":
        return max(1, round(mix["rate_per_s"] * seconds))
    return mix["set_size"]


def preroll_s(mix: dict) -> float:
    """Seconds of an open loop's traffic served before the window opens."""
    return float(mix.get("preroll_s", 0)) if mix["loop"] == "open" else 0.0


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles (midpoints of n equal-probability bins) of
    a length distribution, rounded and clipped to ``[min, max]``."""
    probs = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in probs])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        vals = lo + probs * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_gaps(n: int, span: float) -> np.ndarray:
    """``n`` stratified quantiles of exponential inter-arrival gaps, scaled
    so that together they span ``span`` seconds."""
    probs = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-probs)
    return gaps * (span / gaps.sum())


def stream(mix: dict, seed: int, vocab: int, seconds: float):
    """Yield the mix's requests in order, forever.  An open loop's first set
    spans its pre-roll (``preroll_s``, none where that is 0) and every
    later set spans ``seconds``: the first request of the window's set is
    due at stream time ``preroll_s``."""
    rng = np.random.default_rng(seed)
    index, due = 0, 0.0
    span = preroll_s(mix) or seconds
    while True:
        n = set_size(mix, span)
        gaps = exponential_gaps(n, span) if mix["loop"] == "open" else np.zeros(n)
        for p, o, g in zip(rng.permutation(quantiles(mix["prompt"], n)),
                           rng.permutation(quantiles(mix["output"], n)),
                           rng.permutation(gaps)):
            tokens = rng.integers(1, vocab, size=int(p))
            yield Request(index, due, tuple(int(t) for t in tokens), int(o))
            due += float(g)
            index += 1
        span = seconds
