"""Charge the device's idle time in a traced window to the program's spans.

The program's ``TraceRecorder`` (``repro.obs.trace``) holds a
``jax.profiler.TraceAnnotation`` while each of its spans is open, so a run
with a recorder installed finds the program's spans (``serve.*``) in the
``.xplane.pb`` beside the harness's (``bench.*``) and the device's events.
This module:

- reads them (``read``): what ``trace_reduce.read`` keeps, plus the host's
  ``serve.*`` spans and ``compile`` instants;
- finds device 0's idle gaps in the window as ``trace_reduce`` does: the
  union of its ``XLA Ops`` on the host's clock (``trace_reduce.clock_offset``)
  clipped to ``bench.trace_window``;
- charges every instant of idle, by exact overlap, to the innermost span
  open on the host at that instant, ``serve.*`` and ``bench.*`` alike (the
  window span left out), and keeps the path of open spans above it
  (``reduce``);
- turns that into per-layer readings (``metrics``): idle charged to
  ``serve.*`` spans per decode step, per prefill chunk and per scheduler
  tick, and the count of compiles inside the window.

A trace without ``serve.*`` spans, as a program that installs no recorder
gives, reads ``None`` for every reading.
"""
from __future__ import annotations

from collections import defaultdict

from bench import trace_reduce

SERVE, HARNESS, COMPILE = "serve.", "bench.", "compile"
NO_SPAN = "(no span)"
#: Spans counted in the window: the readings' denominators.
COUNTED = ("serve.tick", "serve.decode_step", "serve.prefill_chunk")
#: The engine's steps inside a tick: the scheduler's own idle excludes them.
ENGINE_STEPS = ("serve.decode_step", "serve.prefill_chunk", "serve.first_token")


def read(path) -> dict:
    """``trace_reduce.read``'s planes, with the host's ``serve.*`` spans and
    ``compile`` instants added to ``host``."""
    from jax.profiler import ProfileData

    events = trace_reduce.read(path)
    extra = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for ln in plane.lines for e in ln.events
             if e.name.startswith(SERVE) or e.name == COMPILE]
    events["host"] = sorted(events["host"] + extra)
    return events


def idle_gaps(events: dict, step_module: str):
    """(w0, w1, gaps): the window and device 0's idle intervals in it, in
    host ns; None without a window span or a device."""
    window = [h for h in events["host"] if h[2] == trace_reduce.WINDOW_SPAN]
    if not window or not events["devices"]:
        return None
    w0, w1 = window[0][0], window[0][1]
    dev = events["devices"][sorted(events["devices"])[0]]
    d = trace_reduce.clock_offset(events["host"], dev.get("modules", []), step_module)
    busy = trace_reduce._union([(max(s + d, w0), min(e + d, w1))
                                for s, e, _ in dev.get("ops", [])
                                if e + d > w0 and s + d < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return w0, w1, gaps


def charge(gaps, spans) -> dict:
    """ns of the ``gaps`` per path of spans open at each instant (names,
    outermost first; ``()`` where none is open).  Spans are ``(start, end,
    name)``; of two open spans the later-starting one is inside, and of two
    starting together the shorter."""
    spans = sorted(spans)
    points = sorted({x for s, e, _ in spans for x in (s, e)}
                    | {x for g in gaps for x in g})
    out = defaultdict(int)
    open_, p, g = [], 0, 0
    for a, b in zip(points, points[1:]):
        while p < len(spans) and spans[p][0] <= a:
            open_.append(spans[p])
            p += 1
        open_ = [s for s in open_ if s[1] > a]
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g < len(gaps) and gaps[g][0] <= a:
            path = tuple(s[2] for s in sorted(open_, key=lambda s: (s[0], -s[1])))
            out[path] += b - a
    return dict(out)


def reduce(events: dict, *, step_module: str) -> dict:
    """The window's idle seconds, charged by innermost span (``by_span``,
    ``NO_SPAN`` for idle with no span open) and by path of open spans
    (``paths``), and the count of each ``COUNTED`` span that starts in the
    window; ``{}`` without a window or a device."""
    found = idle_gaps(events, step_module)
    if found is None:
        return {}
    w0, w1, gaps = found
    spans = [(max(s, w0), min(e, w1), n) for s, e, n in events["host"]
             if n.startswith((SERVE, HARNESS)) and n != trace_reduce.WINDOW_SPAN
             and e > w0 and s < w1]
    paths = charge(gaps, spans)
    by_span = defaultdict(float)
    for path, ns in paths.items():
        by_span[path[-1] if path else NO_SPAN] += ns / 1e9
    return {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(b - a for a, b in gaps) / 1e9,
        "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "paths": sorted(([list(k), v / 1e9] for k, v in paths.items()),
                        key=lambda kv: -kv[1]),
        "spans": {name: sum(n == name and w0 <= s < w1
                            for s, _, n in events["host"])
                  for name in COUNTED},
    }


def compiles_in(recorded, t0: float, t1: float) -> int:
    """``compile`` instants of a recorder's events (``TraceRecorder.events``)
    whose time lies in ``[t0, t1)``, on the recorder's clock."""
    return sum(ev["name"] == COMPILE and t0 <= ev["t"] < t1 for ev in recorded)


def _idle_ms(reduced: dict, per: str, keep) -> float | None:
    """ms of idle charged to ``serve.*`` spans whose path ``keep`` accepts,
    per ``per`` span in the window; None when the window has none."""
    n = reduced["spans"][per]
    if not n:
        return None
    s = sum(sec for path, sec in reduced["paths"]
            if path and path[-1].startswith(SERVE) and keep(path))
    return 1e3 * s / n


def metrics(reduced: dict | None, compiles: int | None = None) -> dict:
    """Per-layer readings from ``reduce``'s output and the window's compile
    count (``compiles_in``); every one None where the trace holds no
    ``serve.*`` span."""
    names = ("decode_host_idle_ms", "chunk_host_idle_ms", "sched_host_idle_ms",
             "compiles_in_window")
    if not reduced or not any(reduced["spans"].values()):
        return dict.fromkeys(names)
    chunk = ("serve.prefill_chunk", "serve.first_token")
    return dict(zip(names, (
        _idle_ms(reduced, "serve.decode_step", lambda p: "serve.decode_step" in p),
        _idle_ms(reduced, "serve.prefill_chunk", lambda p: any(x in p for x in chunk)),
        _idle_ms(reduced, "serve.tick", lambda p: "serve.tick" in p
                 and not any(x in p for x in ENGINE_STEPS)),
        compiles,
    )))
