"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` file with ``jax.profiler.ProfileData`` and keeps:

- the traced window: the harness's host span ``bench.trace_window``;
- the device clock's offset from the host's: the device timeline comes
  out shifted by about a millisecond.  The k-th step on the device is the
  one the k-th ``bench.prefill_chunk`` or ``bench.decode_step`` span
  dispatched, so it cannot start before that span does, and a decode step
  ends before its span does (the span waits for its tokens).  The offset
  is the middle of the range those bounds leave;
- device busy time: the union of the intervals of the ``XLA Ops`` line of
  each device plane, clipped to the window, averaged over the devices;
- steps: each execution on the ``XLA Modules`` line whose name starts with
  ``step_module`` is classed by the paged attention kernel it runs.  The
  kernel's first output is ``f32[lanes, kv_heads, splits, rows, d]``; a step
  whose kernel has fewer than ``chunk_rows`` query rows is a decode step,
  any other a prefill chunk.  Per class: executions, device seconds of the
  step, and device seconds of its kernel calls;
- every device operation's self time (so an op that encloses others, such
  as a loop, counts only its own part), named ``<step class or
  module>/<op>``: all of them in ``op_s``, the ``top`` that took most time
  in ``device_ops``;
- the longest idle gaps of device 0, each named by the innermost harness
  span (``bench.*``) on the host that was open at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

WINDOW_SPAN = "bench.trace_window"
_SHAPE = re.compile(r"\[([0-9,]+)\]")
_SUFFIX = re.compile(r"\.\d+$")


def _op_base(name: str) -> str:
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def _first_shape(name: str) -> list[int] | None:
    rhs = name.split(" = ", 1)[1] if " = " in name else ""
    m = _SHAPE.search(rhs)
    return [int(x) for x in m.group(1).split(",")] if m else None


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops):
    """(label index, self ns) of ops sorted by start: duration minus the
    part covered by ops nested inside it."""
    out = []
    stack = []  # [end, index, child_ns]
    for i, (s, e, _) in enumerate(ops):
        while stack and stack[-1][0] <= s:
            end, j, child = stack.pop()
            out.append((j, ops[j][1] - ops[j][0] - child))
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, i, 0])
    while stack:
        end, j, child = stack.pop()
        out.append((j, ops[j][1] - ops[j][0] - child))
    return out


def _name_gaps(gaps, host) -> dict:
    """Seconds of idle gap per innermost harness span open at each gap's
    midpoint (one sweep: gaps and spans both sorted by time)."""
    spans = [h for h in host if h[2] != WINDOW_SPAN]
    out = defaultdict(float)
    open_spans, p = [], 0
    for mid, sec in sorted(((s + e) / 2, (e - s) / 1e9) for s, e in gaps if e > s):
        while p < len(spans) and spans[p][0] <= mid:
            open_spans.append(spans[p])
            p += 1
        open_spans = [h for h in open_spans if h[1] > mid]
        out[max(open_spans)[2] if open_spans else "(no harness span)"] += sec
    return out


def read(path) -> dict:
    """Planes of interest as plain lists: {"devices": {plane: {"ops":
    [(start, end, name)], "modules": [...]}}, "host": [(start, end, name)]}
    with times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices[plane.name] = {
                key: sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in lines[line].events)
                for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules"))
                if line in lines
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in ln.events if e.name.startswith("bench."))
    return {"devices": devices, "host": sorted(host)}


DISPATCH_SPANS = ("bench.prefill_chunk", "bench.decode_step")
SYNC_SPANS = ("bench.decode_step",)


def clock_offset(host, modules, step_module: str) -> float:
    """ns to add to device times to put them on the host's clock; 0 when
    the steps and the spans that dispatched them do not pair up."""
    spans = [h for h in host if h[2] in DISPATCH_SPANS]
    steps = [m for m in modules if m[2].startswith(step_module)]
    if not steps or len(spans) != len(steps):
        return 0.0
    lo = max(h[0] - m[0] for h, m in zip(spans, steps))
    his = [h[1] - m[1] for h, m in zip(spans, steps) if h[2] in SYNC_SPANS]
    hi = min(his, default=lo)
    return (lo + hi) / 2 if hi >= lo else lo


def reduce(events: dict, *, step_module: str, kernel: str, chunk_rows: int,
           top: int = 10) -> dict:
    events_in = events
    host = events["host"]
    spans = [h for h in host if h[2] == WINDOW_SPAN]
    if not spans or not events["devices"]:
        return {}
    w0, w1 = spans[0][0], spans[0][1]
    events = {"devices": {
        plane: {key: [(s + d, e + d, n) for s, e, n in evs] for key, evs in dev.items()}
        for plane, dev in events["devices"].items()
        for d in [clock_offset(host, dev.get("modules", []), step_module)]
    }}
    busy_ns, steps, op_ns = [], defaultdict(lambda: [0, 0.0, 0.0]), defaultdict(float)
    first_gaps = None
    for plane in sorted(events["devices"]):
        dev = events["devices"][plane]
        ops = [(max(s, w0), min(e, w1), n) for s, e, n in dev.get("ops", [])
               if e > w0 and s < w1]
        merged = _union([(s, e) for s, e, _ in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        if first_gaps is None:
            first_gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
            if merged:
                first_gaps = ([(w0, merged[0][0])] + first_gaps
                              + [(merged[-1][1], w1)])
        starts = [o[0] for o in ops]
        label = ["other"] * len(ops)
        for ms, me, mname in dev.get("modules", []):
            if me <= w0 or ms >= w1:
                continue
            lo, hi = bisect.bisect_left(starts, ms), bisect.bisect_left(starts, me)
            mod = mname.split("(", 1)[0]
            kind = mod
            if mod.startswith(step_module):
                kern = [o for o in ops[lo:hi] if _op_base(o[2]) == kernel]
                shape = _first_shape(kern[0][2]) if kern else None
                if shape is not None and len(shape) >= 2:
                    kind = "decode" if shape[-2] < chunk_rows else "chunk"
                    st = steps[kind]
                    st[0] += 1
                    st[1] += (min(me, w1) - max(ms, w0)) / 1e9
                    st[2] += sum(e - s for s, e, _ in kern) / 1e9
            for i in range(lo, hi):
                label[i] = kind
        for i, self_ns in _self_times(ops):
            op_ns[f"{label[i]}/{_op_base(ops[i][2])}"] += self_ns
    gaps = _name_gaps(first_gaps or [], host)
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) / 1e9,
        "clock_offset_s": {p: clock_offset(host, d.get("modules", []), step_module) / 1e9
                           for p, d in events_in["devices"].items()},
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "steps": {k: {"count": v[0], "device_s": v[1], "kernel_s": v[2]}
                  for k, v in steps.items()},
        "device_ops": [[k, v / 1e9] for k, v in ranked],
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
