#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print one JSON result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``).  One process: check the device, draw the
weights from the seed on the device, build the paged engine, warm every
program the window runs, serve the mix's pre-roll, drive the window for
``--seconds``, read the peak memory, free the engine, and compare a sample
of the requests finished in the window with the plain reference
(``bench/reference.py``, with the configuration's architecture module
``bench/architectures/<name>.py``).  With ``--trace 1`` the window
runs under the profiler and the line carries the per-layer metrics instead
of the end-to-end ones.

No TPU, or fewer chips than the cell asks for: exit 3 with no result.  The
last lines of stderr, and the result's last key ``checks``, give each number
compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, manifest, trace_reduce, traffic  # noqa: E402

OUT = ROOT / ".bench_out"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def phase(name: str) -> None:
    """Log how far set-up has come: seconds since the process started."""
    log(f"set-up {name}: {time.perf_counter() - T_START:.2f} s")


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        log(f"no TPU: JAX runs on {dev['platform']}")
        raise SystemExit(3)
    if dev["count"] < chips:
        log(f"the cell needs {chips} chips, JAX finds {dev['count']}")
        raise SystemExit(3)
    return dev


class CompileCount:
    """Host clock times of JAX's backend compiles from now on."""

    def __init__(self):
        import jax

        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def since(self, t: float) -> int:
        return sum(x >= t for x in self.times)


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


def run_cell(man: dict, cell: dict, seed: int, seconds: float, trace: bool,
             *, dev: dict, peaks: dict | None, conf: dict | None = None,
             mix: dict | None = None) -> dict:
    """Everything after the device check; returns the result line.  The
    cell's configuration and mix are read by name unless given."""
    import jax

    conf = conf or manifest.read_config(man, cell["config"])
    mix = mix or traffic.load_mix(cell["traffic"])
    cfg = harness.program_config(conf)

    params = harness.make_weights(cfg, seed)
    jax.block_until_ready(params)
    phase("weights drawn")
    eng = harness.build_engine(cfg, params, conf, mix, seed)
    log(f"{cfg.name}: pool {eng.cache.pool.num_blocks - 1} blocks of "
        f"{eng.block_size} tokens, weights {harness.weights.nbytes(params)} B")
    phase("engine built")
    calls = harness.CallLog(eng) if trace else None
    compiles = CompileCount()
    harness.warm_up(eng, mix, conf["vocab_size"], seed)
    jax.block_until_ready(eng.cache.pools)
    phase(f"warmed up ({len(compiles.times)} compiles)")

    stream = traffic.stream(mix, seed, conf["vocab_size"], seconds)
    window = harness.Window(eng, mix, stream)
    trace_dir = OUT / "trace"
    on_tick = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        on_tick = _tracer(window, calls, trace_dir, mix["trace"]["seconds"], seconds)
    window.run(seconds, on_tick)
    if on_tick is not None:
        on_tick(window, final=True)
    # Set-up ends where the window opens, after the mix's pre-roll.
    setup_s = window.t0 - T_START
    log(f"compiles inside the window: {compiles.since(window.t0)}")
    jax.block_until_ready(eng.cache.pools)
    stats = [d.memory_stats() or {} for d in jax.devices()[: cell["chips"]]]
    dev = dict(dev, memory_peak_bytes=max(s.get("peak_bytes_in_use", 0) for s in stats))
    record = window.record()
    record["setup_s"] = setup_s
    served = {t.req.index: (t.req.prompt, list(t.entry.req.generated))
              for t in window.all if t.status == "done"}
    log_window(record, setup_s)
    harness.free_engine(eng)
    del eng, window

    breakdown = None
    if trace:
        events = trace_reduce.read(_trace_file(trace_dir))
        reduced = trace_reduce.reduce(events, step_module=mix["trace"]["step_module"],
                                      kernel=mix["trace"]["kernel"],
                                      chunk_rows=mix["engine"]["prefill_chunk"])
        record.update(trace=reduced, work=calls.work(conf), peaks=peaks)
        if reduced:
            dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            log(f"trace: {json.dumps({k: reduced[k] for k in ('steps', 'busy_s', 'window_s')})}"
                f" work {json.dumps(record['work'])}")

    metrics = {}
    for entry in manifest.metrics_for(man, cell["name"], trace):
        mod = manifest.metric_module(entry["name"])
        manifest.check_metric_module(entry, mod)
        value = mod.compute(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    t = time.perf_counter()
    picked = harness.sample_finished(record, served, seed, mix["check"])
    cmp = harness.compare(params, conf, picked, mix)
    correct, checks = harness.verdict(cmp["served"], cmp["requests"],
                                      conf["check"]["limits"])
    log(f"reference over {cmp['requests']} finished requests, {cmp['tokens']} "
        f"served tokens, {time.perf_counter() - t:.1f} s: {json.dumps(cmp['served'])}")
    due = [r for r in record["requests"] if r["in_window"]]
    attempted = len(due)
    failed = sum(r["status"] in harness.TERMINAL and r["status"] != "done" for r in due)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def log_window(record: dict, setup_s: float) -> None:
    due = [r for r in record["requests"] if r["in_window"]]
    late = [r["lateness"] for r in due if r["lateness"] == r["lateness"]]
    ticks = record["ticks"]
    log(f"set-up {setup_s:.2f} s; window {record['window_s']:.3f} s: {len(due)} "
        f"requests due, {sum(r['status'] == 'done' for r in due)} of them done, "
        f"{sum(r['ended'] is not None and r['ended'] >= 0 for r in record['requests'])} "
        f"requests ended; {record['prefilled_tokens']} prompt tokens prefilled, "
        f"{record['generated_tokens']} generated, {len(ticks)} ticks; "
        f"{record['preemptions']} preemptions; "
        f"generator lateness max {max(late, default=0):.4f} s")
    if ticks:
        share = [k["blocks_used_share"] for k in ticks]
        log(f"pool in use {share[0]:.3f} at the opening, {sum(share) / len(share):.3f} "
            f"mean, {share[-1]:.3f} at the close; waiting {ticks[0]['waiting']} at "
            f"the opening, {ticks[-1]['waiting']} at the close")


def _trace_file(trace_dir: Path) -> Path:
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        raise SystemExit(f"no trace written under {trace_dir}")
    return files[-1]


def _tracer(window, calls, trace_dir: Path, trace_seconds: float, seconds: float):
    """An ``on_tick`` hook that traces the last ``trace_seconds`` of the
    window: the profiler starts between two ticks once that much is left,
    and stops after the window's last tick."""
    import jax

    state = {"span": None}

    def on_tick(win, final=False):
        left = win.t0 + seconds - win.clock()
        if state["span"] is None and not final and left <= trace_seconds:
            jax.block_until_ready(win.eng.cache.pools)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            state["span"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            state["span"].__enter__()
            calls.on = True
        elif final and state["span"] is not None:
            jax.block_until_ready(win.eng.cache.pools)
            calls.on = False
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    return on_tick


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest.load()
    cell = manifest.workload(man, args.workload)

    import jax
    from repro.utils.compile_cache import use_compile_cache

    phase("imports")
    log(f"compile cache: {use_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = device_check(cell["chips"])
    phase("device found")
    out = run_cell(man, cell, args.seed, args.seconds, bool(args.trace), dev=dev,
                   peaks=peaks_for(dev["kind"]) if args.trace else None)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
