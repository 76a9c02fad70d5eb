"""One run of one cell: build the served model, warm it, drive the window,
check what it served.

The window drives the program's public serving entry,
``PagedServeEngine.add_request`` and ``PagedServeEngine.step`` (one
scheduler tick), built as a deployment builds it.  After every tick the
harness polls each request it submitted (tokens served, prompt tokens
prefilled, status) and the pool's free blocks, and stamps what changed
with the host clock.
"""
from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field

import numpy as np

from bench import manifest, reference, traffic, weights, work

TERMINAL = ("done", "rejected", "expired", "cancelled", "failed")


# -- the served model --------------------------------------------------------


def _lookup(conf: dict, key: str):
    out = conf
    for part in key.split("."):
        out = out[part]
    return out


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    own preset, checked against the file's published widths, with the
    file's values applied where the program has the option."""
    from repro.configs import get_config

    cfg = get_config(conf["arch"])
    prog = conf["program"]
    got = {k: getattr(cfg, k) for k in prog["asserted"]}
    want = {k: _lookup(conf, v) for k, v in prog["asserted"].items()}
    if got != want:
        raise SystemExit(f"{conf['arch']} is not at the configuration's widths: "
                         f"program {got}, file {want}")
    return cfg.replace(**{k: _lookup(conf, v) for k, v in prog["applied"].items()})


def make_weights(cfg, seed: int):
    """The benchmark's seeded weights in the layout the program serves."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.models import lm

    layout = jax.eval_shape(
        functools.partial(lm.init_params, cfg=cfg, dtype=jnp.dtype(cfg.compute_dtype)),
        jax.random.PRNGKey(0),
    )
    return weights.make(layout, seed)


def pool_blocks(cfg, conf: dict, max_len: int) -> tuple[int, int]:
    """(num_blocks, block_size): the configuration's pool bytes in blocks of
    the size the program's tuner picks, plus the reserved garbage block.
    The program's bytes a token have to be what the configuration's
    architecture counts."""
    from repro.serve import paged
    from repro.tune.autotune import warm_paged_engine

    block = min(warm_paged_engine(cfg, max_len).get("paged_decode", 128), max_len)
    want = manifest.architecture(conf).kv_bytes_per_token(conf)
    per_token = sum(
        s.size * s.dtype.itemsize
        for s in paged.pool_struct(cfg, 1, 1).values()
    )
    if per_token != want:
        raise SystemExit(f"program pools {per_token} B per token, "
                         f"configuration says {want}")
    blocks = conf["serving"]["pool_bytes"] // (block * per_token)
    return blocks + 1, block


def engine_kwargs(mix: dict) -> dict:
    """``PagedServeEngine``'s keyword arguments from the mix's ``engine``
    block, passed through by name.  Two values are built from their
    description: ``mesh`` (``{"shape": [...], "axes": [...]}``) and
    ``degrade`` (the fields of ``DegradeConfig``)."""
    kw = dict(mix["engine"])
    if "mesh" in kw:
        from repro.launch.mesh import make_mesh

        kw["mesh"] = make_mesh(tuple(kw["mesh"]["shape"]), tuple(kw["mesh"]["axes"]))
    if "degrade" in kw:
        from repro.serve.degrade import DegradeConfig

        d = dict(kw["degrade"])
        d["group_sizes"] = tuple(d.get("group_sizes", DegradeConfig.group_sizes))
        kw["degrade"] = DegradeConfig(**d)
    return kw


def build_engine(cfg, params, conf: dict, mix: dict, seed: int):
    """The paged engine as a deployment builds it: the mix's engine
    arguments, the configuration's pool, greedy sampling; block size left
    to the program's tuner."""
    from repro.serve.engine import PagedServeEngine

    kw = engine_kwargs(mix)
    num_blocks, block = pool_blocks(cfg, conf, kw["max_len"])
    eng = PagedServeEngine(cfg, params, num_blocks=num_blocks, temperature=0.0,
                           seed=seed, **kw)
    if eng.block_size != block:
        raise SystemExit(f"engine block {eng.block_size} != tuner's {block}")
    return eng


# -- the window ----------------------------------------------------------------


@dataclass
class Tracked:
    req: traffic.Request
    due: float  # host clock
    submitted: float
    entry: object  # the scheduler's entry for this request
    token_times: list = field(default_factory=list)
    prefill_started: float | None = None
    prompt_done: int = 0
    preemptions: int = 0
    ended: float | None = None
    status: str = "queued"


class Window:
    """Drives the engine through the mix's pre-roll and then the measured
    window, and records what each tick changed.

    The pre-roll (the mix's ``preroll_s``, part of set-up) serves the same
    traffic before the window opens, so the window starts with the engine
    as full as the traffic keeps it.  An open loop's pre-roll is the
    stream's first set, which spans ``preroll_s``; the window is its second
    set.
    Work is counted from the window's opening: tokens stamped after it,
    ticks after it, and the requests due inside it."""

    def __init__(self, eng, mix: dict, requests, clock=time.perf_counter,
                 sleep=time.sleep):
        self.eng = eng
        self.mix = mix
        self.requests = requests
        self.clock = clock
        self.sleep = sleep
        self.live: list[Tracked] = []
        self.all: list[Tracked] = []
        self.ticks: list[dict] = []
        self.prefilled = 0
        self.generated = 0
        self.preempted = 0
        self.total_blocks = eng.cache.pool.num_blocks - 1
        self.t0 = self.t1 = 0.0

    def submit(self, req: traffic.Request, due: float) -> None:
        uid = self.eng.add_request(list(req.prompt), max_new_tokens=req.max_new_tokens)
        entry = self.eng.scheduler.waiting[-1]
        if entry.uid != uid:
            raise RuntimeError(f"request {uid} was not queued")
        t = Tracked(req, due, self.clock(), entry)
        self.live.append(t)
        self.all.append(t)

    def poll(self) -> list[Tracked]:
        """Stamp what the last tick changed; returns requests that ended."""
        now = self.clock()
        counts = now >= self.t0
        ended = []
        for t in self.live:
            e = t.entry
            n = len(e.req.generated)
            if n > len(t.token_times):
                self.generated += counts * (n - len(t.token_times))
                t.token_times.extend([now] * (n - len(t.token_times)))
            if e.prompt_done > t.prompt_done:
                self.prefilled += counts * (e.prompt_done - t.prompt_done)
                t.prompt_done = e.prompt_done
                if t.prefill_started is None:
                    t.prefill_started = now
            if e.metrics.n_preemptions > t.preemptions:
                self.preempted += counts * (e.metrics.n_preemptions - t.preemptions)
                t.preemptions = e.metrics.n_preemptions
            t.status = e.req.status
            if t.status in TERMINAL:
                t.ended = now
                ended.append(t)
        if ended:
            self.live = [t for t in self.live if t.ended is None]
        if counts:
            used = self.total_blocks - self.eng.cache.pool.num_free
            self.ticks.append({"t": now, "blocks_used_share": used / self.total_blocks,
                               "waiting": len(self.eng.scheduler.waiting)})
        return ended

    def run(self, seconds: float, on_tick=None) -> None:
        self.t0 = self.clock() + traffic.preroll_s(self.mix)
        if self.mix["loop"] == "open":
            self._open(seconds, on_tick)
        else:
            self._closed(seconds, on_tick)

    def _tick(self, on_tick):
        import jax

        with jax.profiler.TraceAnnotation("bench.tick"):
            self.eng.step()
        with jax.profiler.TraceAnnotation("bench.poll"):
            ended = self.poll()
        if on_tick is not None:
            on_tick(self)
        return ended

    def _open(self, seconds, on_tick):
        import jax

        # Stream time ``preroll_s`` (the second set's start) is the window's
        # opening.
        base = self.t0 - traffic.preroll_s(self.mix)
        end = self.t0 + seconds
        nxt = next(self.requests)
        while True:
            now = self.clock()
            if now >= end:
                break
            with jax.profiler.TraceAnnotation("bench.admit"):
                while base + nxt.due_s <= now:
                    self.submit(nxt, base + nxt.due_s)
                    nxt = next(self.requests)
            if not self.eng.has_work():
                with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                    self.sleep(max(0.0, min(base + nxt.due_s, end) - now))
                continue
            self._tick(on_tick)
        self.t1 = self.clock()
        # Requests due inside the window that never reached the engine.
        while base + nxt.due_s < end:
            self.all.append(Tracked(nxt, base + nxt.due_s, math.nan, None,
                                    status="not_submitted"))
            nxt = next(self.requests)

    def _closed(self, seconds, on_tick):
        end = self.t0 + seconds
        for _ in range(self.mix["clients"]):
            self.submit(next(self.requests), self.clock())
        while self.clock() < end:
            for _ in self._tick(on_tick):
                if self.clock() < end:
                    self.submit(next(self.requests), self.clock())
        self.t1 = self.clock()

    def record(self) -> dict:
        """What the metrics read, with times relative to the window's
        opening.  ``requests`` holds every request due inside the window
        (``in_window``) and every other one that was served in it."""
        out = []
        for t in self.all:
            in_window = self.t0 <= t.due < self.t1
            if not in_window and (t.ended is not None and t.ended < self.t0):
                continue
            out.append({
                "index": t.req.index,
                "in_window": in_window,
                "due": t.due - self.t0,
                "lateness": t.submitted - t.due,
                "prompt_len": len(t.req.prompt),
                "max_new": t.req.max_new_tokens,
                "token_times": [x - self.t0 for x in t.token_times],
                "prefill_started": (None if t.prefill_started is None
                                    else t.prefill_started - self.t0),
                "ended": None if t.ended is None else t.ended - self.t0,
                "status": t.status,
            })
        return {"window_s": self.t1 - self.t0, "requests": out,
                "ticks": [dict(k, t=k["t"] - self.t0) for k in self.ticks],
                "prefilled_tokens": self.prefilled,
                "generated_tokens": self.generated,
                "preemptions": self.preempted}


# -- set-up ----------------------------------------------------------------------


def warm_up(eng, mix: dict, vocab: int, seed: int) -> None:
    """Run every program the window can run once: a prompt of a full and a
    partial chunk, decode, and, where the pool cannot hold every lane at
    full length, a preemption's evict and restore."""
    rng = np.random.default_rng(seed)
    chunk = mix["engine"]["prefill_chunk"]
    eng.add_request(rng.integers(1, vocab, chunk + 3).tolist(), max_new_tokens=3)
    eng.run_to_completion()
    cache, uid = eng.cache, -1
    if cache.pool.num_blocks - 1 < eng.max_batch * eng.max_blocks:
        cache.allocate_to(uid, chunk)
        cache.evict_to_host(uid, chunk, pad_to=eng.max_blocks)
        cache.restore(uid)
        cache.free(uid)
    eng.finished.clear()


# -- tracing: work of the calls made inside the traced window ------------------


class CallLog:
    """Wraps the engine's two model-step primitives on this instance to log
    each call and to open a host span around it.  A call is
    ``{"kind": "decode", "lengths": [live keys of each occupied lane]}`` or
    ``{"kind": "chunk", "start": first row, "n": rows, "final": bool}``."""

    def __init__(self, eng):
        import jax

        self.calls = []
        self.on = False
        decode, chunk = eng.decode_tick, eng.prefill_chunk_run

        def decode_tick(running):
            if self.on:
                self.calls.append({"kind": "decode",
                                   "lengths": [e.length + 1 for e in running.values()]})
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                return decode(running)

        def prefill_chunk_run(entry, n):
            if self.on:
                final = entry.prompt_done + n == len(entry.req.prompt)
                self.calls.append({"kind": "chunk", "start": entry.prompt_done,
                                   "n": n, "final": final})
            with jax.profiler.TraceAnnotation("bench.prefill_chunk"):
                return chunk(entry, n)

        eng.decode_tick = decode_tick
        eng.prefill_chunk_run = prefill_chunk_run

    def work(self, conf: dict) -> dict:
        """Calls of each kind, and one ``Work`` summed over the logged calls
        for every entry of the configuration's architecture's ``WORK``."""
        table = manifest.architecture(conf).WORK
        out = {k: work.Work() for k in table}
        counts = {"decode": 0, "chunk": 0}
        for call in self.calls:
            counts[call["kind"]] += 1
            for key, count in table.items():
                w = count(conf, call)
                if w is not None:
                    out[key] += w
        return {"counts": counts, **{k: vars(v) for k, v in out.items()}}


# -- correctness ------------------------------------------------------------------


def sample_finished(record: dict, requests: dict, seed: int, check: dict) -> list:
    """Requests finished inside the window to compare: the longest one,
    then others drawn from the seed until ``min_served_tokens`` served
    tokens or ``max_requests`` requests."""
    done = [r for r in record["requests"]
            if r["status"] == "done" and r["ended"] is not None and r["ended"] >= 0]
    if not done:
        return []
    done.sort(key=lambda r: (-(r["prompt_len"] + r["max_new"]), r["index"]))
    rng = np.random.default_rng(seed)
    rest = [done[i] for i in rng.permutation(np.arange(1, len(done)))]
    picked, served = [done[0]], done[0]["max_new"]
    for r in rest:
        if served >= check["min_served_tokens"] or len(picked) >= check["max_requests"]:
            break
        picked.append(r)
        served += r["max_new"]
    return [requests[r["index"]] for r in picked]


def compare(params, conf: dict, finished: list, mix: dict, controls=()) -> dict:
    """Readings over the sampled requests, for the served tokens and for
    each control in ``controls``: ``logit_gap``, the widest gap of a
    token's reference logit below the reference's best, and
    ``logit_gap_mean``, the mean gap over every compared token.  The
    reference is the configuration's architecture's."""
    arch = manifest.architecture(conf)
    spec = arch.spec(conf)
    widest, sums, tokens = {}, {}, 0
    for prompt, generated in finished:
        out = reference.check_request(arch, params, spec, prompt, generated,
                                      length=reference_length(mix),
                                      n_rows=mix["output"]["max"], controls=controls)
        tokens += out.pop("tokens")
        for key, v in out.items():
            if key.endswith("_sum"):
                sums[key[:-4]] = sums.get(key[:-4], 0.0) + v
            else:
                widest[key] = max(widest.get(key, 0.0), v)

    def readings(pre):
        if not tokens:
            return {"logit_gap": math.nan, "logit_gap_mean": math.nan}
        return {"logit_gap": widest[f"{pre}gap"],
                "logit_gap_mean": sums[f"{pre}gap"] / tokens}

    return {"requests": len(finished), "tokens": tokens, "served": readings(""),
            "controls": {q: readings(f"{q}_") for q in controls}}


def verdict(readings: dict, requests: int, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit: every
    reading at or under its limit, over at least one finished request."""
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits.items()}
    checks["requests_compared"] = {"value": requests, "limit": 1}
    correct = requests >= 1 and all(readings[n] <= lim for n, lim in limits.items())
    return bool(correct), checks


def reference_length(mix: dict) -> int:
    """One padded length for every reference call of a mix."""
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    return -(-longest // reference.QUERY_BLOCK) * reference.QUERY_BLOCK


def free_engine(eng) -> None:
    eng.cache.pools = None
    gc.collect()
