"""The window on a fake engine and a fake clock: the pre-roll serves the
traffic before the window opens, and only the window's work is counted."""
from types import SimpleNamespace

import pytest

from bench import harness, traffic
from bench.metrics import _common

SECONDS = 4.0
MIX = {"loop": "open", "rate_per_s": 2.0, "preroll_s": 2.0,
       "prompt": {"dist": "uniform", "min": 8, "max": 24},
       "output": {"dist": "uniform", "min": 8, "max": 16},
       "engine": {"max_batch": 2, "max_len": 64, "prefill_chunk": 16}}
TICK = 0.1


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


class FakeEngine:
    """Each tick prefills one waiting prompt whole and decodes one token
    for every running request, on ``max_batch`` lanes."""

    def __init__(self, clock, lanes=2):
        self.clock, self.lanes = clock, lanes
        self.scheduler = SimpleNamespace(waiting=[], running=[])
        self.cache = SimpleNamespace(pool=SimpleNamespace(num_blocks=9, num_free=8))
        self.uid = 0

    def add_request(self, prompt, max_new_tokens):
        self.uid += 1
        req = SimpleNamespace(generated=[], status="queued", prompt=prompt,
                              max_new=max_new_tokens)
        self.scheduler.waiting.append(SimpleNamespace(
            uid=self.uid, req=req, prompt_done=0,
            metrics=SimpleNamespace(n_preemptions=0)))
        return self.uid

    def has_work(self):
        return bool(self.scheduler.waiting or self.scheduler.running)

    def step(self):
        self.clock.now += TICK
        sched = self.scheduler
        for e in sched.running:
            e.req.generated.append(1)
            if len(e.req.generated) == e.req.max_new:
                e.req.status = "done"
        sched.running = [e for e in sched.running if e.req.status != "done"]
        if sched.waiting and len(sched.running) < self.lanes:
            e = sched.waiting.pop(0)
            e.prompt_done = len(e.req.prompt)
            e.req.generated.append(1)
            e.req.status = "running"
            sched.running.append(e)
        self.cache.pool.num_free = 8 - 2 * len(sched.running)


@pytest.fixture
def record():
    clock = FakeClock()
    eng = FakeEngine(clock)
    win = harness.Window(eng, MIX, traffic.stream(MIX, 3, 100, SECONDS),
                         clock=clock, sleep=clock.sleep)
    start = clock()
    win.run(SECONDS)
    assert win.t0 == pytest.approx(start + MIX["preroll_s"])
    return win, win.record()


def test_the_window_holds_one_whole_set(record):
    _, rec = record
    due = [r for r in rec["requests"] if r["in_window"]]
    assert len(due) == traffic.set_size(MIX, SECONDS) == 8
    assert all(0 <= r["due"] < rec["window_s"] for r in due)
    assert len(_common.ttfts(rec)) == 8


def test_the_preroll_is_served_but_not_counted(record):
    win, rec = record
    before = [r for r in rec["requests"] if not r["in_window"]]
    # Pre-roll requests served inside the window are in the record, due
    # before it opened; none of them is counted as due in the window.
    assert before and all(r["due"] < 0 for r in before)
    every = sum(len(t.token_times) for t in win.all)
    inside = sum(sum(x >= 0 for x in r["token_times"]) for r in rec["requests"])
    assert rec["generated_tokens"] == inside < every
    assert all(k["t"] >= 0 for k in rec["ticks"])


def test_only_gaps_inside_the_window_count(record):
    from bench import manifest

    _, rec = record
    itl = manifest.metric_module("itl_p95_ms").compute(rec)
    assert itl == pytest.approx(1000 * TICK, rel=1e-6)
