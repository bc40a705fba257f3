"""The clock inside DecodeEngine: per-phase host counters and spans of
``step()``/``_loop`` drawn at one boundary (``_phase``), the admission
stamp and per-token stamps on GenRequest, and the queue-wait counters -
on a toy engine under an injected clock (PERF.md section 3 names each
span and counter and the metric that reads it)."""

import time

import numpy as np
import pytest

from paddle_tpu.obs.flight import FLIGHT
from paddle_tpu.obs.trace import TRACER, _SpanCtx
from paddle_tpu.serving import DecodeEngine
from test_paged_decode import CFG, _decoder, _model

HOST_KEYS = ("host_admit_ns", "host_plan_ns", "host_dispatch_ns",
             "host_sync_ns", "host_commit_ns", "host_idle_ns")


class _Clock:
    """The engine's clock, moved by hand: one second a step."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, _step=None):
        self.t += 1.0


def _engine(clock=None, **kw):
    dec = _decoder(_model())
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", CFG["max_len"])
    if clock is not None:
        kw["clock"] = clock
    return DecodeEngine(dec, **kw)


def _prompts(n, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG["vocab_size"], (4 + i,)).astype("int32")
            for i in range(n)]


def test_stats_hold_every_new_key_as_a_flat_number():
    st = _engine().stats()
    for k in HOST_KEYS + ("admitted", "queue_wait_ns"):
        assert isinstance(st[k], int) and st[k] == 0, k


def test_queue_wait_is_the_sum_of_the_waits_the_clock_made():
    """Three requests, two slots, one fake second a step: the first two
    wait one tick, the third until a slot frees; the counters hold
    exactly the stamps' sum."""
    clock = _Clock()
    eng = _engine(clock)
    eng._step_interceptor = clock.tick
    reqs = [eng.submit(p, 3) for p in _prompts(3)]
    assert all(r.admitted_at is None for r in reqs)
    eng.run(timeout=300)
    st = eng.stats()
    assert st["admitted"] == 3 and st["finished"] == 3
    assert reqs[0].admitted_at == reqs[1].admitted_at == 1.0
    assert reqs[2].admitted_at > 1.0            # it waited for a slot
    waits = [r.admitted_at - r.submitted_at for r in reqs]
    assert st["queue_wait_ns"] == int(round(sum(waits) * 1e9))
    for r in reqs:
        assert len(r.token_times) == len(r.tokens) == 3
        assert r.token_times == sorted(r.token_times)
        assert r.token_times[0] == r.first_token_at
        assert r.admitted_at <= r.token_times[0] <= r.finished_at


def test_a_readmission_after_preemption_does_not_count_again():
    """A pool too small for both requests preempts the younger: it is
    admitted twice, stamped and counted once."""
    clock = _Clock()
    eng = _engine(clock, num_pages=8)
    eng._step_interceptor = clock.tick
    p1, p2 = _prompts(2, seed=2)
    r1, r2 = eng.submit(p1, 12), eng.submit(p2, 12)
    first = {}
    while eng._has_work():
        eng.step()
        for r in (r1, r2):
            if r.admitted_at is not None:
                first.setdefault(id(r), r.admitted_at)
    st = eng.stats()
    assert st["preemptions"] >= 1
    assert r1.evictions + r2.evictions == st["preemptions"]
    assert st["admitted"] == 2
    assert [r1.admitted_at, r2.admitted_at] == [first[id(r1)],
                                                first[id(r2)]]
    assert st["queue_wait_ns"] == int(round(
        (r1.admitted_at - r1.submitted_at +
         r2.admitted_at - r2.submitted_at) * 1e9))
    assert len(r1.token_times) == len(r1.tokens) == 12


def test_host_counters_are_monotone_and_sum_to_the_steps_wall_time():
    eng = _engine()
    for p in _prompts(3):
        eng.submit(p, 4)
    eng.step()                          # compiles: keep it out of the sum
    seen = [dict(eng._counters)]
    t0 = time.perf_counter_ns()
    while eng._has_work():
        eng.step()
        seen.append(dict(eng._counters))
    wall = time.perf_counter_ns() - t0
    for a, b in zip(seen, seen[1:]):
        for k in HOST_KEYS:
            assert b[k] >= a[k], k
    work = sum(seen[-1][k] - seen[0][k] for k in HOST_KEYS)
    assert seen[-1]["host_idle_ns"] == 0    # sync mode never idles
    assert seen[-1]["host_sync_ns"] > 0 and seen[-1]["host_commit_ns"] > 0
    # the phases lie end to end inside step(): a few bytecodes between
    # them. 25 % of room for a loaded CPU; PERF.md has the chip's 2 %
    assert 0.75 * wall <= work <= wall, (work, wall)


def test_the_loops_phases_and_its_idle_wait_close_to_its_wall_time():
    """Started loop: with ``host_idle_ns`` the six counters cover the
    loop thread's life from start() to shutdown() to within 20 % on a
    loaded CPU (2 % is what PERF.md reports from the chip)."""
    eng = _engine()
    t0 = time.perf_counter_ns()
    eng.start()
    reqs = [eng.submit(p, 4) for p in _prompts(3)]
    for r in reqs:
        r.get(timeout=300)
    time.sleep(0.12)                    # an idle stretch
    eng.shutdown(drain=True, timeout=60.0)
    wall = time.perf_counter_ns() - t0
    st = eng.stats()
    total = sum(st[k] for k in HOST_KEYS)
    assert st["host_idle_ns"] >= 0.1e9
    assert 0.8 * wall <= total <= wall, (total, wall)


def test_one_step_yields_the_span_tree():
    eng = _engine()
    eng.submit(_prompts(1)[0], 2)
    eng.step()                          # compile outside the trace
    TRACER.start(capture_compiles=False)
    try:
        assert eng.step()
    finally:
        TRACER.stop()
    spans = {s["name"]: s for s in TRACER.spans()}
    parents = {"serving/admit": "serving/step",
               "serving/plan": "serving/step",
               "serving/decode_step": "serving/step",
               "serving/dispatch": "serving/decode_step",
               "serving/sync": "serving/decode_step",
               "serving/commit": "serving/step",
               "serving/step": None}
    assert set(spans) == set(parents)
    for name, parent in parents.items():
        assert spans[name]["parent"] == parent, name
        assert spans[name]["step"] == eng.stats()["steps"]
    dur = lambda n: spans[n]["t1"] - spans[n]["t0"]     # noqa: E731
    children = [n for n, p in parents.items() if p == "serving/step"]
    assert dur("serving/step") - sum(dur(n) for n in children) >= 0
    for n in children:
        assert spans["serving/step"]["t0"] <= spans[n]["t0"]
        assert spans[n]["t1"] <= spans["serving/step"]["t1"]


def test_step_allocates_no_span_when_tracer_and_flight_are_off(monkeypatch):
    eng = _engine()
    eng.submit(_prompts(1)[0], 3)
    eng.step()
    made = []
    real = _SpanCtx.__init__

    def counting(self, *a, **kw):
        made.append(a[1] if len(a) > 1 else None)
        real(self, *a, **kw)

    monkeypatch.setattr(_SpanCtx, "__init__", counting)
    assert eng.step()
    assert len(made) == 7               # flight recorder on: seven spans
    del made[:]
    FLIGHT.configure(enabled=False)
    try:
        assert eng.step()
    finally:
        FLIGHT.configure(enabled=True)
    assert made == []


@pytest.mark.parametrize("key", HOST_KEYS + ("admitted", "queue_wait_ns"))
def test_the_http_exposition_counts_the_new_keys_as_counters(key):
    from paddle_tpu.serving.http import _COUNTER_KEYS
    assert key in _COUNTER_KEYS
