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


LANE_KEYS = ("tokens_fed", "prefill_lane_steps", "prefill_lane_tokens",
             "prefill_lane_cache_tokens_read")


def test_lane_counters_and_slot_step_widths_follow_a_scripted_schedule():
    """Prompts of 10, 7 and 6 tokens at once, three slots, one lane,
    three tokens each. Step 1: the oldest takes the lane (its whole
    prompt, and its first token), the others feed one token each in the
    slot group. Step 2: the second's remaining six are the lane's chunk
    beside the first's decoding; step 3: the third's remaining four.
    Steps 4 and 5 are plain. Every counter is exact per token: row j of
    a chunk fed from position p reads p + j + 1 cached tokens."""
    eng = _engine(num_slots=3, prefix_cache=False)
    assert eng.paged.lanes == (1, 64)
    st0 = eng.stats()
    assert all(st0[k] == 0 for k in LANE_KEYS)
    rng = np.random.RandomState(5)
    reqs = [eng.submit(rng.randint(0, CFG["vocab_size"], (n,))
                       .astype("int32"), 3) for n in (10, 7, 6)]
    want = [   # tokens_fed, lane steps, lane tokens, lane reads,
               # prefill_tokens, cache_tokens_read, active_slot_steps
        (12, 1, 9, 55, 11, 55 + 1 + 1, 3),
        (20, 2, 14, 55 + 27, 17, 57 + 11 + 27 + 2, 6),
        (26, 3, 17, 82 + 18, 20, 97 + 12 + 8 + 18, 9),
        (28, 3, 17, 100, 20, 135 + 9 + 7, 11),
        (29, 3, 17, 100, 20, 151 + 8, 12)]
    for row in want:
        assert eng.step()
        st = eng.stats()
        assert tuple(st[k] for k in LANE_KEYS + (
            "prefill_tokens", "cache_tokens_read",
            "active_slot_steps")) == row
    assert not eng._has_work() and eng.stats()["steps"] == 5
    assert [len(r.tokens) for r in reqs] == [3, 3, 3]
    ids = {r.trace_id: i for i, r in enumerate(reqs)}
    widths = [(rec["engine_step"], ids[rec["trace_id"]], rec["pos"],
               rec["width"]) for rec in FLIGHT.snapshot()
              if rec.get("name") == "engine/slot_step"
              and rec.get("trace_id") in ids]
    assert widths == [(1, 0, 0, 10), (1, 1, 0, 1), (1, 2, 0, 1),
                      (2, 0, 10, 1), (2, 1, 1, 6), (2, 2, 1, 1),
                      (3, 0, 11, 1), (3, 1, 7, 1), (3, 2, 2, 4),
                      (4, 1, 8, 1), (4, 2, 6, 1), (5, 2, 7, 1)]


def test_host_counters_sum_to_the_wall_time_with_lanes_on():
    """The lane's host work (choosing lanes, packing their one input,
    committing a chunk) lies inside serving/plan and serving/commit: with
    every step a lane step but the last, the six counters still cover the
    steps' wall time."""
    # the interpreted kernel makes a step long against the few
    # bytecodes that lie between the phases
    eng = _engine(num_slots=2, prefix_cache=False, attention="kernel")
    eng.warmup()                        # both programs: no compile below
    rng = np.random.RandomState(6)
    for n in (30, 29, 28, 27, 26, 25, 24, 23):
        eng.submit(rng.randint(0, CFG["vocab_size"], (n,)).astype("int32"),
                   2)
    before = dict(eng._counters)
    t0 = time.perf_counter_ns()
    while eng._has_work():
        eng.step()
    wall = time.perf_counter_ns() - t0
    st = eng.stats()
    assert st["prefill_lane_steps"] >= 4
    assert st["prefill_tokens"] == sum(range(22, 30))
    assert st["prefill_lane_tokens"] > st["prefill_tokens"] // 2
    work = sum(st[k] - before[k] for k in HOST_KEYS)
    assert st["host_idle_ns"] == 0
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
    # a collection of the garbage collector may fall inside the step: its
    # span (utils.stats.watch_gc, installed by any started engine of the
    # process) is not the step's
    spans = {s["name"]: s for s in TRACER.spans() if s["name"] != "host/gc"}
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


@pytest.mark.parametrize("key", HOST_KEYS + ("admitted", "queue_wait_ns")
                         + LANE_KEYS)
def test_the_http_exposition_counts_the_new_keys_as_counters(key):
    from paddle_tpu.serving.http import _COUNTER_KEYS
    assert key in _COUNTER_KEYS
