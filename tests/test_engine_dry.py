"""Why the device waited, as the engine sees it (serving/engine.py
``_look``, ``_dry``, ``_timed``) and the collector's pauses
(utils/stats.py ``watch_gc``): a launch with a step in flight that finds
that step finished is DRY, counted once by the phase whose end first saw
it so, with the device's idle time before it estimated inside a bracket;
a step whose sync and its predecessor's both waited, launched ahead and
not dry, is timed by its program; every collection is a ``host/gc`` span
and three process-wide counters. On the CPU at toy sizes."""

import contextlib
import gc
import time

import pytest

from paddle_tpu.obs.flight import FLIGHT
from paddle_tpu.obs.trace import TRACER
from paddle_tpu.serving.engine import _Step
from paddle_tpu.utils.stats import GC_WATCH, global_counters, watch_gc
from test_engine_ahead import _engine, _prompts, _turns

PHASES = ("sync", "commit", "admit", "plan", "dispatch")
GC_KEYS = ("gc_collections", "gc_gen2_collections", "gc_pause_ns")


def _dry_records():
    return [r for r in FLIGHT.snapshot() if r["name"] == "engine/dry"]


def _finished_at(eng, phase, turn, of_turn):
    """The step in flight reads finished from the boundary ``phase`` of
    turn ``turn`` on (``of_turn()`` is the turn now running), running
    before it; every other step reads running, so that every sync waits."""
    done = set()
    span = "serving/" + phase
    real = eng._phase

    @contextlib.contextmanager
    def phase_(name, counter=None):
        hit = name == span and of_turn() == turn \
            and eng._in_flight is not None
        if hit and phase == "dispatch":     # its looks lie inside the span
            done.add(eng._in_flight.step)
        with real(name, counter):
            yield
        if hit and phase != "dispatch":
            done.add(eng._in_flight.step)

    eng._phase = phase_
    eng._ready = lambda rec: rec.step in done


@pytest.mark.parametrize("phase", PHASES)
def test_a_step_seen_finished_at_one_boundary_is_one_dry_launch_there(phase):
    eng = _engine("default")
    for p in _prompts("default", (5, 6, 4), seed=4):
        eng.submit(p, 8)
    turn = [1]
    _finished_at(eng, phase, 4, lambda: turn[0])
    for i, _ in enumerate(_turns(eng), 1):
        turn[0] = i + 1
    st = eng.stats()
    assert st["finished"] == 3
    assert st["steps_launched_dry"] == 1 == st["dry_in_" + phase]
    assert sum(st["dry_in_" + p] for p in PHASES) == 1
    assert st["steps_launched_dry"] <= st["steps_launched_ahead"]
    (rec,) = _dry_records()
    assert rec["phase"] == phase
    assert 0 <= rec["lo_ms"] <= rec["est_ms"] <= rec["hi_ms"]
    assert st["device_dry_ns"] == pytest.approx(rec["est_ms"] * 1e6)


@pytest.mark.parametrize("model_end,want", [
    (3500, 5500),       # the enqueue less where the queue puts its end
    (0, 8000),          # clamped to the upper bound
    (7000, 4000),       # clamped to the lower bound
    (None, 4000),       # no step of its program timed: the lower bound
])
def test_the_estimate_lies_inside_the_bracket(model_end, want):
    eng = _engine("default")
    older = _Step(5, None, None, {})
    older.ahead, older.busy_at = True, 1000
    older.done_at, older.done_in = 5000, "commit"
    older.model_end = model_end
    eng._in_flight = older
    phase, est, lo, hi, _ = eng._dry(older, 9000)
    assert (phase, lo, hi) == ("commit", 4000, 8000)
    assert est == want


def test_a_pause_inside_the_enqueue_is_a_dry_launch_in_dispatch():
    """The step in flight finishes while the next one is being enqueued
    (a collection or the runtime inside the feed and the step's dispatch):
    the look after the enqueue counts it, in ``dispatch``, with the
    enqueue as its bracket."""
    eng = _engine("default")
    for p in _prompts("default", (5, 6, 4), seed=9):
        eng.submit(p, 8)
    done, step = set(), eng.paged.step

    def slow_step(*a, **kw):
        if eng._steps == 4:             # enqueueing step 5: 4 finishes
            done.add(eng._in_flight.step)
            time.sleep(0.02)
        return step(*a, **kw)

    eng.paged.step = slow_step
    eng._ready = lambda rec: rec.step in done
    list(_turns(eng))
    st = eng.stats()
    assert st["steps_launched_dry"] == st["dry_in_dispatch"] == 1
    (rec,) = _dry_records()
    assert rec["engine_step"] == 5 and rec["lo_ms"] == 0
    assert 20 <= rec["hi_ms"] and rec["lo_ms"] <= rec["est_ms"] <= rec["hi_ms"]


def test_a_host_that_sleeps_past_the_step_launches_dry_in_admit():
    """The real loop: an interceptor (inside ``serving/admit``) that
    sleeps far longer than the CPU's step finds every step it launches
    behind already finished, first seen so at the end of admit."""
    eng = _engine("default", attention="kernel")
    eng.warmup()
    eng._step_interceptor = lambda step: time.sleep(0.3)
    eng.start()
    reqs = [eng.submit(p, 4) for p in _prompts("default", (5, 6, 4), seed=6)]
    for r in reqs:
        r.get(timeout=300)
    eng.shutdown(drain=True, timeout=60.0)
    st = eng.stats()
    assert st["steps_launched_ahead"] > 0
    assert st["steps_launched_dry"] == st["steps_launched_ahead"]
    assert st["dry_in_admit"] > 0
    assert st["dry_in_plan"] == st["dry_in_dispatch"] == 0
    assert sum(st["dry_in_" + p] for p in PHASES) == st["steps_launched_dry"]
    assert st["device_dry_ns"] > 0
    assert len(_dry_records()) == st["steps_launched_dry"]


@pytest.mark.parametrize("drive", ["step", "run"])
def test_a_synchronous_drive_has_no_dry_launch_and_no_timed_step(drive):
    eng = _engine("default")
    for p in _prompts("default", (20, 5, 6), seed=7):
        eng.submit(p, 5)
    if drive == "run":
        eng.run(timeout=300)
    else:
        while eng._has_work():
            eng.step()
    st = eng.stats()
    assert st["finished"] == 3 and st["prefill_lane_steps"] > 0
    for k in ("steps_launched_dry", "device_dry_ns", "steps_timed_plain",
              "steps_timed_lanes", "step_ns_plain", "step_ns_lanes"):
        assert st[k] == 0, k
    assert _dry_records() == []


def test_a_timed_step_counts_by_its_program():
    """Every sync waits and no launch is dry (a fake ``_ready``): each step
    launched ahead is timed from its predecessor's sync's end to its own,
    under the program it ran."""
    eng = _engine("default")
    for p in _prompts("default", (20, 5, 6), seed=8):
        eng.submit(p, 6)
    eng._ready = lambda rec: False
    lanes, ends = {}, {}
    sent, timed = eng._sent, eng._timed

    def sent_(*a):
        rec = sent(*a)
        lanes[rec.step] = rec.lanes
        return rec

    def timed_(rec, waited, t):
        ends[rec.step] = t
        timed(rec, waited, t)

    eng._sent, eng._timed = sent_, timed_
    list(_turns(eng))
    st = eng.stats()
    assert st["steps_launched_dry"] == 0
    want = {False: [0, 0], True: [0, 0]}
    for step in range(2, st["steps"] + 1):      # the first found none ahead
        want[lanes[step]][0] += 1
        want[lanes[step]][1] += ends[step] - ends[step - 1]
    assert want[True][0] > 0 and want[False][0] > 0
    assert [st["steps_timed_plain"], st["step_ns_plain"]] == want[False]
    assert [st["steps_timed_lanes"], st["step_ns_lanes"]] == want[True]


@pytest.mark.parametrize("pause,untimed", [("collection", 2),
                                           ("past_the_next_step", 3)])
def test_a_paused_sync_is_no_reading_of_the_devices_clock(pause, untimed):
    """A pause holds step 4's sync's return past the step's end (a
    collection inside it; or a pause so long that step 5, behind it, has
    finished too): neither step 4 nor step 5, whose time starts there, is
    timed (nor, in the second case, step 6: launched dry)."""
    watch_gc()
    eng = _engine("default")
    for p in _prompts("default", (20, 5, 6), seed=8):
        eng.submit(p, 6)
    fired, done = [], set()

    def ready(rec):
        if rec.step == 4 and eng._in_flight is not rec and not fired:
            if pause == "collection":           # at step 4's sync
                fired.append(gc.collect())
            else:
                fired.append(done.add(5))
        return rec.step in done and eng._in_flight is rec

    eng._ready = ready
    list(_turns(eng))
    st = eng.stats()
    assert fired
    assert st["steps_timed_plain"] + st["steps_timed_lanes"] \
        == st["steps"] - 1 - untimed


# ------------------------------------------------------ the collector watcher
def test_the_watcher_installs_once():
    watch_gc()
    watch_gc()
    assert sum(cb is GC_WATCH for cb in gc.callbacks) == 1


def test_a_collection_bumps_the_counters_and_leaves_a_span():
    watch_gc()
    before = {k: global_counters.value(k) for k in GC_KEYS}
    TRACER.start(capture_compiles=False)
    try:
        gc.collect()
    finally:
        TRACER.stop()
    after = {k: global_counters.value(k) for k in GC_KEYS}
    assert after["gc_collections"] > before["gc_collections"]
    assert after["gc_gen2_collections"] > before["gc_gen2_collections"]
    assert after["gc_pause_ns"] > before["gc_pause_ns"]
    spans = [s for s in TRACER.spans() if s["name"] == "host/gc"]
    assert spans and all(s["t1"] > s["t0"] for s in spans)
    marks = [r for r in FLIGHT.snapshot()
             if r["name"] == "host/gc" and r["kind"] == "mark"]
    assert marks and marks[-1]["generation"] == 2
    assert marks[-1]["dur_ms"] > 0


def test_a_started_engine_installs_it_and_its_stats_carry_the_counters():
    eng = _engine("default")
    eng.start()
    eng.shutdown(drain=True, timeout=60.0)
    assert GC_WATCH in gc.callbacks
    gc.collect()
    st = eng.stats()
    for k in GC_KEYS:
        assert st[k] == global_counters.value(k) > 0, k
