"""The engine's loop with one step in flight (serving/engine.py
``_launch`` / ``_land``): the thread ``start()`` runs launches step N+1
while step N is on the device, feeds N+1's decoding rows from N's choices
there, and lands N afterwards. Held here, on the CPU at toy sizes of all
three block kinds: what a started engine delivers is what ``run()``
delivers; EOS learned a step late; ``max_new``, cancellation, deadlines,
drains and shutdowns with a step in flight; what makes the loop land
first (a draft, a spill store, a preemption); a failed step; the state
kind's snapshots; the counters that say how often it engaged.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.lib import manifest
from paddle_tpu import models
from paddle_tpu.serving import DecodeEngine, ServerClosed
from paddle_tpu.serving.server import Expired, ServingError
from paddle_tpu.testing.faults import FaultPlan
from test_paged_decode import CFG as DEFAULT_CFG, _decoder, _model

KINDS = ("default", "latent", "state")
HOST_KEYS = ("host_admit_ns", "host_plan_ns", "host_dispatch_ns",
             "host_sync_ns", "host_commit_ns", "host_idle_ns")
_DECODERS = {}


def _decoder_of(kind):
    """One tiny decoder a kind for the whole file (default block, latent
    block, latent block with a recurrent state a slot)."""
    if kind not in _DECODERS:
        if kind == "default":
            _DECODERS[kind] = (_decoder(_model()),
                               DEFAULT_CFG["vocab_size"])
        else:
            name = {"latent": "kimi_k2", "state": "kimi_linear"}[kind]
            ref = manifest.load_module("reference", name)
            model = manifest.load_module("models", name)
            cfg = model.tiny()
            named = model.make_weights(ref, 7, cfg, jnp.float32)
            _DECODERS[kind] = (models.TransformerDecoder(
                named, n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"], name=model.NAME,
                block=model.block_of(cfg, 64)), cfg["vocab_size"])
    return _DECODERS[kind]


def _engine(kind, **kw):
    dec, _ = _decoder_of(kind)
    kw = {"num_slots": 3, "page_size": 4, "max_seq_len": 32,
          "attention": "gather", **kw}
    if kind == "state":
        kw.setdefault("state_snapshots", 4)
    return DecodeEngine(dec, **kw)


def _prompts(kind, lens, seed=3):
    rng = np.random.RandomState(seed)
    vocab = _decoder_of(kind)[1]
    return [rng.randint(0, vocab, (n,)).astype("int32") for n in lens]


def _serve(eng, prompts, max_new, started, **kw):
    """Every prompt submitted before the first step, then the engine
    driven to the end by its thread (``started``; its first turn waits at
    the interceptor seam until all are in, and the thread is joined at
    the end) or by ``run()``: the schedule is the engine's alone either
    way."""
    news = max_new if isinstance(max_new, (list, tuple)) \
        else [max_new] * len(prompts)
    if not started:
        reqs = [eng.submit(p, n, **kw) for p, n in zip(prompts, news)]
        eng.run(timeout=300)
        return reqs
    gate = threading.Event()
    prev, eng._step_interceptor = eng._step_interceptor, \
        lambda step: gate.wait(60)
    eng.start()
    reqs = [eng.submit(p, n, **kw) for p, n in zip(prompts, news)]
    eng._step_interceptor = prev
    gate.set()
    for r in reqs:
        r.done.wait(300)
    eng.shutdown(drain=True, timeout=60.0)
    return reqs


def _turns(eng):
    """The loop's order driven by hand, a turn at a time: launch the next
    step, then land the one that was in flight. Yields after each turn
    (whether its launch found nothing in flight, what it launched)."""
    while eng._in_flight is not None or eng._has_work():
        older = eng._in_flight
        with contextlib.ExitStack() as dev:
            ahead = eng._launch(dev)
            if older is not None:
                eng._land(older, dev)
        yield older is None, ahead


def _balanced(eng):
    acc = eng.page_accounting()
    assert acc["leaked"] == 0, acc
    assert acc["held_by_slots"] == 0
    assert acc["free"] + acc["held_by_trie"] == acc["total_usable"]
    assert acc["refs_total"] == acc["held_by_trie"]
    if "snapshot_rows_total" in acc:
        assert acc["snapshot_rows_free"] + acc["snapshot_rows_held"] \
            == acc["snapshot_rows_total"]


# ------------------------------------------------- the same work, both ways
@pytest.mark.parametrize("temperature", [None, 0.7],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_started_engine_delivers_what_run_delivers(kind, temperature):
    """One request mix through ``run()`` and through the thread: short and
    long prompts (the long ones through prefill lanes), different
    ``max_new``. Greedy with a queue behind the slots; sampled (a fixed
    key, folded with the step's number) with a slot a request, where both
    schedules give a request the same steps. Token for token the same,
    every token stamped, nothing fed that the serial engine did not feed,
    and the thread ran ahead."""
    lens, news = (5, 11, 3, 9, 14), (6, 3, 7, 1, 4)
    if temperature is not None:
        lens, news = lens[:3], news[:3]
    out = {}
    for started in (False, True):
        eng = _engine(kind, temperature=temperature)
        reqs = _serve(eng, _prompts(kind, lens), news, started)
        assert [r.state for r in reqs] == ["done"] * len(reqs)
        for r, n in zip(reqs, news):
            assert len(r.tokens) == len(r.token_times) == n
        _balanced(eng)
        out[started] = ([r.tokens for r in reqs], eng.stats())
    assert out[True][0] == out[False][0]
    serial, ahead = out[False][1], out[True][1]
    # no stop but max_new: the host knows every last token in advance,
    # so not one row is fed that the serial engine did not feed
    same = ("tokens_fed", "tokens_out", "prefill_tokens",
            "cache_tokens_read", "finished")
    if temperature is not None:     # a slot a request: the same schedule
        same += ("steps", "active_slot_steps", "prefill_lane_steps")
    for k in same:
        assert ahead[k] == serial[k], k
    assert serial["steps_launched_ahead"] == serial["ahead_drains"] == 0
    assert ahead["steps_launched_ahead"] > ahead["steps"] // 2
    assert ahead["ahead_drains"] == 0


@pytest.mark.parametrize("kind", KINDS)
def test_the_last_lane_chunk_feeds_the_first_decoding_step(kind):
    """A prompt that ends in a lane chunk: its first token is a lane's
    choice, a row of the lane program's flat output, and the next step's
    input is taken from there on the device (the feed's second shape)."""
    eng = _engine(kind, num_slots=2)
    lanes, width = eng.paged.lanes
    assert lanes
    prompts = _prompts(kind, (min(2 * width + 1, 25), 3), seed=5)
    want = [r.tokens for r in _serve(_engine(kind, num_slots=2), prompts, 5,
                                     False)]
    fed_from = []
    real = eng.paged.feed
    eng.paged.feed = lambda prev, src, toks: (
        fed_from.append((prev.ndim, [int(x) for x in src])),
        real(prev, src, toks))[1]
    reqs = _serve(eng, prompts, 5, True)
    assert [r.tokens for r in reqs] == want
    assert eng.stats()["prefill_lane_steps"] >= 1
    flat = [src for ndim, src in fed_from if ndim == 1]
    # the long prompt's slot, fed from a lane's row: past the slot group
    assert flat and max(flat[0]) >= eng.num_slots * eng.window
    assert any(ndim == 2 for ndim, _ in fed_from)
    _balanced(eng)


# --------------------------------------------------------------------- stops
def _eos_case(kind, k, prompt_len):
    """A prompt and the token its greedy stream emits k-th, not before."""
    for seed in range(40):
        prompt = _prompts(kind, (prompt_len,), seed=seed)[0]
        toks = _serve(_engine(kind), [prompt], 8, False)[0].tokens
        if toks[k] not in toks[:k]:
            return prompt, toks, toks[k]
    raise AssertionError("no stream with a fresh token at " + str(k))


@pytest.mark.parametrize("on_boundary", [False, True],
                         ids=["inside_a_page", "on_a_page_boundary"])
@pytest.mark.parametrize("kind", KINDS)
def test_eos_is_learned_a_step_late_and_its_row_dropped(kind, on_boundary):
    """Step N chooses EOS while step N+1, which feeds that slot again, is
    already in flight. Nothing after EOS is delivered or stamped; the dead
    row was computed (one token more fed than the serial engine feeds) and
    its page is back; and what the slot left in the prefix index serves a
    follow-up the serial engine's tokens (on a page boundary the state
    kind leaves no snapshot there: the dead row moved the slot's state
    past it, so the follow-up attaches at the prompt's boundary)."""
    k = 3
    # the sequence ends (EOS not fed) at prompt + k tokens
    prompt, toks, eos = _eos_case(kind, k, 9 if on_boundary else 6)
    follow = np.concatenate([prompt, np.asarray(toks[:k], np.int32),
                             prompt[:2]])
    out = {}
    for started in (False, True):
        eng = _engine(kind)
        (r,) = _serve(eng, [prompt], 8, started, eos_id=eos)
        assert r.state == "done" and r.tokens == toks[:k + 1]
        assert len(r.token_times) == k + 1
        _balanced(eng)
        fed = eng.stats()["tokens_fed"]
        (r2,) = _serve(eng, [follow], 4, started)
        _balanced(eng)
        assert r2.prefix_hit_pages >= 1
        out[started] = (fed, r2.tokens)
    assert out[True][0] == out[False][0] + 1        # the dead row
    assert out[True][1] == out[False][1]


@pytest.mark.parametrize("kind", KINDS)
def test_a_slot_is_not_fed_past_its_last_token(kind):
    """``max_new`` reached with a step in flight: the host knows, the slot
    idles in the step launched meanwhile (driven by hand, a turn at a
    time) and is free for the next admission a step after."""
    eng = _engine(kind, num_slots=1)
    p1, p2 = _prompts(kind, (3, 4), seed=9)
    r1, r2 = eng.submit(p1, 2), eng.submit(p2, 2)
    turns = [(idle, ahead is not None) for idle, ahead in _turns(eng)]
    # a turn that finds a step in flight and launches nothing: the one
    # slot's last token is landing, and the queue waits for the slot
    assert turns.count((False, False)) == 2
    assert turns.count((True, True)) == 2       # a request: from idle
    serial = _engine(kind, num_slots=1)
    want = [r.tokens for r in _serve(serial, [p1, p2], 2, False)]
    assert [r1.tokens, r2.tokens] == want
    for k in ("tokens_fed", "steps", "active_slot_steps"):
        assert eng.stats()[k] == serial.stats()[k], k
    _balanced(eng)


@pytest.mark.parametrize("how", ["cancel", "deadline"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_request_settled_while_its_step_is_in_flight(kind, how):
    """A cancellation and a passed deadline seen by the reaper while the
    request's step is on the device: it settles there and then with the
    tokens it had, the step's token is dropped, its slot and pages serve
    the next request, and the other request's stream is untouched."""
    prompts = _prompts(kind, (4, 5, 3), seed=11)
    want = [r.tokens for r in _serve(_engine(kind, num_slots=2), prompts,
                                     12, False)]
    got = {}
    for started in (False, True):
        eng = _engine(kind, num_slots=2)
        reqs = [eng.submit(p, 12) for p in prompts]
        in_flight = []

        def act():
            in_flight.append(eng._in_flight is not None)
            if how == "cancel":
                reqs[0].cancel()
            else:
                reqs[0].deadline = time.monotonic() - 1.0

        with FaultPlan.decode_script(eng, at={4: act}) as fired:
            if started:
                eng.start()
                for r in reqs:
                    r.done.wait(300)
                eng.shutdown(drain=True, timeout=60.0)
            else:
                eng.run(timeout=300)
        assert fired["fired"] == [4] and in_flight == [started]
        r0 = reqs[0]
        if how == "cancel":
            assert r0.state == "cancelled" and r0.error is None
        else:
            assert r0.state == "failed" and isinstance(r0.error, Expired)
        assert len(r0.token_times) == len(r0.tokens)
        assert [r.tokens for r in reqs[1:]] == want[1:]
        _balanced(eng)
        got[started] = r0.tokens
    # the token of the step on the device when it settled is dropped
    assert got[False] == want[0][:len(got[False])] and len(got[False]) > 1
    assert got[True] == got[False][:-1]


# -------------------------------------------------------------------- drains
def test_a_preemption_lands_the_step_in_flight_first():
    """A pool too small for both requests: the youngest is preempted, and
    its replay is ``prompt + tokens``, so the step in flight lands before
    the plan that preempts. Outputs are the serial engine's."""
    p1, p2 = _prompts("default", (5, 6), seed=2)
    want = _serve(_engine("default", num_slots=2, num_pages=8), [p1, p2],
                  12, False)
    eng = _engine("default", num_slots=2, num_pages=8)
    reqs = _serve(eng, [p1, p2], 12, True)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    st = eng.stats()
    assert st["preemptions"] >= 1 and st["ahead_drains"] >= 1
    assert st["steps_launched_ahead"] > 0
    _balanced(eng)


def test_a_draft_lands_every_step_before_the_next():
    """Acceptance decides the next positions: an engine with a draft never
    runs ahead, and says so."""
    def eng_():
        return _engine("default", draft=_decoder(_model()), spec_k=2)
    prompts = _prompts("default", (4, 7, 5, 3), seed=4)
    want = _serve(eng_(), prompts, 6, False)
    eng = eng_()
    reqs = _serve(eng, prompts, 6, True)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    st = eng.stats()
    assert st["steps_launched_ahead"] == 0
    assert st["ahead_drains"] > st["steps"] // 2
    assert st["spec_accepted_tokens"] > 0
    _balanced(eng)


def test_a_spill_store_lands_every_step_before_the_next():
    """Spilled pages go through host memory and are keyed by tokens: such
    an engine lands each step before it launches the next."""
    def eng_():
        return _engine("default", num_slots=2, num_pages=10,
                       kv_spill_pages=8)
    prompts = _prompts("default", (9, 10, 9, 11), seed=6)
    want = _serve(eng_(), prompts, 5, False)
    eng = eng_()
    reqs = _serve(eng, prompts, 5, True)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    st = eng.stats()
    assert st["steps_launched_ahead"] == 0 and st["ahead_drains"] > 0
    assert eng.page_accounting()["leaked"] == 0


@pytest.mark.parametrize("case", ["default", "latent", "state", "draft",
                                  "preempting"])
def test_every_step_is_launched_ahead_after_a_drain_or_from_idle(case):
    """``steps_launched_ahead + ahead_drains +`` the launches that found
    nothing in flight ``== steps``, the loop's order driven by hand."""
    kw = {"draft": dict(draft=_decoder(_model()), spec_k=2),
          "preempting": dict(num_slots=2, num_pages=8)}.get(case, {})
    kind = case if case in KINDS else "default"
    eng = _engine(kind, **kw)
    for p in _prompts(kind, (5, 6, 4, 7)[:2 if case == "preempting" else 4],
                      seed=2):
        eng.submit(p, 12 if case == "preempting" else 5)
    from_idle = sum(1 for idle, ahead in _turns(eng)
                    if idle and ahead is not None)
    st = eng.stats()
    assert st["finished"] == (2 if case == "preempting" else 4)
    assert st["steps_launched_ahead"] + st["ahead_drains"] + from_idle \
        == st["steps"]
    if case == "draft":
        assert st["steps_launched_ahead"] == 0
    else:
        assert st["steps_launched_ahead"] > 0
        assert (st["ahead_drains"] > 0) == (case == "preempting")
    _balanced(eng)


# ------------------------------------------------------------ the state kind
def test_snapshots_are_taken_and_given_in_the_order_of_the_steps():
    """The state kind under the thread: a slot's row is updated in place
    every step, so a snapshot copy (behind the step whose chunk ended on
    the boundary) and an admission's copy into a freed row (before the
    row's next step) must keep their places on the device's queue. A
    churn of resent prompts through few slots, all at once: the tokens,
    the snapshots taken and the tokens they saved are the serial
    engine's."""
    base = _prompts("state", (13,), seed=8)[0]
    tails = _prompts("state", (2, 3, 1, 4, 2, 3), seed=9)
    first = [base, np.concatenate([base[:9], tails[0]])]
    second = [np.concatenate([base[:n], t])
              for n, t in zip((13, 12, 8, 13, 9, 13), tails)]
    out = {}
    for started in (False, True):
        eng = _engine("state", num_slots=2, state_snapshots=6)
        a = _serve(eng, first, 3, started)
        b = _serve(eng, second, 4, started)
        st = eng.stats()
        assert st["snapshot_attach_tokens"] > 0
        assert st["state_snapshots_taken"] >= 2
        _balanced(eng)
        out[started] = ([r.tokens for r in a + b],
                        st["state_snapshots_taken"],
                        st["snapshot_attach_tokens"],
                        st["snapshot_miss_tokens"])
    assert out[True] == out[False]


# ------------------------------------------------------------------ failures
class _FailOnce:
    """The paged decoder, its next step raising once."""

    def __init__(self, paged):
        self._paged, self.failed = paged, False

    def __getattr__(self, name):
        return getattr(self._paged, name)

    def step(self, *a, **kw):
        if not self.failed:
            self.failed = True
            raise RuntimeError("injected: the dispatch died")
        return self._paged.step(*a, **kw)


@pytest.mark.parametrize("kind", KINDS)
def test_a_failed_dispatch_settles_the_step_in_flight_too(kind):
    """Step 4's dispatch dies while step 3 is on the device: the pools of
    both were donated, so both steps' requests (the running and the
    waiting) settle typed, the record in flight is given up, the pools
    are rebuilt and the next request is served."""
    eng = _engine(kind, num_slots=2)
    prompts = _prompts(kind, (3, 4, 5), seed=12)
    reqs = [eng.submit(p, 8) for p in prompts]
    with FaultPlan.decode_script(eng, at={
            4: lambda: setattr(eng, "paged", _FailOnce(eng.paged))}):
        eng.start()
        for r in reqs:
            r.done.wait(300)
    for r in reqs:
        assert r.state == "failed" and isinstance(r.error, ServingError)
        assert len(r.tokens) == len(r.token_times)
    assert eng._in_flight is None
    st = eng.stats()
    assert st["step_failures"] == 1 and st["steps"] == 4
    fresh = eng.submit(prompts[0], 4)
    assert len(fresh.get(timeout=300)) == 4
    eng.shutdown(drain=True, timeout=60.0)
    want = _serve(_engine(kind, num_slots=2), prompts[:1], 4, False)
    assert fresh.tokens == want[0].tokens
    _balanced(eng)


# ----------------------------------------------------------------- lifecycle
@pytest.mark.parametrize("kind", KINDS)
def test_drain_admission_and_a_draining_shutdown_finish_what_flies(kind):
    prompts = _prompts(kind, (4, 6, 5), seed=13)
    want = _serve(_engine(kind, num_slots=2), prompts, 7, False)
    eng = _engine(kind, num_slots=2).start()
    reqs = [eng.submit(p, 7) for p in prompts]
    eng.drain_admission()
    with pytest.raises(ServerClosed):
        eng.submit(prompts[0], 2)
    eng.shutdown(drain=True, timeout=120.0)
    assert eng._thread is None and eng._in_flight is None
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    _balanced(eng)


@pytest.mark.parametrize("kind", KINDS)
def test_a_shutdown_without_drain_gives_the_step_in_flight_up(kind):
    eng = _engine(kind, num_slots=2)
    reqs = [eng.submit(p, 20) for p in _prompts(kind, (4, 6, 5), seed=14)]
    flying = []
    with FaultPlan.decode_script(eng, at={
            5: lambda: (flying.append(eng._in_flight is not None),
                        setattr(eng, "_close_now", True))}):
        eng.start()
        for r in reqs:
            r.done.wait(300)
    eng.shutdown(drain=False, timeout=60.0)
    assert flying == [True] and eng._in_flight is None
    for r in reqs:
        assert r.state == "failed" and isinstance(r.error, ServerClosed)
        assert len(r.tokens) == len(r.token_times)
    acc = eng.page_accounting()
    assert acc["leaked"] == 0 and acc["held_by_slots"] == 0


# ------------------------------------------------------------------ the clock
def test_the_phases_still_close_to_the_loops_wall_time_when_it_runs_ahead():
    """The six ``host_*_ns`` counters cover the thread's life with a step
    in flight as they did without: the sync phase is the wait for the
    OLDER step, and every phase still lies end to end in a turn."""
    eng = _engine("default", num_slots=3, attention="kernel")
    eng.warmup()
    prompts = _prompts("default", (5, 9, 7, 4, 6, 8), seed=15)
    t0 = time.perf_counter_ns()
    eng.start()
    reqs = [eng.submit(p, 10) for p in prompts]
    for r in reqs:
        r.get(timeout=300)
    time.sleep(0.06)
    eng.shutdown(drain=True, timeout=60.0)
    wall = time.perf_counter_ns() - t0
    st = eng.stats()
    assert st["steps_launched_ahead"] > st["steps"] // 2
    total = sum(st[k] for k in HOST_KEYS)
    assert st["host_idle_ns"] >= 0.05e9 and st["host_sync_ns"] > 0
    assert 0.8 * wall <= total <= wall, (total, wall)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.recompile_budget(max_compiles=60)
def test_a_warm_engine_runs_ahead_without_a_compile(kind):
    """``warmup()`` resolves the feed for both shapes of choices beside
    the two step programs: the thread's first steps compile nothing."""
    from paddle_tpu.analysis.sanitizer import compile_watch
    eng = _engine(kind, num_slots=2)
    eng.warmup()
    lanes, width = eng.paged.lanes
    prompts = _prompts(kind, (min(2 * width + 1, 25), 3, 6), seed=16)
    with compile_watch() as watch:
        reqs = _serve(eng, prompts, 4, True)
    assert all(len(r.tokens) == 4 for r in reqs)
    assert eng.stats()["steps_launched_ahead"] > 0
    assert watch.total == 0, watch.events
    _balanced(eng)


@pytest.mark.parametrize("key", ["steps_launched_ahead", "ahead_drains"])
def test_the_new_counters_are_flat_numbers_and_http_counters(key):
    from paddle_tpu.serving.http import _COUNTER_KEYS
    st = _engine("default").stats()
    assert isinstance(st[key], int) and st[key] == 0
    assert key in _COUNTER_KEYS
