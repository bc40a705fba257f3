"""Serving chaos suite — InferenceServer under injected faults.

The acceptance contract (ISSUE: hardened inference serving): N client
threads with injected hung-forward, poisoned-bytes, mid-request-destroy
and burst-overload faults produce zero interpreter crashes or
deadlocks, only typed errors at the boundary, and the circuit breaker
opens under fault and recovers (serves successfully) after the faults
stop. Faults come from paddle_tpu.testing.FaultPlan (e)-(g); every
test is @chaos so a wedge dumps all thread stacks (tests/conftest.py).

Round 6 adds the DECODE-ENGINE chaos family (FaultPlan (j), ISSUE 6):
mid-decode joins/evictions/cancellations and client disconnects
against the continuous-batching engine. The invariant every fault must
preserve: KV pages ALWAYS return to the pool (zero leaks), and
sequences that were not faulted stay TOKEN-IDENTICAL to undisturbed
runs.
"""

import threading
import time

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.serving import (CircuitBreaker, DecodeEngine, Expired,
                                InferenceServer, Rejected, ServerClosed,
                                ServingError, build_http_server,
                                prometheus_text)
from paddle_tpu.testing import FaultPlan
from paddle_tpu.trainer.inference import Inference

pytestmark = pytest.mark.chaos


def tiny_inference(dim=8, out=4, seed=5):
    paddle.init(seed=seed)
    x = paddle.layer.data("x", paddle.data_type.dense_vector(dim))
    o = paddle.layer.fc(x, size=out, act=paddle.activation.Softmax())
    params = paddle.create_parameters(paddle.Topology(o))
    return Inference(output_layer=o, parameters=params)


DEC_CFG = dict(vocab_size=40, d_model=16, n_heads=2, n_layers=2,
               d_ff=32, max_len=32)


def tiny_decoder(seed=7):
    paddle.init(use_tpu=False, seed=0)
    from paddle_tpu.core.registry import reset_name_counters
    reset_name_counters()
    spec = models.transformer_lm(**DEC_CFG)
    costs = spec.cost if isinstance(spec.cost, list) else [spec.cost]
    topo = paddle.Topology(costs, extra_outputs=[spec.output])
    params = topo.init_params(jax.random.PRNGKey(seed))
    return models.TransformerDecoder(params, n_layers=DEC_CFG["n_layers"],
                                     n_heads=DEC_CFG["n_heads"])


def samples(batch=2, dim=8, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(dim).astype(np.float32),) for _ in range(batch)]


def assert_pool_balanced(eng):
    """Round-9 pool invariant: zero leaks AND zero refcount drift.
    With the prefix cache on (the default) a drained engine may park
    finished sequences' pages in the trie, so "everything returned"
    means free + trie-held covers every usable page, and the live
    refcounts are exactly the slot-table + trie references."""
    acc = eng.page_accounting()
    assert acc["leaked"] == 0
    assert acc["free"] + acc["held_by_trie"] == acc["total_usable"]
    assert acc["refs_total"] == \
        acc["held_by_slots"] + acc["held_by_trie"]
    # two-tier extension (ISSUE 20): with a spill store attached, the
    # HOST tier must conserve too — every page ever spilled is either
    # restored, dropped (LRU / integrity / recovery clear) or still
    # resident, and residency never exceeds the configured capacity.
    # A SIGKILL mid-spill (kill_during_spill) must not break this: the
    # ordering contract means a torn spill leaves no store entry.
    if getattr(eng, "spill", None) is not None:
        assert 0 <= acc["spilled"] <= acc["spill_capacity"]
        assert acc["spill_puts"] == (
            acc["spill_restores"] + acc["spill_evicted_lru"]
            + acc["spill_dropped_integrity"] + acc["spill_cleared"]
            + acc["spilled"]), acc
    return acc


class TestServerBasics:
    def test_serves_and_snapshots(self):
        inf = tiny_inference()
        srv = InferenceServer(inf, max_queue=8, workers=2,
                              breaker=False).start()
        try:
            want = np.asarray(inf.infer(samples()))
            got = np.asarray(srv.infer(samples()))
            np.testing.assert_allclose(got, want, rtol=1e-6)
            for _ in range(5):
                srv.infer(samples())
            st = srv.stats()
            assert st["served"] == 6
            assert st["p50_ms"] > 0.0
            assert srv.health()["status"] == "ok"
        finally:
            srv.shutdown(drain=True)
        assert srv.health()["status"] == "stopped"

    def test_graceful_drain_completes_queued_work(self):
        inf = tiny_inference()
        plan = FaultPlan(seed=3)
        srv = InferenceServer(inf, max_queue=16, workers=1,
                              breaker=False).start()
        with plan.flaky_forward(inf, delay={i: 0.05 for i in range(8)}):
            reqs = [srv.submit(samples(seed=i)) for i in range(6)]
            t = threading.Thread(target=srv.shutdown,
                                 kwargs={"drain": True},
                                 name="pt-test-drain")
            t.start()
            for r in reqs:                  # all queued work completes
                assert np.asarray(r.get(timeout=30)).shape == (2, 4)
            t.join(30)
            assert not t.is_alive()
        with pytest.raises(ServerClosed):
            srv.submit(samples())
        assert srv.stats()["served"] == 6

    def test_shutdown_without_drain_fails_queued_typed(self):
        inf = tiny_inference()
        plan = FaultPlan(seed=4)
        srv = InferenceServer(inf, max_queue=16, workers=1,
                              breaker=False).start()
        with plan.flaky_forward(inf, delay={0: 0.2}):
            first = srv.submit(samples())          # occupies the worker
            queued = [srv.submit(samples(seed=i)) for i in range(4)]
            time.sleep(0.05)                        # worker picked first
            srv.shutdown(drain=False, timeout=10)
            dropped = 0
            for r in queued:
                try:
                    r.get(timeout=10)
                except ServerClosed:
                    dropped += 1
            assert dropped >= 3                     # queue was flushed
            first.get(timeout=10)                   # in-flight completed


class TestBackpressure:
    def test_burst_overload_rejects_with_retry_after(self):
        """Burst fault: 30 concurrent requests against queue=3/worker=1
        with a slowed forward — the bounded queue sheds the overflow
        with Rejected(retry_after>0), everything settles, nothing
        crashes or deadlocks."""
        inf = tiny_inference()
        plan = FaultPlan(seed=9)
        srv = InferenceServer(inf, max_queue=3, workers=1,
                              breaker=False).start()
        try:
            with plan.flaky_forward(
                    inf, delay={i: 0.03 for i in range(64)}):
                results, errors = FaultPlan.burst(
                    lambda i: srv.infer(samples(seed=i)), 30,
                    threads=8, timeout=60)
            served = sum(r is not None for r in results)
            rejected = [e for e in errors if isinstance(e, Rejected)]
            other = [e for e in errors
                     if e is not None and not isinstance(e, Rejected)]
            assert other == []              # typed backpressure only
            assert served + len(rejected) == 30
            assert len(rejected) > 0        # the bound actually bound
            assert all(e.retry_after > 0 and e.reason == "queue_full"
                       for e in rejected)
            st = srv.stats()
            assert st["rejected_full"] == len(rejected)
            assert st["served"] == served
        finally:
            srv.shutdown(drain=True)

    def test_deadline_expires_queued_requests(self):
        inf = tiny_inference()
        plan = FaultPlan(seed=10)
        srv = InferenceServer(inf, max_queue=16, workers=1,
                              breaker=False).start()
        try:
            with plan.flaky_forward(inf, delay={0: 0.3}):
                slow = srv.submit(samples())
                doomed = srv.submit(samples(seed=1), deadline=0.05)
                with pytest.raises(Expired):
                    doomed.get()
                slow.get(timeout=10)
            assert srv.stats()["expired"] >= 1
        finally:
            srv.shutdown(drain=True)


class TestHungForwardAndBreaker:
    def test_hung_forward_expires_then_recovers(self):
        """A hung forward (blocks on an Event) must not hang the client:
        the deadline bounds the wait, the request is typed Expired, and
        after the fault is released the server serves again."""
        inf = tiny_inference()
        plan = FaultPlan(seed=11)
        release = threading.Event()
        srv = InferenceServer(inf, max_queue=8, workers=1,
                              breaker=False).start()
        try:
            with plan.flaky_forward(inf, hang={0: release}):
                req = srv.submit(samples(), deadline=0.2)
                t0 = time.monotonic()
                with pytest.raises(Expired):
                    req.get()
                assert time.monotonic() - t0 < 5.0   # client not hung
                release.set()                        # un-wedge the worker
            out = srv.infer(samples(), deadline=10.0)
            assert np.asarray(out).shape == (2, 4)
        finally:
            release.set()
            srv.shutdown(drain=True, timeout=10)

    def test_breaker_opens_under_faults_and_half_open_recovers(self):
        """Poisoned forwards push the failure rate over threshold: the
        breaker OPENS (submit -> Rejected(breaker_open)), then after the
        cooldown it half-opens, probes succeed, and serving resumes."""
        inf = tiny_inference()
        plan = FaultPlan(seed=12)
        breaker = CircuitBreaker(window=16, failure_threshold=0.5,
                                 min_requests=4, cooldown=0.3,
                                 half_open_probes=2)
        srv = InferenceServer(inf, max_queue=16, workers=1,
                              breaker=breaker).start()
        try:
            with plan.flaky_forward(inf, fail_rate=1.0):
                failures = 0
                for i in range(8):
                    try:
                        srv.infer(samples(seed=i), deadline=5.0)
                    except ServingError:
                        failures += 1
                assert failures >= 4
                assert breaker.state == "open"
                with pytest.raises(Rejected) as ei:
                    srv.submit(samples())
                assert ei.value.reason == "breaker_open"
                assert ei.value.retry_after > 0
                assert srv.health()["status"] == "shedding"
            # faults stop; wait out the cooldown, probes close it
            time.sleep(0.35)
            for i in range(3):
                out = srv.infer(samples(seed=100 + i), deadline=10.0)
                assert np.asarray(out).shape == (2, 4)
            assert breaker.state == "closed"
            assert srv.stats()["rejected_breaker"] >= 1
            assert srv.stats()["breaker"]["trips"] >= 1
        finally:
            srv.shutdown(drain=True)


class TestMixedChaosAcceptance:
    def test_eight_clients_mixed_faults_no_crash_no_deadlock(self):
        """THE acceptance run: 8 client threads of mixed traffic against
        a live server while the fault plan injects slow forwards, failed
        (poisoned) forwards, and burst overload — plus concurrent C-ABI
        clone/forward/destroy traffic with a mid-request destroy. Zero
        untyped exceptions, zero deadlocks; the breaker opens under the
        fault storm and the server serves again after it passes."""
        from paddle_tpu import capi_host as ch
        from paddle_tpu.trainer.inference import save_inference_model
        import tempfile
        import os

        inf = tiny_inference()
        # the C-ABI lane gets its own tiny artifact
        tar = os.path.join(tempfile.mkdtemp(), "m.tar")
        paddle.init(seed=6)
        x2 = paddle.layer.data("px", paddle.data_type.dense_vector(8))
        o2 = paddle.layer.fc(x2, size=4,
                             act=paddle.activation.Softmax())
        p2 = paddle.create_parameters(paddle.Topology(o2))
        save_inference_model(tar, o2, p2)

        plan = FaultPlan(seed=13)
        breaker = CircuitBreaker(window=16, failure_threshold=0.5,
                                 min_requests=4, cooldown=0.25,
                                 half_open_probes=1)
        srv = InferenceServer(inf, max_queue=8, workers=2,
                              default_deadline=5.0,
                              breaker=breaker).start()
        src = ch.create(tar)
        assert src > 0
        payload = np.linspace(0, 1, 16).astype(np.float32).tobytes()
        untyped = []

        def http_client(tid):
            import random as _r
            rng = _r.Random(tid)
            for i in range(25):
                try:
                    srv.infer(samples(seed=tid * 100 + i),
                              deadline=rng.choice([0.5, 2.0, 5.0]))
                except (Rejected, Expired, ServingError):
                    pass                        # typed: expected
                except BaseException as e:      # the failure under test
                    untyped.append(repr(e))

        def capi_client(tid):
            import random as _r
            rng = _r.Random(1000 + tid)
            for i in range(25):
                c = ch.create_shared(src)
                if c > 0:
                    blob = payload if rng.random() < 0.5 else \
                        plan.poison_bytes(payload, flips=3,
                                          truncate=rng.randrange(16))
                    r = ch.forward(c, blob, 2, 8)
                    if not isinstance(r, (int, tuple)):
                        untyped.append(repr(r))
                    ch.destroy(c)
                elif c != ch.ERR_BAD_HANDLE:
                    untyped.append(f"create_shared -> {c}")

        # fault storm: half the forwards fail, some are slow
        with plan.flaky_forward(inf, fail_rate=0.5,
                                delay={i: 0.02 for i in range(0, 60, 7)}):
            threads = ([threading.Thread(target=http_client, args=(t,),
                                         name=f"pt-test-http-{t}")
                        for t in range(5)] +
                       [threading.Thread(target=capi_client, args=(t,),
                                         name=f"pt-test-capi-{t}")
                        for t in range(3)])
            killer = FaultPlan.destroy_during(ch.destroy, src,
                                              delay_s=0.4)
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
                assert not t.is_alive(), "client thread wedged"
            killer.join(10)
        assert untyped == []

        # recovery: faults gone — after cooldown the breaker must close
        # and real traffic serves again
        deadline = time.monotonic() + 30
        ok = False
        while time.monotonic() < deadline:
            try:
                out = srv.infer(samples(seed=999), deadline=10.0)
                assert np.asarray(out).shape == (2, 4)
                ok = True
                break
            except (Rejected, Expired):
                time.sleep(0.1)
        assert ok, "server never recovered after faults stopped"
        st = srv.stats()
        assert st["served"] > 0
        srv.shutdown(drain=True, timeout=30)
        ch.destroy(src)                 # typed even if killer got it


class TestHTTPFront:
    def test_http_infer_health_stats(self):
        import json
        import urllib.error
        import urllib.request

        inf = tiny_inference()
        srv = InferenceServer(inf, max_queue=8, workers=1,
                              breaker=False).start()
        httpd = build_http_server(srv, "127.0.0.1", 0)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True,
                             name="pt-test-httpd")
        t.start()
        try:
            base = f"http://127.0.0.1:{port}"
            rows = [[0.1] * 8, [0.2] * 8]
            req = urllib.request.Request(
                base + "/infer",
                data=json.dumps({"rows": rows}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                body = json.loads(r.read())
            assert np.asarray(body["outputs"]).shape == (2, 4)
            with urllib.request.urlopen(base + "/health",
                                        timeout=10) as r:
                assert json.loads(r.read())["status"] == "ok"
            with urllib.request.urlopen(base + "/stats",
                                        timeout=10) as r:
                assert json.loads(r.read())["served"] == 1
            # malformed payload is a 400, not a stack trace
            bad = urllib.request.Request(
                base + "/infer", data=b"{\"rows\": \"nope\"}",
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(bad, timeout=10)
                assert False, "expected HTTPError"
            except urllib.error.HTTPError as e:
                assert e.code == 400
        finally:
            httpd.shutdown()
            srv.shutdown(drain=True)


class TestDecodeEngineChaos:
    """Continuous-batching engine under scheduler chaos (FaultPlan (j)):
    joins, cancellations and evictions land mid-decode; pages must
    always return to the pool and unfaulted sequences stay
    token-identical to undisturbed runs."""

    def test_mid_decode_join_and_cancel_pages_return(self):
        dec = tiny_decoder()
        rng = np.random.RandomState(0)
        p0 = rng.randint(0, 40, (4,)).astype("int32")
        p1 = rng.randint(0, 40, (6,)).astype("int32")
        p2 = rng.randint(0, 40, (5,)).astype("int32")
        # undisturbed references for the two requests that will SURVIVE
        want1 = dec.generate(p1[None, :], max_len=6 + 8)[0]
        want2 = dec.generate(p2[None, :], max_len=5 + 7)[0]

        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"])
        r0 = eng.submit(p0, 14)
        joined = []
        with FaultPlan.decode_script(eng, {
                2: lambda: joined.append(eng.submit(p1, 8)),
                4: lambda: joined.append(eng.submit(p2, 7)),
                6: lambda: r0.cancel()}) as script:
            eng.run(timeout=300)
        assert script["fired"] == [2, 4, 6]
        # the cancelled stream settles with its partial tokens
        assert r0.state == "cancelled"
        assert 0 < r0.num_generated < 14
        assert r0.get(timeout=1) == r0.tokens
        # the survivors are token-identical to solo runs
        assert joined[0].get(timeout=1) == [int(t) for t in want1]
        assert joined[1].get(timeout=1) == [int(t) for t in want2]
        assert_pool_balanced(eng)
        st = eng.stats()
        assert st["cancelled"] == 1 and st["finished"] == 2

    def test_eviction_storm_under_tiny_pool_no_leaks(self):
        """Pool pressure forces repeated preemption while requests keep
        arriving mid-flight; every request still completes exactly, and
        the pool balances to fully free."""
        dec = tiny_decoder()
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, 40, (int(rng.randint(3, 7)),))
                   .astype("int32") for _ in range(5)]
        news = [int(rng.randint(6, 12)) for _ in range(5)]
        want = [dec.generate(p[None, :], max_len=len(p) + n)[0]
                for p, n in zip(prompts, news)]
        # 2 slots x up to ~5 pages of demand against 6 usable pages
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=20, num_pages=7)
        reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        eng.run(timeout=300)
        for i, r in enumerate(reqs):
            assert r.get(timeout=1) == [int(t) for t in want[i]], i
        assert_pool_balanced(eng)

    def test_client_disconnect_during_generation(self):
        """A client that walks away mid-stream (disconnect_after): the
        engine cancels at its next step, frees the pages, and the other
        in-flight sequence is token-identical to a solo run."""
        dec = tiny_decoder()
        rng = np.random.RandomState(2)
        pa = rng.randint(0, 40, (4,)).astype("int32")
        pb = rng.randint(0, 40, (5,)).astype("int32")
        want_b = dec.generate(pb[None, :], max_len=5 + 10)[0]
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"]).start()
        try:
            ra = eng.submit(pa, 20)
            rb = eng.submit(pb, 10)
            killer = FaultPlan.disconnect_after(ra, 4)
            assert rb.get(timeout=120) == [int(t) for t in want_b]
            killer.join(60)
            assert not killer.is_alive()
            ra.done.wait(60)
            assert ra.state == "cancelled"
            assert ra.num_generated >= 4
        finally:
            eng.shutdown(drain=True, timeout=60)
        assert_pool_balanced(eng)
        assert eng.stats()["cancelled"] == 1

    def test_burst_overload_typed_rejections_only(self):
        """A thread-pool burst against a small engine: every submit
        either serves exactly or sheds with a typed Rejected; zero
        untyped errors, zero deadlocks, zero page leaks."""
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=20, max_waiting=3).start()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 40, (int(rng.randint(3, 7)),))
                   .astype("int32") for _ in range(16)]

        def one(i):
            return eng.submit(prompts[i], 4).get(timeout=120)

        try:
            results, errors = FaultPlan.burst(one, 16, threads=6,
                                              timeout=120)
        finally:
            eng.shutdown(drain=True, timeout=60)
        served = sum(r is not None for r in results)
        rejected = [e for e in errors if isinstance(e, Rejected)]
        other = [e for e in errors
                 if e is not None and not isinstance(e, Rejected)]
        assert other == []
        assert served + len(rejected) == 16
        assert served >= 1
        for i, r in enumerate(results):
            if r is not None:
                assert len(r) == 4, i
        assert all(e.reason == "queue_full" and e.retry_after > 0
                   for e in rejected)
        assert_pool_balanced(eng)

    def test_deadline_and_shutdown_are_typed(self):
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=1, page_size=4,
                           max_seq_len=20)
        blocker = eng.submit(np.zeros((3,), "int32"), 10)
        doomed = eng.submit(np.zeros((3,), "int32"), 10,
                            deadline=0.0)            # expired on arrival
        for _ in range(3):
            eng.step()
        with pytest.raises(Expired):
            doomed.get(timeout=5)
        # drainless shutdown: in-flight settles ServerClosed, pages back
        eng.shutdown(drain=False)
        with pytest.raises(ServerClosed):
            blocker.get(timeout=5)
        with pytest.raises(ServerClosed):
            eng.submit(np.zeros((3,), "int32"), 2)
        assert_pool_balanced(eng)
        assert eng.stats()["expired"] == 1


class TestPrefillLaneChaos:
    """A slot fed through prefill lanes is cancelled, or runs out of
    time, MID-PROMPT (its 200 tokens take two steps of 128): it settles
    typed with no token, every page it had written returns, its lanes
    serve the next prompt, and the slot decoding beside it is
    token-identical to a solo run."""

    def _setup(self, **kw):
        DEC = dict(DEC_CFG, max_len=256)
        paddle.init(use_tpu=False, seed=0)
        from paddle_tpu.core.registry import reset_name_counters
        reset_name_counters()
        spec = models.transformer_lm(**DEC)
        topo = paddle.Topology(spec.cost, extra_outputs=[spec.output])
        dec = models.TransformerDecoder(
            topo.init_params(jax.random.PRNGKey(7)),
            n_layers=DEC["n_layers"], n_heads=DEC["n_heads"])
        eng = DecodeEngine(dec, num_slots=2, page_size=4, max_seq_len=256,
                           **kw)
        assert eng.paged.lanes == (1, 64)
        rng = np.random.RandomState(3)
        short = rng.randint(0, 40, (5,)).astype("int32")
        long_ = rng.randint(0, 40, (200,)).astype("int32")
        return dec, eng, short, long_

    def test_cancel_mid_prompt_returns_the_lanes_and_the_pages(self):
        dec, eng, short, long_ = self._setup()
        want = dec.generate(short[None, :], max_len=5 + 9)[0]
        r0 = eng.submit(short, 9)
        joined = []
        with FaultPlan.decode_script(eng, {
                2: lambda: joined.append(eng.submit(long_, 4)),
                3: lambda: joined[0].cancel(),
                4: lambda: joined.append(eng.submit(long_[:150], 3)),
                }) as script:
            eng.run(timeout=300)
        assert script["fired"] == [2, 3, 4]
        doomed, after = joined
        assert doomed.state == "cancelled" and doomed.get(timeout=1) == []
        assert r0.get(timeout=1) == [int(t) for t in want]
        # the cancelled prompt's first 64 tokens were fed and indexed:
        # the next prompt, its first 150 tokens, attaches them
        assert after.prefix_hit_pages == 64 // 4
        assert after.get(timeout=1) == [int(t) for t in dec.generate(
            long_[None, :150], max_len=153)[0]]
        st = eng.stats()
        assert st["cancelled"] == 1 and st["finished"] == 2
        assert st["prefill_lane_tokens"] == 4 + 64 + (150 - 64 - 1)
        assert_pool_balanced(eng)

    def test_expiry_mid_prompt_is_typed_and_leaks_nothing(self):
        now = [0.0]
        dec, eng, short, long_ = self._setup(
            clock=lambda: time.monotonic() + now[0])
        want = dec.generate(short[None, :], max_len=5 + 9)[0]
        r0 = eng.submit(short, 9)
        joined = []

        def late():                      # the deadline passes mid-prompt
            now[0] += 3600.0

        with FaultPlan.decode_script(eng, {
                2: lambda: joined.append(eng.submit(long_, 4,
                                                    deadline=600.0)),
                3: late}) as script:
            eng.run(timeout=300)
        assert script["fired"] == [2, 3]
        with pytest.raises(Expired):
            joined[0].get(timeout=1)
        assert joined[0].tokens == []
        assert r0.get(timeout=1) == [int(t) for t in want]
        st = eng.stats()
        assert st["expired"] == 1 and st["finished"] == 1
        assert st["prefill_lane_tokens"] == 4 + 64
        assert_pool_balanced(eng)


class TestServerEngineIntegration:
    """InferenceServer with an attached DecodeEngine: generate() routes
    through page-aware admission, stats() carries the KV/slot gauges,
    and /metrics exposes them in Prometheus text format."""

    def _server(self):
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"])
        srv = InferenceServer(tiny_inference(), max_queue=8, workers=1,
                              breaker=False, engine=eng).start()
        return dec, eng, srv

    def test_generate_and_engine_stats(self):
        dec, eng, srv = self._server()
        try:
            prompt = np.zeros((3,), "int32")
            want = dec.generate(prompt[None, :], max_len=3 + 6)[0]
            got = srv.generate(prompt, 6, deadline=60.0)
            assert got == [int(t) for t in want]
            st = srv.stats()
            assert st["engine"]["finished"] == 1
            assert st["engine"]["kv_pages_total"] > 0
            assert_pool_balanced(eng)
        finally:
            srv.shutdown(drain=True)
        # shutdown drained the engine thread too
        assert eng.stats()["finished"] == 1
        with pytest.raises(ServerClosed):
            srv.generate(np.zeros((3,), "int32"), 2)

    def test_prometheus_metrics_text(self):
        dec, eng, srv = self._server()
        try:
            srv.infer(samples())
            srv.generate(np.zeros((3,), "int32"), 4, deadline=60.0)
            text = prometheus_text(srv)
        finally:
            srv.shutdown(drain=True)
        assert "# TYPE paddle_tpu_serving_served counter" in text
        assert "paddle_tpu_serving_served 1" in text
        assert "# TYPE paddle_tpu_serving_engine_kv_pages_free gauge" \
            in text
        assert "paddle_tpu_serving_engine_tokens_out 4" in text
        assert "paddle_tpu_serving_engine_slot_utilization" in text
        assert "paddle_tpu_serving_engine_token_latency_p99_ms" in text
        # every line is exposition-format: HELP/TYPE comment or
        # "name value" (the unified registry adds # HELP lines)
        for line in text.strip().splitlines():
            assert line.startswith(("# TYPE ", "# HELP ")) or \
                len(line.split(" ")) == 2, line

    def test_http_generate_and_metrics_endpoints(self):
        import json
        import urllib.error
        import urllib.request

        dec, eng, srv = self._server()
        httpd = build_http_server(srv, "127.0.0.1", 0)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True,
                             name="pt-test-httpd")
        t.start()
        try:
            base = f"http://127.0.0.1:{port}"
            prompt = [0, 0, 0]
            want = dec.generate(np.asarray(prompt, "int32")[None, :],
                                max_len=3 + 5)[0]
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"prompt": prompt,
                                 "max_new_tokens": 5,
                                 "deadline_ms": 60000}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                body = json.loads(r.read())
            assert body["tokens"] == [int(x) for x in want]
            # round-9 response fields: prefix-cache reuse + speculation
            # telemetry ride every /generate reply
            assert body["prefix_hit_pages"] >= 0
            assert body["accepted_tokens"] >= 0
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            assert "paddle_tpu_serving_engine_finished 1" in text
            # malformed generate payload is a 400
            bad = urllib.request.Request(
                base + "/generate", data=b'{"prompt": []}',
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(bad, timeout=10)
                assert False, "expected HTTPError"
            except urllib.error.HTTPError as e:
                assert e.code == 400
            # max_new_tokens < 1 is a 400 too, not the engine's
            # ValueError escaping as a torn connection
            bad = urllib.request.Request(
                base + "/generate",
                data=b'{"prompt": [1], "max_new_tokens": 0}',
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(bad, timeout=10)
                assert False, "expected HTTPError"
            except urllib.error.HTTPError as e:
                assert e.code == 400
        finally:
            httpd.shutdown()
            srv.shutdown(drain=True)

    def test_http_generate_without_engine_is_501(self):
        import json
        import urllib.error
        import urllib.request

        srv = InferenceServer(tiny_inference(), max_queue=4, workers=1,
                              breaker=False).start()
        httpd = build_http_server(srv, "127.0.0.1", 0)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True,
                             name="pt-test-httpd-2")
        t.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps({"prompt": [1],
                                 "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=10)
                assert False, "expected HTTPError"
            except urllib.error.HTTPError as e:
                assert e.code == 501
        finally:
            httpd.shutdown()
            srv.shutdown(drain=True)


class TestPrefixSpecChaos:
    """FaultPlan family (n): prefix-cache / CoW / speculation chaos
    (ISSUE 13). The round-9 invariants under every scenario: zero page
    leaks AND zero refcount underflows (``refs_total`` ==
    ``held_by_slots`` + ``held_by_trie``), and unfaulted sequences stay
    TOKEN-IDENTICAL to undisturbed dense runs — shared-prefix attach,
    copy-on-write and rejected speculation must never corrupt KV."""

    def _want(self, dec, prompt, max_new):
        p = np.asarray(prompt, "int32")
        return [int(t) for t in
                dec.generate(p[None, :], max_len=len(p) + max_new)[0]]

    def test_divergent_twins_cow_token_identity(self):
        """Request pairs sharing a prefix that splits mid-page: the
        late joiners attach the shared full page and CoW the split
        page; every stream is token-exact vs a solo dense run."""
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"])
        plan = FaultPlan(seed=21)
        twins = plan.divergent_twins(eng, max_new=4, pairs=2, vocab=40)
        eng.run(timeout=300)
        for i, (req, prompt) in enumerate(twins):
            assert req.get(timeout=1) == self._want(dec, prompt, 4), i
        st = eng.stats()
        # the first pair misses (cold trie); the second pair walks the
        # radix index: at least one full shared page attaches and the
        # mid-page divergence copies-on-write
        assert st["prefix_hit_pages"] >= 1
        assert st["prefix_cow_copies"] >= 1
        assert st["finished"] == 4
        assert_pool_balanced(eng)

    def test_prefix_evict_storm_reclaims_trie_not_slots(self):
        """Distinct-prompt waves stack finished pages into the trie
        until admission must reclaim LRU leaves; every request still
        completes token-exact and the pool balances."""
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=20, num_pages=9)
        plan = FaultPlan(seed=22)
        schedule, submitted = plan.prefix_evict_storm(
            eng, waves=4, per_wave=2, gap=2, prompt_len=8, max_new=3,
            vocab=40)
        with FaultPlan.decode_script(eng, schedule) as script:
            eng.run(timeout=300)
        assert script["fired"] == sorted(schedule)
        assert len(submitted) == 8
        for i, (req, prompt) in enumerate(submitted):
            assert req.get(timeout=1) == self._want(dec, prompt, 3), i
        st = eng.stats()
        assert st["finished"] == 8
        # the storm actually forced trie reclamation (journaled as
        # engine/prefix_evict), not just slot preemption
        assert st["prefix_evicted_pages"] >= 1
        assert_pool_balanced(eng)

    def test_cancel_mid_verify_returns_shared_refs(self):
        """With speculation on, a cancel lands between a draft
        proposal and the target's verify: the victim's pages AND its
        shared-prefix refs return, the survivor is token-exact."""
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"],
                           draft=tiny_decoder(), spec_k=2)
        rng = np.random.RandomState(23)
        shared = [int(t) for t in rng.randint(0, 40, 6)]
        victim_p = shared + [int(t) for t in rng.randint(0, 40, 3)]
        surv_p = shared + [int(t) for t in rng.randint(0, 40, 3)]
        victim = eng.submit(victim_p, 12)
        surv = eng.submit(surv_p, 8)
        with FaultPlan.decode_script(
                eng, FaultPlan.cancel_mid_verify(victim, at=2)) as s:
            eng.run(timeout=300)
        assert s["fired"] == [2]
        assert victim.state == "cancelled"
        assert victim.get(timeout=1) == victim.tokens
        assert surv.get(timeout=1) == self._want(dec, surv_p, 8)
        st = eng.stats()
        # the same-weights draft means speculation genuinely committed
        # multi-token steps before/around the cancel
        assert st["spec_proposed_tokens"] > 0
        assert st["spec_accepted_tokens"] > 0
        assert st["cancelled"] == 1 and st["finished"] == 1
        assert_pool_balanced(eng)

    def test_spec_identity_with_disagreeing_draft(self):
        """A draft with DIFFERENT weights proposes mostly-wrong tokens:
        acceptance filters them and the output is still token-exact —
        rejected speculation rows never become readable KV."""
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"],
                           draft=tiny_decoder(seed=11), spec_k=2)
        rng = np.random.RandomState(24)
        prompts = [[int(t) for t in rng.randint(0, 40, n)]
                   for n in (5, 7)]
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.run(timeout=300)
        for i, (req, p) in enumerate(zip(reqs, prompts)):
            assert req.get(timeout=1) == self._want(dec, p, 8), i
        st = eng.stats()
        assert st["spec_proposed_tokens"] > 0
        assert_pool_balanced(eng)


class TestTwoTierChaos:
    """FaultPlan family (s): two-tier KV spill/restore chaos (ISSUE
    20). The invariants under every scenario: BOTH tiers balance
    (``assert_pool_balanced`` incl. host-tier conservation), every
    settled request is token-exact, and a torn spill — crash at the
    read or the commit point — never leaves a page simultaneously
    device-owned and host-stored."""

    def _want(self, dec, prompt, max_new):
        p = np.asarray(prompt, "int32")
        return [int(t) for t in
                dec.generate(p[None, :], max_len=len(p) + max_new)[0]]

    def _engine(self, dec, **over):
        kw = dict(num_slots=2, page_size=4, max_seq_len=20,
                  num_pages=9, kv_spill_pages=8)
        kw.update(over)
        return DecodeEngine(dec, **kw)

    def test_spill_storm_restores_and_balances(self):
        """Distinct-prompt waves overflow the tiny pool so cold trie
        leaves spill host-ward; later waves revisit the earliest
        prompts and must RESTORE their pages. Every stream token-exact,
        both tiers conserved."""
        dec = tiny_decoder()
        eng = self._engine(dec)
        plan = FaultPlan(seed=31)
        schedule, submitted = plan.spill_storm(
            eng, waves=5, per_wave=2, gap=2, prompt_len=8, max_new=3,
            vocab=40, revisit_from=2)
        with FaultPlan.decode_script(eng, schedule) as script:
            eng.run(timeout=300)
        assert script["fired"] == sorted(schedule)
        for i, (req, prompt) in enumerate(submitted):
            assert req.get(timeout=1) == self._want(dec, prompt, 3), i
        acc = assert_pool_balanced(eng)
        # the storm genuinely exercised BOTH directions of the tier
        # boundary — pages went host-ward and came back
        assert acc["spill_puts"] >= 1
        assert acc["spill_restores"] >= 1
        st = eng.stats()
        assert st["finished"] == len(submitted)
        assert st["kv_pages_spilled_now"] == acc["spilled"]

    def test_spill_storm_int8_identity(self):
        """The same storm over int8-quantized pages: restore feeds the
        dequant read path and greedy decode stays token-identical to
        the dense float reference (the pinned int8 tolerance contract
        — INT8_KV_RTOL/ATOL on attention outputs keeps argmax stable
        at this scale)."""
        dec = tiny_decoder()
        eng = self._engine(dec, kv_quant="int8")
        assert eng.stats()["kv_quant_bits"] == 8
        plan = FaultPlan(seed=32)
        schedule, submitted = plan.spill_storm(
            eng, waves=4, per_wave=2, gap=2, prompt_len=8, max_new=3,
            vocab=40, revisit_from=2)
        with FaultPlan.decode_script(eng, schedule):
            eng.run(timeout=300)
        for i, (req, prompt) in enumerate(submitted):
            assert req.get(timeout=1) == self._want(dec, prompt, 3), i
        acc = assert_pool_balanced(eng)
        assert acc["spill_puts"] >= 1

    def test_corrupt_spilled_page_degrades_to_miss(self):
        """Bit-rot EVERY host-resident entry (CRC left stale), then
        revisit the stormed prompts: each attempted restore must fail
        verification, drop the entry (``spill_dropped_integrity``) and
        degrade to a prefix miss — recompute, token-exact, balanced."""
        dec = tiny_decoder()
        eng = self._engine(dec)
        plan = FaultPlan(seed=33)
        # revisit_from past the last wave: storm only spills, so the
        # store is populated (not drained) when the corruption lands
        schedule, submitted = plan.spill_storm(
            eng, waves=4, per_wave=2, gap=2, prompt_len=8, max_new=3,
            vocab=40, revisit_from=4)
        with FaultPlan.decode_script(eng, schedule):
            eng.run(timeout=300)
        acc0 = assert_pool_balanced(eng)
        assert acc0["spilled"] >= 1

        class _Rotate:  # deterministic rng stub: hit EVERY entry once
            def __init__(self):
                self.i = 0

            def choice(self, xs):
                xs = sorted(xs)
                v = xs[self.i % len(xs)]
                self.i += 1
                return v

            def randrange(self, n):
                return 0

        rot = _Rotate()
        for _ in range(acc0["spilled"]):
            assert eng.spill.corrupt_one("bitflip", rng=rot) is not None
        # revisit every distinct stormed prompt: restores are attempted
        # against corrupted entries only
        prompts = []
        for _, p in submitted:
            if p not in prompts:
                prompts.append(p)
        reqs = [eng.submit(p, 3) for p in prompts]
        eng.run(timeout=300)
        for i, (req, p) in enumerate(zip(reqs, prompts)):
            assert req.get(timeout=1) == self._want(dec, p, 3), i
        acc = assert_pool_balanced(eng)
        # at least one corrupted entry was hit, failed CRC and was
        # dropped (the revisit churn may also spill-and-restore FRESH
        # uncorrupted pages, so restores can legitimately grow — the
        # pinned contract is that corruption is always caught)
        assert acc["spill_dropped_integrity"] >= 1

    @pytest.mark.parametrize("stage", ["read", "commit"])
    def test_kill_during_spill_stays_balanced(self, stage):
        """WorkerCrash at the read point (nothing changed) or the
        commit point (trie evicted + page freed, store entry NOT yet
        committed): the SIGKILL twin. The survivor's accounting must
        show no page both device-owned and host-stored, and a resumed
        engine drains every request token-exact."""
        from paddle_tpu.testing import WorkerCrash
        dec = tiny_decoder()
        eng = self._engine(dec)
        plan = FaultPlan(seed=34)
        schedule, submitted = plan.spill_storm(
            eng, waves=4, per_wave=2, gap=2, prompt_len=8, max_new=3,
            vocab=40, revisit_from=4)
        with FaultPlan.decode_script(eng, schedule):
            with FaultPlan.kill_during_spill(eng, at=0, stage=stage) \
                    as ks:
                with pytest.raises(WorkerCrash):
                    eng.run(timeout=300)
        assert ks["fired"] == 1 and ks["path"] is not None
        # mid-crash: slots still hold in-flight pages, but nothing
        # leaked, refs match, and the host tier conserves — the torn
        # spill left NO store entry for the in-flight path
        acc = eng.page_accounting()
        assert acc["leaked"] == 0
        assert acc["refs_total"] == \
            acc["held_by_slots"] + acc["held_by_trie"]
        assert acc["spill_puts"] == (
            acc["spill_restores"] + acc["spill_evicted_lru"]
            + acc["spill_dropped_integrity"] + acc["spill_cleared"]
            + acc["spilled"])
        assert tuple(ks["path"]) not in eng.spill._entries
        # the interceptor is disarmed; the engine finishes the storm
        eng.run(timeout=300)
        for i, (req, prompt) in enumerate(submitted):
            assert req.get(timeout=1) == self._want(dec, prompt, 3), i
        assert_pool_balanced(eng)
