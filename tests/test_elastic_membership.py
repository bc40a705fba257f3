"""Elastic coordinator v2 chaos acceptance (docs/robustness.md
"Elastic training"): scale-out/in mid-job with deterministic reshard
and exactly-once data accounting.

Invariants under test:
  * join/leave/lease-expiry bump a monotonic GENERATION and reshard the
    todo queue into canonical (epoch, task_id) order;
  * completions carrying a superseded grant are REJECTED (stale_grants)
    while a live worker's pre-reshape grant is accepted exactly once;
  * task_release hands a reader position to the next holder, so no
    record is read twice or dropped across a reshape;
  * a joining replacement adopts the fleet's published MemoryPlan
    (provenance="adopted") instead of re-probing/re-OOMing;
  * killing one worker AND adding another mid-pass still yields
    exactly-once per-record accounting and (where the schedule permits)
    a digest-identical loss trajectory versus fixed membership.
"""

import collections
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.obs.events import tail
from paddle_tpu.obs.metrics import REGISTRY
from paddle_tpu.testing.faults import FaultPlan
from paddle_tpu.trainer.checkpoint import CheckpointManager
from paddle_tpu.trainer.coordinator import (Coordinator, CoordinatorServer,
                                            FileStore, KVStoreServer,
                                            RpcStore, connect, task_reader)

RECORDS_PER_CHUNK = 4


def _small_trainer(seed=0):
    from paddle_tpu.core import registry
    registry.reset_name_counters()
    paddle.init(use_tpu=False, seed=seed)
    x = paddle.layer.data("x", paddle.data_type.dense_vector(16))
    out = paddle.layer.fc(x, size=4, act=paddle.activation.Softmax(),
                          name="out")
    y = paddle.layer.data("y", paddle.data_type.integer_value(4))
    cost = paddle.layer.classification_cost(out, y, name="cost")
    params = paddle.create_parameters(paddle.Topology(cost))
    return paddle.SGD(cost=cost, parameters=params,
                      update_equation=paddle.optimizer.Adam(
                          learning_rate=1e-2))


def _digest_chunks(chunk):
    r = np.random.RandomState(1000 + int(chunk))
    return [(r.randn(16).astype("float32"), int(r.randint(4)))
            for _ in range(RECORDS_PER_CHUNK)]


class TestMembershipProtocol:
    """join/leave/worker_heartbeat lease protocol + generation/reshard
    determinism (the unit half of the chaos acceptance)."""

    def test_join_bumps_generation_and_returns_roster(self):
        c = Coordinator(list(range(4)), chunks_per_task=1)
        r1 = c.join("w1", info={"host": "a"})
        assert r1["generation"] == 1 and r1["epoch"] == 0
        assert r1["workers"] == ["w1"]
        assert r1["memory_plan"] is None
        r2 = c.join("w2")
        assert r2["generation"] == 2
        assert r2["workers"] == ["w1", "w2"]
        # re-join of a live member renews the lease WITHOUT a reshape
        r3 = c.join("w1")
        assert r3["generation"] == 2
        assert c.workers() == ["w1", "w2"]

    def test_worker_heartbeat_renews_and_unknown_must_rejoin(self):
        c = Coordinator([1], chunks_per_task=1)
        assert c.worker_heartbeat("ghost") == -1
        c.join("w1")
        assert c.worker_heartbeat("w1") == c.generation

    def test_leave_requeues_without_penalty_in_canonical_order(self):
        c = Coordinator(list(range(6)), chunks_per_task=1)
        c.join("a")
        c.join("b")
        for _ in range(2):
            assert c.get_task(0, "a") is not None     # tasks 0, 1
        gb = c.get_task(0, "b")                       # task 2
        gen_before = c.generation
        assert c.leave("a") is True
        assert c.generation == gen_before + 1
        # a's tasks re-queued ahead, canonical (epoch, task_id) order,
        # and WITHOUT a failure penalty (it didn't fail — it shrank)
        assert [t.task_id for t in c._todo] == [0, 1, 3, 4, 5]
        assert all(t.num_failures == 0 for t in c._todo)
        order = []
        while True:
            t = c.get_task(0, "b")
            if t is None:
                break
            order.append(t["task_id"])
            assert c.task_finished(t["task_id"], t["generation"])
        assert order == [0, 1, 3, 4, 5]
        assert c.task_finished(gb["task_id"], gb["generation"])
        assert c.epoch == 1
        assert c.leave("a") is False                  # already gone

    def test_lease_expiry_is_an_implicit_leave(self):
        c = Coordinator([1, 2], chunks_per_task=1, timeout_s=30.0,
                        worker_lease_s=0.05)
        c.join("w1")
        g = c.get_task(0, "w1")
        assert g is not None
        time.sleep(0.08)
        gen_before = c.generation
        assert c.workers() == []                      # sweep expired w1
        assert c.generation == gen_before + 1
        # the dead worker's task went back to todo (with a penalty)
        assert g["task_id"] in [t.task_id for t in c._todo]
        assert c.worker_heartbeat("w1") == -1         # must re-join

    def test_stale_grant_rejected_after_requeue(self):
        c = Coordinator([7], chunks_per_task=1, timeout_s=30.0,
                        worker_lease_s=0.05)
        c.join("victim")
        g1 = c.get_task(0, "victim")
        time.sleep(0.08)
        c.join("spare")            # sweeps the victim, requeues its task
        g2 = c.get_task(0, "spare")
        assert g2["task_id"] == g1["task_id"]
        assert g2["generation"] > g1["generation"]
        # the zombie's completion carries the superseded stamp: refused
        assert c.task_finished(g1["task_id"], g1["generation"]) is False
        assert c.num_stale_grants() == 1
        assert [r for r in tail(50, domain="coordinator",
                                kind="stale_grant")]
        # the live holder's completion lands exactly once
        assert c.task_finished(g2["task_id"], g2["generation"]) is True
        assert c.epoch == 1

    def test_live_workers_pre_reshape_grant_still_accepted(self):
        # a join must NOT invalidate in-flight grants of live members —
        # or their records would be re-served and read twice
        c = Coordinator([1, 2], chunks_per_task=1)
        c.join("w1")
        g = c.get_task(0, "w1")
        c.join("w2")
        assert c.generation > g["generation"]
        assert c.task_finished(g["task_id"], g["generation"]) is True
        assert c.num_stale_grants() == 0

    def test_task_release_hands_position_to_next_holder(self):
        c = Coordinator([5], chunks_per_task=1)
        c.join("w1")
        g = c.get_task(0, "w1")
        assert c.task_release(g["task_id"], g["generation"],
                              {"records_consumed": 2}) is True
        g2 = c.get_task(0, "w1")
        assert g2["task_id"] == g["task_id"]
        assert g2["resume_state"] == {"records_consumed": 2}
        # the position was consumed by that grant, not left behind
        assert c.task_release(g2["task_id"], g2["generation"]) is True
        g3 = c.get_task(0, "w1")
        assert g3["resume_state"] is None

    def test_task_reader_skips_released_prefix(self):
        c = Coordinator(["c0"], chunks_per_task=1)
        c.join("w1")
        g = c.get_task(0, "w1")
        c.task_release(g["task_id"], g["generation"],
                       {"records_consumed": 2})
        c.join("w2")
        recs = list(task_reader(
            c, lambda ch: [(ch, i) for i in range(RECORDS_PER_CHUNK)],
            worker_id="w2")())
        assert recs == [("c0", 2), ("c0", 3)]         # exactly-once
        assert c.epoch == 1

    def test_membership_script_fires_at_exact_grants(self):
        c = Coordinator(list(range(4)), chunks_per_task=1)
        c.join("w1")
        with FaultPlan.membership_script(
                c, {1: lambda: c.join("mid-join")}) as st:
            while True:
                t = c.get_task(0, "w1")
                if t is None:
                    break
                assert c.task_finished(t["task_id"], t["generation"])
        assert st["fired"] == [1]
        assert "mid-join" in c.workers()
        assert c.epoch == 1                 # schedule unperturbed
        assert c.num_stale_grants() == 0    # live grants all honored


@pytest.mark.chaos(timeout=90)
class TestExactlyOnceChaos:
    """The tentpole acceptance: kill one worker AND add one mid-pass;
    every record of the pass is accounted exactly once, and no live
    worker's completion is ever refused."""

    def test_kill_and_join_mid_pass_exactly_once(self):
        # Leases no scheduler pause can outrun (10 s, 60 s a task), on the
        # coordinator's own clock, which here only the test moves: the
        # victim's lease lapses because the clock passes it, never because
        # a live thread was descheduled for half a second.
        now = [1000.0]
        coord = Coordinator(list(range(6)), chunks_per_task=1,
                            timeout_s=60.0, failure_max=10,
                            worker_lease_s=10.0)
        coord.time = lambda: now[0]
        accepted = collections.Counter()
        polls = collections.Counter()       # get_task calls, by worker
        lock = threading.Lock()
        deadline = time.time() + 30.0

        def worker(wid, die_after=None):
            coord.join(wid)
            my_grants = 0
            while time.time() < deadline:
                t = coord.get_task(0, wid)
                polls[wid] += 1
                if t is None:
                    if coord.epoch != 0:
                        break
                    time.sleep(0.02)
                    continue
                my_grants += 1
                skip = int((t.get("resume_state") or {})
                           .get("records_consumed", 0))
                recs = [(c, i) for c in t["chunks"]
                        for i in range(RECORDS_PER_CHUNK)][skip:]
                if die_after is not None and my_grants >= die_after:
                    return        # SIGKILL twin: vanish holding a lease
                if coord.task_finished(t["task_id"], t["generation"]):
                    with lock:
                        accepted.update(recs)
            coord.leave(wid)

        joiners = []

        def scale_out():
            th = threading.Thread(target=worker, args=("w3",),
                                  daemon=True, name="pt-test-w3")
            joiners.append(th)
            th.start()

        with FaultPlan.membership_script(coord, {3: scale_out}) as st:
            threads = [
                threading.Thread(target=worker, args=("w1", 2),
                                 daemon=True,
                                 name="pt-test-w1"),    # dies on grant 2
                threading.Thread(target=worker, args=("w2",),
                                 daemon=True, name="pt-test-w2"),
            ]
            for th in threads:
                th.start()
            threads[0].join(35.0)   # the victim is gone, lease and task held
            # 12 s in two moves: past the victim's last renewal by more
            # than a lease, and never 10 s past a live worker's, each of
            # which has renewed (a whole get_task) after the first move
            now[0] += 6.0
            seen = {w: polls[w] for w in coord.workers() if w != "w1"}
            while time.time() < deadline and any(
                    polls[w] < n + 2 for w, n in seen.items()):
                time.sleep(0.01)
            now[0] += 6.0
            threads[1].join(35.0)
            for th in joiners:
                th.join(35.0)
        assert st["fired"] == [3]           # the join landed on schedule
        assert coord.epoch == 1, "pass never completed under churn"
        expected = collections.Counter(
            {(c, i): 1 for c in range(6)
             for i in range(RECORDS_PER_CHUNK)})
        assert accepted == expected         # exactly-once, every record
        # no live worker's own completion was ever refused
        assert coord.num_stale_grants() == 0
        assert coord.workers() == []        # survivors left, victim swept
        assert coord.generation >= 4        # 3 joins + expiry + leaves


@pytest.mark.chaos(timeout=150)
class TestDigestIdenticalTrajectory:
    """Where the dispatch schedule permits (scale-in at a pass boundary,
    replacement restores the checkpoint), the elastic run's loss
    trajectory is DIGEST-IDENTICAL to a fixed-membership run — the
    reshape moved work, not math."""

    def _run(self, coord, mgr, worker_id, num_passes, losses):
        tr = _small_trainer(seed=0)

        def on_ev(e):
            if isinstance(e, paddle.event.EndIteration):
                losses.append(float(e.cost))

        tr.train(coordinator=coord, chunk_reader=_digest_chunks,
                 batch_size=4, num_passes=num_passes,
                 checkpoint_manager=mgr, event_handler=on_ev,
                 worker_id=worker_id)

    def test_leave_join_at_pass_boundary_is_digest_identical(
            self, tmp_path):
        fixed, elastic = [], []
        coord_a = Coordinator(list(range(4)), chunks_per_task=1)
        self._run(coord_a, CheckpointManager(str(tmp_path / "fixed")),
                  "solo", 2, fixed)
        coord_b = Coordinator(list(range(4)), chunks_per_task=1)
        ck = str(tmp_path / "elastic")
        # w1 trains pass 0, checkpoints, and leaves (scale-in)...
        self._run(coord_b, CheckpointManager(ck), "w1", 1, elastic)
        assert len(elastic) == len(fixed) // 2
        # ...a FRESH trainer joins, restores, and finishes pass 1
        self._run(coord_b, CheckpointManager(ck), "w2", 2, elastic)
        assert len(elastic) == len(fixed)
        np.testing.assert_array_equal(np.asarray(elastic),
                                      np.asarray(fixed))
        assert coord_b.generation >= 2
        leaves = {r.get("worker_id")
                  for r in tail(100, domain="coordinator", kind="leave")}
        assert {"w1", "w2"} <= leaves


class TestMemoryPlanAdoption:
    """A replacement host adopts the fleet's published MemoryPlan from
    its join() response (provenance="adopted") — no re-probe, no
    re-discovered OOM."""

    def test_join_adopts_published_plan_without_probe(self):
        c = Coordinator(list(range(4)), chunks_per_task=1)
        assert c.put_memory_plan({"microbatch": 2, "accum_steps": 2,
                                  "provenance": "adapted"}) is True
        tr = _small_trainer(seed=0)
        tr.train(coordinator=c, chunk_reader=_digest_chunks,
                 batch_size=4, num_passes=1, worker_id="replacement",
                 microbatch="auto", oom_probe=True)
        plan = tr._memory_exec.plan
        # adopted verbatim; a probe would have stamped "probe"
        assert plan.provenance == "adopted"
        assert plan.microbatch == 2 and plan.accum_steps == 2
        kinds = [r["kind"] for r in tail(300, domain="trainer")]
        assert "plan_adopted" in kinds
        assert "oom" not in kinds           # zero induced OOMs

    def test_worker_publishes_its_plan_for_the_next_joiner(self):
        c = Coordinator(list(range(4)), chunks_per_task=1)
        tr = _small_trainer(seed=0)
        tr.train(coordinator=c, chunk_reader=_digest_chunks,
                 batch_size=4, num_passes=1, worker_id="w1",
                 microbatch=2)
        assert (c.memory_plan or {}).get("microbatch") == 2
        assert c.memory_plan["provenance"] == "configured"
        # and the NEXT joiner receives it in its join() response
        assert c.join("w2")["memory_plan"]["microbatch"] == 2


@pytest.mark.chaos(timeout=180)
class TestSigkillPlusJoin:
    """Subprocess acceptance: SIGKILL one elastic worker mid-pass, join
    a replacement, the job completes; the victim's membership lapses by
    lease (journaled) and its task is re-served."""

    def test_sigkill_then_join_completes(self, tmp_path):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        worker = os.path.join(repo, "tests", "elastic_worker.py")
        ckpt = str(tmp_path / "ckpt")
        coord = Coordinator(list(range(6)), chunks_per_task=1,
                            timeout_s=1.5, failure_max=10)
        srv = CoordinatorServer(coord).start()
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        try:
            p1 = subprocess.Popen(
                [sys.executable, worker, str(srv.port), ckpt, "0.25",
                 "w1"],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            deadline = time.time() + 60
            while coord.epoch == 0 and not coord._done and \
                    time.time() < deadline:
                time.sleep(0.1)
            assert time.time() < deadline, "worker never started tasks"
            p1.send_signal(signal.SIGKILL)
            p1.communicate(timeout=30)
            p2 = subprocess.Popen(
                [sys.executable, worker, str(srv.port), ckpt, "0",
                 "w2"],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            out, err = p2.communicate(timeout=120)
            assert p2.returncode == 0, err.decode()
            assert b"WORKER DONE" in out
            assert coord.epoch >= 2          # both passes completed
            joined = {r.get("worker_id")
                      for r in tail(200, domain="coordinator",
                                    kind="join")}
            assert {"w1", "w2"} <= joined
            expired = {r.get("worker_id")
                       for r in tail(200, domain="coordinator",
                                     kind="lease_expired")}
            assert "w1" in expired           # the SIGKILL became a leave
            assert coord.num_stale_grants() == 0
            assert coord.workers() == []     # w2 left gracefully
        finally:
            srv.stop()


class TestThreadingServer:
    """Satellite: the RPC server is concurrent — one slow/blocked RPC
    must not starve heartbeats and expire a healthy worker's lease."""

    def test_blocked_rpc_does_not_expire_healthy_lease(self):
        coord = Coordinator([0, 1], chunks_per_task=1, timeout_s=1.0)
        srv = CoordinatorServer(coord).start()
        entered = threading.Event()
        release = threading.Event()

        def slow():
            entered.set()
            release.wait(15.0)
            return True

        srv.server.register_function(slow, "slow")
        try:
            c1 = connect("127.0.0.1", srv.port)
            t = c1.get_task()
            blocker = threading.Thread(
                target=lambda: connect("127.0.0.1", srv.port).slow(),
                daemon=True, name="pt-test-blocker")
            blocker.start()
            assert entered.wait(10.0), "slow RPC never reached the server"
            # heartbeat through MORE than one lease while slow() blocks
            c2 = connect("127.0.0.1", srv.port)
            until = time.time() + 1.6
            while time.time() < until:
                assert c2.heartbeat(t["task_id"]) is True
                time.sleep(0.2)
            names = [th.name for th in threading.enumerate()]
            assert any(n.startswith("pt-coord-rpc-") for n in names)
            release.set()
            blocker.join(15.0)
            # the lease survived: the task is still ours to finish
            assert c2.task_finished(t["task_id"],
                                    t["generation"]) is True
        finally:
            release.set()
            srv.stop()

    def test_membership_rpc_surface(self):
        coord = Coordinator([1, 2], chunks_per_task=1)
        srv = CoordinatorServer(coord).start()
        try:
            c = connect("127.0.0.1", srv.port)
            resp = c.join("rpc-w")
            assert resp["generation"] == 1
            assert c.worker_heartbeat("rpc-w") == 1
            assert c.generation() == 1
            assert c.workers() == ["rpc-w"]
            assert c.stats()["workers"] == 1
            assert c.num_stale_grants() == 0
            g = c.get_task(0, "rpc-w")
            assert c.task_release(g["task_id"], g["generation"],
                                  {"records_consumed": 1}) is True
            assert c.get_task(0, "rpc-w")["resume_state"] == \
                {"records_consumed": 1}
            assert c.leave("rpc-w") is True
        finally:
            srv.stop()


class TestRpcStore:
    """Snapshot durability WITHOUT a shared filesystem: the KVStore
    interface served over RPC, binary-safe, recoverable."""

    def test_binary_roundtrip_and_missing_key(self):
        kv = KVStoreServer().start()
        try:
            store = RpcStore("127.0.0.1", kv.port)
            store.put("k", b"\x00\xff raw \x01 bytes")
            assert store.get("k") == b"\x00\xff raw \x01 bytes"
            assert store.get("missing") is None
        finally:
            kv.stop()

    def test_coordinator_recovers_through_rpc_store(self):
        kv = KVStoreServer().start()
        try:
            c1 = Coordinator(list(range(4)), chunks_per_task=1,
                             store=RpcStore("127.0.0.1", kv.port))
            c1.join("w1")
            g = c1.get_task(0, "w1")
            assert g is not None
            c2 = Coordinator([], store=RpcStore("127.0.0.1", kv.port))
            assert c2.recovered
            assert c2.chunks == (0, 1, 2, 3)
            assert c2.generation == c1.generation
            # membership leases are deliberately NOT persisted: a fleet
            # re-joins a recovered master
            assert c2.workers() == []
        finally:
            kv.stop()


class TestStoreCoverage:
    """Satellite: FileStore degradation paths and dropped-task
    accounting across snapshot/recover."""

    def test_filestore_oserror_treated_as_absent(self, tmp_path):
        store = FileStore(str(tmp_path))
        os.makedirs(store._path("k"))       # open() -> IsADirectoryError
        with pytest.warns(UserWarning, match="could not read"):
            assert store.get("k") is None

    def test_legacy_unframed_snapshot_recovers(self, tmp_path):
        store = FileStore(str(tmp_path))
        c1 = Coordinator(list(range(3)), chunks_per_task=1, store=store)
        c1.join("w1")
        path = store._path("coordinator/state")
        with open(path, "rb") as f:
            blob = f.read()
        assert blob.startswith(FileStore._MAGIC)
        payload = blob[len(FileStore._MAGIC) + 12:]
        with open(path, "wb") as f:         # an older writer's raw JSON
            f.write(payload)
        c2 = Coordinator([], store=FileStore(str(tmp_path)))
        assert c2.recovered
        assert c2.chunks == (0, 1, 2)
        assert c2.generation == c1.generation

    def test_num_dropped_survives_snapshot_recover(self, tmp_path):
        store = FileStore(str(tmp_path))
        c1 = Coordinator([1, 2], chunks_per_task=1, failure_max=1,
                         store=store)
        t = c1.get_task()
        assert c1.task_failed(t["task_id"]) is True   # dropped outright
        assert c1.num_dropped() == 1
        assert c1.epoch == 0                # todo not drained: no turn
        c2 = Coordinator([], store=store)
        assert c2.recovered
        assert c2.num_dropped() == 1
        assert c2.get_task(0) is not None   # the healthy task re-serves


class TestObservability:
    """Satellite: every membership transition journals, the /metrics
    registry exposes paddle_tpu_coord_* gauges, and a lease-expiry
    storm auto-dumps a flight-recorder bundle."""

    def test_journal_events_and_gauges(self):
        c = Coordinator(list(range(4)), chunks_per_task=1,
                        timeout_s=30.0, worker_lease_s=0.05)
        c.join("w1")
        c.join("w2")
        assert c.get_task(0, "w1") is not None
        c.leave("w2")
        time.sleep(0.08)
        assert c.worker_heartbeat("w1") == -1         # swept: expired
        kinds = {r["kind"] for r in tail(300, domain="coordinator")}
        assert {"join", "leave", "lease_expired", "reshard",
                "generation"} <= kinds
        rec = tail(1, domain="coordinator")[0]
        assert "run_id" in rec and "host" in rec      # correlated
        text = REGISTRY.exposition()
        for gauge in ("paddle_tpu_coord_workers",
                      "paddle_tpu_coord_generation",
                      "paddle_tpu_coord_stale_grants",
                      "paddle_tpu_coord_tasks_todo"):
            assert gauge in text, f"missing {gauge} in exposition"

    def test_lease_expiry_storm_dumps_flight_bundle(self, tmp_path):
        from paddle_tpu.obs.flight import FLIGHT
        FLIGHT.configure(dump_dir=str(tmp_path), min_dump_interval=0.0)
        c = Coordinator([1, 2], chunks_per_task=1, timeout_s=30.0,
                        worker_lease_s=0.03)
        c.join("a")
        c.join("b")
        time.sleep(0.06)
        c.workers()                  # one sweep expires both: a storm
        deadline = time.time() + 10.0     # dump runs off-thread
        bundles = []
        while not bundles and time.time() < deadline:
            bundles = [p for p in os.listdir(tmp_path)
                       if "coord-lease-expiry-storm" in p]
            time.sleep(0.05)
        assert bundles, "lease-expiry storm did not auto-dump a bundle"
