"""Performance observability plane — acceptance suite (ISSUE 11).

Covers the tentpole contract: the continuous step profiler's per-phase
breakdown and cost/roofline gauges (with the bench.py-shared math —
live MFU and an offline bench-style computation from the same inputs
must agree within 10%), device-memory telemetry off-thread, deep
profile windows, the SLO watchdog's declarative objectives +
burn-rate breaches, and THE chaos acceptance: an injected 5x step
stall is journaled as ``slo/step_regression`` naming the injected
phase, and the auto-dumped flight bundle carries the per-phase
breakdown that attributes it.
"""

import json
import os
import threading
import time
import warnings

import pytest

import paddle_tpu as paddle
from paddle_tpu.obs.events import JOURNAL, read_journal, validate
from paddle_tpu.obs.flight import FLIGHT
from paddle_tpu.obs.profile import (PROFILER, cost_of, device_hbm_gbps,
                                    device_peak_flops, roofline)
from paddle_tpu.obs.slo import WATCHDOG, Objective, parse_objective
from paddle_tpu.utils.stats import stat_timer


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


# ------------------------------------------------- shared roofline math

class TestRooflineMath:
    def test_device_peak_tables(self):
        assert device_peak_flops(_Dev("TPU v4")) == 275e12
        assert device_hbm_gbps(_Dev("TPU v5e")) == 819.0
        assert device_peak_flops(_Dev("cpu")) is None
        assert device_hbm_gbps(_Dev("NVIDIA A100")) is None

    def test_roofline_bounds_and_mfu(self):
        # 1 GFLOP at 1 TFLOP/s peak -> 1 ms mxu bound; 0.1 GB at
        # 100 GB/s -> 1 ms hbm bound; measured 2 ms -> frac 2.0
        rf = roofline(2.0, flops=1e9, bytes_acc=1e8,
                      peak_flops=1e12, hbm_gbps=100.0, mxu=True)
        assert rf["mfu"] == pytest.approx(0.5)
        assert rf["roofline_ms"] == pytest.approx(1.0)
        assert rf["roofline_frac"] == pytest.approx(2.0)
        # mxu=False (f32 run): only the hbm bound can bind
        rf = roofline(2.0, flops=1e9, bytes_acc=1e8,
                      peak_flops=1e12, hbm_gbps=50.0, mxu=False)
        assert rf["roofline_bound"] == "hbm"
        assert rf["roofline_ms"] == pytest.approx(2.0)
        # degenerate inputs stay empty, never raise
        assert roofline(0.0, flops=1e9, peak_flops=1e12) == {}
        assert roofline(1.0) == {}

    def test_cost_of_jitted_callable(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(a, b):
            return a @ b

        x = jnp.ones((16, 16), dtype=jnp.float32)
        flops, nbytes = cost_of(f, x, x)
        assert flops and flops > 0
        assert nbytes and nbytes > 0


# ------------------------------------------------- continuous profiler

def _drive_train_steps(n, compute_ms=2.0):
    """n profiler-observed steps with a known compute-phase cost."""
    for _ in range(n):
        with stat_timer("train/data_wait"):
            pass
        with stat_timer("train/h2d"):
            pass
        with stat_timer("train_step"):
            time.sleep(compute_ms / 1e3)
        with stat_timer("train/settle"):
            pass
        PROFILER.on_step("train")


class TestStepProfiler:
    def test_disabled_is_noop(self):
        assert not PROFILER.enabled
        PROFILER.on_step("train")
        assert PROFILER.snapshot()["kinds"] == {}

    def test_phase_breakdown_and_snapshot_shape(self):
        PROFILER.enable(sample_every=2)
        try:
            _drive_train_steps(6)
        finally:
            PROFILER.disable()
        snap = PROFILER.snapshot()
        st = snap["kinds"]["train"]
        assert st["steps"] == 6
        assert st["step_ms_median"] > 0
        assert set(st["phases"]) == {"data_wait", "h2d", "compute",
                                     "settle"}
        # the stall budget went where it was spent
        assert st["phases"]["compute"] > st["phases"]["data_wait"]
        assert set(snap["window"]) == {"remaining", "last_trace_dir"}
        json.dumps(snap)                      # served on GET /profile

    def test_deep_window_captures_trace_artifact(self, tmp_path):
        out = str(tmp_path / "trace")
        PROFILER.enable(sample_every=1)
        try:
            got = PROFILER.arm_window(2, out_dir=out)
            assert got == out
            _drive_train_steps(3)
        finally:
            PROFILER.disable()
        snap = PROFILER.snapshot()
        assert snap["window"]["remaining"] == 0
        assert snap["window"]["last_trace_dir"] == out
        assert os.path.isdir(out) and os.listdir(out)
        recs = JOURNAL.tail(50, domain="profile", kind="window")
        assert recs and recs[-1]["dir"] == out

    def test_memory_sampler_thread_lifecycle_and_pools(self):
        acct = {"total_usable": 10, "allocated": 5}
        PROFILER.register_pool("kv", lambda: dict(acct))
        PROFILER.start_memory_sampler(interval=0.05)
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if "kv" in PROFILER.snapshot()["pools"]:
                    break
                time.sleep(0.02)
            names = [t.name for t in threading.enumerate()]
            assert "pt-obs-profiler" in names
            snap = PROFILER.snapshot()
            assert snap["pools"]["kv"]["occupancy"] == \
                pytest.approx(0.5)
            assert set(snap["memory"]) == {"bytes_in_use",
                                           "watermark_bytes"}
        finally:
            PROFILER.stop_memory_sampler()
        assert not any(t.name == "pt-obs-profiler"
                       for t in threading.enumerate())

    def test_dead_pool_source_dropped(self):
        PROFILER.register_pool("gone", lambda: None)
        PROFILER.sample_memory()
        assert "gone" not in PROFILER.snapshot()["pools"]

    def test_live_mfu_agrees_with_bench_computation(self):
        """THE agreement acceptance: the live gauge and a bench.py-style
        offline computation over the same measured steps agree — they
        share roofline() by construction. The gauge is the mean step of
        the last sample window (``sample_every`` steps), so the offline
        side takes the mean of the same recorded samples: a median of
        the whole run would hold the test to the machine's load (a few
        slow sleeps move the two statistics apart), not to roofline()."""
        PROFILER.configure(peak_flops=1e12, hbm_gbps=1000.0,
                           assume_mxu=True)
        PROFILER.set_cost_source("train", lambda: (5.0e6, 2.0e6))
        PROFILER.enable(sample_every=4)
        try:
            _drive_train_steps(12, compute_ms=5.0)
        finally:
            PROFILER.disable()
        snap = PROFILER.snapshot()
        live = snap["mfu"]["train"]
        window = list(PROFILER._kinds["train"].step_ms)[-4:]
        offline = roofline(sum(window) / len(window),
                           flops=5.0e6, bytes_acc=2.0e6,
                           peak_flops=1e12, hbm_gbps=1000.0,
                           mxu=True)["mfu"]
        assert live > 0 and offline > 0
        assert live == pytest.approx(offline, abs=1e-6)   # the gauge's 6 digits
        assert snap["roofline_frac"]["train"] > 0


# ---------------------------------------------------------- slo watchdog

class TestSLOWatchdog:
    def test_parse_objective_specs(self):
        o = parse_objective("ttft_p50_ms<=50")
        assert (o.metric, o.target, o.kind, o.window) == \
            ("ttft_p50_ms", 50.0, "upper", 32)
        o = parse_objective("tokens_per_s>=100@64")
        assert (o.metric, o.target, o.kind, o.window) == \
            ("tokens_per_s", 100.0, "lower", 64)
        with pytest.raises(ValueError):
            parse_objective("tokens_per_s=100")

    def test_objective_burn_rate_breach_journaled(self):
        WATCHDOG.configure(objectives=[Objective(
            name="p99", metric="p99_ms", target=5.0, window=8)],
            cooldown_s=0.0)
        WATCHDOG.add_source("fake", lambda: {"p99_ms": 50.0})
        breaches = []
        for _ in range(4):                    # window//2 samples arm it
            breaches += WATCHDOG.evaluate()
        assert breaches and breaches[0]["objective"] == "p99"
        assert breaches[0]["burn_rate"] == 1.0
        assert breaches[0]["bound"] == "upper"
        recs = JOURNAL.tail(50, domain="slo", kind="breach")
        assert recs and recs[-1]["value"] == 50.0
        assert WATCHDOG.breaches >= 1

    def test_lower_bound_objective_and_healthy_source(self):
        WATCHDOG.configure(objectives=[Objective(
            name="tput", metric="tokens_per_s", target=100.0,
            kind="lower", window=4)], cooldown_s=0.0)
        WATCHDOG.add_source("fake", lambda: {"tokens_per_s": 500.0})
        for _ in range(6):
            assert WATCHDOG.evaluate() == []   # healthy: no breach
        WATCHDOG.add_source("fake", lambda: {"tokens_per_s": 3.0})
        out = []
        for _ in range(4):
            out += WATCHDOG.evaluate()
        assert out and out[0]["objective"] == "tput"

    def test_dead_source_dropped(self):
        WATCHDOG.configure(objectives=[Objective(
            name="x", metric="x", target=1.0)])
        WATCHDOG.add_source("dying", lambda: None)
        WATCHDOG.evaluate()
        assert "dying" not in WATCHDOG.snapshot()["sources"]

    def test_regression_detected_attributed_and_baseline_unpolluted(self):
        WATCHDOG.configure(regression_factor=3.0, regression_steps=2,
                           min_samples=4, cooldown_s=0.0)
        healthy = {"compute": 8.0, "h2d": 1.0}
        for _ in range(8):
            WATCHDOG.observe_step("train", 10.0, dict(healthy))
        stalled = {"compute": 48.0, "h2d": 1.0}
        for _ in range(2):
            WATCHDOG.observe_step("train", 50.0, dict(stalled))
        recs = JOURNAL.tail(50, domain="slo", kind="step_regression")
        assert len(recs) == 1
        r = validate(recs[-1])
        assert r["step_kind"] == "train" and r["phase"] == "compute"
        assert r["factor"] >= 3.0
        # anomalous samples were NOT folded into the rolling median:
        # a continued stall keeps firing against the pre-stall baseline
        for _ in range(2):
            WATCHDOG.observe_step("train", 50.0, dict(stalled))
        recs = JOURNAL.tail(50, domain="slo", kind="step_regression")
        assert len(recs) == 2
        assert recs[-1]["median_ms"] == pytest.approx(10.0)

    def test_disabled_watchdog_observes_nothing(self):
        assert not WATCHDOG.enabled
        for _ in range(16):
            WATCHDOG.observe_step("train", 1000.0, None)
        assert WATCHDOG.evaluate() == []
        assert JOURNAL.tail(50, domain="slo") == []


# ------------------------------------------------- chaos: the acceptance

class TestChaosStallAttribution:
    """THE acceptance criterion: an injected 5x stall in a specific
    phase is journaled as ``slo/step_regression`` naming that phase,
    and auto-dumps a flight bundle whose reason names it and whose
    profiler state carries the per-phase breakdown."""

    @pytest.mark.chaos
    def test_train_stall_attributed_to_compute_and_bundled(
            self, tmp_path):
        from paddle_tpu.testing.faults import FaultPlan
        from tests.test_oom import _reader, _trainer

        path = str(tmp_path / "events.jsonl")
        dumps = str(tmp_path / "dumps")
        JOURNAL.configure(path)
        FLIGHT.configure(dump_dir=dumps, min_dump_interval=0)
        PROFILER.enable(sample_every=1)
        WATCHDOG.configure(regression_factor=3.0, regression_steps=2,
                           min_samples=4, cooldown_s=0.0)
        tr = _trainer()
        try:
            with FaultPlan.slow_step(tr, step=10, factor=5.0,
                                     n=4) as stats:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    tr.train(_reader(batches=24), num_passes=1,
                             event_handler=lambda e: None,
                             microbatch="auto")
        finally:
            PROFILER.disable()
        assert stats["injected"] >= 1 and stats["slept_ms"] > 0
        JOURNAL.configure(None)
        regs = [r for r in read_journal(path, domain="slo",
                                        kind="step_regression")
                if r["step_kind"] == "train"]
        assert regs, "the injected stall was never journaled"
        r = validate(regs[-1])
        assert r["phase"] == "compute"         # the injected phase
        assert r["factor"] >= 3.0
        # ... and the postmortem bundle rode along, reason naming it
        files = [f for f in os.listdir(dumps)
                 if "slo_step_regression_compute" in f]
        assert files, f"no bundle for the stall in {os.listdir(dumps)}"
        with open(os.path.join(dumps, files[0]),
                  encoding="utf-8") as f:
            bundle = json.load(f)
        assert bundle["reason"] == "slo_step_regression_compute"
        prof = bundle["state"]["profiler"]
        assert "compute" in prof["kinds"]["train"]["phases"]

    @pytest.mark.chaos
    def test_decode_stall_attributed_to_decode_step(self, tmp_path):
        from paddle_tpu.serving import DecodeEngine
        from paddle_tpu.testing.faults import FaultPlan
        from tests.test_serving_faults import tiny_decoder

        path = str(tmp_path / "events.jsonl")
        JOURNAL.configure(path)
        PROFILER.enable(sample_every=1)
        WATCHDOG.configure(regression_factor=3.0, regression_steps=2,
                           min_samples=4, cooldown_s=0.0)
        import numpy as np
        dec = tiny_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=32, num_pages=16)
        prompt = np.arange(4, dtype="int32")
        try:
            with FaultPlan.slow_phase(eng, "decode_step", ms=40.0,
                                      at=18, n=4) as stats:
                res = eng.submit(prompt, 24)
                eng.run(timeout=300)
                assert len(res.get(timeout=1)) == 24
        finally:
            PROFILER.disable()
        assert stats["injected"] >= 2
        JOURNAL.configure(None)
        regs = [r for r in read_journal(path, domain="slo",
                                        kind="step_regression")
                if r["step_kind"] == "decode"]
        assert regs, "the injected decode stall was never journaled"
        assert regs[-1]["phase"] == "decode_step"
