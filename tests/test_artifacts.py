"""Warm-start artifact plane (ISSUE 18, docs/robustness.md "Warm
start & artifact integrity").

The contract under test: compiled decode executables round-trip
through the fingerprinted on-disk store and come back WITHOUT tracing
or XLA compilation, token-identical to plain JIT; every way the store
can be wrong — torn frame, flipped payload bytes, internally-
consistent-but-stale fingerprint, unloadable payload, orphaned tmp
from a killed writer, N racing writers — is detected, journaled
(``artifacts/fallback``), counted, and degrades to JIT instead of
crashing the starting replica. Chaos family (r) in
paddle_tpu/testing/faults.py drives the damage.
"""

import json
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import artifacts as A
from paddle_tpu import models
from paddle_tpu.analysis.sanitizer import compile_watch
from paddle_tpu.artifacts import cache as compile_cache
from paddle_tpu.artifacts.fingerprint import (device_signature,
                                              fingerprint)
from paddle_tpu.artifacts.runtime import ExecutableCache
from paddle_tpu.obs.events import JOURNAL
from paddle_tpu.obs.metrics import REGISTRY
from paddle_tpu.serving.engine import DecodeEngine
from paddle_tpu.testing import FaultPlan

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
    return models.TransformerDecoder(params,
                                     n_layers=DEC_CFG["n_layers"],
                                     n_heads=DEC_CFG["n_heads"])


@pytest.fixture(scope="module")
def decoder():
    return tiny_decoder()


@pytest.fixture
def store(tmp_path):
    st = A.configure(str(tmp_path / "arts"))
    A.EXECUTABLES.clear()
    yield st
    A.configure(None)
    A.EXECUTABLES.clear()


def _journal(kind=None):
    return JOURNAL.tail(50, domain="artifacts", kind=kind)


def _gauge(name):
    return REGISTRY.gauge(name).value()


@jax.jit
def _toy(x, y):
    return x * 2.0 + y


def _toy_args():
    return (np.arange(4, dtype=np.float32),
            np.ones((4,), np.float32))


def _toy_fp(plan=None):
    return fingerprint("toy", {"w": _toy_args()[0]},
                       plan=plan or {"n": 4})


# ---------------------------------------------------------- fingerprints
class TestFingerprint:
    def test_deterministic_and_sensitive(self, decoder):
        plan = {"num_slots": 2, "page_size": 4}
        a = fingerprint("paged_step", decoder.p, plan=plan)
        b = fingerprint("paged_step", decoder.p, plan=plan)
        assert a == b and a.digest == b.digest
        # plan knobs, kind, and model SHAPES all separate executables
        c = fingerprint("paged_step", decoder.p,
                        plan={"num_slots": 4, "page_size": 4})
        d = fingerprint("draft_step", decoder.p, plan=plan)
        assert len({a.digest, c.digest, d.digest}) == 3
        # values do NOT: params are runtime arguments, not identity
        other = tiny_decoder(seed=11)
        assert fingerprint("paged_step", other.p,
                           plan=plan).digest == a.digest

    def test_env_in_identity(self):
        sig = device_signature()
        assert sig["backend"] and sig["jax"] and sig["jaxlib"]
        fp = _toy_fp()
        assert fp.fields["env"]["backend"] == sig["backend"]
        # round-trips through the frame header
        from paddle_tpu.artifacts.fingerprint import Fingerprint
        again = Fingerprint.from_dict(fp.to_dict())
        assert again == fp


# ---------------------------------------------------------------- store
class TestStore:
    def test_round_trip_and_inspect(self, store):
        fp = _toy_fp()
        payload = b"\x00\x01" * 600
        path = store.put("toy-exe", fp, payload, meta={"build_ms": 3})
        assert store.get("toy-exe", fp) == payload
        assert _gauge("paddle_tpu_artifacts_hits") == 1
        row = store.inspect(path)
        assert row["ok"] and row["digest"] == fp.digest
        assert row["kind"] == "toy" and row["size"] > len(payload)
        assert row["meta"]["build_ms"] == 3 and row["age_s"] >= 0

    def test_missing_is_a_miss_not_a_fallback(self, store):
        assert store.get("nope", _toy_fp()) is None
        assert _gauge("paddle_tpu_artifacts_misses") == 1
        assert _gauge("paddle_tpu_artifacts_fallbacks") == 0

    @pytest.mark.parametrize("mode", ["payload", "torn", "magic"])
    def test_corrupt_artifact_degrades_and_journals(self, store, mode):
        fp = _toy_fp()
        payload = b"payload" * 100
        store.put("toy-exe", fp, payload)
        with FaultPlan.corrupt_artifact(store, mode=mode) as stats:
            assert store.get("toy-exe", fp) is None
            assert _gauge("paddle_tpu_artifacts_fallbacks") == 1
            rec = _journal("fallback")[-1]
            assert rec["reason"] == "corrupt"
            assert rec["path"] == stats["path"]
            # verify flags the same defect, with its own audit record
            bad = store.verify()
            assert len(bad) == 1 and not bad[0]["ok"]
            assert _journal("verify_failed")
        # restoration: the artifact serves again, and verify is clean
        assert store.get("toy-exe", fp) == payload
        assert store.verify() == []

    def test_stale_fingerprint_degrades_as_stale(self, store):
        fp = _toy_fp()
        store.put("toy-exe", fp, b"x" * 64)
        with FaultPlan.stale_fingerprint(store) as stats:
            # the doctored frame is INTACT — verify passes it...
            assert store.verify() == []
            # ...only the fingerprint comparison catches it
            assert store.get("toy-exe", fp) is None
            rec = _journal("fallback")[-1]
            assert rec["reason"] == "stale"
            assert stats["doctored_digest"] in rec["detail"]
        assert store.get("toy-exe", fp) == b"x" * 64

    def test_cache_race_single_complete_winner(self, store):
        fp = _toy_fp()
        payloads = [bytes([i]) * (512 + i) for i in range(12)]
        stats = FaultPlan.cache_race(store, "toy-exe", fp, payloads,
                                     threads=8)
        assert stats["errors"] == [] and stats["writes"] == 12
        assert stats["winner"]["ok"], stats["winner"]
        # the survivor is one of the candidates, complete
        assert store.get("toy-exe", fp) in payloads
        # no tmp litter once the dust settles
        leftovers = [n for n in os.listdir(store.root) if ".tmp." in n]
        assert leftovers == []

    def test_killed_writer_leaves_loadable_store(self, store):
        """A writer SIGKILLed mid-write leaves only a private tmp
        sibling — never a partial frame under the final name. Readers
        ignore it; the next put() sweeps it once it is old enough to
        be an orphan (not a live writer's in-flight tmp)."""
        fp = _toy_fp()
        store.put("toy-exe", fp, b"good" * 50)
        orphan = store.path("toy-exe") + ".tmp.99999.1"
        with open(orphan, "wb") as f:
            f.write(b"PTA1\x00partial-frame-from-a-dead-writer")
        # reads are untouched by the orphan
        assert store.get("toy-exe", fp) == b"good" * 50
        assert _gauge("paddle_tpu_artifacts_fallbacks") == 0
        # a FRESH tmp (a live concurrent writer) survives the sweep...
        store.put("toy-exe", fp, b"good" * 50)
        assert os.path.exists(orphan)
        # ...an aged one is swept
        os.utime(orphan, (1, 1))
        store.put("toy-exe", fp, b"good" * 50)
        assert not os.path.exists(orphan)


# -------------------------------------------------------------- resolver
class TestResolver:
    def test_warm_ladder_and_backfill(self, store):
        args = tuple(map(jax.numpy.asarray, _toy_args()))
        fp = _toy_fp()
        exe = A.resolve(fp, _toy, args)
        want = np.asarray(exe(*args))
        # cold build journaled + persisted
        assert _journal("build")[-1]["digest"] == fp.digest
        assert _gauge("paddle_tpu_artifacts_build_ms") > 0
        assert len(store.entries()) == 1
        # rung 1: in-process cache
        assert A.resolve(fp, _toy, args) is exe
        # rung 2: the store (a "new process"), no recompiling
        A.EXECUTABLES.clear()
        exe2 = A.resolve(fp, _toy, args)
        assert exe2 is not exe
        assert _journal("load")[-1]["source"] == "store"
        np.testing.assert_array_equal(np.asarray(exe2(*args)), want)

    def test_unloadable_payload_recovers_by_rebuild(self, store):
        """A valid frame around bytes that don't deserialize (wrong
        jaxlib, junk): journal ``unloadable``, rebuild cold, and the
        backfill REPAIRS the store."""
        args = tuple(map(jax.numpy.asarray, _toy_args()))
        fp = _toy_fp()
        store.put(A.runtime._artifact_name(fp), fp, b"not-an-executable")
        exe = A.resolve(fp, _toy, args)
        assert _journal("fallback")[-1]["reason"] == "unloadable"
        np.testing.assert_array_equal(
            np.asarray(exe(*args)), _toy_args()[0] * 2.0 + 1.0)
        # the junk was overwritten by the rebuild's backfill
        A.EXECUTABLES.clear()
        A.resolve(fp, _toy, args)
        assert _journal("load")[-1]["digest"] == fp.digest

    def test_warm_false_returns_plain_jit(self, store):
        assert A.resolve(_toy_fp(), _toy, _toy_args(),
                         warm=False) is _toy
        assert store.entries() == []

    def test_executable_cache_lru_bounded(self):
        cache = ExecutableCache(capacity=2)
        fps = [_toy_fp(plan={"n": i}) for i in range(3)]
        for i, fp in enumerate(fps):
            cache.put(fp, f"exe{i}")
        assert cache.stats()["entries"] == 2
        assert cache.get(fps[0]) is None       # evicted (oldest)
        assert cache.get(fps[2]) == "exe2"


# ------------------------------------------------------------ the golden
class TestWarmDecode:
    def test_in_process_respawn_token_identical_zero_compiles(
            self, store, decoder):
        """Rung 1 of the warm ladder: a REBUILT engine in the same
        process (a rolling deploy's in-process restart) shares the
        first engine's executable — token-identical, zero step
        compiles. The disk rung's golden is
        TestCrossProcessWarmStart, where a fresh process must load
        from the store."""
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 40, (n,)).astype("int32")
                   for n in (4, 6)]
        news = [8, 6]

        def run(engine):
            reqs = [engine.submit(p, n)
                    for p, n in zip(prompts, news)]
            engine.run(timeout=300)
            return [r.get(timeout=1) for r in reqs]

        # plain-JIT baseline: no artifact plane at all
        want = run(DecodeEngine(decoder, num_slots=2, page_size=4,
                                max_seq_len=DEC_CFG["max_len"],
                                prefix_cache=False, warm_start=False))

        # cold warm-start engine: builds + backfills the store
        got_cold = run(DecodeEngine(decoder, num_slots=2, page_size=4,
                                    max_seq_len=DEC_CFG["max_len"],
                                    prefix_cache=False))
        assert got_cold == want
        names = [r["name"] for r in store.entries()]
        # the step's two programs: plain, and with the prefill lanes
        assert any(n.startswith("paged_step-") for n in names)
        assert any(n.startswith("paged_lane_step-") for n in names)

        # "respawned engine", same process: the executable cache
        # serves it — no disk read, no trace, no compile
        hits0 = A.EXECUTABLES.stats()["hits"]
        with compile_watch() as watch:
            got_warm = run(DecodeEngine(decoder, num_slots=2,
                                        page_size=4,
                                        max_seq_len=DEC_CFG["max_len"],
                                        prefix_cache=False))
        assert got_warm == want
        step_compiles = {k: v for k, v in watch.per_function.items()
                         if "_step_impl" in k}
        assert step_compiles == {}, step_compiles
        assert A.EXECUTABLES.stats()["hits"] > hits0
        # and no further build was journaled — one artifact a
        # program (the lane step, the plain step), shared
        assert len(_journal("build")) == 2

    def test_corrupt_store_still_serves_token_identical(
            self, store, decoder):
        """Acceptance: a corrupt artifact on one replica degrades to
        JIT — journaled — and serves the SAME tokens."""
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, 40, (5,)).astype("int32")

        def run(engine):
            r = engine.submit(prompt, 8)
            engine.run(timeout=300)
            return r.get(timeout=1)

        want = run(DecodeEngine(decoder, num_slots=2, page_size=4,
                                max_seq_len=DEC_CFG["max_len"],
                                prefix_cache=False))   # builds store
        A.EXECUTABLES.clear()
        with FaultPlan.corrupt_artifact(store, mode="payload"):
            got = run(DecodeEngine(decoder, num_slots=2, page_size=4,
                                   max_seq_len=DEC_CFG["max_len"],
                                   prefix_cache=False))
            assert got == want
            assert _journal("fallback")[-1]["reason"] == "corrupt"

    @pytest.mark.parametrize("kind", ["paged_step", "paged_lane_step"])
    def test_one_corrupt_step_artifact_of_two(self, store, decoder, kind):
        """The step is two programs, so two artifacts: with either one
        corrupt the engine serves the same tokens, that program from a
        fresh build and the other from the store, side by side."""
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, 40, (6,)).astype("int32")

        def run():
            eng = DecodeEngine(decoder, num_slots=2, page_size=4,
                               max_seq_len=DEC_CFG["max_len"],
                               prefix_cache=False)
            r = eng.submit(prompt, 8)
            eng.run(timeout=300)
            assert eng.stats()["prefill_lane_steps"] >= 1
            return r.get(timeout=1)

        want = run()                                    # builds store
        name, = [r["name"][:-len(".ptaf")] for r in store.entries()
                 if r["name"].startswith(kind + "-")]
        A.EXECUTABLES.clear()
        loads0, builds0 = len(_journal("load")), len(_journal("build"))
        with FaultPlan.corrupt_artifact(store, name=name, mode="payload"):
            assert run() == want
        assert [r["name"] for r in _journal("fallback")][-1] == name
        other = [r["name"] for r in _journal("load")[loads0:]
                 if r["name"].startswith("paged_")]
        assert len(other) == 1 and not other[0].startswith(kind + "-")
        # the fresh build repaired the store
        assert [r["name"] for r in _journal("build")[builds0:]] == [name]

    def test_engine_warmup_resolves_before_traffic(self, store,
                                                   decoder):
        eng = DecodeEngine(decoder, num_slots=2, page_size=4,
                           max_seq_len=DEC_CFG["max_len"],
                           prefix_cache=False)
        stats = eng.warmup()
        assert stats["warm_start"] is True
        assert any(r["name"].startswith("paged_step-")
                   for r in store.entries())
        # warmup wrote only the null page: decode still correct
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, 40, (4,)).astype("int32")
        want = [int(t) for t in decoder.generate(
            prompt[None, :], max_len=4 + 6)[0]]
        r = eng.submit(prompt, 6)
        eng.run(timeout=300)
        assert r.get(timeout=1) == want


_CHILD_TEMPLATE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_log_compiles", True)
from paddle_tpu import artifacts as A
from paddle_tpu.artifacts.fingerprint import fingerprint
from paddle_tpu.analysis.sanitizer import compile_watch
A.configure({root!r})
@jax.jit
def step(x, y):
    return jnp.tanh(x @ y) * 2.0 + 1.0
args = (jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        jnp.ones((4, 3), jnp.float32) * 0.1)
fp = fingerprint("xproc_step", {{"w": args[0]}}, plan={{"n": 3}})
with compile_watch() as watch:
    exe = A.resolve(fp, step, args)
    out = exe(*args)
print(json.dumps({{
    "out": [float(v) for v in jnp.ravel(out)],
    "step_compiles": {{k: v for k, v in watch.per_function.items()
                       if "step" in k}},
    "is_jit_wrapper": exe is step,
}}))
"""


_DECODE_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import models, artifacts as A
from paddle_tpu.core.registry import reset_name_counters
from paddle_tpu.analysis.sanitizer import compile_watch
from paddle_tpu.serving.engine import DecodeEngine
from paddle_tpu.obs.events import JOURNAL
A.configure({root!r})
paddle.init(use_tpu=False, seed=0)
reset_name_counters()
spec = models.transformer_lm(vocab_size=40, d_model=16, n_heads=2,
                             n_layers=2, d_ff=32, max_len=32)
costs = spec.cost if isinstance(spec.cost, list) else [spec.cost]
topo = paddle.Topology(costs, extra_outputs=[spec.output])
params = topo.init_params(jax.random.PRNGKey(7))
dec = models.TransformerDecoder(params, n_layers=2, n_heads=2)
eng = DecodeEngine(dec, num_slots=2, page_size=4, max_seq_len=32,
                   prefix_cache=False)
with compile_watch() as w:
    r = eng.submit(np.array([5, 9, 3, 1], np.int32), 6)
    eng.run(timeout=300)
print(json.dumps({{
    "tokens": r.get(timeout=1),
    "step_compiles": {{k: v for k, v in w.per_function.items()
                       if "_step_impl" in k}},
    "journal": [e["kind"]
                for e in JOURNAL.tail(20, domain="artifacts")],
}}))
"""


class TestCrossProcessWarmStart:
    def test_fresh_process_loads_without_compiling(self, tmp_path):
        """The respawn contract, end to end: process A builds and
        persists; a GENUINELY fresh process B resolves the same
        fingerprint from disk and never compiles the step — the
        cold_start_to_first_token warm path and the autoscale-up
        MTTR bound both rest on exactly this."""
        import subprocess
        import sys
        root = str(tmp_path / "arts")

        def spawn():
            env = dict(os.environ,
                       PYTHONPATH=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
            env.pop("PADDLE_TPU_COMPILE_CACHE", None)
            r = subprocess.run(
                [sys.executable, "-c",
                 _CHILD_TEMPLATE.format(root=root)],
                capture_output=True, text=True, timeout=240, env=env)
            assert r.returncode == 0, r.stderr[-2000:]
            return json.loads(r.stdout.strip().splitlines()[-1])

        cold = spawn()
        assert cold["step_compiles"], "cold child must compile"
        assert not cold["is_jit_wrapper"]
        assert os.listdir(root)
        warm = spawn()
        assert warm["step_compiles"] == {}, warm["step_compiles"]
        assert not warm["is_jit_wrapper"]
        np.testing.assert_allclose(warm["out"], cold["out"],
                                   rtol=1e-6)

    def test_fresh_process_decode_token_identical(self, tmp_path):
        """The disk-rung golden at full fidelity: a fresh process
        builds + persists the paged decode executable, a second fresh
        process serves the SAME tokens through the store-loaded
        executable with ZERO decode-step compiles — the acceptance
        row for `paddle_tpu artifacts build` + warm `serve`."""
        import subprocess
        import sys
        child = _DECODE_CHILD.format(root=str(tmp_path / "arts"))
        env = dict(os.environ,
                   PYTHONPATH=os.path.dirname(
                       os.path.dirname(os.path.abspath(__file__))))
        env.pop("PADDLE_TPU_COMPILE_CACHE", None)

        def spawn():
            r = subprocess.run([sys.executable, "-c", child],
                               capture_output=True, text=True,
                               timeout=240, env=env)
            assert r.returncode == 0, r.stderr[-2000:]
            return json.loads(r.stdout.strip().splitlines()[-1])

        cold = spawn()
        assert cold["step_compiles"] and "build" in cold["journal"]
        warm = spawn()
        assert warm["tokens"] == cold["tokens"]
        assert warm["step_compiles"] == {}, warm["step_compiles"]
        assert "load" in warm["journal"]
        assert "fallback" not in warm["journal"]


# ------------------------------------------------------------------- CLI
class TestArtifactsCli:
    DEC_SRC = (
        "import jax\n"
        "import paddle_tpu as paddle\n"
        "from paddle_tpu import models\n"
        "from paddle_tpu.core.registry import reset_name_counters\n"
        "paddle.init(use_tpu=False, seed=0)\n"
        "reset_name_counters()\n"
        "spec = models.transformer_lm(vocab_size=40, d_model=16,\n"
        "                             n_heads=2, n_layers=2, d_ff=32,\n"
        "                             max_len=32)\n"
        "costs = (spec.cost if isinstance(spec.cost, list)\n"
        "         else [spec.cost])\n"
        "topo = paddle.Topology(costs, extra_outputs=[spec.output])\n"
        "params = topo.init_params(jax.random.PRNGKey(7))\n"
        "decoder = models.TransformerDecoder(params, n_layers=2,\n"
        "                                    n_heads=2)\n")

    @pytest.fixture
    def built_dir(self, tmp_path, capsys):
        from paddle_tpu import cli
        cfg = tmp_path / "dec.py"
        cfg.write_text(self.DEC_SRC)
        d = str(tmp_path / "arts")
        try:
            rc = cli.main(["artifacts", "build", "--dir", d,
                           "--decode_config", str(cfg),
                           "--gen_slots", "2",
                           "--gen_page_size", "4"])
            assert rc == 0
            out = json.loads(capsys.readouterr().out)
            assert out["action"] == "build" and out["entries"]
            yield d
        finally:
            A.configure(None)
            A.EXECUTABLES.clear()

    def test_build_ls_verify_round_trip(self, built_dir, capsys):
        from paddle_tpu import cli
        assert cli.main(["artifacts", "ls", "--dir", built_dir]) == 0
        ls = json.loads(capsys.readouterr().out)
        assert ls["count"] >= 1
        row = ls["entries"][0]
        assert row["ok"] and row["digest"] and row["age_s"] >= 0
        assert cli.main(["artifacts", "verify",
                         "--dir", built_dir]) == 0
        assert json.loads(capsys.readouterr().out)["defective"] == []

    def test_verify_corrupt_exits_nonzero_and_journals(
            self, built_dir, capsys):
        from paddle_tpu import cli
        victim = next(os.path.join(built_dir, n)
                      for n in sorted(os.listdir(built_dir))
                      if n.endswith(".ptaf"))
        with open(victim, "r+b") as f:
            f.seek(-3, os.SEEK_END)
            f.write(b"\xff\xff\xff")
        rc = cli.main(["artifacts", "verify", "--dir", built_dir])
        assert rc == 1
        out = json.loads(capsys.readouterr().out)
        assert len(out["defective"]) == 1
        assert out["defective"][0]["path"] == victim
        assert _journal("verify_failed")

    def test_dir_required_without_env(self, monkeypatch):
        from paddle_tpu import cli
        monkeypatch.delenv("PADDLE_TPU_ARTIFACTS", raising=False)
        with pytest.raises(SystemExit):
            cli.main(["artifacts", "ls"])


# ---------------------------------------------------- compile-cache seam
class TestCompileCacheSeam:
    def test_resolve_dir_grammar(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.resolve_dir("/x") == "/x"
        assert compile_cache.resolve_dir("0") is None
        assert compile_cache.resolve_dir("off") is None
        assert compile_cache.resolve_dir(None) is None
        assert compile_cache.resolve_dir(None, fallback="/f") == "/f"
        monkeypatch.setenv(compile_cache.ENV_VAR, "/e")
        assert compile_cache.resolve_dir(None) == "/e"
        assert compile_cache.resolve_dir("/x") == "/x"
        monkeypatch.setenv(compile_cache.ENV_VAR, "0")
        assert compile_cache.resolve_dir(None, fallback="/f") is None
        assert compile_cache.ensure_default() is None

    def test_enable_points_jax_at_dir(self, tmp_path, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            d = compile_cache.enable(str(tmp_path / "cc"))
            assert d == str(tmp_path / "cc") and os.path.isdir(d)
            assert jax.config.jax_compilation_cache_dir == d
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_jax_env_var_places_the_cache(self, tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set no code path sets a
        directory: enable() answers JAX's own and leaves the config
        as it found it, whatever flag or env var asked for."""
        outside = str(tmp_path / "outside")
        monkeypatch.setenv(compile_cache.JAX_ENV_VAR, outside)
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "e"))
        prev = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable(str(tmp_path / "cc")) == outside
        assert compile_cache.enable_from_env() == outside
        assert jax.config.jax_compilation_cache_dir == prev
        assert os.listdir(tmp_path) == []

    def test_default_dir_is_in_the_checkout(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.default_dir() == \
            os.path.join(repo, ".jax_cache")

    def test_disabled_scopes_and_restores(self):
        assert jax.config.jax_enable_compilation_cache is True
        with compile_cache.disabled():
            assert jax.config.jax_enable_compilation_cache is False
            with compile_cache.disabled():
                assert jax.config.jax_enable_compilation_cache is False
        assert jax.config.jax_enable_compilation_cache is True
