"""Test config: force an 8-device virtual CPU platform before any backend
initialization.

This is the 'CPU build as fake device' discipline from the reference
(paddle/cuda/include/stub/* let everything unit-test without GPUs): the CPU
XLA backend is the universal fake TPU, and 8 virtual devices exercise every
mesh/sharding path without hardware.
"""

import os

if os.environ.get("PADDLE_TPU_SMOKE"):
    # real-hardware lane (tests/test_tpu_smoke.py): keep the default
    # TPU backend instead of the virtual CPU mesh
    import jax  # noqa: E402
else:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")

    # Persistent XLA compile cache: scores of tests rebuild the same tiny
    # models, and every fresh jit wrapper re-pays the identical XLA
    # compile — the dominant share of tier-1 wall clock. The disk cache
    # is keyed by HLO hash, so it dedupes within one run as well as
    # across runs. Cache HITS still log "Compiling <name>", so
    # compile_watch / recompile_budget counts are unaffected.
    # Deliberately process-local (jax.config, NOT env): the SIGKILL
    # chaos tests time their kills against a worker subprocess's
    # compile-dominated startup, so spawned workers must stay cold.
    # PADDLE_TPU_COMPILE_CACHE=0 disables; any other value overrides
    # the directory (default <checkout>/.jax_cache), unless
    # JAX_COMPILATION_CACHE_DIR places it from outside. The knobs live
    # in paddle_tpu/artifacts/cache.py
    # (the productionized seam — train/serve/router/soak wire the
    # same grammar via --compile_cache).
    from paddle_tpu.artifacts import cache as _compile_cache

    _compile_cache.enable_from_env()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (deselected in tier-1)")
    config.addinivalue_line(
        "markers",
        "chaos(timeout=120): fault-injection chaos tests — faulthandler "
        "dumps all thread stacks if the test exceeds its timeout, so a "
        "deadlocked serving test prints stacks instead of dying to a "
        "silent `timeout -k` kill")
    config.addinivalue_line(
        "markers",
        "recompile_budget(max_compiles=4): enforce an XLA compile "
        "budget — the test fails if any single jitted function "
        "compiles more than max_compiles times while it runs "
        "(paddle_tpu/analysis/sanitizer.py; docs/static_analysis.md)")
    config.addinivalue_line(
        "markers",
        "lockdep_allow_inversion: this test deliberately provokes a "
        "lock-order inversion (chaos/deadlock-witness tests) — skip "
        "the autouse zero-inversions assertion "
        "(paddle_tpu/analysis/lockdep.py)")
    config.addinivalue_line(
        "markers",
        "soak: the long soak acceptance lane (paddle_tpu/loadgen) — "
        "select with `-m soak`; soak-marked tests are implicitly "
        "`slow` so tier-1's `-m 'not slow'` never runs them (the "
        "bounded smoke slice in tests/test_soak.py stays tier-1)")
    config.addinivalue_line(
        "markers",
        "protocol_violation_expected: this test deliberately breaks a "
        "declared event protocol (orphan terminals etc.) — skip the "
        "autouse zero-violations assertion of the protocol witness "
        "(paddle_tpu/obs/protocol.py; docs/observability.md "
        "'Protocol contracts')")


def pytest_collection_modifyitems(config, items):
    """Every soak-marked test is implicitly slow: `-m soak` selects
    the lane, tier-1's `-m 'not slow'` excludes it — one marker, both
    behaviors."""
    for item in items:
        if item.get_closest_marker("soak") is not None:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _chaos_faulthandler(request):
    """Dump-on-timeout for @pytest.mark.chaos: if a chaos test wedges
    (a serving deadlock, a stuck worker join), every thread's stack is
    printed to stderr before the outer timeout kills the run."""
    marker = request.node.get_closest_marker("chaos")
    if marker is None:
        yield
        return
    import faulthandler
    timeout = float(marker.kwargs.get("timeout", 120.0))
    faulthandler.dump_traceback_later(timeout, exit=False)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Expose each phase's outcome to fixtures (the thread-leak check
    only fires on tests that PASSED — a failing test's traceback can
    legitimately pin an abandoned generator alive)."""
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


@pytest.fixture(autouse=True)
def _no_pipeline_thread_leaks(request):
    """Fail any test that leaks a data-pipeline thread (buffered /
    xmap_readers / supervised / the trainer's feed prefetcher — all
    named 'pt-data-*') or a serving worker ('pt-serve-*'), so a
    shutdown regression is caught by CI as a failure instead of as a
    hang. The grace window lets just-closed generators' threads observe
    their stop events (they poll every 0.1s) and drained serving
    workers observe _stopping (they poll every 0.2s)."""
    import gc
    import threading
    import time

    def leaked():
        from paddle_tpu.reader.pipeline import THREAD_PREFIX
        prefixes = (THREAD_PREFIX, "pt-serve", "pt-obs", "pt-coord",
                    "pt-embed", "pt-loadgen")
        return [t for t in threading.enumerate()
                if t.is_alive() and t.name.startswith(prefixes)]

    yield
    rep = getattr(request.node, "rep_call", None)
    if rep is None or not rep.passed:
        return
    if leaked():
        gc.collect()          # close abandoned generators deterministically
    deadline = time.time() + 5.0
    while leaked() and time.time() < deadline:
        time.sleep(0.05)
    left = leaked()
    assert not left, (
        f"test leaked {len(left)} pipeline/serving thread(s): "
        f"{[t.name for t in left]} — a reader or InferenceServer was "
        "abandoned without its fill/worker threads shutting down "
        "(reader/pipeline.py / serving/server.py lifecycle contract)")


@pytest.fixture(autouse=True)
def _recompile_budget(request):
    """@pytest.mark.recompile_budget(max_compiles=N): count XLA
    compilations per jitted function while the test runs and FAIL it
    (at teardown, only when the test body passed) if any one function
    compiled more than N times — the runtime twin of ptlint R2
    (analysis/sanitizer.py). The watch is exposed as
    ``request.node._compile_watch`` for tests that want the counts."""
    marker = request.node.get_closest_marker("recompile_budget")
    if marker is None:
        yield
        return
    from paddle_tpu.analysis.sanitizer import compile_watch
    budget = int(marker.kwargs.get(
        "max_compiles", marker.args[0] if marker.args else 4))
    with compile_watch() as watch:
        request.node._compile_watch = watch
        yield
    rep = getattr(request.node, "rep_call", None)
    if rep is not None and rep.passed:
        watch.check(budget)


@pytest.fixture(autouse=True, scope="module")
def _drop_xla_executables():
    """Release each module's in-memory XLA executables at teardown.

    Every compiled executable holds mmap'd code pages; across ~1000
    tests the suite's map count climbs toward the kernel's
    vm.max_map_count ceiling (65530 default), and crossing it turns
    later native allocations — thread-stack guard pages included —
    into segfaults deep in XLA or pthread_create. Clearing per module
    is nearly free: the persistent disk compile cache above dedupes
    the recompiles, so only re-tracing is paid. The warm-start
    plane's in-process executable cache pins loaded executables the
    same way, so it drops with them."""
    yield
    import gc
    from paddle_tpu.artifacts import EXECUTABLES
    EXECUTABLES.clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _reset_layer_names():
    """Fresh auto-name counters per test so graphs don't collide."""
    from paddle_tpu.core import registry
    registry.reset_name_counters()
    yield


@pytest.fixture(autouse=True)
def _reset_observability():
    """Zero the observability surfaces BEFORE each test — metrics
    registry values, event-journal ring + sink, tracer, and the
    utils/stats global counters/timers — so no test reads another
    test's metric bleed (paddle_tpu/obs; counter hygiene contract in
    docs/observability.md)."""
    from paddle_tpu.obs import reset_all
    reset_all()
    yield


@pytest.fixture(autouse=True)
def _lockdep_witness(request):
    """Deadlock witness for tier-1: every test runs under the lockdep
    runtime (paddle_tpu/analysis/lockdep.py — instrumented locks feed a
    global acquisition-order graph) and FAILS at teardown if any
    lock-order inversion was observed, unless it is marked
    ``lockdep_allow_inversion`` (chaos tests that provoke one on
    purpose). The graph is reset per-test by _reset_observability
    (obs.reset_all -> LOCKDEP.reset), so an inversion is attributed to
    the test that created it."""
    yield
    if request.node.get_closest_marker("lockdep_allow_inversion"):
        return
    rep = getattr(request.node, "rep_call", None)
    if rep is None or not rep.passed:
        return
    from paddle_tpu.analysis.lockdep import LOCKDEP
    count = LOCKDEP.inversion_count
    assert count == 0, (
        f"lockdep witness observed {count} lock-order inversion(s) "
        "during this test — two locks were taken in opposite orders "
        "on different paths (one interleaving deadlocks). The journal "
        "holds a lockdep/inversion record with both stacks; see "
        "docs/static_analysis.md 'Lock discipline'")


@pytest.fixture(autouse=True)
def _protocol_witness(request):
    """Protocol witness for tier-1: every test runs under the
    declared-protocol state machines (paddle_tpu/obs/protocol.py — a
    journal observer advancing obs.catalog.PROTOCOLS per correlation
    key) and FAILS at teardown if any machine was BROKEN (a terminal
    for a key never started), unless marked
    ``protocol_violation_expected``. Machines merely left open are NOT
    violations here — a SIGKILL'd replica legitimately leaves a hop
    that never settles (tests/test_fleet_faults.py); only an explicit
    ``WITNESS.finalize()`` reports those. State is reset per-test by
    _reset_observability (obs.reset_all -> WITNESS.reset)."""
    yield
    if request.node.get_closest_marker("protocol_violation_expected"):
        return
    rep = getattr(request.node, "rep_call", None)
    if rep is None or not rep.passed:
        return
    from paddle_tpu.obs import WITNESS
    count = WITNESS.violation_count
    assert count == 0, (
        f"protocol witness observed {count} protocol violation(s) "
        "during this test — a declared event machine "
        "(obs/catalog.py PROTOCOLS) saw a terminal for a key it never "
        "tracked. The journal holds a protocol/violation record with "
        "the offending chain; see docs/observability.md "
        "'Protocol contracts'")


@pytest.fixture
def rng():
    return np.random.RandomState(0)
