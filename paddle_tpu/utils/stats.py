"""Scoped wall-clock stat timers.

Reference: paddle/utils/Stat.h — `REGISTER_TIMER(name)` RAII scopes
aggregated into `globalStat` (StatSet :63,:111) with periodic printing
(--log_period) and per-thread breakdown; compiled out unless WITH_TIMER.

Here: a process-global registry of named timers with count/total/max/min,
a `stat_timer(name)` context manager, and `print_all_status()` — plus a
bridge to jax.profiler trace annotations so the same scopes show up in
XPlane traces when profiling on TPU.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Dict

import jax

from paddle_tpu.analysis.lockdep import named_lock


class StatItem:
    # add() is a read-modify-write reached concurrently from the
    # pt-serve / pt-data worker pools (serving/forward, pipeline
    # timers) — the per-item lock keeps count/total consistent where
    # the bare += used to drop updates under contention
    __slots__ = ("count", "total", "max", "min", "_lock")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.min = float("inf")
        self._lock = named_lock("stats.item")

    def add(self, dt: float):
        with self._lock:
            self.count += 1
            self.total += dt
            self.max = max(self.max, dt)
            self.min = min(self.min, dt)

    def snapshot(self):
        """(count, total, max) read atomically — the obs metrics
        bridge scrapes this (paddle_tpu/obs/metrics.py)."""
        with self._lock:
            return self.count, self.total, self.max

    def __str__(self):
        avg = self.total / self.count if self.count else 0.0
        return (f"count={self.count} total={self.total * 1e3:.2f}ms "
                f"avg={avg * 1e3:.3f}ms max={self.max * 1e3:.3f}ms "
                f"min={(self.min if self.count else 0.0) * 1e3:.3f}ms")


class StatSet:
    def __init__(self):
        self._lock = named_lock("stats.statset")
        self._stats: Dict[str, StatItem] = {}
        self.enabled = True

    def get(self, name: str) -> StatItem:
        with self._lock:
            if name not in self._stats:
                self._stats[name] = StatItem()
            return self._stats[name]

    def reset(self):
        with self._lock:
            self._stats.clear()

    def items(self):
        with self._lock:
            return dict(self._stats)

    def print_all_status(self):
        for name, item in sorted(self.items().items()):
            print(f"Stat={name:<30} {item}")


class CounterSet:
    """Process-global named event counters (the counter half of Stat.h's
    globalStat). Timers measure durations; counters count occurrences —
    quarantined samples, worker restarts, source stalls
    (reader/pipeline.py), corrupt chunks — so chaos tests can diff exact
    fault counts around an epoch."""

    def __init__(self):
        self._lock = named_lock("stats.counters")
        self._counts: Dict[str, int] = {}

    def bump(self, name: str, n: int = 1) -> int:
        with self._lock:
            v = self._counts.get(name, 0) + n
            self._counts[name] = v
            return v

    def bump_unlocked(self, name: str, n: int = 1) -> None:
        """``bump`` for a caller that must not wait for a lock (a
        ``gc.callbacks`` function runs on whichever thread allocated,
        under whatever locks that thread holds). Safe only for a name
        that one writer at a time bumps: the read and the write are two
        steps."""
        self._counts[name] = self._counts.get(name, 0) + n

    def value(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def reset(self):
        with self._lock:
            self._counts.clear()

    def items(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def print_all_status(self):
        for name, v in sorted(self.items().items()):
            print(f"Counter={name:<30} {v}")


global_stat = StatSet()
global_counters = CounterSet()


_TRACER = None


def _tracer():
    """Lazy obs.trace handle (import-cycle-free: obs imports this
    module; the first stat_timer call happens long after both are
    loaded)."""
    global _TRACER
    if _TRACER is None:
        from paddle_tpu.obs.trace import TRACER
        _TRACER = TRACER
    return _TRACER


@contextlib.contextmanager
def stat_timer(name: str):
    """REGISTER_TIMER parity; also emits a jax.profiler named scope,
    and — while a host trace is active (obs/trace.py) — a span, so
    every timed scope (train_step, data wait, checkpoint write,
    serving/decode_step) lands in the Chrome trace for free."""
    if not global_stat.enabled:
        yield
        return
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        with _tracer().span(name):
            yield
    global_stat.get(name).add(time.perf_counter() - t0)


class _GcWatch:
    """The collector's pauses, from inside the process: one
    ``gc.callbacks`` function. Every collection is a
    ``jax.profiler.TraceAnnotation`` ``host/gc`` (entered at ``start``,
    exited at ``stop``, both on the collecting thread, so it lies in the
    host plane of a profiler trace beside the device's operations), a
    ``TRACER`` span of the same name, and the process-wide counters
    ``gc_collections``, ``gc_gen2_collections`` and ``gc_pause_ns`` of
    ``global_counters``; a generation-2 collection also leaves a
    ``FLIGHT`` mark with its generation and duration. The callback takes
    no lock: it runs under whatever locks the collecting thread holds.
    Collections do not nest, so one pending annotation is enough."""

    NAME = "host/gc"

    def __init__(self):
        self._ann = None
        self._t0 = 0
        self.tracer = self.flight = None    # set by watch_gc()
        #: nanoseconds of every collection since the install, read
        #: without a lock (serving/engine.py: did one fall inside a
        #: dry launch's bracket?)
        self.pause_ns = 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._ann = jax.profiler.TraceAnnotation(self.NAME)
            self._ann.__enter__()
            self._t0 = time.perf_counter_ns()
            return
        t1 = time.perf_counter_ns()
        ann, self._ann = self._ann, None
        if ann is None:             # installed mid-collection
            return
        ann.__exit__(None, None, None)
        ns, gen = t1 - self._t0, info.get("generation", 0)
        self.pause_ns += ns
        global_counters.bump_unlocked("gc_collections")
        global_counters.bump_unlocked("gc_pause_ns", ns)
        self.tracer.add_span_unlocked(self.NAME, self._t0 / 1e9, t1 / 1e9)
        if gen == 2:
            global_counters.bump_unlocked("gc_gen2_collections")
            self.flight.record_unlocked("mark", self.NAME, generation=gen,
                                        dur_ms=ns / 1e6)


GC_WATCH = _GcWatch()
_GC_INSTALL = threading.Lock()


def watch_gc() -> None:
    """Install the process's one collector watcher (:class:`_GcWatch`);
    idempotent. ``DecodeEngine.start()`` and ``SGD.train`` call it."""
    with _GC_INSTALL:
        if GC_WATCH not in gc.callbacks:
            # resolved here: an import inside a collection could wait for
            # the import lock that the collecting thread holds
            from paddle_tpu.obs.flight import FLIGHT
            GC_WATCH.tracer, GC_WATCH.flight = _tracer(), FLIGHT
            gc.callbacks.append(GC_WATCH)


def timed(name: str):
    def deco(fn):
        def wrapper(*a, **kw):
            with stat_timer(name):
                return fn(*a, **kw)
        return wrapper
    return deco
