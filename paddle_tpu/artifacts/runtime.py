"""Executable resolution — the one warm-start decision point.

Every artifact-aware jitted function (the paged decode step, the draft
step, the CoW page copy) resolves its executable through
:func:`resolve`, which walks the warm ladder:

1. the in-process :class:`ExecutableCache` (fingerprint-keyed): N
   engines in one process — the C-ABI host's ``create_shared`` clones,
   the bench/test in-process fleets, a rolling deploy's rebuilt
   replica — share ONE compiled program, so an in-process respawn is
   literally zero-compile;
2. the configured :class:`ArtifactStore` (``PADDLE_TPU_ARTIFACTS`` or
   :func:`configure`): a cross-process warm start deserializes the
   executable — no trace, no XLA compile — after the store verified
   frame integrity and fingerprint match;
3. cold JIT (lower + compile), then BACKFILL both layers so the next
   starter is warm. Store write failures journal and degrade — a
   read-only artifact volume never blocks serving.

Every fallback is journaled (``artifacts/fallback``) and counted
(``paddle_tpu_artifacts_fallbacks``); resolution never raises past a
defect — the cold path always works.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Dict, Optional

from paddle_tpu.analysis.lockdep import named_lock
from paddle_tpu.obs.events import emit as journal_emit
from paddle_tpu.utils.logging import get_logger

from paddle_tpu.artifacts import aot, cache
from paddle_tpu.artifacts.fingerprint import Fingerprint
from paddle_tpu.artifacts.store import ArtifactStore

__all__ = ["ExecutableCache", "EXECUTABLES", "configure",
           "current_store", "resolve", "ENV_STORE"]

#: env var naming the artifact store directory — the cross-process
#: warm-start switch (SubprocessProvisioner forwards it to spawned
#: replicas; unset processes stay cold, which the SIGKILL chaos tests
#: rely on)
ENV_STORE = "PADDLE_TPU_ARTIFACTS"


class ExecutableCache:
    """Process-global fingerprint -> loaded-executable map. Bounded
    (LRU) because compiled executables pin mmap'd code pages — the
    test suite's map-count ceiling (tests/conftest.py
    ``_drop_xla_executables``) clears it per module."""

    def __init__(self, capacity: int = 32):
        self._lock = named_lock("artifacts.executables")
        self._entries: Dict[str, object] = {}  # ptlint: guarded-by(artifacts.executables)
        self._order: list = []  # ptlint: guarded-by(artifacts.executables)
        self.capacity = int(capacity)
        self.hits = 0
        self.misses = 0

    def get(self, fp: Fingerprint):
        with self._lock:
            exe = self._entries.get(fp.digest)
            if exe is not None:
                self.hits += 1
                self._order.remove(fp.digest)
                self._order.append(fp.digest)
            else:
                self.misses += 1
            return exe

    def put(self, fp: Fingerprint, exe) -> None:
        with self._lock:
            if fp.digest not in self._entries:
                self._order.append(fp.digest)
            self._entries[fp.digest] = exe
            while len(self._order) > self.capacity:
                evict = self._order.pop(0)
                self._entries.pop(evict, None)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._order.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries), "hits": self.hits,
                    "misses": self.misses}


EXECUTABLES = ExecutableCache()

_store_lock = threading.Lock()
_store: Optional[ArtifactStore] = None
_store_from_env = False


def configure(root: Optional[str]) -> Optional[ArtifactStore]:
    """Set (or with None clear) the process artifact store. Returns
    the active store."""
    global _store, _store_from_env
    with _store_lock:
        _store = ArtifactStore(root) if root else None
        _store_from_env = False
        return _store


def current_store() -> Optional[ArtifactStore]:
    """The configured store, falling back to ``PADDLE_TPU_ARTIFACTS``
    from the environment (resolved lazily, once)."""
    global _store, _store_from_env
    with _store_lock:
        if _store is None and not _store_from_env:
            _store_from_env = True
            root = os.environ.get(ENV_STORE)
            if root:
                _store = ArtifactStore(root)
        return _store


def _artifact_name(fp: Fingerprint) -> str:
    return f"{fp.fields.get('kind', 'fn')}-{fp.digest}"


def resolve(fp: Fingerprint, jitted, args, *,
            store: Optional[ArtifactStore] = None,
            warm: bool = True) -> Callable:
    """The warm ladder (module doc). ``jitted`` is the ``jax.jit``
    wrapper to cold-compile from; ``args`` are one call's actual
    arguments (shape/dtype donors). Always returns a callable with the
    jitted function's signature."""
    if not warm:
        return jitted
    exe = EXECUTABLES.get(fp)
    if exe is not None:
        return exe
    store = store if store is not None else current_store()
    name = _artifact_name(fp)
    if store is not None:
        blob = store.get(name, fp)
        if blob is not None:
            try:
                exe = aot.load_compiled(blob)
            except Exception as e:  # noqa: BLE001 — degrade, never crash
                # frame was intact but the executable would not load
                # (e.g. jaxlib refuses the payload): same contract as
                # corrupt — journal and JIT
                store._fallback(name, store.path(name), "unloadable",
                                repr(e)[:200])
                exe = None
            if exe is not None:
                journal_emit("artifacts", "load", name=name,
                             digest=fp.digest, source="store")
                EXECUTABLES.put(fp, exe)
                return exe
    # cold: compile eagerly so both layers can be backfilled. What goes
    # into a store is compiled here and now, past the persistent compile
    # cache: this jaxlib's CPU executable that came out of that cache
    # serializes without its object code (the artifact loads, then
    # fails its first dispatch with "Function ... not found")
    t0 = time.monotonic()
    try:
        with (cache.disabled() if store is not None
              else contextlib.nullcontext()):
            exe = aot.compile_aot(jitted, *args)
    except Exception:  # noqa: BLE001 — lower/compile quirk: plain JIT
        get_logger().warning(
            "artifact %s: eager lower+compile failed; serving via "
            "plain JIT (no artifact will be written)", name,
            exc_info=True)
        return jitted
    build_ms = (time.monotonic() - t0) * 1e3
    EXECUTABLES.put(fp, exe)
    if store is not None:
        try:
            payload = aot.serialize_compiled(exe)
            store.put(name, fp, payload,
                      meta={"build_ms": round(build_ms, 3)})
            store.record_build_ms(build_ms)
            journal_emit("artifacts", "build", name=name,
                         digest=fp.digest,
                         build_ms=round(build_ms, 3),
                         payload_bytes=len(payload))
        except Exception as e:  # noqa: BLE001 — RO volume / no backend support
            journal_emit("artifacts", "build_failed", name=name,
                         digest=fp.digest, detail=repr(e)[:200])
            get_logger().warning(
                "artifact %s: built in-process but could not be "
                "persisted (%s) — later processes start cold",
                name, e)
    return exe
