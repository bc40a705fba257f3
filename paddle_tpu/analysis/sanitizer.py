"""Runtime sanitizers: XLA compile budgets and leaked-tracer detection.

The static rules (analysis/rules.py) catch the recompile hazards an AST
can see; this module catches the ones only the live process can — a
feed whose shape drifts every batch, a weak-typed scalar that retraces,
a tracer escaping a jit boundary into host state.

``compile_watch()`` counts ACTUAL XLA compilations (cache misses) per
jitted function while active, by capturing JAX's compile log stream
(``jax_log_compiles`` — stable across JAX versions where the private
dispatch internals are not). ``check(budget)`` turns a blown budget
into :class:`CompileBudgetExceeded` with per-function counts, so a test
marked ``@pytest.mark.recompile_budget(max_compiles=N)`` (see
tests/conftest.py) FAILS when a change starts recompiling a hot step.

``find_tracers(obj)`` walks containers/attributes for JAX tracers that
escaped a trace (the list-append-under-jit bug R3 lints for);
``no_leaked_tracers()`` additionally arms ``jax_check_tracer_leaks``
so jit itself raises at the boundary.
"""

from __future__ import annotations

import contextlib
import logging
import re
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["CompileBudgetExceeded", "CompileWatch", "compile_watch",
           "find_tracers", "no_leaked_tracers", "HostSyncWatch",
           "host_sync_watch"]


class CompileBudgetExceeded(AssertionError):
    """A jitted function compiled more often than its budget allows.
    AssertionError subclass so pytest reports it as a plain failure."""


# the compile log line of the installed JAX (pxla):
# "Compiling jit(<name>) with global shapes and types ..."
_COMPILE_RE = re.compile(r"^Compiling jit\((.+?)\) with ")


class _CaptureHandler(logging.Handler):
    def __init__(self, watch: "CompileWatch"):
        super().__init__(level=logging.DEBUG)
        self._watch = watch

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if m:
            self._watch._record(m.group(1))


class CompileWatch:
    """Per-function XLA compile counts observed while the watch was
    active. ``total`` and ``per_function`` are live; ``check(budget)``
    enforces a per-function ceiling."""

    def __init__(self):
        self.per_function: Dict[str, int] = {}

    def _record(self, name: str) -> None:
        self.per_function[name] = self.per_function.get(name, 0) + 1

    @property
    def total(self) -> int:
        return sum(self.per_function.values())

    def count(self, name: str) -> int:
        return self.per_function.get(name, 0)

    def check(self, max_compiles: int,
              total: Optional[int] = None) -> None:
        """Raise CompileBudgetExceeded when any single function
        compiled more than ``max_compiles`` times (or the grand total
        exceeded ``total``). A hot function recompiling per step shows
        up as one name with a count ~= the step count."""
        over = {k: v for k, v in self.per_function.items()
                if v > max_compiles}
        if over:
            detail = ", ".join(f"{k}: {v}" for k, v in
                               sorted(over.items(), key=lambda kv: -kv[1]))
            raise CompileBudgetExceeded(
                f"compile budget exceeded (max {max_compiles} per "
                f"function): {detail}. A count that scales with the "
                "step count means the step retraces — look for "
                "drifting shapes/dtypes, unhashed static args, or "
                "jax.jit inside a loop (ptlint R2).")
        if total is not None and self.total > total:
            raise CompileBudgetExceeded(
                f"total compile budget exceeded: {self.total} > {total} "
                f"({dict(sorted(self.per_function.items()))})")


@contextlib.contextmanager
def compile_watch(max_compiles: Optional[int] = None,
                  check_leaks: bool = False) -> Iterator[CompileWatch]:
    """Count XLA compilations within the block; on exit, enforce
    ``max_compiles`` per function when given. ``check_leaks`` also arms
    jax_check_tracer_leaks for the scope (strict: jit raises on any
    tracer outliving its trace)."""
    import jax
    watch = CompileWatch()
    handler = _CaptureHandler(watch)
    jlog = logging.getLogger("jax")
    prev_log_compiles = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    # the compile records are WARNING while log_compiles is on; keep
    # them out of the user's console (JAX installs its own stream
    # handler on the "jax" logger) but inside our capture handler
    prev_propagate = jlog.propagate
    muted = [(h, h.level) for h in jlog.handlers]
    for h, _ in muted:
        h.setLevel(logging.ERROR)
    jlog.addHandler(handler)
    jlog.propagate = False
    leak_cm = no_leaked_tracers() if check_leaks else \
        contextlib.nullcontext()
    try:
        with leak_cm:
            yield watch
    finally:
        jlog.removeHandler(handler)
        for h, lvl in muted:
            h.setLevel(lvl)
        jlog.propagate = prev_propagate
        jax.config.update("jax_log_compiles", prev_log_compiles)
    if max_compiles is not None:
        watch.check(max_compiles)


@contextlib.contextmanager
def no_leaked_tracers() -> Iterator[None]:
    """Arm jax_check_tracer_leaks within the scope: a tracer kept
    beyond its trace (stashed in a list/global/attribute) makes the
    owning jit raise instead of silently baking a stale value in."""
    import jax
    prev = jax.config.jax_check_tracer_leaks
    jax.config.update("jax_check_tracer_leaks", True)
    try:
        yield
    finally:
        jax.config.update("jax_check_tracer_leaks", prev)


class HostSyncWatch:
    """Device->host synchronization counts observed while the watch was
    active (a PROXY: it counts ``jax.device_get`` and
    ``jax.block_until_ready`` calls through the ``jax`` module
    attributes — the repo's own host-sync funnel, SGD._fetch_host —
    not implicit syncs like ``float(arr)`` on a pre-bound reference).
    The smoke bench tier (bench.py) gates syncs-per-step on it: a
    change that starts syncing per microbatch instead of per step
    shows up as a count regression, the docs/perf.md 'One host sync
    per step' discipline made enforceable."""

    def __init__(self):
        self.per_kind: Dict[str, int] = {}

    def _record(self, kind: str) -> None:
        self.per_kind[kind] = self.per_kind.get(kind, 0) + 1

    @property
    def total(self) -> int:
        return sum(self.per_kind.values())

    def count(self, kind: str) -> int:
        return self.per_kind.get(kind, 0)


@contextlib.contextmanager
def host_sync_watch() -> Iterator[HostSyncWatch]:
    """Count explicit host syncs within the block (see HostSyncWatch
    for what is and is not counted). Nest-safe: restores the previous
    ``jax`` attributes on exit."""
    import jax
    watch = HostSyncWatch()
    orig_get = jax.device_get
    orig_block = jax.block_until_ready

    def counting_get(*a, **kw):
        watch._record("device_get")
        return orig_get(*a, **kw)

    def counting_block(*a, **kw):
        watch._record("block_until_ready")
        return orig_block(*a, **kw)

    jax.device_get = counting_get
    jax.block_until_ready = counting_block
    try:
        yield watch
    finally:
        jax.device_get = orig_get
        jax.block_until_ready = orig_block


def find_tracers(obj, _path: str = "value", _seen=None, _depth: int = 6
                 ) -> List[Tuple[str, object]]:
    """Walk containers (dict/list/tuple/set) and object __dict__ up to
    ``_depth`` levels for JAX tracers that escaped their trace; returns
    [(path, tracer)]. Use on module state / fixtures after a step to
    prove nothing leaked (tests/test_lint_rules.py)."""
    import jax
    if _seen is None:
        _seen = set()
    oid = id(obj)
    if oid in _seen or _depth < 0:
        return []
    _seen.add(oid)
    if isinstance(obj, jax.core.Tracer):
        return [(_path, obj)]
    out: List[Tuple[str, object]] = []
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.extend(find_tracers(v, f"{_path}[{k!r}]", _seen,
                                    _depth - 1))
    elif isinstance(obj, (list, tuple, set, frozenset)):
        for i, v in enumerate(obj):
            out.extend(find_tracers(v, f"{_path}[{i}]", _seen,
                                    _depth - 1))
    elif hasattr(obj, "__dict__") and not isinstance(obj, type):
        for k, v in vars(obj).items():
            out.extend(find_tracers(v, f"{_path}.{k}", _seen,
                                    _depth - 1))
    return out
