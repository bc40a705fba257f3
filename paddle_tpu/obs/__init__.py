"""paddle_tpu.obs — the unified observability layer.

One place the whole framework reports through (docs/observability.md):

- :mod:`paddle_tpu.obs.metrics` — thread-safe metrics registry
  (counters / gauges / histograms with labels) with ONE Prometheus
  text exposition path; absorbs ``utils/stats`` and the serving
  ``stats()`` plumbing.
- :mod:`paddle_tpu.obs.events`  — versioned-schema structured event
  journal (JSONL file + in-memory ring): faults, OOMs, data faults,
  quarantines, sheds, breaker flips, preemptions, checkpoints — every
  record stamped with run_id/host and, when bound, trace_id/step.
- :mod:`paddle_tpu.obs.context` — the correlation-ID plane: per-run
  ``run_id``, per-request ``trace_id`` (minted at the HTTP front),
  per-iteration ``step``.
- :mod:`paddle_tpu.obs.trace`   — host-side step tracing (bounded span
  rings) with Chrome trace export and XLA-compile instants.
- :mod:`paddle_tpu.obs.flight`  — the always-on flight recorder: a
  bounded ring of recent spans + events, auto-dumped as a postmortem
  bundle on faults/breaker-open/step-failure/SIGTERM and on demand
  (``paddle_tpu obs dump``).
- :mod:`paddle_tpu.obs.merge`   — cross-process fusion of N per-host
  journals + chrome traces into one timeline
  (``paddle_tpu trace merge`` / tools/trace_merge.py).
- :mod:`paddle_tpu.obs.httpd`   — standalone /metrics + /events +
  /flight + /profile endpoint for trainer/coordinator processes.
- :mod:`paddle_tpu.obs.profile` — continuous step profiler (per-phase
  breakdown, live MFU/roofline gauges, device-memory telemetry on the
  ``pt-obs-profiler`` thread, deep ``jax.profiler.trace`` windows).
- :mod:`paddle_tpu.obs.xplane`  — the reader of those windows (and of
  any ``jax.profiler`` trace directory): device busy/idle, time by
  operation and named scope, idle gaps by the host span open during
  them; ``jax.profiler.ProfileData`` alone.
- :mod:`paddle_tpu.obs.slo`     — SLO watchdog: declarative objectives
  over rolling windows + step-regression detection with per-phase
  attribution, journaled under the ``slo`` domain.

The perf regression gate rides on the same layer: ``bench.py``'s smoke
tier measures through ``compile_watch`` / ``host_sync_watch``
(analysis/sanitizer.py) and ``tools/bench_gate.py`` enforces
``BENCH_SMOKE_BASELINE.json`` in tier-1 — including the flight
recorder's always-on overhead row.
"""

from paddle_tpu.obs import context  # noqa: F401
from paddle_tpu.obs.context import (bind, current_fields,  # noqa: F401
                                    new_trace_id)
from paddle_tpu.obs.events import (JOURNAL, EventJournal, emit,  # noqa: F401
                                   emit_event, journal_segments,
                                   read_journal, tail, validate)
from paddle_tpu.obs.flight import FLIGHT, FlightRecorder  # noqa: F401
from paddle_tpu.obs.httpd import (build_obs_http_server,  # noqa: F401
                                  start_obs_server)
from paddle_tpu.obs.metrics import (REGISTRY, MetricsRegistry,  # noqa: F401
                                    stats_families)
from paddle_tpu.obs.profile import PROFILER, StepProfiler  # noqa: F401
from paddle_tpu.obs.protocol import WITNESS, ProtocolWitness  # noqa: F401
from paddle_tpu.obs.slo import (WATCHDOG, Objective,  # noqa: F401
                                SLOWatchdog, parse_objective)
from paddle_tpu.obs.trace import TRACER, Tracer, span  # noqa: F401

__all__ = [
    "REGISTRY", "MetricsRegistry", "stats_families",
    "JOURNAL", "EventJournal", "emit", "emit_event", "tail",
    "read_journal", "journal_segments", "validate",
    "TRACER", "Tracer", "span",
    "FLIGHT", "FlightRecorder",
    "PROFILER", "StepProfiler",
    "WITNESS", "ProtocolWitness",
    "WATCHDOG", "SLOWatchdog", "Objective", "parse_objective",
    "context", "bind", "current_fields", "new_trace_id",
    "build_obs_http_server", "start_obs_server",
    "reset_all",
]

# the flight recorder mirrors every journal record into its ring and
# auto-dumps on the trigger kinds — wired once at import so any entry
# point into the obs package arms it
JOURNAL.add_observer(FLIGHT.observe_journal)

# the protocol witness rides the same observer seam: every record
# advances the catalog-declared machines, and a violation's own
# protocol/violation emission is a flight auto-dump trigger
JOURNAL.add_observer(WITNESS.observe_journal)
from paddle_tpu.obs.protocol import _install_collector as \
    _install_protocol_collector  # noqa: E402

_install_protocol_collector()


def reset_all() -> None:
    """Zero every observability surface (registry values, journal ring
    + sink, tracer, flight recorder, trace context, utils/stats
    counters/timers) — the between-tests hygiene hook
    (tests/conftest.py autouse fixture)."""
    from paddle_tpu.analysis.lockdep import LOCKDEP
    from paddle_tpu.utils.stats import global_counters, global_stat
    REGISTRY.reset()
    JOURNAL.reset()
    TRACER.reset()
    FLIGHT.reset()
    PROFILER.reset()
    WATCHDOG.reset()
    context.reset()
    global_counters.reset()
    global_stat.reset()
    LOCKDEP.reset()
    WITNESS.reset()
