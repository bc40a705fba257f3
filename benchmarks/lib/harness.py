"""What every driver needs: the clock and percentiles, the count of
compiles inside the window, the device's description and memory peak,
the profiler window, and the one result line.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

now = time.monotonic


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of ALL the values (q in [0, 100])."""
    if not values:
        return float("nan")
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(math.ceil(q / 100.0 * len(s))) - 1))
    return float(s[k])


def say(**fields) -> None:
    """An earlier line of standard output: medians, counters, notes."""
    print(json.dumps(fields), flush=True)


class CompileCounter:
    """Counts the programs JAX lowers (each is a compile or a load from
    the persistent cache) between ``open`` and ``close``: inside the
    measured window there must be none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring as monitoring
        self.count = 0
        self._open = False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kw):
        if self._open and name == self.EVENT:
            self.count += 1

    def open(self):
        self._open = True

    def close(self) -> int:
        self._open = False
        return self.count


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": chips, "visible": len(d)}


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest chip used. The CPU backend reports none: 0."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class TraceWindow:
    """Traces ``seconds`` of the measured window from ``start_after``
    seconds into it, on a thread of its own so that the load keeps its
    schedule. ``snapshot()`` is called right after the trace starts and
    right before it stops: the counters of exactly the traced part."""

    def __init__(self, start_after: float, seconds: float, snapshot):
        self.start_after, self.seconds = start_after, seconds
        self.snapshot = snapshot
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.before = self.after = None
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="bench-trace",
                                        daemon=True)

    def start(self, t0: float):
        self._t0 = t0
        self._thread.start()

    def _run(self):
        import jax
        try:
            time.sleep(max(0.0, self._t0 + self.start_after - now()))
            # no Python frames: the readers take the device's planes alone,
            # and tracing every Python call slows the host loop under
            # test (an engine step took 106 ms of wall time traced so,
            # against 87 untraced) and with it raises the idle share
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.before = self.snapshot()
            time.sleep(self.seconds)
            self.after = self.snapshot()
            jax.profiler.stop_trace()
        except BaseException as e:      # surfaced by join()
            self.error = e

    def join(self):
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop within 300 s")
        if self.error is not None:
            raise self.error

    def read(self, chips: int):
        from benchmarks.lib import trace
        return trace.read_trace(self.dir, chips)

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def delta(after: Dict, before: Dict) -> Dict:
    """after - before over the numeric counters both hold."""
    return {k: after[k] - before[k] for k in after
            if k in before and isinstance(after[k], (int, float))
            and not isinstance(after[k], bool)}


def print_result(correct: bool, attempted: int, failed: int,
                 metrics: Dict[str, dict], device: dict, checks: Dict,
                 breakdown: Optional[dict] = None) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; then the ONE result line, last on standard output,
    with the same numbers under ``checks``, which comes last."""
    sys.stdout.flush()
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": l}
                      for k, (v, l) in checks.items()}
    print(json.dumps(line), flush=True)


def free(*arrays_or_trees) -> None:
    """Drop device buffers now, whoever still holds a reference."""
    import jax
    for tree in arrays_or_trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()


def cache_dir(root: str) -> str:
    """Where JAX's persistent compilation cache lives: where
    JAX_COMPILATION_CACHE_DIR says, else a fixed path in the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
