"""One benchmark run of a serving cell with host stamps around it: the tool
for a run that lost steps to a stall (PERF.md section 7). ``benchmarks/run.py``
runs unchanged; around it

  * every phase of the engine's loop (``DecodeEngine._phase``: admit, plan,
    dispatch, sync, commit, idle) is stamped with its start and end, and
    every ``submit`` of the load generator that took over 20 ms;
  * a heartbeat thread sleeps 5 ms and notes each wake-up that came more
    than 10 ms late: a pause of this process (a collection of the garbage
    collector, another thread holding the interpreter) shows there, a wait
    inside the runtime does not;
  * the same heartbeat runs in a WITNESS process of its own, which imports
    nothing and idles: a pause that it sees at the same wall time froze the
    whole machine, and no code of this repository;
  * every collection of the garbage collector is stamped (``gc.callbacks``).

    python benchmarks/tools/stamps.py <out.json> --workload <name> \\
        --seed <n> --seconds 51 --trace 0

After the run's own lines it prints one line ``{"phase": "stamps", ...}``:
the step periods (end of one ``serving/sync`` to the next) inside the window,
each period over 60 ms with the phases, late heartbeats, collections and
witness pauses that fell into it, and the time lost to them; the raw stamps
go to ``<out.json>``. Benchmark runs never do this.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LONG_MS = 60.0          # a step period over this is listed
LATE_MS = 10.0          # a heartbeat later than this is noted

_WITNESS = """
import os, sys, time
f = open(sys.argv[1], "w")
parent, t_end = int(sys.argv[2]), time.time() + 900.0
last = time.time()
while os.getppid() == parent and time.time() < t_end:
    time.sleep(0.005)
    t = time.time()
    if (t - last - 0.005) * 1e3 > %r:
        f.write("%%.6f %%.1f\\n" %% (t, (t - last - 0.005) * 1e3)); f.flush()
    last = time.time()
""" % LATE_MS


class Stamps:
    """The stamps of one process: ``install`` patches the engine and the
    harness and starts the heartbeats, ``remove`` undoes it, ``summary``
    reads the window."""

    def __init__(self, witness_path: str = None):
        self.phases, self.submits, self.beats, self.gcs = [], [], [], []
        self.mark = {}
        self.witness_path = witness_path
        self._stop = threading.Event()
        self._undo, self._child, self._heart = [], None, None

    # ------------------------------------------------------------ install
    def install(self):
        from benchmarks.lib import harness
        from paddle_tpu.serving import engine as E
        phases, submits, mark = self.phases, self.submits, self.mark
        o_phase, o_submit = E.DecodeEngine._phase, E.DecodeEngine.submit
        o_open, o_close = (harness.CompileCounter.open,
                           harness.CompileCounter.close)

        @contextlib.contextmanager
        def phase(eng, span, counter=None):
            t0 = time.perf_counter_ns()
            try:
                with o_phase(eng, span, counter):
                    yield
            finally:
                phases.append((span, t0, time.perf_counter_ns()))

        def submit(eng, *a, **k):
            t0 = time.perf_counter_ns()
            try:
                return o_submit(eng, *a, **k)
            finally:
                t1 = time.perf_counter_ns()
                if t1 - t0 > 20_000_000:
                    submits.append((t0, t1))

        def c_open(counter):            # the window opens
            mark["open"], mark["open_wall"] = (time.perf_counter_ns(),
                                               time.time())
            return o_open(counter)

        def c_close(counter):
            mark["close"] = time.perf_counter_ns()
            return o_close(counter)

        E.DecodeEngine._phase, E.DecodeEngine.submit = phase, submit
        harness.CompileCounter.open, harness.CompileCounter.close = (c_open,
                                                                     c_close)
        self._undo = [(E.DecodeEngine, "_phase", o_phase),
                      (E.DecodeEngine, "submit", o_submit),
                      (harness.CompileCounter, "open", o_open),
                      (harness.CompileCounter, "close", o_close)]
        gc.callbacks.append(self._on_gc)
        if self.witness_path:
            self._child = subprocess.Popen(
                [sys.executable, "-c", _WITNESS, self.witness_path,
                 str(os.getpid())], stdin=subprocess.DEVNULL,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        self._heart = threading.Thread(target=self._beat, daemon=True,
                                       name="stamps-heartbeat")
        self._heart.start()
        return self

    def remove(self):
        self._stop.set()
        if self._heart is not None:
            self._heart.join(1.0)
        if self._child is not None:
            self._child.kill()
            self._child.wait()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        for owner, name, was in self._undo:
            setattr(owner, name, was)
        self._undo = []

    def _beat(self):
        last = time.perf_counter_ns()
        while not self._stop.is_set():
            time.sleep(0.005)
            t = time.perf_counter_ns()
            late = (t - last) / 1e6 - 5.0
            if late > LATE_MS:
                self.beats.append((t, late))
            last = time.perf_counter_ns()

    def _on_gc(self, phase, info):
        t = time.perf_counter_ns()
        if phase == "start":
            self.mark["gc"] = t
        else:
            self.gcs.append((self.mark.pop("gc", t), t,
                             info.get("generation")))

    # ------------------------------------------------------------ reading
    def witness(self):
        """[(ns on this process's clock, late ms)] of the witness's late
        beats, through the wall clock taken at the window's open."""
        if not self.witness_path or "open_wall" not in self.mark:
            return []
        out = []
        with open(self.witness_path) as f:
            for line in f:
                t, late = line.split()
                out.append((self.mark["open"] + int(
                    (float(t) - self.mark["open_wall"]) * 1e9), float(late)))
        return out

    def summary(self, seconds: float) -> dict:
        """The window ``[open, open + seconds)``: the step periods, and each
        over ``LONG_MS`` with what fell into it."""
        t_open = self.mark.get("open")
        if t_open is None:
            return {"phase": "stamps", "error": "the window never opened"}
        t_end = t_open + int(seconds * 1e9)
        win = [(s, a, b) for s, a, b in self.phases
               if a >= t_open and b <= t_end]
        by = {}
        for s, a, b in win:
            by.setdefault(s, []).append((b - a) / 1e6)
        ends = [b for s, _, b in win if s == "serving/sync"]
        periods = [(y - x) / 1e6 for x, y in zip(ends, ends[1:])]
        p50 = _pct(periods, .5)
        seen = self.witness()
        long_ = []
        for lo, hi in zip(ends, ends[1:]):
            if (hi - lo) / 1e6 <= LONG_MS:
                continue
            inside = {}
            for s, a, b in win:
                ov = min(b, hi) - max(a, lo)
                if ov > 0 and s != "serving/step":
                    inside[s] = round(inside.get(s, 0.0) + ov / 1e6, 2)
            def near(t, lo=lo, hi=hi):
                return lo <= t <= hi + 20_000_000

            long_.append({
                "at_s": (lo - t_open) / 1e9, "period_ms": (hi - lo) / 1e6,
                "by_phase_ms": inside,
                "heartbeat_late_ms": [round(x, 1) for t, x in self.beats
                                      if near(t)],
                "witness_late_ms": [round(x, 1) for t, x in seen if near(t)],
                "gc_ms": [round((b - a) / 1e6, 1) for a, b, _ in self.gcs
                          if a < hi and b > lo]})
        def in_win(t):
            return t_open <= t <= t_end

        return {
            "phase": "stamps", "steps": len(ends),
            "period_p50_ms": p50, "period_p95_ms": _pct(periods, .95),
            "period_max_ms": max(periods, default=None),
            "periods_over_%dms" % LONG_MS: len(long_),
            "lost_s": sum(x["period_ms"] - p50 for x in long_) / 1e3,
            "phases_ms": {s: {"n": len(v), "sum_s": sum(v) / 1e3,
                              "p50": _pct(v, .5), "max": max(v)}
                          for s, v in by.items()},
            "heartbeat_late_ms": [round(x, 1) for t, x in self.beats
                                  if in_win(t)],
            "witness_late_ms": [round(x, 1) for t, x in seen if in_win(t)],
            "gc_ms": {"n": sum(in_win(a) for a, _, _ in self.gcs),
                      "over_20": [round((b - a) / 1e6, 1)
                                  for a, b, _ in self.gcs
                                  if in_win(a) and b - a > 20_000_000]},
            "submit_over_20_ms": [round((b - a) / 1e6, 1)
                                  for a, b in self.submits if in_win(a)],
            "long": long_[:40]}

    def raw(self) -> dict:
        """Every stamp, in ns from the window's open."""
        t0 = self.mark.get("open", 0)
        return {"open_wall": self.mark.get("open_wall"),
                "phases": [(s, a - t0, b - t0) for s, a, b in self.phases],
                "submit": [(a - t0, b - t0) for a, b in self.submits],
                "beat": [(t - t0, x) for t, x in self.beats],
                "witness": [(t - t0, x) for t, x in self.witness()],
                "gc": [(a - t0, b - t0, g) for a, b, g in self.gcs]}


def _pct(values, q):
    v = sorted(values)
    return v[min(len(v) - 1, int(q * len(v)))] if v else None


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    out, argv = argv[0], argv[1:]
    from benchmarks import run          # its T_START first: set-up counts whole
    seconds = run.parse(argv).seconds
    stamps = Stamps(out + ".witness.txt").install()
    try:
        return run.main(argv)
    finally:
        stamps.remove()
        line = stamps.summary(seconds)
        print(json.dumps(line), flush=True)
        with open(out, "w") as f:
            json.dump(dict(stamps.raw(), summary=line), f)


if __name__ == "__main__":
    sys.exit(main())
