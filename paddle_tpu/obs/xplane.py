"""One reader for a ``jax.profiler`` trace directory: the device's
operations and the program's host spans on ONE clock.

``utils.stats.stat_timer`` opens a ``jax.profiler.TraceAnnotation``, so
while a profiler session is on (``jax.profiler.trace``, the deep window
of obs/profile.py, ``paddle_tpu profile``) every ``serving/*`` and
``train*`` span lands in the host plane of the same ``.xplane.pb`` that
holds the device's operations. :func:`report` reads that file with
``jax.profiler.ProfileData`` alone (no TensorFlow) and returns, per
device: busy and idle time (the union of the operations' intervals),
device time by operation and by named scope (``jax.named_scope`` /
the kernel's ``name=``), and every idle gap of the busy union
attributed to the host span(s) that were open during it. What no span
covers is ``unattributed`` - never spread over the others.

Two things the v5e's runtime taught (PERF.md section 7). The trace
names an operation by its HLO line WITHOUT its metadata, so a named
scope reaches the report only through the compiled program's own text
(``hlo_text=``: instruction -> ``op_name``; ``paddle_tpu train --job
profile`` hands it the train step's); a kernel's ``name=`` is in the
instruction's name and needs nothing. And the device plane's clock may
sit a few milliseconds off the host plane's: for the serving loop
(``SYNC_LOOP``: the span that launches a step and the span that waits
for one, the same step's or, where the loop keeps a step in flight, the
one before) :func:`clock_offset_bounds` holds the two to causality and
the device's events are shifted back inside the bounds before any gap
is attributed.

The arithmetic works on plain ``(start_ns, duration_ns, name)`` tuples
(tests/test_xplane.py checks it on hand-made lists).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["report", "format_report", "device_report", "attribute_gaps",
           "innermost_segments", "busy_intervals", "self_time_by_name",
           "clock_offset_bounds", "short_op_name", "scope_of",
           "scopes_from_hlo", "event_scope"]

Event = Tuple[int, int, str]            # start_ns, duration_ns, name

#: the device plane's lines of operations and of program executions, as
#: the TPU runtime names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the serving loop's exchange with the device: the span that launches a
#: step and the span that waits for a step's result (serving/engine.py;
#: with a step in flight the wait is for the step BEFORE the one the same
#: turn launched)
SYNC_LOOP = ("serving/dispatch", "serving/sync")
#: host spans the program opens itself (stat_timer names, and the
#: collector watcher's ``host/gc``: utils/stats.py ``watch_gc``)
SPAN_PREFIXES = ("serving/", "train", "host/")
#: a collection of Python's garbage collector: it holds the interpreter,
#: whichever thread runs it, so a gap under it is its own
GC_SPAN = "host/gc"
NO_SCOPE = "(no scope)"
#: an event whose instruction the given program text does not hold: the
#: text is of another program than the one traced
NOT_IN_TEXT = "(not in the program's text)"
UNATTRIBUTED = "unattributed"
#: rows of a report's by-operation and by-scope tables, and how many of
#: the longest gaps it lists one by one
TOP = 12
LONGEST = 5


# ------------------------------------------------------------ arithmetic
def busy_intervals(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """The union of the events' intervals as sorted, disjoint
    (start, end) pairs."""
    out: List[List[int]] = []
    for s, d, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def self_time_by_name(events: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds by name, each event's own time only: what a parent's
    children cover counts for the children."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List] = []              # [end, name, own_ns]
    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= s:
            _, n, own = stack.pop()
            out[n] += own
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, name, d])
    for _, n, own in stack:
        out[n] += own
    return dict(out)


def innermost_segments(spans: Sequence[Event]) -> List[Tuple[int, int, str]]:
    """The nested spans of ONE thread as disjoint (start, end, name)
    segments: at each instant the innermost open span. A parent keeps
    only the time none of its children covers."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []   # (end, name), outermost first
    t = 0                               # written up to here

    def close(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for s, d, name in sorted(spans, key=lambda e: (e[0], -e[1])):
        close(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = max(t, s) if stack else s
        end = s + d
        if stack:                       # a child never outlives its parent
            end = min(end, stack[-1][0])
        stack.append((end, name))
    close(float("inf"))
    return out


def _overlap_ns(gaps: Sequence[Tuple[int, int]],
                segments: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of the (sorted, disjoint) gaps under each segment
    name; segments sorted and disjoint too."""
    out: Dict[str, int] = defaultdict(int)
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, name = segments[k]
            out[name] += min(b, e) - max(a, s)
            k += 1
    return dict(out)


def _minus(spans: Sequence[Tuple[int, int]],
           holes: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The sorted, disjoint (start, end) ``spans`` less the sorted,
    disjoint ``holes``."""
    out: List[Tuple[int, int]] = []
    j = 0
    for a, b in spans:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > a:
                out.append((a, holes[k][0]))
            a = max(a, holes[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def attribute_gaps(ops: Sequence[Event],
                   host_threads: Dict[str, Sequence[Event]]) -> dict:
    """Every idle gap of one device's busy union (first operation's
    start to the last one's end), attributed to the host spans open
    during it. ``host_threads`` maps a thread to its spans; on each
    thread a gap's time goes to the innermost span, except that the time
    under a collection (``GC_SPAN``, on whichever thread ran it) is the
    collection's ahead of every thread's spans. ``unattributed`` is the
    gap time during which NO span of any thread was open; with several
    host threads the named shares may overlap each other (a feed
    thread's ``train/h2d`` under the loop's ``train/data_wait``), never
    ``unattributed``.

    -> {"n_gaps", "gap_ns", "by_span": {name: ns}, "unattributed_ns",
        "longest": [{"start_ns", "gap_ns", "by_span"}, ...]}"""
    busy = busy_intervals(ops)
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:]) if a1 > b0]
    collections = busy_intervals([e for spans in host_threads.values()
                                  for e in spans if e[2] == GC_SPAN])
    per_thread = [innermost_segments([e for e in spans if e[2] != GC_SPAN])
                  for spans in host_threads.values()]
    covered = busy_intervals([(s, e - s, "") for segs in per_thread
                              for s, e, _ in segs]
                             + [(s, e - s, "") for s, e in collections])
    cover_segs = [(s, e, "any") for s, e in covered]

    def split(some_gaps) -> Tuple[Dict[str, int], int]:
        by: Dict[str, int] = defaultdict(int)
        gc_ns = _overlap_ns(some_gaps, [(s, e, GC_SPAN)
                                        for s, e in collections])
        by.update(gc_ns)
        rest = _minus(some_gaps, collections)
        for segs in per_thread:
            for name, ns in _overlap_ns(rest, segs).items():
                by[name] += ns
        total = sum(b - a for a, b in some_gaps)
        return dict(by), total - _overlap_ns(some_gaps, cover_segs).get(
            "any", 0)

    by_span, unattributed = split(gaps)
    top = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:LONGEST]:
        by, un = split([(a, b)])
        if un:
            by[UNATTRIBUTED] = un
        top.append({"start_ns": a, "gap_ns": b - a, "by_span": by})
    return {"n_gaps": len(gaps), "gap_ns": sum(b - a for a, b in gaps),
            "by_span": by_span, "unattributed_ns": unattributed,
            "longest": top}


def _nearest(sorted_keys: Sequence[int], x: int) -> int:
    """Index of the key nearest to x."""
    i = bisect.bisect_left(sorted_keys, x)
    if i == 0:
        return 0
    if i == len(sorted_keys):
        return i - 1
    return i if sorted_keys[i] - x < x - sorted_keys[i - 1] else i - 1


def clock_offset_bounds(modules: Sequence[Event],
                        launches: Sequence[Event],
                        waits: Sequence[Event]) -> Optional[Tuple[int, int]]:
    """How far the device's clock may sit ahead of the host's (ns), by
    causality in the serving loop: the k-th execution of the step program
    (``modules``, device clock) starts no earlier than the k-th launch
    span starts, and ends no later than the k-th wait span ends. The
    loop may keep a step in flight (serving/engine.py): the wait for
    step k then ends after the launch of step k+1 has started, and the
    launch NEAREST to an execution's start is the next step's. So the
    launches are held to the executions by order, one each: of the
    alignments (the trace's edges cut an unknown number of spans) that
    leave ``lo <= hi``, the one whose bounds lie nearest to 0, then the
    narrowest. Each execution's wait is the one whose end lies nearest
    (right while the offset is under half a step, with or without a step
    in flight). -> (lo, hi): device - host lies in [lo, hi]; the clocks
    agree where lo <= 0 <= hi. None without the spans, or where no
    alignment keeps every execution behind its launch."""
    if not (modules and launches and waits):
        return None
    starts = sorted(s for s, _, _ in launches)
    ends = sorted(s + d for s, d, _ in waits)
    mods = sorted(modules)
    lo = max(s + d - ends[_nearest(ends, s + d)] for s, d, _ in mods)
    best = None
    for a in range(len(starts) - len(mods) + 1):
        hi = min(s - starts[i + a] for i, (s, _, _) in enumerate(mods))
        if lo <= hi:
            key = (max(lo, 0, -hi), hi - lo)    # how far from 0, how wide
            if best is None or key < best[0]:
                best = key, hi
    # no alignment keeps causality (the launches of the first executions
    # cut off): nothing to hold the clocks to
    return None if best is None else (lo, best[1])


# ------------------------------------------------------------------ names
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: components of an op_name path that JAX's transformations add
_WRAPPER = re.compile(
    r"^(jit|pjit|jvp|vjp|transpose|vmap|pmap|shard_map|remat|checkpoint|"
    r"custom_jvp|custom_vjp|custom_vjp_call|rematted_computation|while|"
    r"cond|scan|body|branch_\d+_fun|closed_call|core_call|named)"
    r"(\(.*\))?$")


def short_op_name(name: str) -> str:
    """The TPU trace names an operation by its whole HLO line,
    '%fusion.7 = bf16[..] fusion(...)' -> 'fusion:fusion': the opcode
    and the result's name without instance numbers, so that instances
    add up. A Mosaic (Pallas) kernel reads 'tpu_custom_call:<result>',
    and its result is named after the kernel's ``name=``."""
    lhs, sep, rhs = name.partition(" = ")
    lhs = ".".join(p for p in lhs.lstrip("%").split(".") if not p.isdigit())
    if not sep:
        return lhs
    if 'custom_call_target="tpu_custom_call"' in rhs:
        return "tpu_custom_call:" + lhs
    m = _OPCODE.search(" " + rhs)
    return (m.group(1) + ":" if m else "") + lhs


def scope_of(op_name: Optional[str]) -> str:
    """'jit(_step_impl)/jit(main)/paged_attn/dot_general' -> 'paged_attn':
    the outermost component of an operation's ``op_name`` path that the
    program chose (not a transformation's wrapper, not the primitive at
    the end). No such component: ``NO_SCOPE``."""
    if not op_name:
        return NO_SCOPE
    parts = [p for p in op_name.split("/") if p]
    for p in parts[:-1]:
        if not _WRAPPER.match(p):
            return p
    return NO_SCOPE


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")


def scopes_from_hlo(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} for every instruction of a compiled
    program's text (``compiled.as_text()``): its ``op_name`` metadata
    through :func:`scope_of`, ``NO_SCOPE`` where it has none (what the
    compiler inserts itself). The trace names an event by the same
    instruction."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(n.group(1) if n else None)
    return out


def event_scope(hlo_line: str, scopes: Optional[Dict[str, str]]) -> str:
    """The scope of a traced operation, which the trace names by its
    HLO line, through the program's ``scopes`` (:func:`scopes_from_hlo`):
    ``NOT_IN_TEXT`` where the text does not hold the instruction,
    ``NO_SCOPE`` for every operation where no text was given."""
    if scopes is None:
        return NO_SCOPE
    instr = hlo_line.partition(" = ")[0].strip().lstrip("%")
    return scopes.get(instr, NOT_IN_TEXT)


# ---------------------------------------------------------------- reports
def device_report(ops: Sequence[Event], scoped: Sequence[Event],
                  host_threads: Dict[str, Sequence[Event]]) -> dict:
    """One device's numbers from its operations (named by operation in
    ``ops`` and by scope in ``scoped``) and the host's spans."""
    lo = min(s for s, _, _ in ops)
    hi = max(s + d for s, d, _ in ops)
    busy = sum(b - a for a, b in busy_intervals(ops))

    def ranked(events):
        rows = sorted(self_time_by_name(events).items(),
                      key=lambda kv: -kv[1])[:TOP]
        return [[n, ns / 1e9] for n, ns in rows]

    idle = attribute_gaps(ops, host_threads)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
            "idle_s": (hi - lo - busy) / 1e9,
            "idle_share": 1.0 - busy / (hi - lo) if hi > lo else 0.0,
            "by_op": ranked(ops), "by_scope": ranked(scoped),
            "idle": {"n_gaps": idle["n_gaps"],
                     "by_span": sorted(
                         ([n, ns / 1e9] for n, ns in idle["by_span"].items()),
                         key=lambda kv: -kv[1]),
                     "unattributed_s": idle["unattributed_ns"] / 1e9,
                     "longest": [
                         {"gap_ms": g["gap_ns"] / 1e6,
                          "by_span_ms": {n: ns / 1e6 for n, ns
                                         in g["by_span"].items()}}
                         for g in idle["longest"]]}}


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _step_program(modules: Sequence[Event]) -> List[Event]:
    """The executions of the program that holds most of the device's
    time: the step, not the page copies beside it."""
    total: Dict[str, int] = defaultdict(int)
    for _, d, name in modules:
        total[name] += d
    if not total:
        return []
    step = max(total, key=total.get)
    return [e for e in modules if e[2] == step]


def report(trace_dir: str, hlo_text: Optional[str] = None) -> dict:
    """The newest trace under ``trace_dir`` -> {"xplane": path,
    "host_spans": {name: [count, seconds]}, "devices": {plane name:
    device_report + "clock"}}. Host spans are the host plane's events
    whose name starts with one of ``SPAN_PREFIXES``. ``hlo_text`` (the
    compiled step's ``as_text()``) gives the operations their scopes:
    an operation the text does not hold reads ``NOT_IN_TEXT``, and
    without a text every operation reads ``NO_SCOPE``. Where the trace
    holds the launch and wait spans of ``SYNC_LOOP`` (module doc),
    "clock" is {"offset_bounds_ms": [lo, hi], "applied_ms": x} and the
    device's events were moved by -x before the gaps were attributed."""
    from jax.profiler import ProfileData
    path = newest_xplane(trace_dir)
    data = ProfileData.from_file(path)
    scopes = scopes_from_hlo(hlo_text) if hlo_text else None
    host_threads: Dict[str, List[Event]] = {}
    devices: Dict[str, Tuple[List[Event], List[Event], List[Event]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans = [(int(ev.start_ns), int(ev.duration_ns), ev.name)
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES)]
                if spans:
                    host_threads[f"{line.name}#{i}"] = spans
        elif plane.name.startswith("/device:"):
            ops: List[Event] = []
            scoped: List[Event] = []
            modules: List[Event] = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = [(int(ev.start_ns), int(ev.duration_ns),
                                ev.name.split("(")[0])
                               for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    ops.append((s, d, short_op_name(ev.name)))
                    scoped.append((s, d, event_scope(ev.name, scopes)))
            if ops:
                devices[plane.name] = (ops, scoped, modules)
    host_spans: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for spans in host_threads.values():
        for _, d, name in spans:
            host_spans[name][0] += 1
            host_spans[name][1] += d / 1e9
    all_spans = [e for spans in host_threads.values() for e in spans]
    launches = [e for e in all_spans if e[2] == SYNC_LOOP[0]]
    waits = [e for e in all_spans if e[2] == SYNC_LOOP[1]]
    out = {}
    for name, (ops, scoped, modules) in devices.items():
        # the first and last execution may be cut by the trace's edges
        bounds = clock_offset_bounds(_step_program(modules)[1:-1],
                                     launches, waits)
        clock = None
        if bounds is not None:
            lo, hi = bounds
            # inside the bounds the nearest to 0: no evidence, no shift
            shift = 0 if lo <= 0 <= hi else (lo if lo > 0 else hi)
            clock = {"offset_bounds_ms": [lo / 1e6, hi / 1e6],
                     "applied_ms": shift / 1e6}
            if shift:
                ops = [(s - shift, d, n) for s, d, n in ops]
                scoped = [(s - shift, d, n) for s, d, n in scoped]
        out[name] = dict(device_report(ops, scoped, host_threads),
                         clock=clock)
    return {"xplane": path, "host_spans": dict(host_spans), "devices": out}


def format_report(rep: dict) -> str:
    """The report as the text ``paddle_tpu profile`` prints."""
    lines = [f"xplane: {rep['xplane']}"]
    if rep["host_spans"]:
        lines.append("-- host spans (count, seconds):")
        for name, (n, s) in sorted(rep["host_spans"].items(),
                                   key=lambda kv: -kv[1][1]):
            lines.append(f"   {name:<28} x{n:<6} {s:9.4f} s")
    if not rep["devices"]:
        lines.append("(no device plane ran an operation: nothing but the "
                     "host's spans to report)")
    for name, d in rep["devices"].items():
        lines.append(f"== {name}: window {d['window_s']:.4f} s, busy "
                     f"{d['busy_s']:.4f} s, idle {d['idle_s']:.4f} s "
                     f"({100 * d['idle_share']:.2f} %)")
        if d.get("clock"):
            lo, hi = d["clock"]["offset_bounds_ms"]
            lines.append(f"-- device clock - host clock in [{lo:.3f}, "
                         f"{hi:.3f}] ms by causality; events moved by "
                         f"{-d['clock']['applied_ms']:.3f} ms")
        lines.append("-- device time by operation (self time):")
        for n, s in d["by_op"]:
            lines.append(f"   {s * 1e3:10.3f} ms  {n[:90]}")
        lines.append("-- device time by named scope:")
        for n, s in d["by_scope"]:
            lines.append(f"   {s * 1e3:10.3f} ms  {n[:90]}")
        idle = d["idle"]
        lines.append(f"-- idle gaps ({idle['n_gaps']}) by the host span "
                     "open during them:")
        for n, s in idle["by_span"]:
            lines.append(f"   {s * 1e3:10.3f} ms  {n}")
        lines.append(f"   {idle['unattributed_s'] * 1e3:10.3f} ms  "
                     f"{UNATTRIBUTED}")
    return "\n".join(lines)
