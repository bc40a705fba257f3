"""From the profiler's trace to numbers: device busy time (the union of
the intervals in which an operation runs), idle gaps, time by operation
name, and the part of collective time that no compute covers.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` with nothing but
JAX. The arithmetic works on plain ``(start_ns, duration_ns, name)``
tuples, so a test can check it on a hand-made list.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[int, int, str]          # start_ns, duration_ns, name

#: device-plane line names, as the TPU runtime writes them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: substrings of an operation's name that mark a collective
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")


def union_ns(events: Iterable[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for s, d, _ in sorted(events):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """The union as sorted, disjoint (start, end) intervals."""
    out: List[List[int]] = []
    for s, d, _ in sorted(events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def self_time_by_name(events: Sequence[Event]) -> Dict[str, int]:
    """Nanoseconds by name, each event's own time only: the part of a
    parent (a ``while``, a fusion holding children) that its children
    cover counts for the children."""
    out: Dict[str, int] = defaultdict(int)
    stack: List[List] = []              # [end, name, self_ns]
    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out[n] += own
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, name, d])
    for end, n, own in stack:
        out[n] += own
    return dict(out)


def idle_gaps(events: Sequence[Event], lo: int = None,
              hi: int = None) -> List[Tuple[str, int]]:
    """Every gap of the busy union inside [lo, hi], labelled by the
    operation that ends it (what the device waited for)."""
    evs = sorted(events)
    if not evs:
        return []
    gaps = []
    end = lo if lo is not None else evs[0][0]
    for s, d, name in evs:
        if s > end:
            gaps.append((f"before {name}", s - end))
        end = max(end, s + d)
    if hi is not None and hi > end:
        gaps.append(("after the last operation", hi - end))
    return gaps


def exposed_ns(collectives: Sequence[Event],
               compute: Sequence[Event]) -> int:
    """The part of the collectives' union during which no compute
    operation runs on the same device."""
    cover = merged(compute)
    total = 0
    for s, e in merged(collectives):
        covered = 0
        for a, b in cover:
            if b <= s:
                continue
            if a >= e:
                break
            covered += min(b, e) - max(a, s)
        total += (e - s) - covered
    return total


def is_collective(name: str) -> bool:
    return any(m in name for m in COLLECTIVE_MARKS)


class Trace:
    """One traced window: per device the operations' and the programs'
    events, and the window's length."""

    def __init__(self, devices: List[dict], window_s: float = None):
        self.devices = devices          # [{"ops": [...], "modules": [...]}]
        if window_s is None:
            # by the device's own clock, first operation's start to the
            # last one's end: the host's stamps around start_trace and
            # stop_trace are on another clock and miss by milliseconds
            window_s = sum(
                max(s + d for s, d, _ in dev["ops"]) -
                min(s for s, _, _ in dev["ops"])
                for dev in devices) / len(devices) / 1e9
        self.window_s = float(window_s)

    # -- reductions, averaged over the devices used
    def busy_s(self) -> float:
        return sum(union_ns(d["ops"]) for d in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def seconds_by_name(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for n, ns in self_time_by_name(d["ops"]).items():
                out[n] += ns / 1e9 / len(self.devices)
        return dict(out)

    def seconds_matching(self, pred) -> float:
        """Union time of the operations whose name ``pred`` accepts."""
        return sum(union_ns([e for e in d["ops"] if pred(e[2])])
                   for d in self.devices) / len(self.devices) / 1e9

    def steps(self, module_pred, op_pred=None) -> dict:
        """The executions of the program ``module_pred`` accepts, the first
        and last of each device left out (they may be cut by the window's
        edges): how many (``n``; ``n_all`` with the edges); ``module_s``,
        their mean span from start to end, which holds whatever the
        program waits for inside it (the host's transfers); and ``ops_s``,
        the mean time per execution in which an operation (one that
        ``op_pred`` accepts; any, without it) ran inside the span."""
        n = n_all = 0
        mod_ns = ops_ns = 0
        for d in self.devices:
            mods = sorted(e for e in d["modules"] if module_pred(e[2]))
            n_all += len(mods)
            mods = mods[1:-1]
            n += len(mods)
            mod_ns += sum(m[1] for m in mods)
            sel = [e for e in d["ops"] if op_pred is None or op_pred(e[2])]
            ops_ns += union_ns(sel) - exposed_ns(sel, mods)
        k = len(self.devices)
        return {"n": n / k, "n_all": n_all / k,
                "module_s": mod_ns / n / 1e9 if n else 0.0,
                "ops_s": ops_ns / n / 1e9 if n else 0.0}

    def exposed_collective_s(self) -> float:
        tot = 0
        for d in self.devices:
            coll = [e for e in d["ops"] if is_collective(e[2])]
            comp = [e for e in d["ops"] if not is_collective(e[2])]
            tot += exposed_ns(coll, comp)
        return tot / len(self.devices) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.seconds_by_name().items(), key=lambda kv: -kv[1])
        gaps: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for label, ns in idle_gaps(d["ops"]):
                gaps[label] += ns / 1e9 / len(self.devices)
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in
                              sorted(gaps.items(), key=lambda kv: -kv[1])[:top]]}


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO line,
    '%fusion.7.remat = bf16[..]{..} copy(...)'. -> 'copy:fusion.remat':
    the opcode and the result's name without its instance numbers, so
    that instances of one kind add up. A Mosaic (Pallas) kernel gets the
    opcode 'tpu_custom_call'."""
    lhs, sep, rhs = name.partition(" = ")
    lhs = ".".join(p for p in lhs.lstrip("%").split(".") if not p.isdigit())
    if not sep:
        return lhs
    if KERNEL_MARK in rhs:
        return "tpu_custom_call:" + lhs
    m = _OPCODE.search(" " + rhs)
    return (m.group(1) + ":" if m else "") + lhs


def read_trace(trace_dir: str, chips: int, generalise: bool = True) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir`` -> Trace, over the
    first ``chips`` device planes that ran anything."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            key = "ops" if line.name == OPS_LINE else "modules"
            for ev in line.events:
                name = ev.name
                if generalise and key == "ops":
                    name = short_name(name)
                dev[key].append((int(ev.start_ns), int(ev.duration_ns), name))
        if dev["ops"]:
            devices.append(dev)
    if len(devices) < chips:
        raise RuntimeError(
            f"the trace shows operations on {len(devices)} device(s), "
            f"the cell uses {chips}: planes "
            f"{[p.name for p in data.planes]}")
    return Trace(devices[:chips])


def dump_summary(trace_dir: str, out_path: str, limit: int = 60) -> None:
    """Write what the trace's planes, lines and names look like - the look
    by hand that comes before any reader is written against them."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    data = ProfileData.from_file(paths[-1])
    with open(out_path, "w") as f:
        for plane in data.planes:
            f.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name}: {len(evs)} events\n")
                agg: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
                for ev in evs:
                    a = agg[ev.name]
                    a[0] += 1
                    a[1] += ev.duration_ns / 1e6
                for n, (c, ms) in sorted(agg.items(),
                                         key=lambda kv: -kv[1][1])[:limit]:
                    f.write(f"    {ms:12.3f} ms {c:8d} x {n[:150]}\n")
                if evs and plane.name.startswith("/device"):
                    ev = evs[len(evs) // 2]
                    f.write("    stats of one event: " + repr(
                        [(k, str(v)[:80]) for k, v in ev.stats][:12]) + "\n")
