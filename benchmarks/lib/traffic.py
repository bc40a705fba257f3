"""The one general traffic generator. A mix is a data file
(benchmarks/traffic/<mix>.json) of parameters: its ``kind`` (open_loop,
closed_loop, train_job), lengths, rate or clients, sharing. Everything is
drawn from ``--seed``; the program receives only the generated inputs.

Every seed gets the SAME set of sizes and gaps, in another order
(stratified draws, then a seeded permutation): runs with different seeds
then carry the same work, and differ by order alone.

A serving mix has a ``ramp_s``: load offered at the mix's own rate (or by
its own clients) for that long BEFORE the window opens, as part of set-up,
so that the window opens on an engine in steady state. The ramp's requests
are a stratified set of their own, due at negative times.

``RngPlane`` is a copy of paddle_tpu/loadgen/synth.py (sound: named
independent streams), kept here so that the program cannot move its own
yardstick.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict

import numpy as np

_MASK64 = (1 << 64) - 1


def stable_hash64(x: int) -> int:
    """splitmix64 finalizer: deterministic across processes."""
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return x ^ (x >> 31)


class RngPlane:
    """Named, independent RNG streams off one seed: adding a stream never
    perturbs another's draws."""

    def __init__(self, seed: int):
        self.seed = int(seed)
        self._streams: Dict[str, np.random.Generator] = {}

    def stream(self, name: str) -> np.random.Generator:
        gen = self._streams.get(name)
        if gen is None:
            h = 0
            for ch in name:
                h = stable_hash64(h ^ ord(ch))
            material = [self.seed & 0xFFFFFFFF, (self.seed >> 32) & 0xFFFFFFFF,
                        h & 0xFFFFFFFF, (h >> 32) & 0xFFFFFFFF]
            gen = np.random.default_rng(np.random.SeedSequence(material))
            self._streams[name] = gen
        return gen


# ------------------------------------------------------------------ samplers
def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / max(n, 1)


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths of the distribution ``spec`` describes, one
    from each of n equal strata, in a seeded order."""
    u = _strata(n)
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        x = np.array([float(spec["median"]) *
                      math.exp(float(spec["sigma"]) * nd.inv_cdf(float(q)))
                      for q in u])
    elif spec["dist"] == "uniform":
        x = float(spec["min"]) + u * (float(spec["max"]) + 1 -
                                      float(spec["min"])) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", None)
    x = np.clip(np.rint(x), lo, hi).astype(np.int64)
    return x[rng.permutation(n)]


def arrivals(rate_per_s: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Offsets in [0, seconds) at which requests are due: round(rate x
    seconds) gaps, one from each stratum of the exponential, in a seeded
    order - a Poisson-like stream with the same count and the same gaps
    for every seed."""
    n = int(round(float(rate_per_s) * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-_strata(n))
    gaps = gaps[rng.permutation(n)]
    t = np.cumsum(gaps) - gaps[0]
    return t * (seconds / float(np.sum(gaps)))


def _open_loop(mix: dict, plane: RngPlane, part: str, seconds: float,
               shift: float, vocab: int) -> list:
    """The requests of one part (the ramp or the window) of an open-loop
    mix, due at ``shift`` + an offset in [0, seconds)."""
    due = arrivals(mix["arrivals"]["rate_per_s"], seconds,
                   plane.stream(part + "arrivals")) + shift
    n = len(due)
    plen = lengths(mix["prompt_len"], n, plane.stream(part + "prompt_len"))
    olen = lengths(mix["output_len"], n, plane.stream(part + "output_len"))
    tok = plane.stream(part + "tokens")
    return [{"due": float(due[i]),
             "prompt": tok.integers(0, vocab, int(plen[i]), dtype=np.int32),
             "max_new": int(olen[i])} for i in range(n)]


# ------------------------------------------------------------------ the mixes
def generate(mix: dict, seed: int, seconds: float, vocab: int,
             chips: int = 1) -> dict:
    """The inputs of one run of the mix ``mix`` (a traffic file's dict)."""
    plane = RngPlane(seed)
    kind = mix["kind"]
    if kind == "open_loop":
        ramp = float(mix["ramp_s"])
        return {"kind": kind,
                "requests": _open_loop(mix, plane, "", seconds, 0.0, vocab),
                "ramp_requests": _open_loop(mix, plane, "ramp_", ramp, -ramp,
                                            vocab)}
    if kind == "closed_loop":
        c = int(mix["clients"])
        turns = int(mix["turns_per_client"])
        tok = plane.stream("tokens")
        hist = [tok.integers(0, vocab, int(mix["history_len"]),
                             dtype=np.int32) for _ in range(c)]
        slen = lengths(mix["suffix_len"], c * turns,
                       plane.stream("suffix_len")).reshape(c, turns)
        olen = lengths(mix["output_len"], c * turns,
                       plane.stream("output_len")).reshape(c, turns)
        return {"kind": kind, "clients": [
            {"history": hist[i],
             "turns": [{"suffix": tok.integers(0, vocab, int(slen[i, j]),
                                               dtype=np.int32),
                        "max_new": int(olen[i, j])} for j in range(turns)]}
            for i in range(c)]}
    if kind == "train_job":
        b = int(mix["rows_per_chip_step"]) * chips   # each chip does as much
        t = int(mix["seq_len"])
        base = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF]

        def batch(step: int) -> np.ndarray:
            """[rows, seq_len + 1] token ids of step ``step``: every row of
            every step differs."""
            rng = np.random.default_rng(np.random.SeedSequence(
                base + [int(step)]))
            return rng.integers(0, vocab, (b, t + 1), dtype=np.int32)
        return {"kind": kind, "batch": batch, "rows": b, "seq_len": t}
    raise ValueError(f"unknown traffic kind {kind!r}")
