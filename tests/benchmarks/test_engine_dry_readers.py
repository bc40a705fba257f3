"""The three per-layer readers PR 43 adds (benchmarks/layer_metrics/
engine_dry_share.py, host_gc_share.py, engine_lane_step_extra_ms.py):
numbers from the window's DecodeEngine.stats() deltas, silent where the
program has no such counter (the parent's) or the window nothing to
divide by; their entries name the four serving cells and the gap."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import harness, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = ["opt13b_chat", "opt13b_agent_prefix", "kimik2_agent_2k",
         "kimilinear_agent_2k"]
NEW = {"engine_dry_share": "%", "host_gc_share": "%",
       "engine_lane_step_extra_ms": "ms"}
# what a program before PR 43 reports (PR 42's counters)
PARENT = {"steps": 900, "steps_launched_ahead": 899, "ahead_drains": 0,
          "host_sync_ns": 5_000_000_000, "tokens_out": 20_000}


def _read(name, ctx):
    return manifest.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name,ctx,want", [
    ("engine_dry_share",
     {"window_s": 51.0, "counters": {"device_dry_ns": 102_000_000}}, 0.2),
    ("engine_dry_share",
     {"window_s": 2.0, "counters": {"device_dry_ns": 0}}, 0.0),
    ("host_gc_share",
     {"window_s": 51.0, "counters": {"gc_pause_ns": 255_000_000}}, 0.5),
    ("engine_lane_step_extra_ms",
     {"counters": {"steps_timed_plain": 4, "step_ns_plain": 80_000_000,
                   "steps_timed_lanes": 2, "step_ns_lanes": 56_000_000}},
     8.0),
    ("engine_lane_step_extra_ms",
     {"counters": {"steps_timed_plain": 1, "step_ns_plain": 3_000_000,
                   "steps_timed_lanes": 1, "step_ns_lanes": 2_500_000}},
     -0.5),
])
def test_each_reads_its_counters(name, ctx, want):
    assert _read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("ctx", [
    {}, {"counters": None}, {"counters": {}},
    {"window_s": 51.0, "counters": PARENT},
    # a window of no time, or no timed step of one program
    {"window_s": 0.0, "counters": {"device_dry_ns": 5, "gc_pause_ns": 5}},
    {"window_s": 51.0, "counters": {
        "steps_timed_plain": 10, "step_ns_plain": 1, "steps_timed_lanes": 0,
        "step_ns_lanes": 0}},
])
def test_each_reads_nothing_where_there_is_nothing_to_read(name, ctx):
    assert _read(name, ctx) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_its_entry_names_the_four_serving_cells_and_the_gap(name):
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": NEW[name], "better": "lower",
                 "source": "program_counter",
                 "layer": "entry and scheduling", "moves": "gap_p95_ms",
                 "workloads": CELLS}
    assert [x["name"] for x in MANIFEST["per_layer"][-3:]] == list(NEW)
    for cell in CELLS:
        names = [x["name"] for x in manifest.cell(MANIFEST, cell)["per_layer"]]
        assert name in names


def test_they_read_the_deltas_of_a_started_engines_stats():
    """The serve driver's own arithmetic (harness.delta of two stats())
    over a tiny engine driven by its thread: the counters are there, the
    shares are shares of the window."""
    import sys
    import time
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_paged_decode import CFG, _decoder, _model
    from paddle_tpu.serving import DecodeEngine
    rng = np.random.RandomState(0)
    eng = DecodeEngine(_decoder(_model()), num_slots=2, page_size=4,
                       max_seq_len=CFG["max_len"])
    before, t0 = eng.stats(), time.perf_counter()
    eng.start()
    reqs = [eng.submit(rng.randint(0, CFG["vocab_size"], (5,)).astype(
        "int32"), 12) for _ in range(2)]
    for r in reqs:
        r.get(timeout=300)
    eng.shutdown(drain=True, timeout=60.0)
    ctx = {"window_s": time.perf_counter() - t0,
           "counters": harness.delta(eng.stats(), before)}
    for name in ("engine_dry_share", "host_gc_share"):
        assert 0.0 <= _read(name, ctx) <= 100.0, name
