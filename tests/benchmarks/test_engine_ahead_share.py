"""The per-layer reader PR 40 adds (benchmarks/layer_metrics/
engine_ahead_share.py): steps_launched_ahead over steps from the window's
DecodeEngine.stats() deltas, silent where the program has no such counter
(the parent's serial loop) or the window no step."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import harness, manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVING = ["opt13b_chat", "opt13b_agent_prefix", "kimik2_agent_2k",
           "kimilinear_agent_2k"]


def _read(ctx):
    return manifest.load_module("layer_metrics", "engine_ahead_share").read(
        ctx)


@pytest.mark.parametrize("counters,want", [
    ({"steps": 200, "steps_launched_ahead": 190, "ahead_drains": 0}, 95.0),
    ({"steps": 8, "steps_launched_ahead": 0, "ahead_drains": 8}, 0.0),
    ({"steps": 3, "steps_launched_ahead": 3}, 100.0),
])
def test_it_is_the_share_of_steps_launched_ahead(counters, want):
    assert _read({"counters": counters}) == pytest.approx(want)


@pytest.mark.parametrize("ctx", [
    {}, {"counters": None}, {"counters": {}},
    {"counters": {"steps": 0, "steps_launched_ahead": 0}},
    # a program whose loop is serial has no such counter: nothing, not 0
    {"counters": {"steps": 120, "host_sync_ns": 5}},
])
def test_it_reads_nothing_where_there_is_nothing_to_read(ctx):
    assert _read(ctx) is None


def test_its_entry_names_the_four_serving_cells_and_the_gap():
    (m,) = [m for m in MANIFEST["per_layer"]
            if m["name"] == "engine_ahead_share"]
    assert m == {"name": "engine_ahead_share", "unit": "%",
                 "better": "higher", "source": "program_counter",
                 "layer": "entry and scheduling", "moves": "gap_p95_ms",
                 "workloads": SERVING}
    assert MANIFEST["per_layer"][-1] is m       # appended, nothing moved
    for cell in SERVING:
        names = [x["name"] for x in manifest.cell(MANIFEST, cell)["per_layer"]]
        assert "engine_ahead_share" in names


def test_it_reads_the_deltas_of_a_started_engines_stats():
    """The serve driver's own arithmetic (harness.delta of two stats())
    over a tiny engine driven by its thread: most steps are launched
    ahead; driven by run(), none."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_paged_decode import CFG, _decoder, _model
    from paddle_tpu.serving import DecodeEngine
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, CFG["vocab_size"], (5,)).astype("int32")
               for _ in range(2)]
    shares = {}
    for started in (True, False):
        eng = DecodeEngine(_decoder(_model()), num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"])
        before = eng.stats()
        reqs = [eng.submit(p, 12) for p in prompts]
        if started:
            eng.start()
            for r in reqs:
                r.get(timeout=300)
            eng.shutdown(drain=True, timeout=60.0)
        else:
            eng.run(timeout=300)
        shares[started] = _read(
            {"counters": harness.delta(eng.stats(), before)})
    assert shares[False] == 0.0
    assert 50.0 < shares[True] <= 100.0
