"""The per-layer readers that PR 31 adds (benchmarks/layer_metrics/), each
on a hand-made ``ctx`` with known deltas; ``None`` (never 0) where its
counters are missing, as on a parent commit that lacks them."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import manifest  # noqa: E402

EMPTY = {"trace": None, "traced_counters": None, "counters": {},
         "late_ms": [], "end_to_end": {}, "chips": 1}
#: a window's deltas of DecodeEngine.stats() as harness.delta gives them
WINDOW = {"steps": 600, "host_admit_ns": 300_000_000,
          "host_plan_ns": 600_000_000, "host_dispatch_ns": 900_000_000,
          "host_commit_ns": 1_200_000_000, "host_sync_ns": 40_000_000_000,
          "host_idle_ns": 5, "admitted": 50, "queue_wait_ns": 10_000_000_000,
          "prefix_hit_pages": 750, "prefix_miss_pages": 250,
          "preemptions": 3, "finished": 120}
#: the same window on a parent without this PR's counters
PARENT = {k: v for k, v in WINDOW.items()
          if not k.startswith("host_") and k not in ("admitted",
                                                     "queue_wait_ns")}


def _read(metric, counters, **more):
    ctx = dict(EMPTY, counters=counters, **more)
    return manifest.load_module("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("metric,want", [
    ("engine_host_ms_per_step", 5.0),       # 3e9 ns / 600 steps
    ("engine_queue_wait_ms", 200.0),        # 1e10 ns / 50
    ("engine_prefix_hit_share", 75.0),
    ("engine_preempt_share", 2.5),
])
def test_engine_reader_on_known_deltas(metric, want):
    assert _read(metric, WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "engine_host_ms_per_step", "engine_queue_wait_ms",
    "engine_prefix_hit_share", "engine_preempt_share",
    "train_data_wait_ms"])
def test_reader_finds_nothing_in_an_empty_context(metric):
    assert _read(metric, {}) is None
    assert manifest.load_module("layer_metrics", metric).read(
        {"counters": None}) is None


@pytest.mark.parametrize("metric,reads", [
    ("engine_host_ms_per_step", False), ("engine_queue_wait_ms", False),
    ("engine_prefix_hit_share", True), ("engine_preempt_share", True)])
def test_reader_on_a_parent_without_the_new_counters(metric, reads):
    got = _read(metric, PARENT)
    assert (got is not None) == reads


def test_a_window_without_steps_or_admissions_reads_nothing():
    assert _read("engine_host_ms_per_step", dict(WINDOW, steps=0)) is None
    assert _read("engine_queue_wait_ms", dict(WINDOW, admitted=0)) is None
    assert _read("engine_preempt_share", dict(WINDOW, finished=0)) is None
    assert _read("engine_prefix_hit_share",
                 dict(WINDOW, prefix_hit_pages=0, prefix_miss_pages=0)) is None


def test_train_data_wait_reads_the_process_global_stat_item():
    from paddle_tpu.utils.stats import global_stat
    global_stat.reset()
    train_ctx = {"end_to_end": {"train_tok_s": 30000.0}}
    assert _read("train_data_wait_ms", {}, **train_ctx) is None   # no item
    item = global_stat.get("train/data_wait")
    for dt in (0.001, 0.002, 0.006):
        item.add(dt)
    assert _read("train_data_wait_ms", {}, **train_ctx) == pytest.approx(3.0)
    # a serving cell's context never reads it, whatever the process holds
    assert _read("train_data_wait_ms", WINDOW) is None


def test_each_pair_of_configuration_and_traffic_stands_once():
    """The driver refuses a manifest that gives a pair twice, so the
    four-chip cell has a traffic file of its own."""
    cells = manifest.load_manifest()["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs)), pairs


def test_the_four_chip_mix_is_the_one_chip_mix_unchanged():
    one = manifest.load_json("traffic", "pretrain_4x2048.json")
    four = manifest.load_json("traffic", "pretrain_4x2048_dp4.json")
    one.pop("note"), four.pop("note")
    assert four == one
