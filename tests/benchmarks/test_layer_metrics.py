"""The per-layer readers that PR 31 adds (benchmarks/layer_metrics/), each
on a hand-made ``ctx`` with known deltas; ``None`` (never 0) where its
counters are missing, as on a parent commit that lacks them; and (PR 33)
the kernels' exact names: a roofline reads the same with and without a
second Mosaic kernel in its step."""

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


# ------------------------------------------------- kernels by their own names
MS = 1_000_000
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.mark.parametrize("op,paged,flash", [
    ("tpu_custom_call:paged_window_attention", True, False),
    ("tpu_custom_call:flash_fwd", False, True),
    ("tpu_custom_call:flash_bwd_dq", False, True),
    ("tpu_custom_call:flash_bwd_dkv", False, True),
    ("tpu_custom_call:jvp_flash_fwd_", False, True),
    ("tpu_custom_call:jvp_flash_bwd_dq_", False, True),
    ("tpu_custom_call:jvp_flash_bwd_dkv_", False, True),
    ("tpu_custom_call:grouped_expert_matmul", False, False),
    ("tpu_custom_call:decode_attention", False, False),
    ("tpu_custom_call:paged_window_attention_v2", False, False),
    ("tpu_custom_call:jvp_flash_fwd", False, False),
    ("tpu_custom_call:lstm_fwd", False, False),
    ("fusion:paged_window_attention", False, False),
    ("paged_window_attention", False, False),
    ("tpu_custom_call:", False, False),
])
def test_kernel_matches_are_exact(op, paged, flash):
    from benchmarks.lib import names
    assert names.is_paged_attn_kernel(op) is paged
    assert names.is_flash_kernel(op) is flash


def _step_trace(kernels, other=None):
    """Ten executions of a step program, 10 ms each: 2 ms of fusions, then
    ``kernels`` (name, ms) back to back, then ``other`` (a second Mosaic
    kernel that is none of the benchmark's) if given."""
    from benchmarks.lib import trace
    ops, mods = [], []
    for i in range(10):
        t = i * 12 * MS
        mods.append((t, 10 * MS, "jit__step_impl(1)"))
        mods.append((t, 10 * MS, "jit_step(1)"))
        ops.append((t, 2 * MS, "fusion:fusion"))
        at = t + 2 * MS
        for name, ms in kernels + ([other] if other else []):
            ops.append((at, ms * MS, name))
            at += ms * MS
    return trace.Trace([{"ops": ops, "modules": mods}])


@pytest.mark.parametrize("metric,kernels,more", [
    ("paged_attn_roofline",
     [("tpu_custom_call:paged_window_attention", 4)],
     {"traced_counters": {"cache_tokens_read": 30_000}, "kv_itemsize": 2}),
    ("flash_roofline",
     [("tpu_custom_call:jvp_flash_fwd_", 1),
      ("tpu_custom_call:jvp_flash_bwd_dq_", 2),
      ("tpu_custom_call:jvp_flash_bwd_dkv_", 1)],
     {"rows_per_chip": 4, "seq_len": 2048}),
])
def test_a_second_custom_call_in_the_step_is_not_counted(metric, kernels,
                                                         more):
    """The first configuration that puts a second Mosaic kernel into a
    step (a grouped expert product, say) must not have it read as
    attention: each roofline reads the same with and without it."""
    opt = manifest.load_module("models", "opt")
    cfg = {"hidden_size": 2048, "ffn_dim": 8192, "num_hidden_layers": 24,
           "vocab_size": 50272, "max_position_embeddings": 2048}
    base = dict(EMPTY, config=cfg, model=opt, peaks=PEAKS, **more)
    reader = manifest.load_module("layer_metrics", metric)
    alone = reader.read(dict(base, trace=_step_trace(kernels)))
    beside = reader.read(dict(base, trace=_step_trace(
        kernels, ("tpu_custom_call:grouped_expert_matmul", 3))))
    assert alone is not None and alone == pytest.approx(beside)
    if metric == "paged_attn_roofline":     # 30,000 tokens x 196,608 B
        assert alone == pytest.approx(
            100.0 * 30_000 * 196_608 / 1e11 / 0.040)
    else:                                   # 4 ms of kernels a step
        least = 4 * 3 * 2 * 2048 * 2048 * 2048 * 24 / 1e12
        assert alone == pytest.approx(100.0 * least / 0.004)
    # a step whose only custom call is another kernel reads nothing
    none = reader.read(dict(base, trace=_step_trace(
        [], ("tpu_custom_call:grouped_expert_matmul", 3))))
    assert none is None
