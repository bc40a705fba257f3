"""What decides ``correct`` has to fail what it is there to catch.

These tests skip the harness's look for a chip and drive the rest of a
run (``run.run_cell``) at a tiny size on the CPU, with the cell's own
limits: a sound run comes out correct; with the timed path broken
underneath - a token altered where it is produced, a step that returns
its state unchanged, half of the batch left out - ``correct`` comes out
false. The control (the reference computed in fp8, put in the program's
place) is kept here at a size a test run can hold; its readings at the
cells' own sizes on the chip are in PERF.md.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import check, manifest  # noqa: E402

MANIFEST = manifest.load_manifest()
CELL = manifest.cell        # the readings' test stands tiny_cell in its place
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(workload: str) -> dict:
    """The cell with its own architecture's tiny configuration
    (``cell["model"].tiny()``) and its mix cut to a CPU's size."""
    cell = CELL(MANIFEST, workload)
    cell["config"] = cell["model"].tiny()
    mix = cell["traffic"]
    if mix["kind"] == "open_loop":
        mix["arrivals"] = {"rate_per_s": 6.0}
        mix["ramp_s"] = 0.5
        mix["prompt_len"] = {"dist": "lognormal", "median": 8, "sigma": 0.6,
                             "min": 2, "max": 24}
        mix["output_len"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                             "min": 2, "max": 16}
        cell["limits"]["pad_to"] = 64
    elif mix["kind"] == "closed_loop":
        mix.update({"clients": 4, "history_len": 16, "turns_per_client": 8,
                    "ramp_s": 0.5,
                    "suffix_len": {"dist": "uniform", "min": 3, "max": 6},
                    "output_len": {"dist": "uniform", "min": 4, "max": 8}})
        cell["limits"]["pad_to"] = 64
    else:
        mix.update({"rows_per_chip_step": 4, "seq_len": 32,
                    "compute_dtype": "float32"})
        cell["config"]["max_position_embeddings"] = 32
        cell["chips"] = 1
    return cell


def drive(cell, capsys, seed=2 ** 31 + 99, seconds=1.5) -> dict:
    rc = run.run_cell(cell, seed, seconds, False, DEVICE, PEAKS,
                      time.monotonic())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _driver(w) -> str:
    return manifest.load_json("traffic", w["traffic"] + ".json")["driver"]


SERVE = [w["name"] for w in MANIFEST["workloads"] if _driver(w) == "serve"]
TRAIN = [w["name"] for w in MANIFEST["workloads"]
         if _driver(w) == "train" and w["chips"] == 1]


@pytest.mark.parametrize("workload", SERVE + TRAIN)
def test_a_sound_run_is_correct(workload, capsys):
    res = drive(tiny_cell(workload), capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]
    for m in res["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("workload", SERVE)
def test_an_altered_token_is_not_correct(workload, capsys, monkeypatch):
    """A token altered where it is produced: the step's output."""
    from paddle_tpu.models.decode import PagedDecoder
    orig = PagedDecoder.step
    calls = {"n": 0}
    cell = tiny_cell(workload)
    vocab = int(cell["config"]["vocab_size"])

    def bad_step(self, *a, **kw):
        nxt, k, v = orig(self, *a, **kw)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            nxt = (nxt + 1) % vocab
        return nxt, k, v

    monkeypatch.setattr(PagedDecoder, "step", bad_step)
    res = drive(cell, capsys)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", TRAIN)
def test_a_step_that_keeps_its_state_is_not_correct(workload, capsys,
                                                    monkeypatch):
    import paddle_tpu as paddle
    monkeypatch.setattr(paddle.optimizer.Adam, "update",
                        lambda self, params, grads, state, bs, **kw:
                        (params, state))
    res = drive(tiny_cell(workload), capsys)
    assert res["correct"] is False
    assert res["checks"]["grad_norm_gap_worst_leaf"]["value"] == \
        pytest.approx(1.0, abs=1e-3)
    assert res["checks"]["change_norm_gap_worst_leaf"]["value"] == \
        pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("workload", TRAIN)
def test_half_of_the_batch_left_out_is_not_correct(workload, capsys,
                                                   monkeypatch):
    """The program steps on the first half of each batch's rows, the mean
    taken over those."""
    cell = tiny_cell(workload)
    orig = cell["model"].rows_of
    monkeypatch.setattr(cell["model"], "rows_of",
                        lambda b: orig(b)[:b.shape[0] // 2])
    res = drive(cell, capsys)
    assert res["correct"] is False
    g = res["checks"]["grad_norm_gap_worst_leaf"]
    assert g["value"] > g["limit"]


# ------------------------------------------------------------------ control
@pytest.mark.parametrize("workload", SERVE[:1] + TRAIN)
def test_the_control_put_through_the_run_is_not_correct(workload, capsys):
    """The reference in fp8 in the program's place, judged by the same
    decision and the cell's own limits as a run (the driver's control
    path, which benchmarks/tools/readings.py drives on the chip)."""
    from benchmarks.lib import harness
    cell = tiny_cell(workload)
    driver = manifest.load_module("drivers", cell["traffic"]["driver"])
    env = {"compiles": harness.CompileCounter(), "on_chip": False,
           "peaks": PEAKS, "t_start": time.monotonic(), "control": "fp8"}
    out = driver.run(cell, 2 ** 31 + 5, 1.5, False, env)
    assert out["correct"] is True
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    control = [l for l in lines if l.get("phase") in ("control",
                                                      "control_fp8")]
    assert len(control) == 1
    assert control[0].get("control_correct", control[0].get("correct")) \
        is False
    if workload in TRAIN:       # each fault the cell can have fails it too
        for fault in ("fault_half_batch", "fault_frozen_state"):
            assert [l["correct"] for l in lines
                    if l.get("phase") == fault] == [False]

def test_readings_tool_reads_the_control_of_a_serving_cell(capsys,
                                                           monkeypatch):
    """benchmarks/tools/readings.py (the chip's tool for the readings that
    limits are set from) on the CPU at the cell's tiny size: per seed a
    reading, and for the first seed the fp8 control's, held to the cell's
    own limit by the run's own decision."""
    from benchmarks.lib import peaks
    from benchmarks.tools import readings
    monkeypatch.setattr(run, "look_for_chips", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": chips, "visible": chips})
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: PEAKS)
    monkeypatch.setattr(manifest, "cell", lambda man, w: tiny_cell(w))
    assert readings.main(["--workload", SERVE[0], "--seconds", "1.0",
                          "--seeds", str(2 ** 31 + 3), "12",
                          "--control-seeds", "1"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    got = [l for l in lines if l.get("phase") == "reading"]
    assert [l["seed"] for l in got] == [2 ** 31 + 3, 12]
    assert all(l["correct"] for l in got)
    control, = [l for l in lines if l.get("phase") == "control"]
    assert control["seed"] == 2 ** 31 + 3 and control["rounding"] == "fp8"
    assert control["control_correct"] is False
    assert control["control_gap"] > control["program_gap"]



def _served_like(ref, cfg, n=4, seed=5):
    """Greedy sequences of the float32 reference itself: what a sound
    program serves."""
    import jax
    import jax.numpy as jnp
    params = jax.jit(lambda: ref.init_params(seed, cfg))()
    rng = np.random.default_rng(0)
    fwd = jax.jit(lambda p, s: ref.forward(p, s, cfg))
    out = []
    for _ in range(n):
        prompt = rng.integers(0, cfg["vocab_size"], 12).astype(np.int32)
        seq = list(prompt)
        for _ in range(10):
            pad = np.pad(np.asarray(seq, np.int32), (0, 32 - len(seq)))
            logits = fwd(params, jnp.asarray(pad))
            seq.append(int(np.argmax(np.asarray(logits[len(seq) - 1]))))
        out.append((prompt, seq[len(prompt):]))
    return out


def test_serving_control_in_fp8_reads_a_gap():
    ref = manifest.load_module("reference", "opt")
    cfg = manifest.load_module("models", "opt").tiny(deployment=False)
    sample = _served_like(ref, cfg)
    sound = check.served_logit_gap(ref, cfg, 5, sample, 32)
    control = check.served_logit_gap(ref, cfg, 5, sample, 32,
                                     rounding="fp8")
    assert sound["widest_gap"] == 0.0
    assert control["widest_gap"] > 0.0
    assert sound["tokens"] == control["tokens"] == 40
    for w in SERVE:             # held to each serving cell's own limit
        limit = manifest.cell(MANIFEST, w)["limits"]["served_logit_gap"][
            "limit"]
        assert check.decide({"served_logit_gap": (0.0, limit)})
        assert not check.decide(
            {"served_logit_gap": (control["widest_gap"], limit)})
    assert not check.decide({"x": (float("nan"), 1.0)})


def test_training_control_in_fp8_and_faults_read_gaps():
    ref = manifest.load_module("reference", "opt")
    cfg = dict(manifest.load_module("models", "opt").tiny(deployment=False),
               max_position_embeddings=32)
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, 64, (4, 33)).astype(np.int32)
               for _ in range(3)]
    want = check.reference_three_steps(ref, cfg, 11, batches, 1e-4)
    again = check.compare_training(
        check.reference_three_steps(ref, cfg, 11, batches, 1e-4), want)
    assert again["grad_norm_gap_worst_leaf"] == 0.0
    fp8 = check.compare_training(check.reference_three_steps(
        ref, cfg, 11, batches, 1e-4, rounding="fp8"), want)
    assert fp8["grad_sketch_gap_rms"] > 0.05            # first order
    assert fp8["grad_norm_gap_worst_leaf"] > 1e-3       # second order
    assert again["grad_sketch_gap_rms"] == 0.0
    half = check.compare_training(check.reference_three_steps(
        ref, cfg, 11, batches, 1e-4, rows=[0, 1]), want)
    assert half["grad_norm_gap_worst_leaf"] > 0.1
    frozen = check.compare_training(check.reference_three_steps(
        ref, cfg, 11, batches, 1e-4, frozen=True), want)
    assert frozen["change_norm_gap_worst_leaf"] == pytest.approx(1.0, abs=1e-3)
    assert frozen["grad_sketch_gap_rms"] == 0.0         # its gradient is sound
