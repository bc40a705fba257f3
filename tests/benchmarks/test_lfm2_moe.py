"""The LFM2 configuration's files (benchmarks/models/lfm2_moe.py, its
reader, its limits) on the CPU: the counts against a hand count at the
published widths, what the file states, the new reader on a hand-made
trace, the fp8 control held to the cell's own limit at the tiny size, and
two faults of the state this cell's `correct` has to catch.
"""

import contextlib
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
CELL = manifest.cell(MANIFEST, "lfm2_doc_8k")
CFG, MODEL, REF = CELL["config"], CELL["model"], CELL["reference"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_flop_and_byte_functions_match_a_hand_count():
    """ISSUE 41's own count, parameters x 2 bytes: a conv layer's matrices
    16.78 M, an attention layer's 10.49 M, one expert 9.44 M, the dense
    FFN 72.35 M, the router 0.13 M, the table 134.2 M (and as much again
    for the head, which this file unties): 3.90 B in all, 7.79 GB."""
    conv = 2048 * 6144 + 2048 * 2048
    assert MODEL.conv_params(CFG) == conv == 16_777_216
    attn = 2048 * (2048 + 512 + 512) + 2048 * 2048
    assert MODEL.attn_params(CFG) == attn == 10_485_760
    expert = 3 * 2048 * 1536
    assert MODEL.expert_params(CFG) == expert == 9_437_184
    assert MODEL.conv_layers(CFG) == tuple(
        i for i in range(40) if i % 4 != 2)
    per_token = (30 * conv + 10 * attn + 2 * 3 * 2048 * 11776
                 + 38 * 2048 * 64 + 2048 * 65536)
    assert MODEL.dense_params_per_token(CFG) == per_token
    total = (per_token + 65536 * 2048 + 38 * (8 * expert + 64)
             + 40 * 2 * 2048 + 10 * 2 * 64 + 2048 + 30 * 3 * 2048)
    assert MODEL.total_params(CFG) == total
    assert 7.78e9 < 2 * total < 7.80e9                  # 7.79 GB in bf16
    # tied, as the family has it, the chip would hold the issue's 3.76 B
    tied = MODEL.total_params(dict(CFG, tie_word_embeddings=True))
    assert total - tied == 65536 * 2048 and 3.75e9 < tied < 3.77e9
    import jax
    shapes = jax.eval_shape(lambda: REF.init_params(0, CFG))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == total
    # the cache: K and V a token over the 10 attention layers, two float32
    # tails a sequence over the 30 conv layers
    assert MODEL.kv_bytes_per_token(CFG, 2) == 10 * 2 * 8 * 64 * 2 == 20_480
    assert MODEL.state_bytes_per_slot(CFG) == 30 * 2 * 2048 * 4 == 491_520
    c = {"active_slot_steps": 900, "tokens_fed": 1000,
         "cache_tokens_read": 2_000_000, "expert_assignments_held": 250,
         "state_rows_stepped": 900}
    attn_flops = 4 * 32 * 64
    assert MODEL.serve_flops(CFG, c) == pytest.approx(
        1000 * (2.0 * per_token + 30 * 2 * 3 * 2048) + 250 * 2.0 * expert
        + 2_000_000 * 10 * attn_flops)
    assert MODEL.serve_flops(CFG, dict(c, tokens_fed=900)) < \
        MODEL.serve_flops(CFG, c)
    least, bound = MODEL.paged_attn_least_s(CFG, c, 2, PEAKS)
    by_bytes = 2_000_000 * 20_480 / 819e9
    by_flops = 2_000_000 * 10 * attn_flops / 197e12
    assert by_flops / by_bytes == pytest.approx(0.0166, abs=0.001)
    assert (least, bound) == (pytest.approx(by_bytes), "hbm_bytes")
    assert MODEL.state_least_s(CFG, c, PEAKS) == pytest.approx(
        900 * 2 * 491_520 / 819e9)
    assert MODEL.held_experts(CFG) == 8


def test_the_file_states_the_deployment_and_what_reaches_the_reference():
    z = REF.sizes(check.cfg_of(check.cfg_key(CFG)))      # no nested dict
    assert (z["held"], z["E"], z["lo"], z["k"]) == (8, 64, 0, 4)
    assert (z["H"], z["G"], z["dh"], z["K"], z["dense"]) == (32, 8, 64, 3, 2)
    assert (z["route_eps"], z["route_scale"], z["theta"]) == (1e-6, 1.0, 1e6)
    assert len(z["conv"]) == 30 and not z["tied"]
    assert CFG["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert CFG["rope_theta"] == CFG["rope_parameters"]["rope_theta"]
    assert CFG["reduced"] == ["num_experts"]
    assert CFG["published"] == {"num_experts": 64}
    assert CFG["num_experts"] * CFG["ep_ranks"] == 64
    for key in ("tie_word_embeddings", "conv_init_std", "rotate_half",
                "head_dim", "norm_topk_eps", "conv_tails_dtype",
                "max_seq_len", "weights"):
        assert key in CFG["assumed"], key
    dep = CFG["deployment"]
    assert (dep["num_slots"], dep["max_seq_len"], dep["page_size"],
            dep["num_pages"], dep["state_snapshots"]) == \
        (32, 9216, 32, 9216, 64)
    mix = CELL["traffic"]
    assert (mix["clients"], mix["history_len"], mix["turns_per_client"],
            mix["ramp_s"], mix["poll_s"], mix["trace_seconds"]) == \
        (32, 8192, 32, 16.0, 0.002, 3.0)
    assert (mix["suffix_len"]["min"], mix["suffix_len"]["max"],
            mix["output_len"]["min"], mix["output_len"]["max"]) == \
        (64, 128, 128, 256)
    # every client's history keeps a snapshot, with rows to spare
    assert dep["state_snapshots"] > mix["clients"]
    # the histories' pages and every live turn's fit the pool
    longest = mix["history_len"] + mix["suffix_len"]["max"] + \
        mix["output_len"]["max"]
    assert longest <= CELL["limits"]["pad_to"] <= dep["max_seq_len"]
    assert CELL["limits"]["pad_to"] % REF.BLOCK_ROWS == 0
    assert mix["clients"] * -(-longest // dep["page_size"]) < dep["num_pages"]
    assert CELL["limits"]["sample_requests"] == 6
    tiny = MODEL.tiny()
    assert set(tiny) - {"name", "deployment"} <= set(CFG)
    assert tiny["num_experts"] < tiny["num_experts"] * tiny["ep_ranks"]
    assert {"conv", "full_attention"} == set(tiny["layer_types"])


def test_the_parent_without_the_description_fails_at_import(tmp_path,
                                                            monkeypatch):
    """benchmarks/models/lfm2_moe.py against a program whose
    models/block.py has no ShortConvBlock (the parent commit) raises
    ImportError when it is loaded: the cell stops at once, not after the
    weights are made."""
    from paddle_tpu.models import block
    monkeypatch.delattr(block, "ShortConvBlock")
    with pytest.raises(ImportError, match="gated short convolutions"):
        manifest.load_module("models", "lfm2_moe")


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def seconds_matching(self, pred):
        return sum(s for op, s in self.seconds.items() if pred(op))


def _reader(name):
    return manifest.load_module("layer_metrics", name)


@pytest.mark.parametrize("others", [
    {}, {"tpu_custom_call:paged_window_attention": 5.0,
         "tpu_custom_call:kda_short_conv": 3.0,
         "tpu_custom_call:short_conv_2": 7.0, "fusion:fusion": 1.0}],
    ids=["alone", "beside-other-custom-calls"])
def test_short_conv_roofline_reads_its_kernel_alone(others):
    c = {"state_rows_stepped": 10_000}
    ctx = {"trace": _Trace({"tpu_custom_call:short_conv": 0.5, **others}),
           "traced_counters": c, "model": MODEL, "config": CFG,
           "peaks": PEAKS}
    want = 100.0 * (10_000 * 2 * 491_520 / 819e9) / 0.5
    read = _reader("short_conv_roofline").read
    assert read(ctx) == pytest.approx(want)
    assert 0 < want < 100
    # a parent without the kernel or the counter, a trace without its
    # events, no trace at all: nothing, and nothing raised
    assert read(dict(ctx, trace=_Trace(others))) is None
    assert read(dict(ctx, traced_counters={"steps": 3})) is None
    assert read(dict(ctx, traced_counters=None)) is None
    assert read({"counters": {}}) is None


def test_the_new_reader_is_the_cells_and_the_old_ones_read_it_too():
    names = {m["name"] for m in CELL["per_layer"]}
    assert names == {
        "engine_slot_util", "engine_prefill_share", "decode_step_dev_ms",
        "serve_mfu", "paged_attn_roofline", "device_idle_share.serve",
        "engine_host_ms_per_step", "engine_prefix_hit_share",
        "engine_preempt_share", "expert_hit_share",
        "engine_snapshot_hit_share", "engine_ahead_share",
        "short_conv_roofline"}
    assert {m["name"] for m in CELL["end_to_end"]} == {
        "out_tok_s", "ttft_p95_ms", "gap_p95_ms", "setup_s"}
    m, = [m for m in MANIFEST["per_layer"]
          if m["name"] == "short_conv_roofline"]
    assert m["workloads"] == ["lfm2_doc_8k"] and m["layer"] == "kernels"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert len(MANIFEST["workloads"]) == 7


def _served_like(cfg, n=3, seed=5):
    """Greedy sequences of the float32 reference itself."""
    import jax
    import jax.numpy as jnp
    params = jax.jit(lambda: REF.init_params(seed, cfg))()
    rng = np.random.default_rng(0)
    fwd = jax.jit(lambda p, s: REF.forward(p, s, cfg))
    out = []
    for _ in range(n):
        prompt = rng.integers(0, cfg["vocab_size"], 12).astype(np.int32)
        seq = list(prompt)
        for _ in range(8):
            pad = np.pad(np.asarray(seq, np.int32), (0, 32 - len(seq)))
            logits = fwd(params, jnp.asarray(pad))
            seq.append(int(np.argmax(np.asarray(logits[len(seq) - 1]))))
        out.append((prompt, seq[len(prompt):]))
    return out


def test_the_fp8_control_fails_the_cells_limit_at_the_tiny_size():
    """The reference in fp8 put in the program's place reads a gap over
    the cell's own limit; the reference against itself reads none."""
    cfg = MODEL.tiny(deployment=False)
    sample = _served_like(cfg)
    sound = check.served_logit_gap(REF, cfg, 5, sample, 32)
    control = check.served_logit_gap(REF, cfg, 5, sample, 32,
                                     rounding="fp8")
    limit = CELL["limits"]["served_logit_gap"]["limit"]
    assert sound["widest_gap"] == 0.0 and sound["tokens"] == 24
    assert check.decide({"served_logit_gap": (sound["widest_gap"], limit)})
    assert not check.decide(
        {"served_logit_gap": (control["widest_gap"], limit)})
    assert limit < 0.5497           # tests/benchmarks/test_correct.py's


def test_the_reference_in_blocks_is_the_reference_whole():
    """Attention and the head in blocks of ``BLOCK_ROWS`` query rows (512
    tokens: two blocks) against one block of all rows (511 tokens, which
    no block divides, padded by one): the same logits."""
    import jax
    import jax.numpy as jnp
    cfg = MODEL.tiny(deployment=False)
    params = jax.jit(lambda: REF.init_params(3, cfg))()
    seq = np.random.default_rng(2).integers(0, 64, 512).astype(np.int32)
    fwd = jax.jit(lambda p, s: REF.forward(p, s, cfg))
    blocks = np.asarray(fwd(params, jnp.asarray(seq)))
    whole = np.asarray(fwd(params, jnp.asarray(seq[:511])))
    np.testing.assert_allclose(blocks[:511], whole, atol=2e-4)


# ------------------------------------------------ faults of the state it keeps
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _tiny_cell():
    cell = manifest.cell(MANIFEST, "lfm2_doc_8k")
    cell["config"] = cell["model"].tiny()
    cell["traffic"].update({
        "clients": 4, "history_len": 16, "turns_per_client": 8,
        "ramp_s": 0.5, "suffix_len": {"dist": "uniform", "min": 3, "max": 6},
        "output_len": {"dist": "uniform", "min": 4, "max": 8}})
    cell["limits"]["pad_to"] = 64
    return cell


def _drive(cell, capsys) -> dict:
    rc = run.run_cell(cell, 2 ** 31 + 41, 1.5, False, DEVICE,
                      {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
                      time.monotonic())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def fresh_programs():
    """A fault that is compiled into the step must not reach the next
    test through the in-process memo of executables, which is keyed by
    shapes and plan (paddle_tpu/artifacts) and dropped a module."""
    from paddle_tpu.artifacts import EXECUTABLES
    EXECUTABLES.clear()
    yield
    EXECUTABLES.clear()


def test_a_frozen_state_is_not_correct(capsys, monkeypatch, fresh_programs):
    """The convolution's tails never written (every step and every
    snapshot reads zeros): the served tokens fall under the reference's
    best by more than the cell's limit."""
    from paddle_tpu.ops import pallas_kda as kda_ops
    orig = kda_ops.short_conv

    def frozen(tails, *a, **kw):
        y, _ = orig(tails, *a, **kw)
        return y, tails

    monkeypatch.setattr(kda_ops, "short_conv", frozen)
    res = _drive(_tiny_cell(), capsys)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_snapshot_of_another_sequence_is_not_correct(capsys, monkeypatch):
    """Every admission given the tails of the slot's last tenant in place
    of its own history's snapshot (the row copy left out)."""
    from paddle_tpu.models.decode import PagedDecoder
    monkeypatch.setattr(PagedDecoder, "copy_state",
                        lambda self, k, v, src, dst: (k, v))
    res = _drive(_tiny_cell(), capsys)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


# ------------------------------------------- the tool that looks for a stall
def test_stamps_tell_a_pause_of_the_process_from_one_of_the_machine(
        capsys, tmp_path):
    """benchmarks/tools/stamps.py around a tiny run: the window's steps
    are stamped; a pause of this process alone (the interpreter held for
    ~0.15 s inside a plan) makes a long period that the heartbeat sees and
    the witness process does not; the run itself stays correct and the
    patches are taken out again."""
    from benchmarks.tools import stamps
    from paddle_tpu.serving import engine as E
    was = E.DecodeEngine._phase
    held = []

    t = time.perf_counter()
    sum(range(1_000_000))
    n = int(0.15 / (time.perf_counter() - t) * 1_000_000)

    def hold(counter):                  # once, well inside the window
        if counter == "host_plan_ns" and not held and \
                "open" in st.mark and \
                time.perf_counter_ns() - st.mark["open"] > 400_000_000:
            held.append(1)
            sum(range(n))               # one call: nothing else runs

    st = stamps.Stamps(str(tmp_path / "witness.txt")).install()
    inner = E.DecodeEngine._phase

    @contextlib.contextmanager
    def phase(eng, span, counter=None):
        with inner(eng, span, counter):
            hold(counter)
            yield

    E.DecodeEngine._phase = phase
    try:
        res = _drive(_tiny_cell(), capsys)
    finally:
        E.DecodeEngine._phase = inner
        st.remove()
    assert E.DecodeEngine._phase is was
    assert res["correct"] is True and held, res["checks"]["served_logit_gap"]
    line = st.summary(1.5)
    assert line["steps"] > 20 and line["period_p50_ms"] < 60
    long_ = max(line["long"],
                key=lambda x: x["by_phase_ms"].get("serving/plan", 0.0))
    assert long_["period_ms"] > 100
    assert long_["by_phase_ms"]["serving/plan"] > 100
    assert max(long_["heartbeat_late_ms"]) > 60
    assert all(x < 60 for x in long_["witness_late_ms"])
    assert line["lost_s"] > 0.05
    assert st.raw()["phases"]
