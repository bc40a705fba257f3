"""The Kimi-Linear configuration's files (benchmarks/models/kimi_linear.py,
its readers, its limits) on the CPU: the counts against a hand count at
the published widths, the two new readers on known numbers and a hand-made
trace, the fp8 control held to the cell's own limit at the tiny size.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import check, manifest  # noqa: E402

MANIFEST = manifest.load_manifest()
CELL = manifest.cell(MANIFEST, "kimilinear_agent_2k")
CFG, MODEL, REF = CELL["config"], CELL["model"], CELL["reference"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_flop_and_byte_functions_match_a_hand_count():
    """ISSUE 39's own count, parameters x 2 bytes: a KDA layer's matrices
    39.46 M (39.51 M with its convolutions), an MLA layer's 29.11 M, the
    dense FFN 63.70 M, one expert 7.078 M, the router 0.590 M, an eighth
    of the head 47.19 M; 2.09 B in all, 4.19 GB."""
    kda = (4 * 2304 * 4096 + 2 * 2304 * 128 + 2 * 128 * 4096 + 2304 * 32)
    assert MODEL.kda_params(CFG) == kda == 39_460_864
    small = 3 * 4 * 4096 + 32 + 4096 + 128
    assert MODEL.kda_small_params(CFG) == small
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    assert MODEL.attn_params(CFG) == mla == 29_114_368
    expert = 3 * 2304 * 1024
    assert MODEL.expert_params(CFG) == expert == 7_077_888
    assert MODEL.kda_layers(CFG) == (0, 1, 2, 4, 5, 6)
    per_token = (6 * kda + 2 * mla + 3 * 2304 * 9216
                 + 7 * (2304 * 256 + expert) + 2304 * 20480)
    assert MODEL.dense_params_per_token(CFG) == per_token
    total = (per_token + 20480 * 2304 + 7 * (32 * expert + 256)
             + 8 * 2 * 2304 + 2 * 512 + 2304 + 6 * small)
    assert MODEL.total_params(CFG) == total
    assert 4.18e9 < 2 * total < 4.20e9                  # 4.19 GB in bf16
    import jax
    shapes = jax.eval_shape(lambda: REF.init_params(0, CFG))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == total
    # the cache: a latent row a token over the 2 MLA layers, a float32
    # state a slot over the 6 KDA layers
    assert MODEL.kv_bytes_per_token(CFG, 2) == 576 * 2 * 2 == 2304
    assert MODEL.state_bytes_per_slot(CFG) == 6 * 32 * 128 * 128 * 4 \
        == 12_582_912
    c = {"active_slot_steps": 900, "tokens_fed": 1000,
         "cache_tokens_read": 2_000_000, "expert_assignments_held": 250,
         "state_rows_stepped": 900}
    attn_flops = 2 * 32 * (576 + 512)
    state_flops = 6 * 32 * 128 * 128
    assert MODEL.serve_flops(CFG, c) == pytest.approx(
        1000 * (2.0 * per_token + 6 * state_flops) + 250 * 2.0 * expert
        + 2_000_000 * 2 * attn_flops)
    # tokens fed are the engine's own count of rows, lanes too
    assert MODEL.serve_flops(CFG, dict(c, tokens_fed=900)) < \
        MODEL.serve_flops(CFG, c)
    least, bound = MODEL.paged_attn_least_s(CFG, c, 2, PEAKS)
    by_bytes = 2_000_000 * 2304 / 819e9
    by_flops = 2_000_000 * 2 * attn_flops / 197e12
    assert by_flops / by_bytes == pytest.approx(0.25, abs=0.01)
    assert (least, bound) == (pytest.approx(by_bytes), "hbm_bytes")
    # the state kernel: a row read and written a slot-step, bytes bind
    # (its 3 passes are 0.6 GFLOP a slot-step against 25 MB)
    assert MODEL.state_least_s(CFG, c, PEAKS) == pytest.approx(
        900 * 2 * 12_582_912 / 819e9)
    assert 900 * 6 * state_flops / 197e12 < \
        MODEL.state_least_s(CFG, c, PEAKS) / 5
    assert MODEL.held_experts(CFG) == 32


def test_the_file_states_the_deployment_and_what_reaches_the_reference():
    z = REF.sizes(check.cfg_of(check.cfg_key(CFG)))      # no nested dict
    assert (z["held"], z["E"], z["lo"], z["k"]) == (32, 256, 0, 8)
    assert (z["Hk"], z["dk"], z["conv"], z["r"]) == (32, 128, 4, 128)
    assert sorted(z["kda"] & set(range(8))) == [0, 1, 2, 4, 5, 6]
    lin = CFG["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            lin["kda_layers"], lin["full_attn_layers"]) == (
        CFG["kda_num_heads"], CFG["kda_head_dim"],
        CFG["kda_short_conv_kernel_size"], CFG["kda_layers"],
        CFG["full_attn_layers"])
    assert CFG["q_lora_rank"] is None and CFG["mla_use_nope"] is True
    for key in ("kda_gate_rank", "kda_scalars", "kda_state_dtype",
                "kda_a_log_dt_bias", "kda_output_gate_bias"):
        assert key in CFG["assumed"], key
    dep = CFG["deployment"]
    assert (dep["num_slots"], dep["max_seq_len"], dep["page_size"]) == \
        (128, 4096, 32)
    mix = CELL["traffic"]
    assert (mix["clients"], mix["history_len"]) == (128, 2048)
    assert (mix["suffix_len"]["min"], mix["suffix_len"]["max"],
            mix["output_len"]["min"], mix["output_len"]["max"]) == \
        (32, 64, 256, 512)
    # every client's history keeps a snapshot, with rows to spare
    assert dep["state_snapshots"] > mix["clients"]
    # the histories' pages and every live turn's fit the pool
    longest = mix["history_len"] + mix["suffix_len"]["max"] + \
        mix["output_len"]["max"]
    assert longest <= CELL["limits"]["pad_to"] <= dep["max_seq_len"]
    assert mix["clients"] * -(-longest // dep["page_size"]) < dep["num_pages"]
    tiny = MODEL.tiny()
    assert set(tiny) - {"name", "deployment"} <= set(CFG)


def test_the_parent_without_the_kernel_fails_at_import(tmp_path):
    """benchmarks/models/kimi_linear.py in a checkout whose program has no
    ops/pallas_kda.py (the parent commit) raises ImportError when it is
    loaded: the cell stops at once, not after the weights are made."""
    import shutil
    home = tmp_path / "benchmarks" / "models"
    home.mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, "benchmarks", "models",
                             "kimi_linear.py"), home)
    with pytest.raises(ImportError, match="recurrent"):
        manifest.load_module("models", "kimi_linear",
                             str(tmp_path / "benchmarks"))


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def seconds_matching(self, pred):
        return sum(s for op, s in self.seconds.items() if pred(op))


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def test_kda_state_roofline_reads_its_kernel_alone():
    c = {"state_rows_stepped": 10_000}
    ctx = {"trace": _Trace({"tpu_custom_call:kda_state_update": 0.5,
                            "tpu_custom_call:paged_latent_attention": 5.0,
                            "tpu_custom_call:kda_state_update_2": 7.0,
                            "fusion:fusion": 1.0}),
           "traced_counters": c, "model": MODEL, "config": CFG,
           "peaks": PEAKS}
    want = 100.0 * (10_000 * 2 * 12_582_912 / 819e9) / 0.5
    assert _reader("kda_state_roofline").read(ctx) == pytest.approx(want)
    assert 0 < want < 100
    # a parent without the kernel or the counter, a trace without its
    # events, no trace at all: nothing, and nothing raised
    read = _reader("kda_state_roofline").read
    assert read(dict(ctx, trace=_Trace(
        {"tpu_custom_call:paged_latent_attention": 5.0}))) is None
    assert read(dict(ctx, traced_counters={"steps": 3})) is None
    assert read(dict(ctx, traced_counters=None)) is None
    assert read({"counters": {}}) is None


def test_engine_snapshot_hit_share_on_known_deltas():
    read = _reader("engine_snapshot_hit_share").read
    assert read({"counters": {"snapshot_attach_tokens": 6144,
                              "snapshot_miss_tokens": 2048}}) == \
        pytest.approx(75.0)
    assert read({"counters": {"snapshot_attach_tokens": 64,
                              "snapshot_miss_tokens": 0}}) == 100.0
    # an engine without the counters (the parent), or a window in which
    # the trie matched nothing: nothing
    assert read({"counters": {"steps": 9}}) is None
    assert read({"counters": {"snapshot_attach_tokens": 0,
                              "snapshot_miss_tokens": 0}}) is None
    assert read({}) is None


def test_the_new_readers_are_the_cells_and_the_old_ones_read_it_too():
    names = {m["name"] for m in CELL["per_layer"]}
    assert {"kda_state_roofline", "engine_snapshot_hit_share",
            "latent_attn_roofline", "serve_mfu", "expert_hit_share",
            "engine_prefix_hit_share"} <= names
    assert {m["name"] for m in CELL["end_to_end"]} == {
        "out_tok_s", "ttft_p95_ms", "gap_p95_ms", "setup_s"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in ("kda_state_roofline", "engine_snapshot_hit_share"):
            assert m["workloads"] == ["kimilinear_agent_2k"]


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


def test_the_weights_init_keeps_the_decay_between_nought_and_one():
    """A_log = log U(1, 16) and dt_bias = softplus^-1(exp(U(log .001,
    log .1))): with nothing added to dt_bias the decay exp(g) of every
    channel lies in (0.2, 0.9999)."""
    import jax
    import jax.numpy as jnp
    w = (np.uint32(3), np.uint32(0))
    a_log = np.asarray(REF.make_leaf(w, 1, "l0.a_log", (64,), jnp.float32,
                                     0.02))
    dt_bias = np.asarray(REF.make_leaf(w, 2, "l0.dt_bias", (64, 32),
                                       jnp.float32, 0.02))
    assert 0.0 <= a_log.min() and a_log.max() <= np.log(16.0)
    dt = np.asarray(jax.nn.softplus(jnp.asarray(dt_bias)))
    assert 0.00099 < dt.min() and dt.max() < 0.1001
    decay = np.exp(-np.exp(a_log)[:, None] * dt)
    assert 0.2 < decay.min() and decay.max() < 0.9999
    assert REF.leaf_kind("l3.router_bias") == "" and \
        REF.leaf_kind("l3.kv_norm_g") == "_g"
