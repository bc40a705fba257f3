"""The Kimi-K2 configuration's files (benchmarks/models/kimi_k2.py, its
readers, its limits) on the CPU: the counts against a hand count at the
published widths, the two new readers on known numbers, the fp8 control
held to the cell's own limit at the tiny size.
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
CELL = manifest.cell(MANIFEST, "kimik2_agent_2k")
CFG, MODEL, REF = CELL["config"], CELL["model"], CELL["reference"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_flop_and_byte_functions_match_a_hand_count():
    """ISSUE 35's own count, parameters x 2 bytes: attention 101.12 M a
    layer, one expert 44.04 M, the dense FFN 396.4 M, the router 2.75 M,
    an eighth of the head 146.8 M."""
    attn = (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
            + 8192 * 7168)
    assert MODEL.attn_params(CFG) == attn == 101_122_048
    expert = 3 * 7168 * 2048
    assert MODEL.expert_params(CFG) == expert == 44_040_192
    per_token = (6 * attn + 3 * 7168 * 18432
                 + 5 * (7168 * 384 + expert) + 7168 * 20480)
    assert MODEL.dense_params_per_token(CFG) == per_token
    total = (per_token + 20480 * 7168 + 5 * (12 * expert + 384)
             + 6 * (2 * 7168 + 1536 + 512) + 7168)
    assert MODEL.total_params(CFG) == total
    assert 8.34e9 < 2 * total < 8.36e9                  # 8.35 GB in bf16
    import jax
    shapes = jax.eval_shape(lambda: REF.init_params(0, CFG))
    assert sum(int(np.prod(s.shape)) for s in shapes.values()) == total
    assert MODEL.kv_bytes_per_token(CFG, 2) == 576 * 2 * 6 == 6912
    c = {"active_slot_steps": 1000, "cache_tokens_read": 2_000_000,
         "expert_assignments_held": 250}
    attn_flops = 2 * 64 * (576 + 512)
    assert MODEL.serve_flops(CFG, c) == pytest.approx(
        1000 * 2.0 * per_token + 250 * 2.0 * expert
        + 2_000_000 * 6 * attn_flops)
    # the assignments are the step's own count, never k x held / E
    assert MODEL.serve_flops(CFG, dict(c, expert_assignments_held=0)) < \
        MODEL.serve_flops(CFG, c)
    least, bound = MODEL.paged_attn_least_s(CFG, c, 2, PEAKS)
    by_bytes = 2_000_000 * 6912 / 819e9
    by_flops = 2_000_000 * 6 * attn_flops / 197e12
    assert by_flops / by_bytes == pytest.approx(0.50, abs=0.01)
    assert (least, bound) == (pytest.approx(by_bytes), "hbm_bytes")
    assert MODEL.held_experts(CFG) == 12


def test_the_file_states_the_deployment_and_what_reaches_the_reference():
    z = REF.sizes(check.cfg_of(check.cfg_key(CFG)))      # no nested dict
    assert (z["held"], z["E"], z["lo"], z["k"]) == (12, 384, 0, 8)
    assert (z["factor"], z["orig"], z["beta_fast"], z["beta_slow"],
            z["mscale"], z["mscale_all_dim"]) == (32.0, 4096, 1.0, 1.0,
                                                  1.0, 1.0)
    rs = CFG["rope_scaling"]
    assert (rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], rs["mscale"],
            rs["mscale_all_dim"]) == (32, 4096, 1, 1, 1, 1)
    dep = CFG["deployment"]
    assert dep["num_pages"] * dep["page_size"] >= 262144
    assert (dep["num_slots"], dep["max_seq_len"]) == (64, 4096)
    mix = CELL["traffic"]
    assert (mix["clients"], mix["history_len"]) == (64, 2048)
    longest = mix["history_len"] + mix["suffix_len"]["max"] + \
        mix["output_len"]["max"]
    assert longest <= CELL["limits"]["pad_to"] <= dep["max_seq_len"]
    tiny = MODEL.tiny()
    assert set(tiny) - {"name", "deployment"} <= set(CFG) | {
        "max_position_embeddings"}


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def seconds_matching(self, pred):
        return sum(s for op, s in self.seconds.items() if pred(op))


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def test_latent_attn_roofline_reads_its_kernel_alone():
    c = {"cache_tokens_read": 2_000_000}
    ctx = {"trace": _Trace({"tpu_custom_call:paged_latent_attention": 0.1,
                            "tpu_custom_call:paged_window_attention": 5.0,
                            "fusion:fusion": 1.0}),
           "traced_counters": c, "model": MODEL, "config": CFG,
           "kv_itemsize": 2, "peaks": PEAKS}
    want = 100.0 * (2_000_000 * 6912 / 819e9) / 0.1
    assert _reader("latent_attn_roofline").read(ctx) == pytest.approx(want)
    # a parent without the kernel, or a trace without its events: nothing
    other = dict(ctx, trace=_Trace(
        {"tpu_custom_call:paged_window_attention": 5.0}))
    assert _reader("latent_attn_roofline").read(other) is None
    assert _reader("latent_attn_roofline").read(
        dict(ctx, traced_counters={})) is None


def test_expert_hit_share_on_known_deltas():
    ctx = {"counters": {"expert_hits_held": 45, "expert_layer_steps": 5,
                        "steps": 1}, "model": MODEL, "config": CFG}
    assert _reader("expert_hit_share").read(ctx) == pytest.approx(75.0)
    # an engine without the counters (the parent), or a model without
    # expert layers (they stay 0): nothing, and the model is not touched
    assert _reader("expert_hit_share").read({"counters": {"steps": 9}}) \
        is None
    assert _reader("expert_hit_share").read(
        {"counters": {"expert_layer_steps": 0, "expert_hits_held": 0}}) \
        is None


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


def test_bf16_rounding_counts_the_moved_expert_sets():
    """``held_expert_sets`` under "bf16" against float32: the tool for
    how often the served precision moves a position's held set."""
    import jax.numpy as jnp
    cfg = MODEL.tiny(deployment=False)
    params = REF.init_params(3, cfg)
    seq = jnp.asarray(np.random.default_rng(1).integers(0, 64, 24), jnp.int32)
    a = np.asarray(REF.held_expert_sets(params, seq, cfg))
    b = np.asarray(REF.held_expert_sets(params, seq, cfg, "bf16"))
    assert a.shape == b.shape == (2, 24, 4) and a.dtype == bool
    assert 0 < a.sum() <= 2 * 24 * 2
    assert (a != b).any(-1).mean() < 0.25
