"""CPU tests of the benchmark (BENCHMARK.json, benchmarks/): the manifest
and the files it names, the traffic generator, the trace reducer, the
FLOP/byte functions, the plain reference against the program, and the
command's refusal to run off the chip. Tiny sizes, a few seconds.

No topology call here: nothing in this file touches the TPU's library.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import manifest, trace, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = manifest.load_manifest()
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]
LAYER_METRICS = [m["name"] for m in MANIFEST["per_layer"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]
OPT = manifest.load_module("models", "opt")
TINY = OPT.tiny(deployment=False)

#: the cuts the model-configs guide (section 4) allows, by the keys that
#: the catalog's ``config.json``s give them: depth and the layer pattern,
#: the experts held here, the vocabulary. No width is among them.
REDUCIBLE = {
    "num_hidden_layers", "num_layers", "n_layer", "n_layers",
    "layer_types", "mlp_layer_types", "layers_block_type",
    "hybrid_override_pattern", "hybrid_layer_pattern", "mlp_only_layers",
    "first_k_dense_replace", "num_dense_layers", "n_dense_first_layers",
    "max_window_layers", "attn_layer_indices", "full_attention_layers",
    "num_nextn_predict_layers",
    "n_routed_experts", "num_experts", "num_local_experts",
    "moe_num_experts", "vocab_size"}


def is_width(key: str) -> bool:
    """What ``reduced`` may never name: a hidden, intermediate, latent,
    state or projection size, a key that ends in _dim or _rank, a head
    size, an expansion factor, the experts per token."""
    return key != "vocab_size" and re.search(
        r"(_dim|_rank|_size|_width|_per_tok|_per_token|top_k|topk|factor|"
        r"expand|window)$|^d_[a-z]+$", key) is not None


# ------------------------------------------------------------------ manifest
def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in MANIFEST["end_to_end"])
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace"), m
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_exist_and_parse(workload):
    cell = manifest.cell(MANIFEST, workload)
    assert cell["config"]["source"].startswith("https://")
    assert cell["traffic"]["kind"] in ("open_loop", "closed_loop",
                                       "train_job")
    manifest.load_module("drivers", cell["traffic"]["driver"])
    ref, model = cell["reference"], cell["model"]
    assert hasattr(ref, "forward") and hasattr(ref, "init_params")
    # the contract of benchmarks/models/<reference>.py, by the cell's driver
    needs = {"serve": ("build_engine", "serve_flops", "paged_attn_least_s"),
             "train": ("build_trainer", "to_named", "leaf_index", "rows_of",
                       "train_flops_per_token", "flash_least_s")}
    for fn in ("make_weights", "kv_bytes_per_token", "total_params",
               "tiny") + needs[cell["traffic"]["driver"]]:
        assert callable(getattr(model, fn, None)), fn
    tiny = model.tiny()
    assert tiny["reference"] == cell["config"]["reference"]
    assert model.total_params(tiny) < 1e6 < model.total_params(cell["config"])
    assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    assert any("mfu" in m["name"] for m in cell["per_layer"])
    assert any(m["name"].endswith("_roofline") for m in cell["per_layer"])
    for check_name, lim in cell["limits"].items():
        if isinstance(lim, dict) and check_name != "not_compared":
            assert lim["lower"] < lim["limit"] < lim["upper"], check_name


def config_faults(conf: dict, cfg: dict, pinned: dict) -> list:
    """What is wrong with one configuration's file against its entry of
    the manifest and its pinned case (the published sizes by their keys),
    whatever its architecture: [] where nothing is. A key that is not
    reduced holds the published value; a reduced one says it under
    ``published``."""
    faults = []
    if not any(is_width(k) for k in pinned):
        faults.append("no width is pinned")
    for k, v in pinned.items():
        where = cfg.get("published", {}) if k in conf["reduced"] else cfg
        if where.get(k) != v:
            faults.append(f"{k!r} is {where.get(k)!r}, published {v!r}")
    if cfg.get("reduced") != conf["reduced"]:
        faults.append("reduced differs between the file and the manifest")
    for k in conf["reduced"]:
        if k not in REDUCIBLE:
            faults.append(f"reduced names {k!r}, which is no cut of depth, "
                          f"experts held or vocabulary")
        if k not in cfg.get("published", {}):
            faults.append(f"no published value of the reduced key {k!r}")
        elif cfg["published"][k] == cfg.get(k):
            faults.append(f"{k!r} is listed as reduced and is as published")
    if not cfg.get("assumed"):
        faults.append("assumed is empty")
    if not str(cfg.get("source", "")).startswith("https://") \
            or cfg.get("source") != conf["source"]:
        faults.append("source is no https:// URL, or not the manifest's")
    return faults


def _config(name):
    conf = next(c for c in MANIFEST["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, conf["file"])) as f:
        return conf, json.load(f)


def _pinned(name) -> dict:
    """tests/benchmarks/published/<name>.json: the configuration's
    published sizes by their keys, written down by the PR that adds it."""
    with open(os.path.join(os.path.dirname(__file__), "published",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_files_keep_the_published_widths(name):
    """Structure, for every entry, and its pinned case."""
    conf, cfg = _config(name)
    assert config_faults(conf, cfg, _pinned(name)) == []


def test_opt_is_pinned_at_its_published_sizes():
    for name in ("opt-1.3b", "opt-1.3b-train"):
        assert _pinned(name) == {
            "hidden_size": 2048, "num_attention_heads": 32, "ffn_dim": 8192,
            "vocab_size": 50272, "max_position_embeddings": 2048,
            "num_hidden_layers": 24}


def test_the_width_test_takes_another_architecture_and_refuses_a_width():
    """A second entry whose widths are not OPT's passes the test of
    structure; a ``reduced`` that names a width, a reduced key without
    its published value, an empty ``assumed`` and a source that is no
    URL are each refused."""
    conf = {"name": "toy-moe", "source": "https://example.org/toy-moe",
            "file": "x/configs/toy-moe.json",
            "reduced": ["num_hidden_layers", "layer_types", "num_experts"],
            "why": "-"}
    cfg = {"name": "toy-moe", "source": conf["source"], "reference": "toy",
           "hidden_size": 1536, "num_attention_heads": 12, "head_dim": 128,
           "moe_intermediate_size": 768, "num_experts_per_tok": 8,
           "vocab_size": 200192, "num_hidden_layers": 5, "num_experts": 16,
           "layer_types": ["sliding", "sliding", "sliding", "full",
                           "sliding"],
           "reduced": list(conf["reduced"]),
           "published": {"num_hidden_layers": 32, "num_experts": 128,
                         "layer_types": ["sliding", "sliding", "sliding",
                                         "full"] * 8},
           "assumed": {"weights": "random from --seed"}}
    pinned = {"hidden_size": 1536, "head_dim": 128, "num_experts": 128,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8,
              "vocab_size": 200192, "num_hidden_layers": 32}
    assert config_faults(conf, cfg, pinned) == []
    for width in ("hidden_size", "moe_intermediate_size", "head_dim",
                  "num_experts_per_tok", "kv_lora_rank"):
        bad = dict(conf, reduced=conf["reduced"] + [width])
        faults = config_faults(bad, dict(cfg, reduced=bad["reduced"]),
                               pinned)
        assert any(width in f and "no cut" in f for f in faults), width
    assert config_faults(conf, dict(cfg, hidden_size=1024), pinned)
    assert config_faults(conf, dict(cfg, published={}), pinned)
    assert config_faults(conf, dict(cfg, assumed={}), pinned)
    assert config_faults(dict(conf, source="a paper"),
                         dict(cfg, source="a paper"), pinned)
    assert config_faults(conf, dict(cfg, reduced=[]), pinned)
    assert config_faults(conf, cfg, {"vocab_size": 200192})
    assert not [k for k in REDUCIBLE if is_width(k)]
    assert all(is_width(k) for k in (
        "hidden_size", "ffn_dim", "intermediate_size", "kv_lora_rank",
        "head_dim", "num_experts_per_tok", "moe_intermediate_size",
        "d_model", "expert_ffn_hidden_size", "mamba_expand"))


def test_names_and_units_hold_only_the_allowed_characters():
    names = [c["name"] for c in MANIFEST["configs"]] + WORKLOADS + \
        [w["traffic"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
    for n in names:
        assert NAME.match(n), n
    assert len(set(WORKLOADS)) == len(WORKLOADS)
    metric_names = [m["name"] for m in
                    MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for w in MANIFEST["workloads"] + MANIFEST["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"], w["name"]


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_moves_what_its_cells_report(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    e2e = {x["name"]: x for x in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e and m["moves"] != "setup_s"
    reporters = e2e[m["moves"]].get("workloads", WORKLOADS)
    for w in m.get("workloads", reporters):
        assert w in WORKLOADS and w in reporters, (metric, w)
    reader = manifest.load_module("layer_metrics", metric)
    # a reader that finds nothing to read returns nothing, never 0
    empty = {"trace": None, "traced_counters": None, "counters": {},
             "late_ms": [], "end_to_end": {}, "chips": 1}
    assert reader.read(empty) is None


# ------------------------------------------------------------------- traffic
def _mix(name):
    return manifest.load_json("traffic", name + ".json")


def _vocab(mix_name) -> int:
    """The vocabulary of a configuration whose cell uses the mix."""
    w = next(w for w in MANIFEST["workloads"] if w["traffic"] == mix_name)
    return int(_config(w["config"])[1]["vocab_size"])


@pytest.mark.parametrize("mix_name", sorted({w["traffic"] for w in
                                             MANIFEST["workloads"]}))
def test_traffic_repeats_for_a_seed_and_differs_across_seeds(mix_name):
    mix = _mix(mix_name)
    big = 2 ** 31 + 17

    def flat(g):
        if g["kind"] == "open_loop":
            return [(r["due"], r["prompt"].tolist(), r["max_new"])
                    for r in g["requests"]]
        if g["kind"] == "closed_loop":
            return [(c["history"].tolist(),
                     [(t["suffix"].tolist(), t["max_new"])
                      for t in c["turns"]]) for c in g["clients"]]
        return [g["batch"](i).tolist() for i in range(2)]

    vocab = _vocab(mix_name)
    a = flat(traffic.generate(mix, big, 5.0, vocab))
    b = flat(traffic.generate(mix, big, 5.0, vocab))
    c = flat(traffic.generate(mix, big + 1, 5.0, vocab))
    assert a == b and a != c


def test_every_seed_carries_the_same_work():
    mix = _mix("chat")
    sizes = []
    for seed in (1, 2, 2 ** 31 + 5):
        g = traffic.generate(mix, seed, 10.0, _vocab("chat"))["requests"]
        sizes.append((len(g), sorted(len(r["prompt"]) for r in g),
                      sorted(r["max_new"] for r in g)))
        due = [r["due"] for r in g]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 10.0
    assert sizes[0] == sizes[1] == sizes[2]
    lens = sizes[0][1]
    assert lens[0] >= 16 and lens[-1] <= 384
    assert 80 <= lens[len(lens) // 2] <= 112        # median ~96


def test_train_rows_all_differ():
    g = traffic.generate(_mix("pretrain_4x2048"), 3, 1.0,
                         _vocab("pretrain_4x2048"), chips=4)
    b0, b1 = g["batch"](0), g["batch"](1)
    assert b0.shape == (16, 2049)
    rows = {tuple(r[:32]) for r in np.concatenate([b0, b1])}
    assert len(rows) == 32


def test_the_ramp_is_a_stratified_set_of_its_own_before_the_window():
    mix = _mix("chat")
    sets = []
    for seed in (4, 2 ** 31 + 9):
        g = traffic.generate(mix, seed, 10.0, _vocab("chat"))
        ramp = g["ramp_requests"]
        due = [r["due"] for r in ramp]
        assert due == sorted(due) and -mix["ramp_s"] <= due[0] and due[-1] < 0
        assert len(ramp) == round(mix["arrivals"]["rate_per_s"] * mix["ramp_s"])
        assert all(r["due"] >= 0 for r in g["requests"])
        sets.append((sorted(len(r["prompt"]) for r in ramp),
                     sorted(r["max_new"] for r in ramp)))
        # a unique prompt each, the ramp's too: no shared prefix
        firsts = {tuple(r["prompt"][:8]) for r in ramp + g["requests"]}
        assert len(firsts) == len(ramp) + len(g["requests"])
    assert sets[0] == sets[1]


def test_a_mix_holds_no_key_that_the_generator_or_driver_does_not_read():
    """A knob that no code reads changes nothing when it is set."""
    read = {"open_loop": {"kind", "driver", "arrivals", "ramp_s",
                          "prompt_len", "output_len", "trace_seconds",
                          "poll_s", "note"},
            "closed_loop": {"kind", "driver", "clients", "ramp_s",
                            "history_len", "turns_per_client", "suffix_len",
                            "output_len", "trace_seconds", "poll_s", "note"},
            "train_job": {"kind", "driver", "seq_len", "rows_per_chip_step",
                          "learning_rate", "compute_dtype",
                          "trace_seconds", "note"}}
    for w in MANIFEST["workloads"]:
        mix = _mix(w["traffic"])
        assert set(mix) <= read[mix["kind"]], (w["traffic"], set(mix))
        if mix["kind"] == "open_loop":
            assert set(mix["arrivals"]) <= {"rate_per_s", "knee_per_s",
                                            "knee_note"}


# --------------------------------------------------------------------- trace
def test_trace_reducer_on_a_hand_made_list():
    ms = 1_000_000
    ops = [(0, 4 * ms, "fusion.1"), (1 * ms, 2 * ms, "custom-call.3"),
           (6 * ms, 2 * ms, "all-reduce.7"), (7 * ms, 2 * ms, "fusion.2"),
           (12 * ms, 1 * ms, "fusion.1")]
    assert trace.union_ns(ops) == 8 * ms            # [0,4] [6,9] [12,13]
    by = trace.self_time_by_name(ops)
    assert by["fusion.1"] == 3 * ms                 # 4 - child 2, + 1
    assert by["custom-call.3"] == 2 * ms
    gaps = trace.idle_gaps(ops)
    assert sorted(g[1] for g in gaps) == [2 * ms, 3 * ms]
    assert gaps[0][0] == "before all-reduce.7"
    coll = [e for e in ops if trace.is_collective(e[2])]
    comp = [e for e in ops if not trace.is_collective(e[2])]
    assert trace.exposed_ns(coll, comp) == 1 * ms   # [6,7] of [6,8]
    mods = [(0, 4 * ms, "jit_step"), (6 * ms, 3 * ms, "jit_step"),
            (12 * ms, 1 * ms, "jit_step")]
    tr = trace.Trace([{"ops": ops, "modules": mods}], window_s=0.016)
    assert tr.busy_s() == pytest.approx(0.008)
    assert tr.idle_share() == pytest.approx(0.5)
    st = tr.steps(lambda n: n == "jit_step", trace.is_collective)
    assert st["n"] == 1 and st["n_all"] == 3
    assert st["module_s"] == pytest.approx(0.003)
    assert st["ops_s"] == pytest.approx(0.002)
    # device-busy time inside a step's span: the span [5, 11] holds the
    # wait before its first operation and after its last, the busy time
    # ([6, 9]) does not; operations outside the span do not count
    wide = [(-1 * ms, 1 * ms, "jit_step"), (5 * ms, 6 * ms, "jit_step"),
            (12 * ms, 1 * ms, "jit_step")]
    st = trace.Trace([{"ops": ops, "modules": wide}],
                     window_s=0.016).steps(lambda n: n == "jit_step")
    assert st["module_s"] == pytest.approx(0.006)
    assert st["ops_s"] == pytest.approx(0.003)
    assert tr.exposed_collective_s() == pytest.approx(0.001)
    bd = tr.breakdown()
    assert bd["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert len(bd["idle_gaps"]) == 2


# -------------------------------------------------------------------- counts
def test_flop_and_byte_functions_match_a_hand_count():
    counts = OPT
    cfg = {"hidden_size": 2048, "ffn_dim": 8192, "num_hidden_layers": 1,
           "vocab_size": 50272, "max_position_embeddings": 2048}
    # one layer: q,k,v,out 4 x 2048^2 = 16,777,216; ffn 2 x 2048 x 8192
    assert counts.matmul_params(cfg) == 16_777_216 + 33_554_432
    assert counts.kv_bytes_per_token(cfg, 2) == 2 * 2048 * 2
    full = dict(cfg, num_hidden_layers=24)
    assert counts.kv_bytes_per_token(full, 2) == 196_608
    # one token fed at cache length 100: 2 x weights + head + 4 d n
    want = 2 * 50_331_648 + 2 * 2048 * 50272 + 4 * 2048 * 100
    fed = {"active_slot_steps": 1, "cache_tokens_read": 100}
    assert counts.serve_flops(cfg, fed) == pytest.approx(want)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    s, which = counts.paged_attn_least_s(
        full, {"cache_tokens_read": 36_000}, 2, peaks)
    assert which == "hbm_bytes"
    assert s == pytest.approx(36_000 * 196_608 / 819e9)
    # training: 6 x weights + 6 d V + 3 x (2 T^2 d)/T per token
    t = 2048
    want = 6 * 50_331_648 + 6 * 2048 * 50272 + 3 * 2 * t * 2048
    assert counts.train_flops_per_token(cfg, t) == pytest.approx(want)
    s, which = counts.flash_least_s(cfg, 4, t, 2, peaks)
    assert which == "flops"
    assert s == pytest.approx(4 * 3 * 2 * t * t * 2048 / 197e12)
    assert counts.total_params(full) == pytest.approx(1.3157e9, rel=1e-3)


# ----------------------------------------------------- reference vs program
def _tiny_weights(dtype="float32"):
    import jax.numpy as jnp
    ref = manifest.load_module("reference", "opt")
    return ref, OPT.make_weights(ref, 7, TINY, jnp.dtype(dtype))


def test_reference_agrees_with_transformer_decoder():
    import jax
    import jax.numpy as jnp
    from paddle_tpu import models
    ref, named = _tiny_weights()
    dec = models.TransformerDecoder(named, n_layers=2, n_heads=4)
    ids = np.random.default_rng(0).integers(0, 64, 24).astype(np.int32)
    got = dec._prefill(dec.p, jnp.asarray(ids)[None], 24, 24)[0][0]
    want = ref.forward(jax.jit(lambda: ref.init_params(7, TINY))(),
                       jnp.asarray(ids), TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_reference_agrees_with_transformer_lm_cost():
    import jax
    import jax.numpy as jnp
    ref, named = _tiny_weights()
    job = {"learning_rate": 1e-4, "compute_dtype": "float32"}
    cfg = dict(TINY, max_position_embeddings=32)
    named = OPT.make_weights(ref, 7, cfg, jnp.float32)
    trainer = OPT.build_trainer(named, cfg, job, 1, False)
    batch = np.random.default_rng(1).integers(0, 64, (4, 33)).astype(np.int32)
    cost = trainer.train_batch(OPT.rows_of(batch))
    cost = float(cost[0] if isinstance(cost, (tuple, list)) else cost)
    p = jax.jit(lambda: ref.init_params(7, cfg))()
    want, _ = ref.batch_loss_and_grad(p, jnp.asarray(batch[:, :-1]),
                                      jnp.asarray(batch[:, 1:]), cfg)
    assert cost == pytest.approx(float(want), rel=1e-5)


# ----------------------------------------------- one program for every seed
@pytest.mark.parametrize("seed", [7, 2**31 - 1, 2**31 + 5, 9500000011])
def test_the_seeds_words_give_the_weights_the_whole_number_gives(seed):
    """The seed as two words that a program takes as arguments
    (check.seed_words) makes the very weights that the whole number, closed
    over as a constant, made before: no reading of any cell moves."""
    import jax
    from benchmarks.lib import check
    ref = manifest.load_module("reference", "opt")
    closed = jax.jit(lambda: ref.init_params(seed, TINY))()
    words = check.from_seed(lambda w: ref.init_params(w, TINY), seed)
    assert check.seed_words(seed) == ref.seed_words(seed)
    for k in closed:
        np.testing.assert_array_equal(np.asarray(closed[k]),
                                      np.asarray(words[k]))
    a = jax.jit(lambda: check.sketch_vectors(
        ref, check.seed_words(seed), TINY, 2))()
    b = check.from_seed(lambda w: check.sketch_vectors(ref, w, TINY, 2), seed)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_a_new_seed_compiles_nothing(monkeypatch):
    """Every program that set-up makes from the seed is the same program
    for every seed, to the letter of its lowered text: the persistent cache
    then holds it after a checkout's first run, whatever seed comes."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import check
    ref = manifest.load_module("reference", "opt")
    texts, real = {}, jax.jit

    def spy(fn, **kw):
        jitted = real(fn, **kw)

        def call(*args):
            texts.setdefault(seed, []).append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    monkeypatch.setattr(jax, "jit", spy)
    for seed in (7, 9500000011):
        named = OPT.make_weights(ref, seed, TINY, jnp.float32)
        check.from_seed(lambda w: ref.init_params(w, TINY), seed)
        check.leaf_sketch(ref, seed, TINY, ref.init_params(seed, TINY))
        check.from_seed(lambda w, p: {
            k: p[k] - v for k, v in OPT.to_named(
                ref.init_params(w, TINY)).items()}, seed, named)
    assert len(texts[7]) == 4 and texts[7] == texts[9500000011]


# ------------------------------------------------------------------ command
def test_run_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"correct"' not in out.stdout
