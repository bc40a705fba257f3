"""The harness takes a configuration of another architecture as files
only. The proof: a second, toy architecture that exists nowhere but in this
test - a plain reference and the program's side of it (an adapter with the
contract of benchmarks/models/<reference>.py) whose leaves, configuration
keys and counts have other names than OPT's, a configuration with a
``layer_types`` list, a traffic mix, limits, and a manifest with one cell
under the ``serve`` driver - is written into ``tmp_path`` and goes through
``run.run_cell`` on the CPU to a ``correct`` result, with ``serve_mfu`` and
``paged_attn_roofline`` read from its own counts against a hand-made
trace. No file of benchmarks/ is written, and the drivers and readers it
runs are the ones that are there.

Its mathematics is the one block the program has (a model_config PR brings
the program's side of a new block with it); what is new here is everything
the benchmark's files used to know by OPT's names.
"""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import harness, manifest  # noqa: E402
from test_layer_metrics import PEAKS, _step_trace  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}

REFERENCE = '''
"""Plain reference of the toy block: pre-norm, learned positions, ReLU
MLP, tied head; float32. Leaves and configuration keys of its own."""
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
SEEN = []           # every configuration forward() was handed (trace time)
SHAPES = {"wte": lambda z: (z["V"], z["d"]), "wpe": lambda z: (z["P"], z["d"]),
          "out_norm_w": lambda z: (z["d"],), "out_norm_b": lambda z: (z["d"],),
          "attn_norm_w": lambda z: (z["L"], z["d"]),
          "attn_norm_b": lambda z: (z["L"], z["d"]),
          "wq": lambda z: (z["L"], z["d"], z["d"]),
          "wk": lambda z: (z["L"], z["d"], z["d"]),
          "wv": lambda z: (z["L"], z["d"], z["d"]),
          "wo": lambda z: (z["L"], z["d"], z["d"]),
          "mlp_norm_w": lambda z: (z["L"], z["d"]),
          "mlp_norm_b": lambda z: (z["L"], z["d"]),
          "w_in": lambda z: (z["L"], z["d"], z["f"]),
          "b_in": lambda z: (z["L"], z["f"]),
          "w_out": lambda z: (z["L"], z["f"], z["d"])}


def sizes(cfg):
    return {"d": cfg["n_embd"], "f": cfg["n_inner"], "h": cfg["n_head"],
            "L": cfg["n_layer"], "V": cfg["vocab_size"],
            "P": cfg["n_positions"]}


def init_params(seed, cfg, dtype=jnp.float32):
    z = sizes(cfg)
    # the harness hands the seed over as two traced words (check.seed_words)
    lo, hi = seed if isinstance(seed, tuple) else (
        int(seed) & 0x7FFFFFFF, int(seed) >> 31)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        r = 0.2 * jax.random.normal(jax.random.fold_in(key, i), shape(z),
                                    jnp.float32)
        out[name] = (1.0 + r if name.endswith("norm_w") else r).astype(dtype)
    return out


def _norm(x, w, b):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b


def forward(params, tokens, cfg, rounding=None):
    """tokens [T] -> logits [T, V]. Every layer of ``layer_types`` is
    "full" here; the list has to ARRIVE, as a list."""
    SEEN.append(cfg)
    types = cfg["layer_types"]
    if not isinstance(types, list) or len(types) != cfg["n_layer"] \\
            or set(types) != {"full"}:
        raise ValueError(f"layer_types came as {types!r}")
    z = sizes(cfg)
    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)
    t, dh = tokens.shape[0], z["d"] // z["h"]
    x = p["wte"][tokens] + p["wpe"][:t]
    for i in range(z["L"]):
        a = _norm(x, p["attn_norm_w"][i], p["attn_norm_b"][i])
        heads = lambda w: mm(a, w[i]).reshape(t, z["h"], dh).transpose(1, 0, 2)
        q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
        s = mm(q, k.transpose(0, 2, 1)) * dh ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        o = mm(jax.nn.softmax(s, -1), v).transpose(1, 0, 2).reshape(t, z["d"])
        x = x + mm(o, p["wo"][i])
        m = _norm(x, p["mlp_norm_w"][i], p["mlp_norm_b"][i])
        x = x + mm(jax.nn.relu(mm(m, p["w_in"][i]) + p["b_in"][i]),
                   p["w_out"][i])
    return mm(_norm(x, p["out_norm_w"], p["out_norm_b"]), p["wte"].T)
'''

MODEL = '''
"""The program's side of the toy block: its leaves under the program's
parameter names, its engine, its own counts."""
import jax

TOP = {"wte": "tok_emb.w0", "wpe": "pos_emb.w0", "out_norm_w": "lnf.w0",
       "out_norm_b": "lnf.wbias"}
LAYER = {"attn_norm_w": "ln1.w0", "attn_norm_b": "ln1.wbias", "wq": "q.w0",
         "wk": "k.w0", "wv": "v.w0", "wo": "proj.w0", "mlp_norm_w": "ln2.w0",
         "mlp_norm_b": "ln2.wbias", "w_in": "up.w0", "b_in": "up.wbias",
         "w_out": "down.w0"}


def to_named(p):
    out = {f"_toy_{name}": p[leaf] for leaf, name in TOP.items()}
    for leaf, name in LAYER.items():
        for i in range(p[leaf].shape[0]):
            out[f"_toy_l{i}_{name}"] = p[leaf][i]
    return out


def make_weights(reference, seed, cfg, dtype):
    from benchmarks.lib import check
    return check.from_seed(
        lambda w: to_named(reference.init_params(w, cfg, dtype)), seed)


def build_engine(named, cfg, deployment):
    from paddle_tpu import models
    from paddle_tpu.serving import DecodeEngine
    dec = models.TransformerDecoder(named, n_layers=cfg["n_layer"],
                                    n_heads=cfg["n_head"], name="toy")
    return dec, DecodeEngine(
        dec, num_slots=deployment["num_slots"],
        page_size=deployment["page_size"], num_pages=deployment["num_pages"],
        max_seq_len=deployment["max_seq_len"], max_waiting=1 << 30)


def _full_layers(cfg):
    return sum(1 for t in cfg["layer_types"] if t == "full")


def kv_bytes_per_token(cfg, itemsize):
    return 2 * cfg["n_embd"] * cfg["n_layer"] * itemsize


def total_params(cfg):
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f + f + 4 * d) \\
        + (cfg["vocab_size"] + cfg["n_positions"]) * d + 2 * d


def serve_flops(cfg, counters):
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_token = 2.0 * cfg["n_layer"] * (4 * d * d + 2 * d * f) \\
        + 2.0 * d * cfg["vocab_size"]
    return counters["active_slot_steps"] * per_token \\
        + 4.0 * d * _full_layers(cfg) * counters["cache_tokens_read"]


def paged_attn_least_s(cfg, counters, itemsize, peaks):
    by_bytes = counters["cache_tokens_read"] * 2 * cfg["n_embd"] \\
        * _full_layers(cfg) * itemsize / peaks["hbm_bytes_per_s"]
    return by_bytes, "hbm_bytes"


def tiny(deployment=True):
    raise NotImplementedError("the toy is its own tiny size")
'''

CONFIG = {
    "name": "toy", "source": "https://example.org/toy/config.json",
    "reference": "toy", "n_embd": 32, "n_head": 4, "n_inner": 48,
    "n_layer": 2, "layer_types": ["full", "full"], "vocab_size": 96,
    "n_positions": 64, "torch_dtype": "float32", "reduced": [],
    "assumed": {"weights": "random from --seed"},
    "deployment": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
                   "num_pages": 80}}
TRAFFIC = {
    "kind": "open_loop", "driver": "serve", "arrivals": {"rate_per_s": 6.0},
    "ramp_s": 0.5,
    "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2,
                   "max": 24},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
                   "max": 16}}
LIMITS = {"sample_requests": 4, "pad_to": 64,
          "served_logit_gap": {"limit": 0.01, "lower": 1e-4, "upper": 0.1}}


def toy_manifest(bench: str) -> dict:
    """One configuration, one cell, the serving end-to-end metrics and
    four of the serving readers, all under the names BENCHMARK.json has."""
    real = manifest.load_manifest()
    cell = "toy_chat"
    e2e = [dict(m, workloads=[cell]) for m in real["end_to_end"]
           if m["name"] in ("ttft_p95_ms", "gap_p95_ms", "setup_s")]
    layer = [dict(m, workloads=[cell]) for m in real["per_layer"]
             if m["name"] in ("serve_mfu", "paged_attn_roofline",
                              "decode_step_dev_ms", "engine_prefill_share")]
    assert len(e2e) == 3 and len(layer) == 4
    return {"configs": [{"name": "toy", "source": CONFIG["source"],
                         "file": os.path.join(bench, "configs", "toy.json"),
                         "reduced": [], "why": "the harness's proof"}],
            "workloads": [{"name": cell, "config": "toy",
                           "traffic": "toy_open", "chips": 1,
                           "why": "short unique prompts on the CPU"}],
            "end_to_end": e2e, "per_layer": layer}


class HandMadeTrace:
    """Stands where harness.TraceWindow stands: the counters of the 'traced
    part' are the engine's own, the device's events are made by hand - ten
    steps, in each 2 ms of fusions, 4 ms of the paged kernel and 3 ms of
    another Mosaic kernel that is not attention."""
    made = []

    def __init__(self, start_after, seconds, snapshot):
        self.snapshot, self.dir = snapshot, None
        self.before = self.after = None
        HandMadeTrace.made.append(self)

    def start(self, t0):
        self.before = self.snapshot()

    def join(self):
        self.after = self.snapshot()

    def read(self, chips):
        return _step_trace(
            [("tpu_custom_call:paged_window_attention", 4)],
            ("tpu_custom_call:grouped_expert_matmul", 3))

    def cleanup(self):
        pass


def test_a_new_architecture_runs_as_files_only(tmp_path, capsys, monkeypatch):
    bench = str(tmp_path / "bench")
    for kind, name, body in (
            ("reference", "toy.py", REFERENCE), ("models", "toy.py", MODEL),
            ("configs", "toy.json", json.dumps(CONFIG)),
            ("traffic", "toy_open.json", json.dumps(TRAFFIC)),
            ("limits", "toy_chat.json", json.dumps(LIMITS))):
        os.makedirs(os.path.join(bench, kind), exist_ok=True)
        with open(os.path.join(bench, kind, name), "w") as f:
            f.write(body)
    cell = manifest.cell(toy_manifest(bench), "toy_chat")
    assert cell["home"] == bench
    for mod in (cell["reference"], cell["model"]):
        assert mod.__file__.startswith(bench)
    monkeypatch.setattr(harness, "TraceWindow", HandMadeTrace)
    HandMadeTrace.made.clear()

    rc = run.run_cell(cell, 2 ** 31 + 7, 1.5, True, DEVICE, PEAKS,
                      time.monotonic())
    assert rc == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    res = lines[-1]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["served_logit_gap"]["value"] < 1e-3

    # the reference was handed the configuration with its list, and no
    # nested dict
    seen = cell["reference"].SEEN
    assert seen and all(c["layer_types"] == ["full", "full"] for c in seen)
    assert all(not isinstance(v, dict) for c in seen for v in c.values())
    assert all("deployment" not in c and c["n_embd"] == 32 for c in seen)

    # both shares come from the toy's own counts: worked out again here
    # from the counters the run printed, with the toy's formulas
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == {"serve_mfu", "paged_attn_roofline",
                        "decode_step_dev_ms", "engine_prefill_share"}
    window = next(l for l in lines if l.get("phase") == "window")
    counters = next(l for l in lines if l.get("phase") == "counters")["window"]
    d, f, layers, vocab = 32, 48, 2, 96
    flops = counters["active_slot_steps"] * (
        2.0 * layers * (4 * d * d + 2 * d * f) + 2.0 * d * vocab) \
        + 4.0 * d * layers * counters["cache_tokens_read"]
    assert got["serve_mfu"] == pytest.approx(
        100.0 * flops / window["window_s"] / PEAKS["bf16_flops"])
    tw, = HandMadeTrace.made
    traced = harness.delta(tw.after, tw.before)
    assert traced["cache_tokens_read"] > 0
    least = traced["cache_tokens_read"] * 2 * d * layers * 4 \
        / PEAKS["hbm_bytes_per_s"]              # float32 cache: 4 bytes
    # the paged kernel's 10 x 4 ms alone: the other custom call's 30 ms
    # are not attention's
    assert got["paged_attn_roofline"] == pytest.approx(100.0 * least / 0.040)
    assert got["decode_step_dev_ms"] == pytest.approx(9.0)
    assert res["device"]["busy_s"] == pytest.approx(0.090)
    assert ["tpu_custom_call:paged_window_attention", 0.040] in [
        [n, pytest.approx(s)] for n, s in res["breakdown"]["device_ops"]]

    # what ran it is what benchmarks/ has: nothing there was written
    driver = manifest.load_module("drivers", "serve", cell["home"])
    reader = manifest.load_module("layer_metrics", "serve_mfu", cell["home"])
    for mod in (driver, reader):
        assert mod.__file__.startswith(manifest.BENCH_DIR)
    assert not os.path.exists(os.path.join(manifest.BENCH_DIR, "models",
                                           "toy.py"))
