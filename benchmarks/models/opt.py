"""The program's side of the OPT block (``reference: "opt"``): everything
the benchmark knows about this one architecture beside its plain reference
(benchmarks/reference/opt.py). ``manifest.cell()`` loads it as
``cell["model"]``; the drivers, the per-layer readers and the CPU tests
reach it through the cell, never by name. The only file of the OPT
configurations that imports ``paddle_tpu``.

What a file of benchmarks/models/ holds (PERF.md section 3):

    make_weights(reference, seed, cfg, dtype)   the reference's weights from
        the seed, under the names the program's parameter table uses
    build_engine(named, cfg, deployment)        the served model
    build_trainer(named, cfg, job, chips, on_chip), to_named, leaf_index,
        rows_of                                 a configuration that is trained
    serve_flops, paged_attn_least_s, train_flops_per_token, flash_least_s,
        kv_bytes_per_token, total_params        operations and bytes that the
        algorithm needs, from the configuration's shapes and the engine's
        exact counters (the WHOLE counters dict: an architecture whose
        layers read different cache lengths counts them apart) - never from
        the program's own estimate, so that a kernel change cannot move its
        own yardstick. ``cfg`` is a ``config.json``-shaped dict
    tiny(deployment=True)                       a small configuration of the
        SAME architecture for the CPU tests
"""

from __future__ import annotations

import jax
import numpy as np

NAME = "tfm"          # transformer_lm's default name: fixes the table's keys

#: reference leaf -> suffix of the program's parameter name
_LAYER = {"ln1_g": "ln1.w0", "ln1_b": "ln1.wbias", "q": "q.w0", "k": "k.w0",
          "v": "v.w0", "o": "proj.w0", "ln2_g": "ln2.w0",
          "ln2_b": "ln2.wbias", "up": "up.w0", "up_b": "up.wbias",
          "down": "down.w0"}
_TOP = {"tok_emb": "tok_emb.w0", "pos_emb": "pos_emb.w0",
        "lnf_g": "lnf.w0", "lnf_b": "lnf.wbias"}


def program_name(leaf: str, layer=None) -> str:
    if layer is None:
        return f"_{NAME}_{_TOP[leaf]}"
    return f"_{NAME}_l{layer}_{_LAYER[leaf]}"


def to_named(stacked: dict) -> dict:
    """The reference's stacked leaves under the program's names."""
    out = {program_name(k): stacked[k] for k in _TOP}
    n_layers = stacked["q"].shape[0]
    for leaf in _LAYER:
        for i in range(n_layers):
            out[program_name(leaf, i)] = stacked[leaf][i]
    return out


def leaf_index(cfg: dict):
    """[(program name, reference leaf, layer or None)] of every leaf."""
    n_layers = int(cfg["num_hidden_layers"])
    idx = [(program_name(k), k, None) for k in _TOP]
    for leaf in _LAYER:
        idx += [(program_name(leaf, i), leaf, i) for i in range(n_layers)]
    return idx


def make_weights(reference, seed: int, cfg: dict, dtype):
    """All weights on the device in ONE jitted call from the seed, in the
    type they are used in, under the program's names. The seed goes in as
    an argument (its two words), so one program serves every seed."""
    return jax.jit(lambda lo, hi: to_named(
        reference.init_params((lo, hi), cfg, dtype)))(
            *reference.seed_words(seed))


def build_engine(named: dict, cfg: dict, deployment: dict):
    """The served model as its users build it: TransformerDecoder over the
    parameter table, DecodeEngine with its default attention."""
    from paddle_tpu import models
    from paddle_tpu.serving import DecodeEngine
    dec = models.TransformerDecoder(
        named, n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]), name=NAME)
    eng = DecodeEngine(
        dec, num_slots=int(deployment["num_slots"]),
        page_size=int(deployment["page_size"]),
        num_pages=int(deployment["num_pages"]),
        max_seq_len=int(deployment["max_seq_len"]),
        max_waiting=1 << 30)        # never refuse: an open loop's queue grows
    return dec, eng


def build_trainer(named: dict, cfg: dict, job: dict, chips: int,
                  on_chip: bool):
    """The training job through the v2 entry points:
    transformer_lm(tie_embeddings) + SGD(Adam), bf16 compute, f32 state."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core import registry
    registry.reset_name_counters()
    paddle.init(use_tpu=True if on_chip else None, trainer_count=chips,
                compute_dtype=job.get("compute_dtype", "bfloat16"), seed=0)
    spec = models.transformer_lm(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_layers=int(cfg["num_hidden_layers"]), d_ff=int(cfg["ffn_dim"]),
        max_len=int(cfg["max_position_embeddings"]), tie_embeddings=True,
        name=NAME)
    topo = paddle.Topology(spec.cost)
    missing = set(topo.param_specs) ^ set(named)
    if missing:
        raise RuntimeError(f"parameter tables differ: {sorted(missing)[:8]}")
    params = paddle.Parameters(named, topo.init_state(), topo.param_specs)
    trainer = paddle.SGD(
        cost=spec.cost, parameters=params,
        update_equation=paddle.optimizer.Adam(
            learning_rate=float(job["learning_rate"])))
    return trainer


def rows_of(batch) -> list:
    """[rows, T+1] ids -> the reader's sample tuples (tokens, positions,
    next tokens)."""
    t = batch.shape[1] - 1
    pos = np.arange(t, dtype="int32")
    return [(batch[i, :-1], pos, batch[i, 1:]) for i in range(batch.shape[0])]


# -------------------------------------------------------------------- counts
def _z(cfg):
    return (int(cfg["hidden_size"]), int(cfg["ffn_dim"]),
            int(cfg["num_hidden_layers"]), int(cfg["vocab_size"]))


def matmul_params(cfg) -> int:
    """Weights of the blocks' matrix products: q, k, v, out (4 d^2) and
    the two FFN matrices (2 d f), per layer. Embeddings, biases and
    LayerNorm are not in it."""
    d, f, L, _ = _z(cfg)
    return L * (4 * d * d + 2 * d * f)


def total_params(cfg) -> int:
    d, f, L, v = _z(cfg)
    p = int(cfg["max_position_embeddings"])
    return matmul_params(cfg) + L * (f + 4 * d) + (v + p) * d + 2 * d


def kv_bytes_per_token(cfg, itemsize: int) -> int:
    """K and V of one cached token over all layers."""
    d, _, L, _ = _z(cfg)
    return 2 * d * L * itemsize


def serve_flops(cfg, counters: dict) -> float:
    """Model FLOPs of a serving window: each token fed (prompt or output;
    the engine's ``active_slot_steps``) costs 2 x the blocks' weights + the
    tied head (2 d V), and attention over its true cache length n costs
    4 d n per layer (QK^T and PV); ``cache_tokens_read`` is the sum of n
    over all tokens fed."""
    d, _, L, v = _z(cfg)
    tokens_fed = counters["active_slot_steps"]
    cache_tokens_read = counters["cache_tokens_read"]
    return (tokens_fed * (2.0 * matmul_params(cfg) + 2.0 * d * v)
            + 4.0 * d * L * cache_tokens_read)


def paged_attn_least_s(cfg, counters: dict, itemsize: int, peaks: dict):
    """Least time for the decode-attention kernel's work: it must read
    the K and V of every cached token it attends to (the engine's
    ``cache_tokens_read``), and do 4 d FLOPs per cached token and layer.
    -> (seconds, which bound binds)."""
    d, _, L, _ = _z(cfg)
    cache_tokens_read = counters["cache_tokens_read"]
    by_bytes = cache_tokens_read * kv_bytes_per_token(cfg, itemsize) \
        / peaks["hbm_bytes_per_s"]
    by_flops = 4.0 * d * L * cache_tokens_read / peaks["bf16_flops"]
    return max(by_bytes, by_flops), \
        "hbm_bytes" if by_bytes >= by_flops else "flops"


def attn_fwd_flops_causal(cfg, seq_len: int) -> float:
    """Causal self-attention forward of ONE sequence over all layers:
    QK^T and PV are 2 T^2 d each, and the causal half of them is needed."""
    d, _, L, _ = _z(cfg)
    return L * 2.0 * seq_len * seq_len * d


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward per trained token: 6 x the blocks' weights,
    6 d V for the tied head, 3 x the causal attention forward.
    Recomputation is not counted."""
    d, _, _, v = _z(cfg)
    return (6.0 * matmul_params(cfg) + 6.0 * d * v
            + 3.0 * attn_fwd_flops_causal(cfg, seq_len) / seq_len)


def flash_least_s(cfg, rows: int, seq_len: int, itemsize: int, peaks: dict):
    """Least time for the attention kernels of one training step on one
    chip (``rows`` sequences): forward + backward is 3 x the causal
    forward FLOPs; it reads q, k, v and writes o forward (4 T d), and
    reads q, k, v, o, do and writes dq, dk, dv backward (8 T d)."""
    d, _, L, _ = _z(cfg)
    by_flops = rows * 3.0 * attn_fwd_flops_causal(cfg, seq_len) \
        / peaks["bf16_flops"]
    by_bytes = rows * L * 12.0 * seq_len * d * itemsize \
        / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        "flops" if by_flops >= by_bytes else "hbm_bytes"


# ---------------------------------------------------------------------- tiny
def tiny(deployment: bool = True) -> dict:
    """A small configuration of this architecture for the CPU tests
    (tests/benchmarks): float32, a wide init so that logits differ, and
    with ``deployment`` a four-slot engine."""
    cfg = {"name": "tiny", "reference": "opt", "hidden_size": 32,
           "num_hidden_layers": 2, "num_attention_heads": 4, "ffn_dim": 64,
           "vocab_size": 64, "max_position_embeddings": 64,
           "torch_dtype": "float32", "init_std": 0.2}
    if deployment:
        cfg["deployment"] = {"num_slots": 4, "page_size": 4,
                             "max_seq_len": 64, "num_pages": 80}
    return cfg
