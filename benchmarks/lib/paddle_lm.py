"""What the benchmark takes from the program: the system under test.
The only file of benchmarks/lib that imports ``paddle_tpu``. It hands the
program the weights that the reference's ``init_params`` makes from the
seed, under the names the program's parameter table uses.
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


def leaf_index(n_layers: int):
    """[(program name, reference leaf, layer or None)] of every leaf."""
    idx = [(program_name(k), k, None) for k in _TOP]
    for leaf in _LAYER:
        idx += [(program_name(leaf, i), leaf, i) for i in range(n_layers)]
    return idx


def make_weights(reference, seed: int, cfg: dict, dtype):
    """All weights on the device in ONE jitted call from the seed, in the
    type they are used in, under the program's names."""
    return jax.jit(lambda: to_named(
        reference.init_params(seed, cfg, dtype)))()


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
