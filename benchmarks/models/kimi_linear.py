"""The program's side of the Kimi-Linear block (``reference:
"kimi_linear"``): everything the benchmark knows about this architecture
beside its plain reference (benchmarks/reference/kimi_linear.py). The
contract is that of benchmarks/models/kimi_k2.py; this configuration is
served only.

The served model is one chip of an expert-parallel deployment: the file's
``num_experts`` experts are HELD here, ``ep_ranks`` chips share each layer
(the router is ``num_experts * ep_ranks`` wide) and this chip is rank
``ep_rank``. Layer i (0-based) is a KDA layer where ``i + 1`` is in
``kda_layers``, else an MLA layer.
"""

from __future__ import annotations

import os

import jax

NAME = "tfm"

# A program from before PR 39 has no block with a recurrent state and
# cannot serve this architecture: say so when the cell is looked up, at
# once, not after 4 GB of weights have been made.
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_KERNEL = os.path.join(_ROOT, "paddle_tpu", "ops", "pallas_kda.py")
if not os.path.isfile(_KERNEL):
    raise ImportError(f"this program has no {_KERNEL}: it cannot serve a "
                      f"block with a recurrent (delta-rule) state")

#: reference leaf -> suffix of the program's parameter name
_KDA = {"w_q": "kda_q.w0", "w_k": "kda_k.w0", "w_v": "kda_v.w0",
        "conv_q": "kda_q_conv.w0", "conv_k": "kda_k_conv.w0",
        "conv_v": "kda_v_conv.w0", "w_fa": "kda_f_a.w0",
        "w_fb": "kda_f_b.w0", "a_log": "kda_a_log.w0",
        "dt_bias": "kda_dt_bias.w0", "w_beta": "kda_beta.w0",
        "w_ga": "kda_g_a.w0", "w_gb": "kda_g_b.w0",
        "o_norm_g": "kda_o_norm.w0", "w_o": "proj.w0"}
_MLA = {"w_q": "q.w0", "w_dkv": "kv_down.w0", "kv_norm_g": "kv_norm.w0",
        "w_ukv": "kv_up.w0", "w_o": "proj.w0"}
_LAYER = {"attn_norm_g": "attn_norm.w0", "ffn_norm_g": "ffn_norm.w0",
          "w_gate": "gate.w0", "w_up": "up.w0", "w_down": "down.w0",
          "router": "router.w0", "router_bias": "router.wbias",
          "e_gate": "experts.gate", "e_up": "experts.up",
          "e_down": "experts.down", "s_gate": "shared.gate",
          "s_up": "shared.up", "s_down": "shared.down"}
_TOP = {"tok_emb": "tok_emb.w0", "head": "lm_head.w0",
        "norm_f_g": "norm_f.w0"}


def kda_layers(cfg) -> tuple:
    """The 0-based KDA layers among the configuration's layers."""
    return tuple(i for i in range(int(cfg["num_hidden_layers"]))
                 if i + 1 in cfg["kda_layers"])


def program_name(leaf: str, cfg: dict) -> str:
    """``l3.w_dkv`` -> ``_tfm_l3_kv_down.w0``; ``l0.w_q`` ->
    ``_tfm_l0_kda_q.w0`` (layer 0 is a KDA layer)."""
    if leaf in _TOP:
        return f"_{NAME}_{_TOP[leaf]}"
    layer, _, name = leaf.partition(".")
    own = _KDA if int(layer[1:]) in kda_layers(cfg) else _MLA
    return f"_{NAME}_{layer}_{own.get(name) or _LAYER[name]}"


def make_weights(reference, seed: int, cfg: dict, dtype):
    """The reference's weights under the program's names, LEAF BY LEAF:
    one jitted call a leaf, the seed's words and the leaf's index as
    arguments, so that leaves of one shape and kind share a program and a
    new seed compiles nothing."""
    make = jax.jit(
        lambda lo, hi, index, name, shape, std: reference.make_leaf(
            (lo, hi), index, name, shape, dtype, std),
        static_argnames=("name", "shape", "std"))
    lo, hi = reference.seed_words(seed)
    return {program_name(name, cfg): make(
        lo, hi, i, name=reference.leaf_kind(name), shape=shape,
        std=reference.leaf_std(cfg, name))
        for i, (name, shape) in enumerate(sorted(
            reference.leaf_shapes(cfg).items()))}


def block_of(cfg: dict, max_positions: int):
    """The configuration as the decoders' block description."""
    from paddle_tpu.models.block import DeltaLatentBlock
    return DeltaLatentBlock(
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        max_positions=int(max_positions),
        first_dense_layers=int(cfg["first_k_dense_replace"]),
        experts_per_token=int(cfg["num_experts_per_token"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        expert_rank=int(cfg.get("ep_rank", 0)),
        rms_eps=float(cfg["rms_norm_eps"]),
        rotary=not cfg.get("mla_use_nope", False),
        state_layers=kda_layers(cfg),
        state_heads=int(cfg["kda_num_heads"]))


def build_engine(named: dict, cfg: dict, deployment: dict):
    """The served model as its users build it: TransformerDecoder over the
    parameter table with the block's description, DecodeEngine with its
    default attention."""
    from paddle_tpu import models
    from paddle_tpu.serving import DecodeEngine
    dec = models.TransformerDecoder(
        named, n_layers=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]), name=NAME,
        block=block_of(cfg, int(deployment["max_seq_len"])))
    eng = DecodeEngine(
        dec, num_slots=int(deployment["num_slots"]),
        page_size=int(deployment["page_size"]),
        num_pages=int(deployment["num_pages"]),
        max_seq_len=int(deployment["max_seq_len"]),
        state_snapshots=int(deployment["state_snapshots"]),
        max_waiting=1 << 30)
    return dec, eng


# -------------------------------------------------------------------- counts
def _z(cfg) -> dict:
    g = lambda k: int(cfg[k])
    n_kda = len(kda_layers(cfg))
    return {"d": g("hidden_size"), "f": g("intermediate_size"),
            "fm": g("moe_intermediate_size"), "H": g("num_attention_heads"),
            "L": g("num_hidden_layers"), "V": g("vocab_size"),
            "rkv": g("kv_lora_rank"), "dn": g("qk_nope_head_dim"),
            "dr": g("qk_rope_head_dim"), "dv": g("v_head_dim"),
            "dense": g("first_k_dense_replace"), "held": g("num_experts"),
            "E": g("num_experts") * int(cfg.get("ep_ranks", 1)),
            "shared": g("num_shared_experts"), "Hk": g("kda_num_heads"),
            "dk": g("kda_head_dim"), "conv": g("kda_short_conv_kernel_size"),
            "r": g("kda_gate_rank"), "n_kda": n_kda,
            "n_mla": g("num_hidden_layers") - n_kda}


def kda_params(cfg) -> int:
    """One KDA layer's matrices a token passes: W_q, W_k, W_v, W_o, the
    two low-rank pairs (decay, output gate) and W_beta."""
    z = _z(cfg)
    c = z["Hk"] * z["dk"]
    return 4 * z["d"] * c + 2 * (z["d"] * z["r"] + z["r"] * c) \
        + z["d"] * z["Hk"]


def kda_small_params(cfg) -> int:
    """A KDA layer's leaves that are no matrix product: the three
    convolutions, A_log, dt_bias, the output norm's gain."""
    z = _z(cfg)
    c = z["Hk"] * z["dk"]
    return 3 * z["conv"] * c + z["Hk"] + c + z["dk"]


def attn_params(cfg) -> int:
    """One MLA layer's attention matrices: W_q, W_dkv, W_ukv, W_o."""
    z = _z(cfg)
    return (z["d"] * z["H"] * (z["dn"] + z["dr"])
            + z["d"] * (z["rkv"] + z["dr"])
            + z["rkv"] * z["H"] * (z["dn"] + z["dv"])
            + z["H"] * z["dv"] * z["d"])


def expert_params(cfg) -> int:
    """One routed expert's three matrices."""
    z = _z(cfg)
    return 3 * z["d"] * z["fm"]


def dense_params_per_token(cfg) -> int:
    """The matrices EVERY token fed passes, over all layers and the head:
    both kinds of attention, the dense layers' FFN, the router and the
    shared expert of each expert layer, the untied head. The routed
    experts are counted by the assignments that fell on held experts."""
    z = _z(cfg)
    n_moe = z["L"] - z["dense"]
    return (z["n_kda"] * kda_params(cfg) + z["n_mla"] * attn_params(cfg)
            + z["dense"] * 3 * z["d"] * z["f"]
            + n_moe * (z["d"] * z["E"] + z["shared"] * expert_params(cfg))
            + z["d"] * z["V"])


def total_params(cfg) -> int:
    """Everything held on this chip: the above with the held experts, the
    embedding, the norms' gains, the router's bias and the KDA layers'
    small leaves."""
    z = _z(cfg)
    n_moe = z["L"] - z["dense"]
    norms = z["L"] * 2 * z["d"] + z["n_mla"] * z["rkv"] + z["d"]
    return (dense_params_per_token(cfg) + z["V"] * z["d"] + norms
            + z["n_kda"] * kda_small_params(cfg)
            + n_moe * (z["held"] * expert_params(cfg) + z["E"]))


def kv_bytes_per_token(cfg, itemsize: int) -> int:
    """The latent row [c_kv | k_r] of one cached token over the MLA
    layers: what the algorithm keeps a token (the KDA layers keep a state
    a sequence, :func:`state_bytes_per_slot`)."""
    z = _z(cfg)
    return (z["rkv"] + z["dr"]) * itemsize * z["n_mla"]


def state_bytes_per_slot(cfg) -> int:
    """A sequence's float32 state over the KDA layers: a [dk, dv] matrix a
    head (the convolutions' tails are a thousandth of it and not
    counted)."""
    z = _z(cfg)
    return z["n_kda"] * z["Hk"] * z["dk"] * z["dk"] * 4


def attn_flops_per_cached_token(cfg) -> float:
    """The absorbed form, one MLA layer, one cached token attended to by
    one token fed."""
    z = _z(cfg)
    return 2.0 * z["H"] * (2 * z["rkv"] + z["dr"])


def state_flops_per_token(cfg) -> float:
    """One KDA layer, one token fed: three passes over a head's state
    (the prediction S'^T k, the rank-one update, the read S^T q), 2 FLOPs
    an element each."""
    z = _z(cfg)
    return 6.0 * z["Hk"] * z["dk"] * z["dk"]


def serve_flops(cfg, counters: dict) -> float:
    """Model FLOPs of a serving window: each token fed (the engine's
    ``tokens_fed``: slot rows and lane rows) costs 2 x the matrices it
    passes and three passes over each KDA layer's state; each
    token-expert assignment that fell on a HELD expert 2 x one expert;
    latent attention over the true cache length, ``cache_tokens_read``
    being the sum of it over all tokens fed, in the MLA layers alone."""
    z = _z(cfg)
    fed = counters.get("tokens_fed", counters["active_slot_steps"])
    return (fed * (2.0 * dense_params_per_token(cfg)
                   + z["n_kda"] * state_flops_per_token(cfg))
            + counters.get("expert_assignments_held", 0) * 2.0
            * expert_params(cfg)
            + counters["cache_tokens_read"] * z["n_mla"]
            * attn_flops_per_cached_token(cfg))


def paged_attn_least_s(cfg, counters: dict, itemsize: int, peaks: dict):
    """Least time for the latent attention kernel's work over the MLA
    layers: the latent row of every cached token attended to, read once,
    against the absorbed form's FLOPs. -> (seconds, which bound binds)."""
    z = _z(cfg)
    read = counters["cache_tokens_read"]
    by_bytes = read * kv_bytes_per_token(cfg, itemsize) \
        / peaks["hbm_bytes_per_s"]
    by_flops = read * z["n_mla"] * attn_flops_per_cached_token(cfg) \
        / peaks["bf16_flops"]
    return max(by_bytes, by_flops), \
        "hbm_bytes" if by_bytes >= by_flops else "flops"


def state_least_s(cfg, counters: dict, peaks: dict) -> float:
    """Least time for the state kernel's work: every slot-step's state
    (``state_rows_stepped``, a slot fed by several lanes counted once)
    read and written once over the KDA layers at the HBM peak. Its FLOPs
    are a thousand times less time."""
    return counters["state_rows_stepped"] * 2.0 * state_bytes_per_slot(cfg) \
        / peaks["hbm_bytes_per_s"]


def held_experts(cfg) -> int:
    return _z(cfg)["held"]


# ---------------------------------------------------------------------- tiny
def tiny(deployment: bool = True) -> dict:
    """The same architecture small, for the CPU tests: one period (a dense
    KDA layer, two KDA expert layers, one MLA expert layer), 16 routed
    experts of which 4 are held (rank 1 of 4), top-2; KDA heads at the
    published 128 so that the state kernel (interpreted) takes them;
    float32, a wide init."""
    cfg = {"name": "tiny", "reference": "kimi_linear", "hidden_size": 64,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_attention_heads": 4, "num_hidden_layers": 4,
           "vocab_size": 64, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16,
           "first_k_dense_replace": 1, "num_experts": 4, "ep_ranks": 4,
           "ep_rank": 1, "num_experts_per_token": 2,
           "num_shared_experts": 1, "routed_scaling_factor": 2.446,
           "rms_norm_eps": 1e-5, "mla_use_nope": True,
           "kda_layers": [1, 2, 3], "kda_num_heads": 2, "kda_head_dim": 128,
           "kda_short_conv_kernel_size": 4, "kda_gate_rank": 8,
           "torch_dtype": "float32", "init_std": 0.2}
    if deployment:
        cfg["deployment"] = {"num_slots": 4, "page_size": 4,
                             "max_seq_len": 64, "num_pages": 80,
                             "state_snapshots": 6}
    return cfg
