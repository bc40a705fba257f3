"""The program's side of the Kimi-K2 block (``reference: "kimi_k2"``):
everything the benchmark knows about this architecture beside its plain
reference (benchmarks/reference/kimi_k2.py). The contract is that of
benchmarks/models/opt.py (PERF.md section 3); this configuration is
served only, so the trainer's functions are not here.

The served model is one chip of an expert-parallel deployment: the file's
``n_routed_experts`` experts are HELD here, ``ep_ranks`` chips share each
layer (the router is ``n_routed_experts * ep_ranks`` wide) and this chip
is rank ``ep_rank``.
"""

from __future__ import annotations

import os

import jax

NAME = "tfm"

# A program from before PR 35 has no block descriptions and cannot serve
# this architecture: say so when the cell is looked up, at once, not after
# 8 GB of weights have been made.
_BLOCKS = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "paddle_tpu", "models", "block.py")
if not os.path.isfile(_BLOCKS):
    raise ImportError(f"this program has no {_BLOCKS}: it cannot serve a "
                      f"latent-attention block")

#: reference leaf -> suffix of the program's parameter name
_LAYER = {"attn_norm_g": "attn_norm.w0", "w_dq": "q_down.w0",
          "q_norm_g": "q_norm.w0", "w_uq": "q_up.w0",
          "w_dkv": "kv_down.w0", "kv_norm_g": "kv_norm.w0",
          "w_ukv": "kv_up.w0", "w_o": "proj.w0",
          "ffn_norm_g": "ffn_norm.w0", "w_gate": "gate.w0",
          "w_up": "up.w0", "w_down": "down.w0", "router": "router.w0",
          "router_bias": "router.wbias", "e_gate": "experts.gate",
          "e_up": "experts.up", "e_down": "experts.down",
          "s_gate": "shared.gate", "s_up": "shared.up",
          "s_down": "shared.down"}
_TOP = {"tok_emb": "tok_emb.w0", "head": "lm_head.w0",
        "norm_f_g": "norm_f.w0"}


def program_name(leaf: str) -> str:
    """``l3.w_dq`` -> ``_tfm_l3_q_down.w0``; ``head`` -> ``_tfm_lm_head.w0``."""
    if leaf in _TOP:
        return f"_{NAME}_{_TOP[leaf]}"
    layer, _, name = leaf.partition(".")
    return f"_{NAME}_{layer}_{_LAYER[name]}"


def make_weights(reference, seed: int, cfg: dict, dtype):
    """The reference's weights under the program's names, LEAF BY LEAF:
    one jitted call a leaf, the seed's words and the leaf's index as
    arguments, so that leaves of one shape share a program, a new seed
    compiles nothing, and no leaf ever stands twice beside 8 GB."""
    make = jax.jit(
        lambda lo, hi, index, name, shape, std: reference.make_leaf(
            (lo, hi), index, name, shape, dtype, std),
        static_argnames=("name", "shape", "std"))
    lo, hi = reference.seed_words(seed)
    # the static name says only whether the leaf is a gain: leaves of one
    # shape, kind and scale are then one compiled program
    return {program_name(name): make(
        lo, hi, i, name="_g" if name.endswith("_g") else "", shape=shape,
        std=reference.leaf_std(cfg, name))
        for i, (name, shape) in enumerate(sorted(
            reference.leaf_shapes(cfg).items()))}


def block_of(cfg: dict, max_positions: int):
    """The configuration as the decoders' block description."""
    from paddle_tpu.models.block import LatentBlock
    return LatentBlock(
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        max_positions=int(max_positions),
        first_dense_layers=int(cfg["first_k_dense_replace"]),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        expert_rank=int(cfg.get("ep_rank", 0)),
        rms_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(cfg.get("rope_factor", 1.0)),
        rope_original_max=int(
            cfg.get("rope_original_max_position_embeddings", 4096)),
        rope_beta_fast=float(cfg.get("rope_beta_fast", 32.0)),
        rope_beta_slow=float(cfg.get("rope_beta_slow", 1.0)),
        rope_mscale=float(cfg.get("rope_mscale", 1.0)),
        rope_mscale_all_dim=float(cfg.get("rope_mscale_all_dim", 0.0)))


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
        max_waiting=1 << 30)
    return dec, eng


# -------------------------------------------------------------------- counts
def _z(cfg) -> dict:
    g = lambda k: int(cfg[k])
    return {"d": g("hidden_size"), "f": g("intermediate_size"),
            "fm": g("moe_intermediate_size"), "H": g("num_attention_heads"),
            "L": g("num_hidden_layers"), "V": g("vocab_size"),
            "rq": g("q_lora_rank"), "rkv": g("kv_lora_rank"),
            "dn": g("qk_nope_head_dim"), "dr": g("qk_rope_head_dim"),
            "dv": g("v_head_dim"), "dense": g("first_k_dense_replace"),
            "held": g("n_routed_experts"),
            "E": g("n_routed_experts") * int(cfg.get("ep_ranks", 1)),
            "shared": g("n_shared_experts")}


def attn_params(cfg) -> int:
    """One layer's attention matrices: W_dq, W_uq, W_dkv, W_ukv (the 64
    W_uk_i and W_uv_i of the absorbed form are its columns), W_o."""
    z = _z(cfg)
    return (z["d"] * z["rq"] + z["rq"] * z["H"] * (z["dn"] + z["dr"])
            + z["d"] * (z["rkv"] + z["dr"])
            + z["rkv"] * z["H"] * (z["dn"] + z["dv"])
            + z["H"] * z["dv"] * z["d"])


def expert_params(cfg) -> int:
    """One routed expert's three matrices."""
    z = _z(cfg)
    return 3 * z["d"] * z["fm"]


def dense_params_per_token(cfg) -> int:
    """The matrices EVERY token fed passes, over all layers and the head:
    attention, the dense layers' FFN, the router and the shared expert of
    each expert layer, the untied head. The routed experts are not in it:
    they are counted by the assignments that fell on held experts."""
    z = _z(cfg)
    n_moe = z["L"] - z["dense"]
    return (z["L"] * attn_params(cfg) + z["dense"] * 3 * z["d"] * z["f"]
            + n_moe * (z["d"] * z["E"] + z["shared"] * expert_params(cfg))
            + z["d"] * z["V"])


def total_params(cfg) -> int:
    """Everything held on this chip: the above with the held experts, the
    embedding, the norms' gains and the router's bias."""
    z = _z(cfg)
    n_moe = z["L"] - z["dense"]
    norms = z["L"] * (2 * z["d"] + z["rq"] + z["rkv"]) + z["d"]
    return (dense_params_per_token(cfg) + z["V"] * z["d"] + norms
            + n_moe * (z["held"] * expert_params(cfg) + z["E"]))


def kv_bytes_per_token(cfg, itemsize: int) -> int:
    """The latent row [c_kv | k_rope] of one cached token over all layers:
    what the algorithm keeps (the pool pads it to whole lane tiles)."""
    z = _z(cfg)
    return (z["rkv"] + z["dr"]) * itemsize * z["L"]


def attn_flops_per_cached_token(cfg) -> float:
    """The absorbed form, one layer, one cached token attended to by one
    token fed: every head's score (rkv + dr lanes) and its weighted sum of
    c_kv (rkv lanes), 2 FLOPs each."""
    z = _z(cfg)
    return 2.0 * z["H"] * (2 * z["rkv"] + z["dr"])


def serve_flops(cfg, counters: dict) -> float:
    """Model FLOPs of a serving window: each token fed (the engine's
    ``active_slot_steps``) costs 2 x the matrices it passes; each
    token-expert assignment that fell on a HELD expert (the engine's
    ``expert_assignments_held``, counted by the step itself, not reckoned
    from k x held / E) 2 x one expert; attention over the true cache
    length n 2 H (2 rkv + dr) n per layer, ``cache_tokens_read`` being the
    sum of n over all tokens fed."""
    z = _z(cfg)
    return (counters["active_slot_steps"] * 2.0 * dense_params_per_token(cfg)
            + counters.get("expert_assignments_held", 0) * 2.0
            * expert_params(cfg)
            + counters["cache_tokens_read"] * z["L"]
            * attn_flops_per_cached_token(cfg))


def paged_attn_least_s(cfg, counters: dict, itemsize: int, peaks: dict):
    """Least time for the latent attention kernel's work: the latent row
    of every cached token attended to, read once (it is key and value),
    against the absorbed form's FLOPs. -> (seconds, which bound binds)."""
    z = _z(cfg)
    read = counters["cache_tokens_read"]
    by_bytes = read * kv_bytes_per_token(cfg, itemsize) \
        / peaks["hbm_bytes_per_s"]
    by_flops = read * z["L"] * attn_flops_per_cached_token(cfg) \
        / peaks["bf16_flops"]
    return max(by_bytes, by_flops), \
        "hbm_bytes" if by_bytes >= by_flops else "flops"



def held_experts(cfg) -> int:
    return _z(cfg)["held"]


# ---------------------------------------------------------------------- tiny
def tiny(deployment: bool = True) -> dict:
    """The same architecture small, for the CPU tests: 1 dense + 2 expert
    layers, 16 routed experts of which 4 are held (rank 1 of 4), top-2,
    YaRN past an original context of 16; float32, a wide init."""
    cfg = {"name": "tiny", "reference": "kimi_k2", "hidden_size": 64,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_attention_heads": 4, "num_hidden_layers": 3,
           "vocab_size": 64, "q_lora_rank": 24, "kv_lora_rank": 16,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "first_k_dense_replace": 1, "n_routed_experts": 4, "ep_ranks": 4,
           "ep_rank": 1, "num_experts_per_tok": 2, "n_shared_experts": 1,
           "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
           "rope_theta": 50000, "rope_factor": 32,
           "rope_original_max_position_embeddings": 16, "rope_beta_fast": 1,
           "rope_beta_slow": 1, "rope_mscale": 1, "rope_mscale_all_dim": 1,
           "max_position_embeddings": 64, "torch_dtype": "float32",
           "init_std": 0.2}
    if deployment:
        cfg["deployment"] = {"num_slots": 4, "page_size": 4,
                             "max_seq_len": 64, "num_pages": 80}
    return cfg
