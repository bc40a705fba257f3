"""The program's side of the LFM2 block (``reference: "lfm2_moe"``):
everything the benchmark knows about this architecture beside its plain
reference (benchmarks/reference/lfm2_moe.py). The contract is that of
benchmarks/models/kimi_k2.py; this configuration is served only.

The served model is one chip of an expert-parallel deployment: the file's
``num_experts`` experts are HELD here, ``ep_ranks`` chips share each layer
(the router is ``num_experts * ep_ranks`` wide) and this chip is rank
``ep_rank``. Layer i is a gated short convolution where ``layer_types[i]``
is ``"conv"``, else full attention (grouped-query, per-head K/V).
"""

from __future__ import annotations

import jax

NAME = "tfm"

# A program from before PR 41 has no block of gated short convolutions
# beside per-head pages and cannot serve this architecture: say so when
# the cell is looked up, at once, not after 8 GB of weights have been made.
try:
    from paddle_tpu.models.block import ShortConvBlock
except ImportError as e:
    raise ImportError(
        "this program has no paddle_tpu.models.block.ShortConvBlock: it "
        "cannot serve a block of gated short convolutions beside per-head "
        "K/V pages") from e

#: reference leaf -> suffix of the program's parameter name
_LAYER = {"op_norm_g": "op_norm.w0", "ffn_norm_g": "ffn_norm.w0",
          "w_in": "conv_in.w0", "conv": "conv.w0", "w_out": "conv_out.w0",
          "w_q": "q.w0", "w_k": "k.w0", "w_v": "v.w0",
          "q_norm_g": "q_norm.w0", "k_norm_g": "k_norm.w0",
          "w_o": "proj.w0",
          "w_gate": "gate.w0", "w_up": "up.w0", "w_down": "down.w0",
          "router": "router.w0", "router_bias": "router.wbias",
          "e_gate": "experts.gate", "e_up": "experts.up",
          "e_down": "experts.down"}
_TOP = {"tok_emb": "tok_emb.w0", "head": "lm_head.w0",
        "norm_f_g": "norm_f.w0"}


def conv_layers(cfg) -> tuple:
    """The 0-based conv layers among the configuration's layers."""
    return tuple(i for i, t in enumerate(
        cfg["layer_types"][:int(cfg["num_hidden_layers"])]) if t == "conv")


def program_name(leaf: str, cfg: dict = None) -> str:
    """``l3.w_in`` -> ``_tfm_l3_conv_in.w0``."""
    if leaf in _TOP:
        return f"_{NAME}_{_TOP[leaf]}"
    layer, _, name = leaf.partition(".")
    return f"_{NAME}_{layer}_{_LAYER[name]}"


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
    return {program_name(name): make(
        lo, hi, i, name=reference.leaf_kind(name), shape=shape,
        std=reference.leaf_std(cfg, name))
        for i, (name, shape) in enumerate(sorted(
            reference.leaf_shapes(cfg).items()))}


def _z(cfg) -> dict:
    g = lambda k: int(cfg[k])
    n_conv = len(conv_layers(cfg))
    return {"d": g("hidden_size"), "f": g("intermediate_size"),
            "fm": g("moe_intermediate_size"), "H": g("num_attention_heads"),
            "G": g("num_key_value_heads"),
            "dh": int(cfg.get("head_dim") or
                      g("hidden_size") // g("num_attention_heads")),
            "L": g("num_hidden_layers"), "V": g("vocab_size"),
            "K": g("conv_L_cache"), "dense": g("num_dense_layers"),
            "held": g("num_experts"),
            "E": g("num_experts") * int(cfg.get("ep_ranks", 1)),
            "tied": bool(cfg.get("tie_word_embeddings", False)),
            "n_conv": n_conv, "n_attn": g("num_hidden_layers") - n_conv}


def block_of(cfg: dict, max_positions: int):
    """The configuration as the decoders' block description."""
    z = _z(cfg)
    return ShortConvBlock(
        n_heads=z["H"], head_dim=z["dh"], max_positions=int(max_positions),
        conv_layers=conv_layers(cfg), first_dense_layers=z["dense"],
        experts_per_token=int(cfg["num_experts_per_tok"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        route_eps=float(cfg.get("norm_topk_eps", 1e-6)),
        expert_rank=int(cfg.get("ep_rank", 0)),
        rms_eps=float(cfg["norm_eps"]),
        rope_theta=float(cfg["rope_theta"]))


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
def conv_params(cfg) -> int:
    """One conv layer's matrices a token passes: W_in [d, 3d], W_out."""
    d = _z(cfg)["d"]
    return d * 3 * d + d * d


def attn_params(cfg) -> int:
    """One attention layer's matrices: W_q, W_k, W_v, W_o."""
    z = _z(cfg)
    return (z["d"] * (z["H"] + 2 * z["G"]) * z["dh"]
            + z["H"] * z["dh"] * z["d"])


def expert_params(cfg) -> int:
    """One routed expert's three matrices."""
    z = _z(cfg)
    return 3 * z["d"] * z["fm"]


def dense_params_per_token(cfg) -> int:
    """The matrices EVERY token fed passes, over all layers and the head:
    both kinds of operator, the dense layers' FFN, the router of each
    expert layer, the head. The routed experts are counted by the
    assignments that fell on held experts."""
    z = _z(cfg)
    n_moe = z["L"] - z["dense"]
    return (z["n_conv"] * conv_params(cfg) + z["n_attn"] * attn_params(cfg)
            + z["dense"] * 3 * z["d"] * z["f"] + n_moe * z["d"] * z["E"]
            + z["d"] * z["V"])


def total_params(cfg) -> int:
    """Everything held on this chip: the above with the held experts, the
    embedding where the head is not tied to it, the norms' gains, the
    router's bias and the convolutions' taps."""
    z = _z(cfg)
    n_moe = z["L"] - z["dense"]
    norms = z["L"] * 2 * z["d"] + z["n_attn"] * 2 * z["dh"] + z["d"]
    return (dense_params_per_token(cfg)
            + (0 if z["tied"] else z["V"] * z["d"]) + norms
            + z["n_conv"] * z["K"] * z["d"]
            + n_moe * (z["held"] * expert_params(cfg) + z["E"]))


def kv_bytes_per_token(cfg, itemsize: int) -> int:
    """K and V of one cached token over the attention layers: what the
    algorithm keeps a token (the conv layers keep a tail a sequence,
    :func:`state_bytes_per_slot`)."""
    z = _z(cfg)
    return 2 * z["G"] * z["dh"] * itemsize * z["n_attn"]


def state_bytes_per_slot(cfg) -> int:
    """A sequence's float32 tails over the conv layers: the last K - 1
    inputs of each layer's convolution."""
    z = _z(cfg)
    return z["n_conv"] * (z["K"] - 1) * z["d"] * 4


def attn_flops_per_cached_token(cfg) -> float:
    """One attention layer, one cached token attended to by one token
    fed: q.k and p.v over every query head."""
    z = _z(cfg)
    return 4.0 * z["H"] * z["dh"]


def serve_flops(cfg, counters: dict) -> float:
    """Model FLOPs of a serving window: each token fed (the engine's
    ``tokens_fed``: slot rows and lane rows) costs 2 x the matrices it
    passes and 2 x the taps of each conv layer; each token-expert
    assignment that fell on a HELD expert 2 x one expert; attention over
    the true cache length, ``cache_tokens_read`` being the sum of it over
    all tokens fed, in the attention layers alone."""
    z = _z(cfg)
    fed = counters.get("tokens_fed", counters["active_slot_steps"])
    return (fed * (2.0 * dense_params_per_token(cfg)
                   + z["n_conv"] * 2.0 * z["K"] * z["d"])
            + counters.get("expert_assignments_held", 0) * 2.0
            * expert_params(cfg)
            + counters["cache_tokens_read"] * z["n_attn"]
            * attn_flops_per_cached_token(cfg))


def lane_tokens(cfg) -> int:
    """Tokens of a prefill lane: as many as fill the kernel's 128-row
    query tile at this configuration's heads (two kv heads of 64 a lane
    chunk x 4 query heads each: 16), ops/pallas_decode.py
    ``window_tile_tokens``."""
    z = _z(cfg)
    return max(1, 128 // (max(1, 128 // z["dh"]) * (z["H"] // z["G"])))


def paged_attn_least_s(cfg, counters: dict, itemsize: int, peaks: dict):
    """Time at the peaks for the paged attention kernel's OWN traffic over
    the attention layers, not the algorithm's floor: K and V of every
    cached token attended to, against its FLOPs. A decoding row reads its
    slot's cache for itself; the tokens of one prefill lane read it once
    together (the kernel brings a slot's pages once a LANE), so the lanes'
    share of ``cache_tokens_read`` (``prefill_lane_cache_tokens_read``,
    exact a token) counts a lane's width less in bytes, and whole in
    FLOPs. A part-filled lane is so counted low. What this is not: the
    algorithm needs one read a slot and step, and a 128-token suffix is 8
    lanes, 8 walks of the slot's cache, all counted here; the engine has
    no counter of the lane-fed slot-steps' cache lengths to count them
    once (PERF.md section 7, "Open after PR 41"). The accepted model
    files count every lane token's cache length whole
    (benchmarks/models/opt.py), which reads this cell's share half as
    high again: the two are not one quantity (docs/observability.md).
    -> (seconds, which bound binds)."""
    z = _z(cfg)
    read = counters["cache_tokens_read"]
    lanes = counters.get("prefill_lane_cache_tokens_read", 0)
    rows_read = read - lanes + lanes / lane_tokens(cfg)
    by_bytes = rows_read * kv_bytes_per_token(cfg, itemsize) \
        / peaks["hbm_bytes_per_s"]
    by_flops = read * z["n_attn"] * attn_flops_per_cached_token(cfg) \
        / peaks["bf16_flops"]
    return max(by_bytes, by_flops), \
        "hbm_bytes" if by_bytes >= by_flops else "flops"


def state_least_s(cfg, counters: dict, peaks: dict) -> float:
    """Least time for the convolution kernel's work on the tails: every
    slot-step's tails (``state_rows_stepped``, a slot fed by several lanes
    counted once) read and written once over the conv layers at the HBM
    peak."""
    return counters["state_rows_stepped"] * 2.0 * state_bytes_per_slot(cfg) \
        / peaks["hbm_bytes_per_s"]


def held_experts(cfg) -> int:
    return _z(cfg)["held"]


# ---------------------------------------------------------------------- tiny
def tiny(deployment: bool = True) -> dict:
    """The same architecture small, for the CPU tests: two periods of the
    published pattern (conv, conv, attention, conv), 2 dense layers then
    expert layers, 8 routed experts of which 2 are held (rank 1 of 4),
    top-2; GQA 4 on 2; 128 channels so that the convolution kernel
    (interpreted) takes them; float32, a wide init."""
    cfg = {"name": "tiny", "reference": "lfm2_moe", "hidden_size": 128,
           "intermediate_size": 160, "moe_intermediate_size": 48,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 8, "vocab_size": 64, "conv_L_cache": 3,
           "layer_types": ["conv", "conv", "full_attention", "conv"] * 2,
           "num_dense_layers": 2, "num_experts": 2, "ep_ranks": 4,
           "ep_rank": 1, "num_experts_per_tok": 2,
           "routed_scaling_factor": 1.0, "norm_eps": 1e-5,
           "rope_theta": 1000000.0, "tie_word_embeddings": False,
           "torch_dtype": "float32", "init_std": 0.2,
           "conv_init_std": 0.333}
    if deployment:
        cfg["deployment"] = {"num_slots": 4, "page_size": 4,
                             "max_seq_len": 64, "num_pages": 80,
                             "state_snapshots": 6}
    return cfg
