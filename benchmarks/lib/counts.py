"""Operations and bytes that the algorithm needs, from the configuration's
shapes and exact counts of tokens - never from the program's own estimate,
so that a kernel change cannot move its own yardstick. ``cfg`` is a
``config.json``-shaped dict (hidden_size, ffn_dim, ...).
"""

from __future__ import annotations


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


def serve_flops(cfg, tokens_fed: int, cache_tokens_read: int) -> float:
    """Model FLOPs of a serving window: each token fed (prompt or output)
    costs 2 x the blocks' weights + the tied head (2 d V), and attention
    over its true cache length n costs 4 d n per layer (QK^T and PV);
    ``cache_tokens_read`` is the sum of n over all tokens fed."""
    d, _, L, v = _z(cfg)
    return (tokens_fed * (2.0 * matmul_params(cfg) + 2.0 * d * v)
            + 4.0 * d * L * cache_tokens_read)


def paged_attn_least_s(cfg, cache_tokens_read: int, itemsize: int,
                       peaks: dict):
    """Least time for the decode-attention kernel's work: it must read
    the K and V of every cached token it attends to, and do 4 d FLOPs per
    cached token and layer. -> (seconds, which bound binds)."""
    d, _, L, _ = _z(cfg)
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
