"""Plain reference of the Kimi-K2 block (moonshotai/Kimi-K2-Instruct
``config.json``, ``model_type: kimi_k2``: the DeepSeek-V3 block), in
straightforward ``jax.numpy`` and float32 at ``precision=highest``: the
UNABSORBED attention over the whole sequence, no cache, no kernel, no
batching. It imports nothing of the program under test and makes its own
weights from the seed.

    x = tok_emb[ids]
    per layer:  x = x + MLA(RMSNorm(x));  x = x + FFN_i(RMSNorm(x))
    logits = RMSNorm(x) . head^T                      (untied head)

    MLA   c_q = RMSNorm(h W_dq); q = c_q W_uq -> heads of [q_nope | q_rope]
          [c' | k_r] = h W_dkv; c_kv = RMSNorm(c'); k_rope = RoPE(k_r), one
          a token for all heads; [k_nope_i | v_i] = c_kv W_ukv per head i
          score_i = (q_nope_i . k_nope_i + RoPE(q_rope_i) . k_rope) * sigma
    FFN   layer < first_k_dense_replace: SwiGLU at intermediate_size; later
          s = sigmoid(h W_g); idx = top-k(s + bias) over ALL router outputs;
          w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor;
          y = sum_j w_j E_idx_j(h) + E_shared(h)

**The chip's share.** ``n_routed_experts`` counts the experts HELD here;
``ep_ranks`` chips share each layer and this is rank ``ep_rank``, so the
router is ``n_routed_experts * ep_ranks`` wide, its top-k and its
normalisation are over all of them, and only the terms of the experts
``[n * ep_rank, n * ep_rank + n)`` are added (the shared expert whole).
What the absent experts would add is left out, and that partial sum goes
on to the next layer.

Departure from the published code, listed in the configuration file under
``assumed``: the rotary lanes are not de-interleaved first (on random
weights a relabelling of W_uq's and W_dkv's columns).

What the deployment STORES is taken as stored: the weights in the
configuration's ``torch_dtype`` (made so by the harness) and each token's
cache row [c_kv | k_rope] rounded to it; everything computed from them is
float32.

``rounding`` is the control of the benchmark's ``correct``: with
``"fp8"`` every matrix product takes both operands rounded to
float8_e4m3 (scaled per tensor, accumulated in float32), the nearest
precision below the configuration's bfloat16; ``"bf16"`` rounds them to
bfloat16 (what the served precision does to an operand), for counting
how often it moves the router's choice (``held_expert_sets``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02


def sizes(cfg: dict) -> dict:
    """The block's sizes from the configuration's top-level scalars (the
    harness hands ``forward`` no nested dict)."""
    g = lambda k, d=None: cfg[k] if d is None else cfg.get(k, d)
    held = int(g("n_routed_experts"))
    ranks = int(g("ep_ranks", 1))
    return {
        "d": int(g("hidden_size")), "f": int(g("intermediate_size")),
        "fm": int(g("moe_intermediate_size")),
        "H": int(g("num_attention_heads")),
        "L": int(g("num_hidden_layers")), "V": int(g("vocab_size")),
        "rq": int(g("q_lora_rank")), "rkv": int(g("kv_lora_rank")),
        "dn": int(g("qk_nope_head_dim")), "dr": int(g("qk_rope_head_dim")),
        "dv": int(g("v_head_dim")),
        "dense": int(g("first_k_dense_replace")),
        "held": held, "E": held * ranks, "lo": held * int(g("ep_rank", 0)),
        "k": int(g("num_experts_per_tok")),
        "shared": int(g("n_shared_experts")),
        "route_scale": float(g("routed_scaling_factor")),
        "eps": float(g("rms_norm_eps")),
        "theta": float(g("rope_theta")),
        "factor": float(g("rope_factor", 1.0)),
        "orig": int(g("rope_original_max_position_embeddings", 4096)),
        "beta_fast": float(g("rope_beta_fast", 32.0)),
        "beta_slow": float(g("rope_beta_slow", 1.0)),
        "mscale": float(g("rope_mscale", 1.0)),
        "mscale_all_dim": float(g("rope_mscale_all_dim", 0.0)),
        "stored": str(g("torch_dtype", "float32")),
    }


# ------------------------------------------------------------------- weights
def seed_words(seed) -> tuple:
    """The two uint32 words a key is made from (the seed's low 31 bits,
    the rest); a pair, traced or not, passes through."""
    if isinstance(seed, tuple):
        return seed
    seed = int(seed)
    return np.uint32(seed & 0x7FFFFFFF), np.uint32(seed >> 31)


def seed_key(seed):
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.key(lo, impl="rbg"), hi)


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: shape} of every leaf, layer by layer (``l<i>.<leaf>``):
    nothing is stacked over layers, so that no leaf is larger than one
    layer's experts. A name that ends in ``_g`` is a gain (near 1)."""
    z = sizes(cfg)
    d, H = z["d"], z["H"]
    out = {"tok_emb": (z["V"], d), "head": (z["V"], d), "norm_f_g": (d,)}
    for i in range(z["L"]):
        lay = {"attn_norm_g": (d,), "w_dq": (d, z["rq"]),
               "q_norm_g": (z["rq"],),
               "w_uq": (z["rq"], H * (z["dn"] + z["dr"])),
               "w_dkv": (d, z["rkv"] + z["dr"]), "kv_norm_g": (z["rkv"],),
               "w_ukv": (z["rkv"], H * (z["dn"] + z["dv"])),
               "w_o": (H * z["dv"], d), "ffn_norm_g": (d,)}
        if i < z["dense"]:
            lay.update(w_gate=(d, z["f"]), w_up=(d, z["f"]),
                       w_down=(z["f"], d))
        else:
            fs = z["fm"] * z["shared"]
            lay.update(router=(d, z["E"]), router_bias=(z["E"],),
                       e_gate=(z["held"], d, z["fm"]),
                       e_up=(z["held"], d, z["fm"]),
                       e_down=(z["held"], z["fm"], d),
                       s_gate=(d, fs), s_up=(d, fs), s_down=(fs, d))
        out.update({f"l{i}.{k}": v for k, v in lay.items()})
    return out


def leaf_std(cfg: dict, name: str) -> float:
    """N(0, init_std) for every leaf but the embedding, which a
    configuration may draw at a scale of its own
    (``embedding_init_std``)."""
    std = float(cfg.get("init_std", INIT_STD))
    return float(cfg.get("embedding_init_std", std)) if name == "tok_emb" \
        else std


def make_leaf(words, index, name: str, shape, dtype, std: float):
    """One leaf from the seed's words and its index among the sorted
    names: gains 1 + N(0, std), everything else N(0, std) (the router's
    bias too, so that it takes part in the choice). ``index`` may be
    traced: leaves of one shape are then one program."""
    key = jax.random.fold_in(seed_key(words), index)
    r = jax.random.normal(key, shape, jnp.float32) * std
    if name.endswith("_g"):
        r = 1.0 + r
    return r.astype(dtype)


def init_params(seed, cfg: dict, dtype=jnp.float32) -> dict:
    """All weights from the seed (a whole number or its ``seed_words``)."""
    words = seed_words(seed)
    return {name: make_leaf(words, i, name, shape, dtype,
                            leaf_std(cfg, name))
            for i, (name, shape) in enumerate(sorted(
                leaf_shapes(cfg).items()))}


# ------------------------------------------------------------------ rounding
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUNDINGS = {None: lambda x: x, "fp8": _fp8,
             "bf16": lambda x: jax.lax.reduce_precision(x, 8, 7)}


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _f32(a):
    return a.astype(jnp.float32)


# --------------------------------------------------------------------- parts
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def yarn_inv_freq(z: dict) -> np.ndarray:
    """The rotary lanes' frequencies under YaRN, [dr / 2] float64:
    ``f_j = theta^(-2j/dr)`` interpolated (divided by ``factor``) where
    the ramp is 1, kept where it is 0. ``factor`` 1 is plain RoPE."""
    dr = z["dr"]
    f = z["theta"] ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    if z["factor"] <= 1.0:
        return f

    def cd(beta):     # the lane whose wavelength makes beta turns in `orig`
        return dr * math.log(z["orig"] / (beta * 2 * math.pi)) / (
            2 * math.log(z["theta"]))
    low = max(math.floor(cd(z["beta_fast"])), 0)
    high = min(math.ceil(cd(z["beta_slow"])), dr - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dr // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return f / z["factor"] * ramp + f * (1.0 - ramp)


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(z: dict) -> float:
    """sigma = (dn + dr)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1."""
    m = _yarn_mscale(z["factor"], z["mscale_all_dim"]) \
        if z["mscale_all_dim"] else 1.0
    return (z["dn"] + z["dr"]) ** -0.5 * m * m


def rope(x, z: dict):
    """x [..., T, dr] rotated by its position 0..T-1, rotate-half pairing
    (j, j + dr/2); the cos/sin factor mscale / mscale_all_dim. The
    angles' cosines and sines are made on the host in float64 (the chip's
    float32 cosine of a thousand radians is off in the fourth digit)."""
    ang = np.outer(np.arange(x.shape[-2], dtype=np.float64),
                   yarn_inv_freq(z))                       # [T, dr/2], host
    m = _yarn_mscale(z["factor"], z["mscale"]) / \
        _yarn_mscale(z["factor"], z["mscale_all_dim"] or 0.0)
    cos = jnp.asarray(np.tile(np.cos(ang), 2) * m, jnp.float32)
    sin = jnp.asarray(np.tile(np.sin(ang), 2) * m, jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(h, w, z: dict, rnd):
    """MLA over h [T, d], causal over all T, one head at a time."""
    t = h.shape[0]
    H, dn, dr, dv, rkv = z["H"], z["dn"], z["dr"], z["dv"], z["rkv"]
    c_q = rms_norm(_mm(h, _f32(w["w_dq"]), rnd), _f32(w["q_norm_g"]),
                   z["eps"])
    q = _mm(c_q, _f32(w["w_uq"]), rnd).reshape(t, H, dn + dr)
    q = q.transpose(1, 0, 2)                                # [H, T, .]
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], z)
    ckr = _mm(h, _f32(w["w_dkv"]), rnd)
    # a token's cache row, [c_kv | k_rope], as the deployment STORES it
    # (``torch_dtype``, like the weights); nothing else is rounded
    # (reduce_precision, not a cast there and back: the compiler may drop
    # such a pair as excess precision it is allowed to keep)
    fi = jnp.finfo(jnp.dtype(z["stored"]))
    stored = lambda a: jax.lax.reduce_precision(a, fi.nexp, fi.nmant)
    c_kv = stored(rms_norm(ckr[:, :rkv], _f32(w["kv_norm_g"]), z["eps"]))
    k_rope = stored(rope(ckr[:, rkv:], z))                  # [T, dr]
    kv = _mm(c_kv, _f32(w["w_ukv"]), rnd).reshape(t, H, dn + dv)
    kv = kv.transpose(1, 0, 2)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    causal = jnp.tril(jnp.ones((t, t), jnp.bool_))
    sigma = softmax_scale(z)

    def head(a):
        qn, qr, kn, vv = a
        s = (_mm(qn, kn.T, rnd) + _mm(qr, k_rope.T, rnd)) * sigma
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm(p, vv, rnd)

    o = jax.lax.map(head, (q_nope, q_rope, k_nope, v))      # [H, T, dv]
    return _mm(o.transpose(1, 0, 2).reshape(t, H * dv), _f32(w["w_o"]), rnd)


def swiglu(h, gate, up, down, rnd):
    return _mm(jax.nn.silu(_mm(h, _f32(gate), rnd))
               * _mm(h, _f32(up), rnd), _f32(down), rnd)


def route(h, w, z: dict, rnd):
    """-> (idx [T, k] over all router outputs, weights [T, k])."""
    s = jax.nn.sigmoid(_mm(h, _f32(w["router"]), rnd))
    _, idx = jax.lax.top_k(s + _f32(w["router_bias"]), z["k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    wts = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, wts * z["route_scale"]


def expert_ffn(h, w, z: dict, rnd, shared: bool = True):
    """This share's part of the expert layer: the held experts' terms,
    one expert at a time, and (``shared``) the shared expert whole."""
    idx, wts = route(h, w, z, rnd)

    def one(y, a):
        e, gate, up, down = a
        col = jnp.sum(jnp.where(idx == z["lo"] + e, wts, 0.0), axis=-1)
        return y + col[:, None] * swiglu(h, gate, up, down, rnd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(z["held"]), w["e_gate"], w["e_up"],
                         w["e_down"]))
    if shared:
        y = y + swiglu(h, w["s_gate"], w["s_up"], w["s_down"], rnd)
    return y


def _layer_params(params: dict, i: int) -> dict:
    pre = f"l{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def _run(params: dict, tokens, cfg: dict, rounding):
    """-> (logits [T, V], [expert layers, T, held] bool: which of the
    held experts each position chose)."""
    z = sizes(cfg)
    rnd = ROUNDINGS[rounding]
    held = z["lo"] + jnp.arange(z["held"])
    x = _f32(params["tok_emb"][tokens])
    sets = []
    for i in range(z["L"]):
        w = _layer_params(params, i)
        x = x + attention(rms_norm(x, _f32(w["attn_norm_g"]), z["eps"]),
                          w, z, rnd)
        h = rms_norm(x, _f32(w["ffn_norm_g"]), z["eps"])
        if i < z["dense"]:
            x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], rnd)
        else:
            idx, _ = route(h, w, z, rnd)
            sets.append(jnp.any(idx[:, :, None] == held[None, None], axis=1))
            x = x + expert_ffn(h, w, z, rnd)
    x = rms_norm(x, _f32(params["norm_f_g"]), z["eps"])
    return _mm(x, _f32(params["head"]).T, rnd), sets


def forward(params: dict, tokens, cfg: dict, rounding=None):
    """tokens [T] int32 -> logits [T, V] float32. ``params`` as
    ``init_params`` gives them (any float dtype; computed in float32)."""
    return _run(params, tokens, cfg, rounding)[0]


def held_expert_sets(params: dict, tokens, cfg: dict, rounding=None):
    """[expert layers, T, held] bool along ``forward``'s own path under
    ``rounding``. Two calls (None against "bf16") count how often the
    served precision moves a position's held set."""
    return jnp.stack(_run(params, tokens, cfg, rounding)[1])
