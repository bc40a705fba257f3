"""Plain reference of the LFM2 block (LiquidAI/LFM2-24B-A2B ``config.json``,
``model_type: lfm2_moe``; the layer as the transformers ``lfm2_moe``
modelling file has it), in straightforward ``jax.numpy`` and float32 at
``precision=highest``: the short convolution as shifted sums over the whole
sequence, attention over the whole sequence in blocks of query rows, no
cache, no kernel, no batching. It imports nothing of the program under test
and makes its own weights from the seed.

    x = tok_emb[ids]
    per layer:  x = x + OP_i(RMSNorm(x));  x = x + FFN_i(RMSNorm(x))
    logits = RMSNorm(x) . head^T

Layer i is what ``layer_types[i]`` says:

    conv             [B | C | u] = h W_in             (split in that order)
                     v_t = B_t * u_t
                     c_t = sum_j conv[j] * v_{t-K+1+j}    depthwise, causal,
                     K = conv_L_cache taps, zeros before the sequence
                     out = (C * c) W_out
    full_attention   q = h W_q -> [H, dh]; k, v = h W_k, h W_v -> [G, dh]
                     q = RMSNorm_dh(q; gain), k = RMSNorm_dh(k; gain)
                     q, k rotated over the whole head, theta from
                     ``rope_theta``, halves paired (rotate_half)
                     y = softmax_causal(q k^T dh^-0.5) v, kv head j serving
                     query heads j*H/G .. (j+1)*H/G - 1;  out = y W_o
    FFN   layer < num_dense_layers: SwiGLU at intermediate_size; later
          s = sigmoid(h W_g); idx = top-k(s + bias) over ALL router outputs;
          w = s[idx] / (sum s[idx] + 1e-6) * routed_scaling_factor;
          y = sum_j w_j E_idx_j(h)                       (no shared expert)

**The chip's share.** ``num_experts`` counts the experts HELD here;
``ep_ranks`` chips share each layer and this is rank ``ep_rank``: the
router is ``num_experts * ep_ranks`` wide, its top-k and normalisation are
over all of them, and only the terms of experts
``[n * ep_rank, n * ep_rank + n)`` are added.

What the deployment STORES is taken as stored: the weights in the
configuration's ``torch_dtype`` (made so by the harness) and each token's
keys (normalised and rotated) and values rounded to it; the convolution's
inputs are float32, like everything computed.

``rounding`` is the control of the benchmark's ``correct``: with ``"fp8"``
every matrix product takes both operands rounded to float8_e4m3 (scaled
per tensor, accumulated in float32), the nearest precision below the
configuration's bfloat16; ``"bf16"`` rounds them to bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
#: the per-layer leaves a training harness would stack (none: served only)
LAYER_LEAVES = ()
#: query rows a block of the attention and of the head: [H, 256, T] scores
#: and [256, V] logits at a time, so that 8,704 tokens fit beside the weights
BLOCK_ROWS = 256


def sizes(cfg: dict) -> dict:
    """The block's sizes from the configuration's top-level scalars and
    lists (the harness hands ``forward`` no nested dict)."""
    g = lambda k, d=None: cfg[k] if d is None else cfg.get(k, d)
    held = int(g("num_experts"))
    H = int(g("num_attention_heads"))
    return {
        "d": int(g("hidden_size")), "f": int(g("intermediate_size")),
        "fm": int(g("moe_intermediate_size")), "H": H,
        "G": int(g("num_key_value_heads")),
        "dh": int(g("head_dim", int(g("hidden_size")) // H)),
        "L": int(g("num_hidden_layers")), "V": int(g("vocab_size")),
        "K": int(g("conv_L_cache")),
        "dense": int(g("num_dense_layers")),
        "held": held, "E": held * int(g("ep_ranks", 1)),
        "lo": held * int(g("ep_rank", 0)),
        "k": int(g("num_experts_per_tok")),
        "route_scale": float(g("routed_scaling_factor")),
        "route_eps": float(g("norm_topk_eps", 1e-6)),
        "eps": float(g("norm_eps")),
        "theta": float(g("rope_theta")),
        "tied": bool(g("tie_word_embeddings", False)),
        "conv": frozenset(i for i, t in enumerate(g("layer_types"))
                          if t == "conv"),
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
    """{leaf: shape} of every leaf, layer by layer (``l<i>.<leaf>``). A
    name that ends in ``_g`` is a gain (near 1)."""
    z = sizes(cfg)
    d, H, G, dh = z["d"], z["H"], z["G"], z["dh"]
    out = {"tok_emb": (z["V"], d), "norm_f_g": (d,)}
    if not z["tied"]:
        out["head"] = (z["V"], d)
    for i in range(z["L"]):
        if i in z["conv"]:
            lay = {"w_in": (d, 3 * d), "conv": (z["K"], d), "w_out": (d, d)}
        else:
            lay = {"w_q": (d, H * dh), "w_k": (d, G * dh), "w_v": (d, G * dh),
                   "q_norm_g": (dh,), "k_norm_g": (dh,), "w_o": (H * dh, d)}
        if i < z["dense"]:
            lay.update(w_gate=(d, z["f"]), w_up=(d, z["f"]),
                       w_down=(z["f"], d))
        else:
            lay.update(router=(d, z["E"]), router_bias=(z["E"],),
                       e_gate=(z["held"], d, z["fm"]),
                       e_up=(z["held"], d, z["fm"]),
                       e_down=(z["held"], z["fm"], d))
        lay.update(op_norm_g=(d,), ffn_norm_g=(d,))
        out.update({f"l{i}.{k}": v for k, v in lay.items()})
    return out


def leaf_std(cfg: dict, name: str) -> float:
    """N(0, init_std) for every leaf but the embedding and the
    convolutions' taps, which a configuration may draw at scales of their
    own."""
    std = float(cfg.get("init_std", INIT_STD))
    if name == "tok_emb":
        return float(cfg.get("embedding_init_std", std))
    if name.endswith(".conv"):
        return float(cfg.get("conv_init_std", std))
    return std


def leaf_kind(name: str) -> str:
    """How a leaf is drawn: "_g" a gain, "" everything else."""
    return "_g" if name.endswith("_g") else ""


def make_leaf(words, index, name: str, shape, dtype, std: float):
    """One leaf from the seed's words and its index among the sorted
    names: gains 1 + N(0, std), everything else N(0, std) (the router's
    bias too). Only ``leaf_kind(name)`` is read of the name. ``index`` may
    be traced: leaves of one shape and kind are then one program."""
    key = jax.random.fold_in(seed_key(words), index)
    r = jax.random.normal(key, shape, jnp.float32) * std
    if leaf_kind(name) == "_g":
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


def short_conv(x, w):
    """x [T, C], w [K, C]: depthwise, causal, zeros before the sequence,
    no activation: y_t = sum_j w[j] * x_{t-K+1+j}."""
    K, T = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * ext[j:j + T] for j in range(K))


def gated_conv(h, w, z: dict, rnd):
    """The gated short convolution over h [T, d]."""
    b, c, u = jnp.split(_mm(h, _f32(w["w_in"]), rnd), 3, axis=-1)
    return _mm(c * short_conv(b * u, _f32(w["conv"])), _f32(w["w_out"]), rnd)


def rope(x, z: dict):
    """x [T, heads, dh] at positions 0..T-1: rotate-half pairing
    (j, j + dh/2), the angles made on the host in float64."""
    dh = z["dh"]
    ang = np.outer(np.arange(x.shape[0], dtype=np.float64),
                   z["theta"] ** (-np.arange(0, dh, 2, dtype=np.float64)
                                  / dh))
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, w, z: dict, rnd):
    """Grouped-query attention over h [T, d], causal over all T, a block
    of query rows at a time."""
    t = h.shape[0]
    H, G, dh = z["H"], z["G"], z["dh"]
    heads = lambda a, n: a.reshape(t, n, dh)
    q = heads(_mm(h, _f32(w["w_q"]), rnd), H)
    k = heads(_mm(h, _f32(w["w_k"]), rnd), G)
    v = heads(_mm(h, _f32(w["w_v"]), rnd), G)
    q = rope(rms_norm(q, _f32(w["q_norm_g"]), z["eps"]), z)
    k = rope(rms_norm(k, _f32(w["k_norm_g"]), z["eps"]), z)
    # a token's keys and values as the deployment STORES them
    # (reduce_precision, not a cast there and back: the compiler may drop
    # such a pair as excess precision it is allowed to keep)
    fi = jnp.finfo(jnp.dtype(z["stored"]))
    stored = lambda a: jax.lax.reduce_precision(a, fi.nexp, fi.nmant)
    k, v = rnd(stored(k)), rnd(stored(v))
    rows = BLOCK_ROWS if t % BLOCK_ROWS == 0 else t
    kpos = jnp.arange(t)

    q = rnd(q)

    def block(a):
        qb, start = a                                   # [rows, H, dh]
        qb = qb.reshape(rows, G, H // G, dh)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=HIGHEST) \
            * dh ** -0.5
        seen = kpos[None, :] <= (start + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", rnd(p), v, precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(t // rows, rows, H, dh),
                            jnp.arange(0, t, rows)))
    return _mm(o.reshape(t, H * dh), _f32(w["w_o"]), rnd)


def swiglu(h, gate, up, down, rnd):
    return _mm(jax.nn.silu(_mm(h, _f32(gate), rnd))
               * _mm(h, _f32(up), rnd), _f32(down), rnd)


def route(h, w, z: dict, rnd):
    """-> (idx [T, k] over all router outputs, weights [T, k])."""
    s = jax.nn.sigmoid(_mm(h, _f32(w["router"]), rnd))
    _, idx = jax.lax.top_k(s + _f32(w["router_bias"]), z["k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    wts = picked / (jnp.sum(picked, axis=-1, keepdims=True) + z["route_eps"])
    return idx, wts * z["route_scale"]


def expert_ffn(h, w, z: dict, rnd):
    """This share's part of the expert layer: the held experts' terms,
    one expert at a time."""
    idx, wts = route(h, w, z, rnd)

    def one(y, a):
        e, gate, up, down = a
        col = jnp.sum(jnp.where(idx == z["lo"] + e, wts, 0.0), axis=-1)
        return y + col[:, None] * swiglu(h, gate, up, down, rnd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(z["held"]), w["e_gate"], w["e_up"],
                         w["e_down"]))
    return y


def _layer_params(params: dict, i: int) -> dict:
    pre = f"l{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def forward(params: dict, tokens, cfg: dict, rounding=None):
    """tokens [T] int32 -> logits [T, V] float32. ``params`` as
    ``init_params`` gives them (any float dtype; computed in float32)."""
    z = sizes(cfg)
    rnd = ROUNDINGS[rounding]
    x = _f32(params["tok_emb"][tokens])
    for i in range(z["L"]):
        w = _layer_params(params, i)
        h = rms_norm(x, _f32(w["op_norm_g"]), z["eps"])
        x = x + (gated_conv if i in z["conv"] else attention)(h, w, z, rnd)
        h = rms_norm(x, _f32(w["ffn_norm_g"]), z["eps"])
        if i < z["dense"]:
            x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], rnd)
        else:
            x = x + expert_ffn(h, w, z, rnd)
    x = rms_norm(x, _f32(params["norm_f_g"]), z["eps"])
    head = rnd(_f32(params["tok_emb" if z["tied"] else "head"]))
    t = x.shape[0]
    rows = BLOCK_ROWS if t % BLOCK_ROWS == 0 else t
    return jax.lax.map(
        lambda xb: jnp.matmul(xb, head.T, precision=HIGHEST),
        rnd(x).reshape(t // rows, rows, -1)).reshape(t, -1)
