"""Plain reference of the Kimi-Linear block (moonshotai/Kimi-Linear-48B-A3B
``config.json``, ``model_type: kimi_linear``; Kimi Team 2025,
arXiv:2510.26692; the KDA layer as flash-linear-attention's
``fla/layers/kda.py`` and ``naive_recurrent_kda``), in straightforward
``jax.numpy`` and float32 at ``precision=highest``: the recurrence as a
``lax.scan`` over tokens, the UNABSORBED latent attention over the whole
sequence, no cache, no kernel, no batching. It imports nothing of the
program under test and makes its own weights from the seed.

    x = tok_emb[ids]                     (no position embedding anywhere)
    per layer:  x = x + ATTN_i(RMSNorm(x));  x = x + FFN_i(RMSNorm(x))
    logits = RMSNorm(x) . head^T                      (untied head)

Layer i (0-based) is a KDA layer where ``i + 1`` is in ``kda_layers``,
else an MLA layer.

    KDA   q~, k~, v~ = h W_q, h W_k, h W_v          [d -> H*dk] each
          q_t[c] = silu(sum_j conv_q[j, c] * q~_{t-3+j}[c]), zeros before
          the sequence; k, v alike, each its own depthwise kernel of 4
          per head: q <- q / |q| * dk^-0.5, k <- k / |k|   (eps 1e-6)
          g = -exp(A_log)[head] * softplus(W_fb (W_fa h) + dt_bias) <= 0
          beta = sigmoid(h W_beta)                       a number a head
          S [dk, dv] from zero:  S' = exp(g)[:, None] * S;
          u = beta * (v - S'^T k);  S = S' + k u^T;  o = S^T q
          y = RMSNorm_dv(o; gain) * sigmoid(W_gb (W_ga h));  out = y W_o
    MLA   q = h W_q -> heads of [q_nope | q_r] (no low-rank query);
          [c' | k_r] = h W_dkv; c_kv = RMSNorm(c'); the k_r lanes are
          shared by the heads and NOT rotated (``mla_use_nope``);
          [k_nope_i | v_i] = c_kv W_ukv per head i;
          score_i = (q_nope_i . k_nope_i + q_r_i . k_r) * (dn + dr)^-0.5
    FFN   layer < first_k_dense_replace: SwiGLU at intermediate_size; later
          s = sigmoid(h W_g); idx = top-k(s + bias) over ALL router outputs;
          w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor;
          y = sum_j w_j E_idx_j(h) + E_shared(h)

**The chip's share.** ``num_experts`` counts the experts HELD here;
``ep_ranks`` chips share each layer and this is rank ``ep_rank``: the
router is ``num_experts * ep_ranks`` wide, its top-k and normalisation are
over all of them, and only the terms of experts
``[n * ep_rank, n * ep_rank + n)`` are added (the shared expert whole).

What the deployment STORES is taken as stored: the weights in the
configuration's ``torch_dtype`` (made so by the harness) and each token's
latent cache row [c_kv | k_r] rounded to it; the recurrent state and the
convolutions' inputs are float32, like everything computed.

``rounding`` is the control of the benchmark's ``correct``: with ``"fp8"``
every matrix product takes both operands rounded to float8_e4m3 (scaled
per tensor, accumulated in float32), the nearest precision below the
configuration's bfloat16; ``"bf16"`` rounds them to bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
#: the per-layer leaves a training harness would stack (none: served only)
LAYER_LEAVES = ()


def sizes(cfg: dict) -> dict:
    """The block's sizes from the configuration's top-level scalars and
    lists (the harness hands ``forward`` no nested dict)."""
    g = lambda k, d=None: cfg[k] if d is None else cfg.get(k, d)
    held = int(g("num_experts"))
    return {
        "d": int(g("hidden_size")), "f": int(g("intermediate_size")),
        "fm": int(g("moe_intermediate_size")),
        "H": int(g("num_attention_heads")),
        "L": int(g("num_hidden_layers")), "V": int(g("vocab_size")),
        "rkv": int(g("kv_lora_rank")),
        "dn": int(g("qk_nope_head_dim")), "dr": int(g("qk_rope_head_dim")),
        "dv": int(g("v_head_dim")),
        "dense": int(g("first_k_dense_replace")),
        "held": held, "E": held * int(g("ep_ranks", 1)),
        "lo": held * int(g("ep_rank", 0)),
        "k": int(g("num_experts_per_token")),
        "shared": int(g("num_shared_experts")),
        "route_scale": float(g("routed_scaling_factor")),
        "eps": float(g("rms_norm_eps")),
        "Hk": int(g("kda_num_heads")), "dk": int(g("kda_head_dim")),
        "conv": int(g("kda_short_conv_kernel_size")),
        "r": int(g("kda_gate_rank")),
        "kda": frozenset(int(i) - 1 for i in g("kda_layers")),
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


def _ffn_leaves(z: dict, i: int) -> dict:
    d = z["d"]
    if i < z["dense"]:
        return {"w_gate": (d, z["f"]), "w_up": (d, z["f"]),
                "w_down": (z["f"], d)}
    fs = z["fm"] * z["shared"]
    return {"router": (d, z["E"]), "router_bias": (z["E"],),
            "e_gate": (z["held"], d, z["fm"]),
            "e_up": (z["held"], d, z["fm"]),
            "e_down": (z["held"], z["fm"], d),
            "s_gate": (d, fs), "s_up": (d, fs), "s_down": (fs, d)}


def leaf_shapes(cfg: dict) -> dict:
    """{leaf: shape} of every leaf, layer by layer (``l<i>.<leaf>``). A
    name that ends in ``_g`` is a gain (near 1)."""
    z = sizes(cfg)
    d, H, c = z["d"], z["H"], z["Hk"] * z["dk"]
    out = {"tok_emb": (z["V"], d), "head": (z["V"], d), "norm_f_g": (d,)}
    for i in range(z["L"]):
        if i in z["kda"]:
            lay = {"w_q": (d, c), "w_k": (d, c), "w_v": (d, c),
                   "conv_q": (z["conv"], c), "conv_k": (z["conv"], c),
                   "conv_v": (z["conv"], c),
                   "w_fa": (d, z["r"]), "w_fb": (z["r"], c),
                   "a_log": (z["Hk"],), "dt_bias": (c,),
                   "w_beta": (d, z["Hk"]),
                   "w_ga": (d, z["r"]), "w_gb": (z["r"], c),
                   "o_norm_g": (z["dk"],), "w_o": (c, d)}
        else:
            lay = {"w_q": (d, H * (z["dn"] + z["dr"])),
                   "w_dkv": (d, z["rkv"] + z["dr"]),
                   "kv_norm_g": (z["rkv"],),
                   "w_ukv": (z["rkv"], H * (z["dn"] + z["dv"])),
                   "w_o": (H * z["dv"], d)}
        lay.update(attn_norm_g=(d,), ffn_norm_g=(d,), **_ffn_leaves(z, i))
        out.update({f"l{i}.{k}": v for k, v in lay.items()})
    return out


def leaf_std(cfg: dict, name: str) -> float:
    """N(0, init_std) for every leaf but the embedding, which a
    configuration may draw at a scale of its own."""
    std = float(cfg.get("init_std", INIT_STD))
    return float(cfg.get("embedding_init_std", std)) if name == "tok_emb" \
        else std


def leaf_kind(name: str) -> str:
    """How a leaf is drawn: "_g" a gain, "a_log", "dt_bias", "" a
    matrix (the router's bias too)."""
    for kind in ("_g", "a_log", "dt_bias"):
        if name.endswith(kind):
            return kind
    return ""


def make_leaf(words, index, name: str, shape, dtype, std: float):
    """One leaf from the seed's words and its index among the sorted
    names: gains 1 + N(0, std); ``a_log`` = log U(1, 16) and ``dt_bias``
    the inverse softplus of exp(U(log 0.001, log 0.1)), as FLA's layer
    initialises them (at these the decay exp(g) of a channel lies between
    0.14 and 0.999, a memory of a token to a thousand); everything else
    N(0, std). Only ``leaf_kind(name)`` is read of the name. ``index``
    may be traced: leaves of one shape and kind are then one program."""
    key = jax.random.fold_in(seed_key(words), index)
    kind = leaf_kind(name)
    if kind == "a_log":
        r = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(0.001), math.log(0.1)))
        r = dt + jnp.log(-jnp.expm1(-dt))
    else:
        r = jax.random.normal(key, shape, jnp.float32) * std
        if kind == "_g":
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
    then SiLU: y_t = silu(sum_j w[j] * x_{t-K+1+j})."""
    K, T = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(w[j] * ext[j:j + T] for j in range(K)))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(h, w, z: dict, rnd):
    """Kimi Delta Attention over h [T, d]: the recurrence token by token
    over a float32 state [Hk, dk, dv] that starts at zero."""
    t, Hk, dk = h.shape[0], z["Hk"], z["dk"]
    heads = lambda a: a.reshape(t, Hk, dk)
    q, k, v = (heads(short_conv(_mm(h, _f32(w[f"w_{n}"]), rnd),
                                _f32(w[f"conv_{n}"]))) for n in "qkv")
    q, k = _unit(q) * dk ** -0.5, _unit(k)
    a = _mm(_mm(h, _f32(w["w_fa"]), rnd), _f32(w["w_fb"]), rnd)
    g = -jnp.exp(_f32(w["a_log"]))[:, None] * jax.nn.softplus(
        heads(a + _f32(w["dt_bias"])))                         # [T, Hk, dk]
    beta = jax.nn.sigmoid(_mm(h, _f32(w["w_beta"]), rnd))      # [T, Hk]

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=HIGHEST))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((Hk, dk, dk), jnp.float32),
                        (q, k, v, g, beta))                    # [T, Hk, dv]
    gate = _mm(_mm(h, _f32(w["w_ga"]), rnd), _f32(w["w_gb"]), rnd)
    y = rms_norm(o, _f32(w["o_norm_g"]), z["eps"]) * \
        jax.nn.sigmoid(heads(gate))
    return _mm(y.reshape(t, Hk * dk), _f32(w["w_o"]), rnd)


def attention(h, w, z: dict, rnd):
    """MLA over h [T, d], causal over all T, one head at a time; no
    rotation, no low-rank query."""
    t = h.shape[0]
    H, dn, dr, dv, rkv = z["H"], z["dn"], z["dr"], z["dv"], z["rkv"]
    q = _mm(h, _f32(w["w_q"]), rnd).reshape(t, H, dn + dr).transpose(1, 0, 2)
    q_nope, q_r = q[..., :dn], q[..., dn:]
    ckr = _mm(h, _f32(w["w_dkv"]), rnd)
    # a token's cache row, [c_kv | k_r], as the deployment STORES it
    # (reduce_precision, not a cast there and back: the compiler may drop
    # such a pair as excess precision it is allowed to keep)
    fi = jnp.finfo(jnp.dtype(z["stored"]))
    stored = lambda a: jax.lax.reduce_precision(a, fi.nexp, fi.nmant)
    c_kv = stored(rms_norm(ckr[:, :rkv], _f32(w["kv_norm_g"]), z["eps"]))
    k_r = stored(ckr[:, rkv:])                                  # [T, dr]
    kv = _mm(c_kv, _f32(w["w_ukv"]), rnd).reshape(t, H, dn + dv)
    kv = kv.transpose(1, 0, 2)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    causal = jnp.tril(jnp.ones((t, t), jnp.bool_))
    sigma = (dn + dr) ** -0.5

    def head(a):
        qn, qr, kn, vv = a
        s = (_mm(qn, kn.T, rnd) + _mm(qr, k_r.T, rnd)) * sigma
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm(p, vv, rnd)

    o = jax.lax.map(head, (q_nope, q_r, k_nope, v))             # [H, T, dv]
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


def forward(params: dict, tokens, cfg: dict, rounding=None):
    """tokens [T] int32 -> logits [T, V] float32. ``params`` as
    ``init_params`` gives them (any float dtype; computed in float32)."""
    z = sizes(cfg)
    rnd = ROUNDINGS[rounding]
    x = _f32(params["tok_emb"][tokens])
    for i in range(z["L"]):
        w = _layer_params(params, i)
        h = rms_norm(x, _f32(w["attn_norm_g"]), z["eps"])
        x = x + (kda if i in z["kda"] else attention)(h, w, z, rnd)
        h = rms_norm(x, _f32(w["ffn_norm_g"]), z["eps"])
        if i < z["dense"]:
            x = x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], rnd)
        else:
            x = x + expert_ffn(h, w, z, rnd)
    x = rms_norm(x, _f32(params["norm_f_g"]), z["eps"])
    return _mm(x, _f32(params["head"]).T, rnd)
