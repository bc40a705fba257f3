"""Plain reference of the OPT decoder block (Zhang et al. 2022,
arXiv:2205.01068; facebook/opt-* ``config.json``), in straightforward
``jax.numpy`` and float32 at ``precision=highest``: no kernel, no cache,
no batching. It imports nothing of the program under test and makes its
own weights from the seed.

    x = tok_emb[ids] + pos_emb[0..T-1]
    per layer:  x = x + Wo . MHA(LN1(x));  x = x + W2 . relu(W1 . LN2(x) + b1)
    logits = LN_f(x) . tok_emb^T                      (tied head)

Departures from the published block, which the repository's block makes
and the configuration files list under ``assumed``: no bias on q/k/v/out
and on fc2, and positions start at 0 (the published model offsets its
position table by 2).

``rounding`` is the control of the benchmark's ``correct``: with
``"fp8"`` every matrix product takes both operands rounded to
float8_e4m3 (scaled per tensor to the format's range, accumulated in
float32) - the nearest precision below the bfloat16 that the
configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
INIT_STD = 0.02           # OPT's init_std

#: the leaves of one layer, in a fixed order: name -> shape from (d, f)
LAYER_LEAVES = {
    "ln1_g": lambda d, f: (d,), "ln1_b": lambda d, f: (d,),
    "q": lambda d, f: (d, d), "k": lambda d, f: (d, d),
    "v": lambda d, f: (d, d), "o": lambda d, f: (d, d),
    "ln2_g": lambda d, f: (d,), "ln2_b": lambda d, f: (d,),
    "up": lambda d, f: (d, f), "up_b": lambda d, f: (f,),
    "down": lambda d, f: (f, d),
}


def sizes(cfg: dict) -> dict:
    """The block's sizes from a ``config.json``-shaped dict."""
    return {"d": int(cfg["hidden_size"]), "f": int(cfg["ffn_dim"]),
            "h": int(cfg["num_attention_heads"]),
            "L": int(cfg["num_hidden_layers"]),
            "V": int(cfg["vocab_size"]),
            "P": int(cfg["max_position_embeddings"])}


def seed_words(seed) -> tuple:
    """The two uint32 words a key is made from: the seed's low 31 bits and
    the rest. A jitted program takes them as ARGUMENTS and is then one
    program for every seed; a seed closed over is a constant of the
    program, which every new seed compiles anew (6 s of set-up for these
    weights on the v5e's host). A pair, traced or not, passes through."""
    if isinstance(seed, tuple):
        return seed
    seed = int(seed)
    return np.uint32(seed & 0x7FFFFFFF), np.uint32(seed >> 31)


def seed_key(seed):
    """A key from any whole number (or its ``seed_words``): 31 bits seed
    the key, the rest are folded in, so seeds past 2**31 neither wrap nor
    overflow. The 'rbg' generator: threefry took 70 s for these 1.3e9
    values on the v5e."""
    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.key(lo, impl="rbg"), hi)


def _leaf(key, name: str, shape, dtype, std: float):
    """Gains near 1, everything else N(0, std): biases are not zero, so
    that a path that drops one is seen."""
    r = jax.random.normal(key, shape, jnp.float32) * std
    if name.endswith("_g"):
        r = 1.0 + r
    return r.astype(dtype)


def init_params(seed, cfg: dict, dtype=jnp.float32) -> dict:
    """All weights from the seed (a whole number or its ``seed_words``),
    layers stacked on a leading axis. Trace it inside one ``jax.jit`` that
    takes the words as arguments: the device then makes them in one call,
    in ``dtype``, by one program for every seed."""
    z = sizes(cfg)
    key = seed_key(seed)
    shapes = {"tok_emb": (z["V"], z["d"]), "pos_emb": (z["P"], z["d"]),
              "lnf_g": (z["d"],), "lnf_b": (z["d"],)}
    for name, fn in LAYER_LEAVES.items():
        shapes[name] = (z["L"],) + fn(z["d"], z["f"])
    std = float(cfg.get("init_std", INIT_STD))
    return {name: _leaf(jax.random.fold_in(key, i), name, shape, dtype, std)
            for i, (name, shape) in enumerate(sorted(shapes.items()))}


# ------------------------------------------------------------------ rounding
def _fp8(x):
    """Round to float8_e4m3 and back, scaled so the tensor's largest
    magnitude sits at the format's largest (448). Straight-through for
    gradients."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    r = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(r - x)


ROUNDINGS = {None: lambda x: x, "fp8": _fp8}


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _layer(x, w, n_heads: int, rnd):
    """One pre-LayerNorm block over x [T, d], causal over all T."""
    t, d = x.shape
    dh = d // n_heads
    h1 = _ln(x, w["ln1_g"], w["ln1_b"])
    q = _mm(h1, w["q"], rnd).reshape(t, n_heads, dh).transpose(1, 0, 2)
    k = _mm(h1, w["k"], rnd).reshape(t, n_heads, dh).transpose(1, 0, 2)
    v = _mm(h1, w["v"], rnd).reshape(t, n_heads, dh).transpose(1, 0, 2)
    s = _mm(q, k.transpose(0, 2, 1), rnd) * (dh ** -0.5)      # [h, T, T]
    causal = jnp.tril(jnp.ones((t, t), jnp.bool_))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = _mm(p, v, rnd).transpose(1, 0, 2).reshape(t, d)
    x = x + _mm(a, w["o"], rnd)
    h2 = _ln(x, w["ln2_g"], w["ln2_b"])
    u = jax.nn.relu(_mm(h2, w["up"], rnd) + w["up_b"])
    return x + _mm(u, w["down"], rnd)


def forward(params: dict, tokens, cfg: dict, rounding=None,
            remat: bool = False):
    """tokens [T] int32 -> logits [T, V] float32. ``params`` as
    ``init_params`` gives them (any float dtype; computed in float32)."""
    z = sizes(cfg)
    rnd = ROUNDINGS[rounding]
    f32 = lambda a: a.astype(jnp.float32)
    t = tokens.shape[0]
    x = f32(params["tok_emb"][tokens]) + f32(params["pos_emb"][:t])
    stacked = {n: params[n] for n in LAYER_LEAVES}

    def body(x, w):
        w = {n: f32(a) for n, a in w.items()}
        return _layer(x, w, z["h"], rnd), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, stacked)
    x = _ln(x, f32(params["lnf_g"]), f32(params["lnf_b"]))
    return _mm(x, f32(params["tok_emb"]).T, rnd)


def row_loss(params: dict, tokens, labels, cfg: dict, rounding=None):
    """Next-token cross entropy SUMMED over one row's positions (the
    trainer's cost of a sequence), float32."""
    logits = forward(params, tokens, cfg, rounding, remat=True)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


# ------------------------------------------------------------------ training
ADAM = {"b1": 0.9, "b2": 0.999, "eps": 1e-8}


@functools.partial(jax.jit, static_argnames=("cfg_key", "rounding"))
def _row_grad(params, tokens, labels, cfg_key, rounding):
    cfg = dict(cfg_key)
    return jax.value_and_grad(row_loss)(params, tokens, labels, cfg,
                                        rounding)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _acc(total, g):
    return jax.tree_util.tree_map(jnp.add, total, g)


def batch_loss_and_grad(params, tokens, labels, cfg, rounding=None,
                        rows=None):
    """Mean over rows of the row losses, and its gradient, one row at a
    time so that it fits beside nothing else. tokens/labels [B, T].
    ``rows`` (indices) restricts the mean to those rows - the 'half of
    the batch left out' fault."""
    cfg_key = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str, bool))))
    rows = list(range(tokens.shape[0])) if rows is None else list(rows)
    loss, total = 0.0, None
    for r in rows:
        l, g = _row_grad(params, tokens[r], labels[r], cfg_key, rounding)
        loss = loss + l
        total = g if total is None else _acc(total, g)
    n = float(len(rows))
    return loss / n, jax.tree_util.tree_map(lambda a: a / n, total)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def adam_step(params, grads, m, v, step, lr):
    """Plain Adam (Kingma & Ba), bias-corrected; ``step`` counts from 1."""
    b1, b2, eps = ADAM["b1"], ADAM["b2"], ADAM["eps"]
    t = step.astype(jnp.float32)

    def one(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * jnp.square(g)
        mhat = m2 / (1 - jnp.power(b1, t))
        vhat = v2 / (1 - jnp.power(b2, t))
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m2, v2

    out = {k: one(params[k], grads[k], m[k], v[k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})
