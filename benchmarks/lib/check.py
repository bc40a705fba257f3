"""What decides ``correct``: the comparison of what the timed path
produced with the configuration's plain reference.

Serving: over a seeded sample of the requests the window finished, the
longest among them, the reference runs once over each prompt with its
served tokens; the number is the widest gap by which a served token's
logit lies below the reference's best at that position.

Training: the reference follows the first three optimizer steps on the
same batches; the numbers are the three losses' relative gaps, and by the
worst leaf the gap of the first gradient's norm and of the norm of the
parameters' change after the three steps.

The control (the reference computed in fp8, put in the program's place)
is not run by benchmark runs: tests/benchmarks and PERF.md hold it.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.lib import harness


def decide(checks: dict) -> bool:
    """``correct``: every number compared is at or under its limit
    (``checks``: name -> (value, limit); a NaN is over any limit)."""
    return all(v <= l for v, l in checks.values())


def seed_words(seed) -> tuple:
    """The seed as two uint32 words (its low 31 bits, the rest): what a
    jitted program takes as ARGUMENTS to be one program for every seed.
    A seed closed over is a constant of the program, so that every new
    seed compiles it anew: seconds of a shared host's CPU in every run's
    set-up, and the widest swing ``setup_s`` had. A reference's
    ``init_params(seed, ...)`` is handed these words, traced."""
    seed = int(seed)
    return np.uint32(seed & 0x7FFFFFFF), np.uint32(seed >> 31)


def from_seed(fn, seed: int, *args):
    """``fn((low, rest), *args)`` run as one jitted program that takes the
    seed's words, and ``args``, as arguments."""
    import jax
    return jax.jit(lambda lo, hi, *a: fn((lo, hi), *a))(
        *seed_words(seed), *args)


def sample_finished(finished: list, n: int, seed: int) -> list:
    """``n`` of the finished requests, drawn from the seed, the longest
    (prompt + served tokens) always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 77])
    rest = [int(i) for i in rng.permutation(order[1:])[:max(n - 1, 0)]]
    return [finished[i] for i in [order[0]] + rest]


@functools.lru_cache(maxsize=None)
def _gap_fn(reference, cfg_key, rounding):
    """jit: (params, seq [T], served-next [T]) -> per position, how far
    the judged token's float32-reference logit lies below the best. With
    ``rounding`` the judged token is the one the lower precision puts
    first (the control); without, the served one."""
    import jax
    import jax.numpy as jnp
    cfg = cfg_of(cfg_key)

    def fn(params, seq, nxt):
        logits = reference.forward(params, seq, cfg)
        if rounding is not None:
            nxt = jnp.argmax(reference.forward(params, seq, cfg, rounding),
                             axis=-1)
        best = jnp.max(logits, axis=-1)
        picked = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        return best - picked
    return jax.jit(fn)


def _frozen(v):
    """A scalar as it is, a list as a tuple (of scalars and lists);
    anything else, a dict above all, raises TypeError."""
    if isinstance(v, (int, float, str, bool)):
        return v
    if isinstance(v, list):
        return tuple(_frozen(x) for x in v)
    raise TypeError(type(v))


def cfg_key(cfg: dict):
    """The configuration as a hashable key: its scalars and its lists
    (``layer_types``), as tuples. Nested dicts (``deployment``,
    ``assumed``, ``published``) stay out, with any list that holds one:
    the reference never sees them."""
    out = []
    for k, v in cfg.items():
        try:
            out.append((k, _frozen(v)))
        except TypeError:
            pass
    return tuple(sorted(out))


def cfg_of(key) -> dict:
    """``cfg_key``'s configuration again, its lists restored."""
    def thaw(v):
        return [thaw(x) for x in v] if isinstance(v, tuple) else v
    return {k: thaw(v) for k, v in key}


def served_logit_gap(reference, cfg: dict, seed: int, sample: list,
                     pad_to: int, rounding=None) -> dict:
    """The widest gap over the sample's served positions, and how many
    tokens were compared. ``sample``: [(prompt ids, served ids)]."""
    import jax
    import jax.numpy as jnp
    wdtype = jnp.dtype(cfg.get("torch_dtype", "float32"))
    params = from_seed(lambda w: reference.init_params(w, cfg, wdtype), seed)
    fn = _gap_fn(reference, cfg_key(cfg), rounding)
    widest, n_tokens, per_request = 0.0, 0, []
    for prompt, served in sample:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        if len(seq) > pad_to:
            raise RuntimeError(f"request of {len(seq)} tokens > {pad_to}")
        nxt = np.zeros(pad_to, np.int32)
        first = len(prompt) - 1
        nxt[first:first + len(served)] = served
        seq = np.pad(seq, (0, pad_to - len(seq)))
        gaps = np.asarray(fn(params, jnp.asarray(seq), jnp.asarray(nxt)))
        g = float(np.max(gaps[first:first + len(served)]))
        if not np.isfinite(g):
            g = float("inf")
        per_request.append(g)
        widest = max(widest, g)
        n_tokens += len(served)
    harness.free(params)
    return {"widest_gap": widest, "tokens": n_tokens,
            "per_request": per_request}


# ------------------------------------------------------------------ training
def leaf_norms(reference, tree: dict) -> dict:
    """{leaf: float32 norms, one per layer for stacked leaves} (host)."""
    import jax
    import jax.numpy as jnp

    def one(name, a):
        a = a.astype(jnp.float32)
        if name in reference.LAYER_LEAVES:
            return jnp.sqrt(jnp.sum(jnp.square(a).reshape(a.shape[0], -1),
                                    axis=1))
        return jnp.sqrt(jnp.sum(jnp.square(a)))[None]
    out = jax.jit(lambda t: {k: one(k, v) for k, v in t.items()})(tree)
    return {k: np.asarray(v) for k, v in out.items()}


SKETCHES = 4       # random directions per leaf


def sketch_vectors(reference, words: tuple, cfg: dict, k) -> dict:
    """The k-th random direction of every leaf, from the seed's
    ``seed_words``, in the reference's stacked layout (trace inside a jit
    that takes the words as arguments: nothing of it is kept). The inner
    product of a gradient with it is a SKETCH of the gradient: unlike a
    norm, in which unbiased rounding noise cancels to second order, it
    moves in the first order of every element's error."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(
        lambda: reference.init_params(0, cfg, jnp.float32))
    lo, hi = words
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(lo ^ np.uint32(0x2545F491), impl="rbg"), hi), k)
    return {name: jax.random.normal(jax.random.fold_in(key, i), sh.shape,
                                    jnp.float32)
            for i, (name, sh) in enumerate(sorted(shapes.items()))}


def sketch_of(tree: dict, vectors: dict, layered=()) -> dict:
    """{leaf: <a, r> / |r|}; for a leaf named in ``layered`` one value per
    slice of its leading axis."""
    import jax.numpy as jnp
    out = {}
    for name, a in tree.items():
        a, r = a.astype(jnp.float32), vectors[name]
        if name in layered:
            a, r = a.reshape(a.shape[0], -1), r.reshape(r.shape[0], -1)
            out[name] = jnp.sum(a * r, axis=1) / \
                jnp.sqrt(jnp.sum(r * r, axis=1))
        else:
            out[name] = jnp.sum(a * r) / jnp.sqrt(jnp.sum(r * r))
    return out


def leaf_sketch(reference, seed: int, cfg: dict, tree: dict) -> dict:
    """{leaf: [layers (1 for an unstacked leaf), SKETCHES]} (host): the
    sketches of the reference's stacked ``tree``, one direction at a
    time."""
    import jax
    import jax.numpy as jnp

    def fn(words, t):
        def one(k):
            return sketch_of(t, sketch_vectors(reference, words, cfg, k),
                             reference.LAYER_LEAVES)
        return jax.lax.map(one, jnp.arange(SKETCHES))
    out = from_seed(fn, seed, tree)
    return {k: np.asarray(v).reshape(SKETCHES, -1).T for k, v in out.items()}


def leaf_sizes(reference, cfg: dict) -> dict:
    """{leaf: elements of one layer's (or the whole) leaf}."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(
        lambda: reference.init_params(0, cfg, jnp.float32))
    return {k: int(np.prod(sh.shape[1:] if k in reference.LAYER_LEAVES
                           else sh.shape)) for k, sh in shapes.items()}


def reference_three_steps(reference, cfg: dict, seed: int, batches: list,
                          lr: float, rounding=None, rows=None,
                          frozen: bool = False) -> dict:
    """Losses of the first three steps, per-leaf norms of the first
    gradient and of the parameters' change after the three. ``rounding``
    (the control), ``rows`` (half the batch left out) and ``frozen`` (a
    step that returns its state unchanged) put the reference in the
    program's place with that fault."""
    import jax
    import jax.numpy as jnp
    make = lambda: from_seed(
        lambda w: reference.init_params(w, cfg, jnp.float32), seed)
    p = make()
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, g_norms, g_sketch = [], None, None
    for i, batch in enumerate(batches[:3]):
        tok = jnp.asarray(batch[:, :-1])
        lab = jnp.asarray(batch[:, 1:])
        loss, g = reference.batch_loss_and_grad(p, tok, lab, cfg, rounding,
                                                rows)
        losses.append(float(loss))
        if i == 0:
            g_norms = leaf_norms(reference, g)
            g_sketch = leaf_sketch(reference, seed, cfg, g)
        if not frozen:
            p, m, v = reference.adam_step(p, g, m, v,
                                          jnp.asarray(i + 1, jnp.int32),
                                          jnp.asarray(lr, jnp.float32))
        del g
    p0 = make()
    d_norms = leaf_norms(reference, jax.tree_util.tree_map(jnp.subtract, p, p0))
    harness.free(p, p0, m, v)
    return {"losses": losses, "grad_norms": g_norms, "change_norms": d_norms,
            "grad_sketch": g_sketch, "leaf_sizes": leaf_sizes(reference, cfg)}


def worst_leaf_gap(got: dict, want: dict, skip=None) -> tuple:
    """max over leaves of |got - want| / max(want, median of want): the
    gap between the two NORMS, against the reference's norm of that leaf
    or of the median leaf, whichever is larger. -> (gap, leaf)."""
    names = [(k, i) for k in sorted(want) for i in range(len(want[k]))
             if not (skip and (k, i) in skip)]
    w = np.array([want[k][i] for k, i in names], np.float64)
    g = np.array([got[k][i] for k, i in names], np.float64)
    med = float(np.median(w))
    gaps = np.abs(g - w) / np.maximum(w, med)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    j = int(np.argmax(gaps))
    return float(gaps[j]), f"{names[j][0]}[{names[j][1]}]"


def sketch_gap_rms(got: dict, ref: dict) -> float:
    """Root mean square, over all leaves and directions, of
    (<g, r> - <g_ref, r>) / |r| against the size a sketch of the
    reference's gradient has, max(|g_ref|, median |g_ref|) / sqrt(n): the
    relative error of the gradient's elements, averaged over the leaves."""
    want, norms, sizes = ref["grad_sketch"], ref["grad_norms"], \
        ref["leaf_sizes"]
    med = float(np.median(np.concatenate(list(norms.values()))))
    sq = []
    for k in sorted(want):
        scale = (np.maximum(norms[k], med) / np.sqrt(sizes[k]))[:, None]
        d = (np.asarray(got[k], np.float64) - want[k]) / scale
        sq.append(np.where(np.isfinite(d), d, np.inf).ravel() ** 2)
    return float(np.sqrt(np.mean(np.concatenate(sq))))


def still_leaves(grad_norms: dict) -> set:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): under Adam they move by round-off
    alone, and are left out of the change."""
    allv = np.concatenate([v for v in grad_norms.values()])
    cut = 1e-3 * float(np.median(allv))
    return {(k, i) for k, v in grad_norms.items()
            for i in range(len(v)) if v[i] < cut}


def compare_training(prog: dict, ref: dict) -> dict:
    """The numbers of a training cell, each a float: name -> value."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    g, leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    out["grad_norm_gap_worst_leaf"] = g
    skip = still_leaves(ref["grad_norms"])
    c, leaf_c = worst_leaf_gap(prog["change_norms"], ref["change_norms"],
                               skip)
    out["change_norm_gap_worst_leaf"] = c
    out["grad_sketch_gap_rms"] = sketch_gap_rms(prog["grad_sketch"], ref)
    out["_worst"] = {"grad": leaf, "change": leaf_c, "skipped": len(skip)}
    return out
