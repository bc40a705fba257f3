"""Mixture-of-experts FFN with capacity-based top-k routing.

No 2017 reference counterpart (the reference predates MoE); this is the
expert-parallel leg of the mesh vocabulary (dp/mp/sp/pp/ep) built the
GShard/Mesh-TF way, which is also the XLA-friendly way:

  - routing is expressed as dense one-hot dispatch/combine tensors and
    einsums, so every shape is static and the whole block stays inside
    one jit trace (no data-dependent gather/scatter control flow);
  - expert weight tables carry a leading `E` dim sharded over the mesh's
    `ep` axis; with tokens sharded over `dp`, XLA lowers the dispatch
    einsum to the all-to-all over ICI that hand-written MoE stacks issue
    explicitly.

The dispatch tensor is [n, E, C] — fine for the token counts a single
chip sees (the ep axis divides E, dp divides n), but it is the textbook
memory trade-off of einsum routing. For single-host token counts past
~100k, `dispatch_mode='sort'` (moe_sorted_ffn) replaces it with an
argsort + gather/scatter that never materializes [n,E,C] — measured
crossover and numbers in docs/perf.md.

Both dispatch and combine are built in f32 (routing decisions must not
depend on the compute dtype), then cast so the big einsums run on the
MXU in the activation dtype.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.ops.linear import einsum_two_terms as _mm


def moe_capacity(n_tokens: int, num_experts: int, k: int,
                 capacity_factor: float) -> int:
    """Per-expert token budget: ceil(k * n / E * factor), at least k."""
    cap = int(-(-k * n_tokens * capacity_factor // num_experts))
    return max(cap, k)


def moe_dispatch(gate_logits: jnp.ndarray, valid: Optional[jnp.ndarray],
                 *, k: int, capacity: int, normalize: bool = True
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k capacity routing.

    gate_logits: [n, E] (any float dtype; routing math runs in f32).
    valid: [n] 0/1 mask (padded sequence slots must not eat capacity).

    Returns (dispatch [n,E,C] 0/1, combine [n,E,C] gate-weighted,
    aux f32 scalar — the switch-transformer load-balance loss,
    E * sum_e mean(probs_e) * mean(assigned_e), which is 1.0 at a
    perfectly uniform router).

    Normalization convention (k > 1): combine weights are divided by
    the total of the KEPT slots — if one of a token's experts
    overflows capacity, the surviving expert's weight renormalizes to
    1.0. This deliberately differs from GShard, which normalizes over
    the pre-drop top-k probability mass (leaving the survivor
    underweighted); full-mass routing on the kept experts preserved
    output scale better in our convergence tests. Pass
    normalize=False for raw gate products.
    """
    n, num_experts = gate_logits.shape
    assert 1 <= k <= num_experts, (
        f"moe_dispatch: k={k} must be in [1, num_experts={num_experts}]")
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    if valid is None:
        valid = jnp.ones((n,), jnp.float32)
    valid = valid.astype(jnp.float32)
    probs = probs * valid[:, None]

    remaining = probs
    fill = jnp.zeros((num_experts,), jnp.float32)   # kept tokens per expert
    dispatch = jnp.zeros((n, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((n, num_experts, capacity), jnp.float32)
    first_choice = None
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                      # [n]
        onehot = jax.nn.one_hot(idx, num_experts,
                                dtype=jnp.float32) * valid[:, None]
        if first_choice is None:
            first_choice = onehot
        gate_j = jnp.sum(probs * onehot, axis=-1)                 # [n]
        # position of each token inside its expert's buffer: tokens
        # already kept in earlier slots (fill) + earlier tokens of this
        # slot (exclusive cumsum). Overflow (pos >= capacity) is dropped.
        pos = jnp.cumsum(onehot, axis=0) - onehot + fill[None, :]
        pos_tok = jnp.sum(pos * onehot, axis=-1)                  # [n]
        keep = ((pos_tok < capacity) & (gate_j > 0)).astype(jnp.float32)
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0)
        slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity,
                              dtype=jnp.float32)                  # [n, C]
        placed = (onehot * keep[:, None])[:, :, None] * slot[:, None, :]
        dispatch = dispatch + placed
        combine = combine + gate_j[:, None, None] * placed
        remaining = remaining * (1.0 - onehot)

    if normalize and k > 1:
        total = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(total, 1e-9)

    n_valid = jnp.maximum(jnp.sum(valid), 1.0)
    me = jnp.sum(probs, axis=0) / n_valid            # mean router prob
    ce = jnp.sum(first_choice, axis=0) / n_valid     # mean top-1 assignment
    aux = num_experts * jnp.sum(me * ce)
    return dispatch, combine, aux


def moe_sorted_ffn(x: jnp.ndarray, valid: Optional[jnp.ndarray],
                   gate_w: jnp.ndarray, w_up: jnp.ndarray,
                   w_down: jnp.ndarray, *, k: int = 2,
                   capacity_factor: float = 1.25,
                   capacity: Optional[int] = None,
                   act=jax.nn.relu, normalize: bool = True
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-based dispatch: the einsum path's O(n*E*C) dispatch/combine
    tensors replaced by an argsort + gather/scatter — the Megablocks-style
    formulation for LARGE single-host token counts (>~100k), where
    [n,E,C] no longer fits and the dispatch einsum's n*E*C*d FLOPs dwarf
    the expert FFN itself.

    Numerics match moe_ffn exactly: (token, choice) pairs are ranked in
    choice-major token order per expert (a stable argsort on expert id),
    which reproduces the einsum path's fill discipline — einsum positions
    are fill(prev rounds' KEPT) + within-round rank, and fill saturates
    at capacity exactly when total prior entries do, so keep decisions
    and kept slots agree (see tests/test_sparse.py parity test).

    Single-host by design (the scatter/gather does not ride an ep
    all-to-all the way the dispatch einsum does); for the ep-sharded
    multi-chip path keep dispatch_mode='einsum'.
    """
    n, d = x.shape
    num_experts = gate_w.shape[-1]
    if capacity is None:
        capacity = moe_capacity(n, num_experts, k, capacity_factor)
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    if valid is None:
        valid = jnp.ones((n,), jnp.float32)
    valid = valid.astype(jnp.float32)
    probs = probs * valid[:, None]

    remaining = probs
    idx_rounds, gate_rounds = [], []
    first_choice = None
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                      # [n]
        onehot = jax.nn.one_hot(idx, num_experts,
                                dtype=jnp.float32) * valid[:, None]
        if first_choice is None:
            first_choice = onehot
        gate_rounds.append(jnp.sum(probs * onehot, axis=-1))
        # invalid tokens route to the E sentinel: they sort past every
        # real expert and never consume capacity (einsum path: onehot
        # masked by valid)
        idx_rounds.append(jnp.where(valid > 0, idx, num_experts))
        remaining = remaining * (1.0 - onehot)

    kn = k * n
    ek = jnp.concatenate(idx_rounds).astype(jnp.int32)            # [kn]
    gk = jnp.concatenate(gate_rounds)                             # [kn]
    order = jnp.argsort(ek, stable=True)     # choice-major within expert
    es = ek[order]
    gs = gk[order]
    tok = (order % n).astype(jnp.int32)      # flat entry j*n+i -> token i
    # rank within the expert's segment = global rank - segment start
    starts = jnp.searchsorted(es, jnp.arange(num_experts + 1,
                                             dtype=es.dtype))
    pos = jnp.arange(kn, dtype=jnp.int32) - starts[es].astype(jnp.int32)
    keep = ((pos < capacity) & (es < num_experts) &
            (gs > 0)).astype(jnp.float32)
    dump = num_experts * capacity            # scratch row for drops
    dest = jnp.where(keep > 0, es * capacity + pos, dump)

    cdt = x.dtype
    xs = x[tok] * keep.astype(cdt)[:, None]                       # [kn, d]
    buf = jnp.zeros((num_experts * capacity + 1, d), cdt)
    expert_in = buf.at[dest].add(xs)[:-1].reshape(
        num_experts, capacity, d)
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(cdt)))
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cdt))

    w = gs * keep
    if normalize and k > 1:
        tot = jnp.zeros((n,), jnp.float32).at[tok].add(w)
        w = w / jnp.maximum(tot, 1e-9)[tok]
    flat_out = jnp.concatenate(
        [expert_out.reshape(num_experts * capacity, d),
         jnp.zeros((1, d), cdt)])
    contrib = flat_out[dest] * w.astype(cdt)[:, None]
    y = jnp.zeros((n, d), cdt).at[tok].add(contrib)

    n_valid = jnp.maximum(jnp.sum(valid), 1.0)
    me = jnp.sum(probs, axis=0) / n_valid
    ce = jnp.sum(first_choice, axis=0) / n_valid
    aux = num_experts * jnp.sum(me * ce)
    return y, aux


def moe_ffn(x: jnp.ndarray, valid: Optional[jnp.ndarray],
            gate_w: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
            *, k: int = 2, capacity_factor: float = 1.25,
            capacity: Optional[int] = None,
            act=jax.nn.relu, mesh=None, ep_axis: str = "ep",
            dispatch_mode: str = "einsum"
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [n, d] -> (y [n, d], aux loss).

    gate_w [d, E]; w_up [E, d, f]; w_down [E, f, d]. When `mesh` has an
    `ep` axis the expert-major intermediates are constrained to it so
    GSPMD keeps each expert's FFN on its owning devices and inserts the
    token all-to-all at the dispatch/combine einsums.

    `capacity` overrides the factor-derived per-expert buffer; pass
    capacity=n at inference for drop-free routing (the capacity limit
    only buys memory/balance at training scale — see models/block.py).
    """
    if dispatch_mode == "auto":
        # measured (tools/moe_dispatch_bench.py, v5e, bf16, d=512 f=2048
        # E=8 k=2): sort beats einsum at every single-host size — 1.8x at
        # 8k tokens, 5.4x at 32k — and is the only path that compiles at
        # >=131k. einsum remains for ep meshes, where the dispatch einsum
        # carries the token all-to-all.
        ep_sharded = mesh is not None and ep_axis in mesh.axis_names \
            and mesh.shape.get(ep_axis, 1) > 1
        dispatch_mode = "einsum" if ep_sharded else "sort"
    if dispatch_mode == "sort":
        assert mesh is None or ep_axis not in mesh.axis_names or \
            mesh.shape.get(ep_axis, 1) == 1, \
            "dispatch_mode='sort' is single-host; use 'einsum' under ep"
        return moe_sorted_ffn(x, valid, gate_w, w_up, w_down, k=k,
                              capacity_factor=capacity_factor,
                              capacity=capacity, act=act)
    assert dispatch_mode == "einsum", dispatch_mode
    n, d = x.shape
    num_experts = gate_w.shape[-1]
    if capacity is None:
        capacity = moe_capacity(n, num_experts, k, capacity_factor)
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32))
    dispatch, combine, aux = moe_dispatch(logits, valid, k=k,
                                          capacity=capacity)
    cdt = x.dtype

    def _ep(t):
        if mesh is not None and ep_axis in mesh.axis_names:
            spec = jax.sharding.PartitionSpec(
                ep_axis, *([None] * (t.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                t, jax.sharding.NamedSharding(mesh, spec))
        return t

    # [n,E,C] x [n,d] -> [E,C,d]: the token all-to-all rides this einsum
    expert_in = _ep(jnp.einsum("nec,nd->ecd", dispatch.astype(cdt), x))
    h = _ep(act(jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(cdt))))
    expert_out = _ep(jnp.einsum("ecf,efd->ecd", h, w_down.astype(cdt)))
    y = jnp.einsum("nec,ecd->nd", combine.astype(cdt), expert_out)
    return y, aux


# ------------------------------------------------- a chip's share, drop-free
# The serving form of the DeepSeek-V3 family's expert layer (models/block.py
# LatentBlock; used inside PagedDecoder's step): sigmoid scores, a
# bias-corrected top-k over ALL router outputs, normalised and scaled
# weights, NO token dropped at any token count and no [n, E, n] tensor. The
# layer is told which experts it holds, [lo, lo + n_held) of the router's
# outputs, and adds only their terms: what an expert-parallel deployment's
# one chip computes before the exchange, which this file does not stand in
# for.
def sigmoid_topk_route(h: jnp.ndarray, gate_w: jnp.ndarray,
                       bias: jnp.ndarray, *, k: int, scale: float,
                       eps: float = 1e-20
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h [n, d], gate_w [d, E], bias [E] -> (idx [n, k] int32 over all E
    outputs, weights [n, k] float32). Scores, choice and weights are
    float32 at the highest precision whatever the compute dtype: the bias
    moves the CHOICE (``top_k(s + bias)``), never the weight
    (``s[idx] / (sum s[idx] + eps) * scale``; ``eps`` is the family's own:
    DeepSeek-V3's and Kimi's 1e-20, LFM2's 1e-6)."""
    s = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), weights * scale


def _held_onehot(idx, lo: int, n_held: int):
    """[n, k, n_held] bool: assignment j of token t fell on held expert e."""
    return idx[:, :, None] == lo + jnp.arange(n_held, dtype=idx.dtype)


def held_combine(idx, weights, *, lo: int, n_held: int) -> jnp.ndarray:
    """[n, n_held] float32: the weight token t gives held expert e, zero
    where it did not choose it."""
    return jnp.sum(jnp.where(_held_onehot(idx, lo, n_held),
                             weights[:, :, None], 0.0), axis=1)


def held_load(idx, active, *, lo: int, n_held: int) -> jnp.ndarray:
    """int32 [2]: (assignments of ACTIVE tokens that fell on held experts,
    held experts that got at least one active token). The second sizes a
    grouped product that would skip the experts no token chose."""
    hit = _held_onehot(idx, lo, n_held) & active[:, None, None]
    return jnp.stack([jnp.sum(hit, dtype=jnp.int32),
                      jnp.sum(jnp.any(hit, axis=(0, 1)), dtype=jnp.int32)])


def swiglu(x, w_gate, w_up, w_down):
    """(silu(x Wg) * x Wu) Wd over x [n, d] float32 -> float32, each
    product at the activations' precision (ops/linear.einsum_two_terms)."""
    a = jax.nn.silu(_mm("nd,df->nf", x, w_gate)) * _mm("nd,df->nf", x, w_up)
    return _mm("nf,fd->nd", a, w_down)


def held_experts_ffn(x, comb, w_gate, w_up, w_down) -> jnp.ndarray:
    """sum_e comb[:, e] * SwiGLU_e(x): x [n, d] float32, comb [n, n_held]
    float32, w_gate/w_up [n_held, d, f], w_down [n_held, f, d] -> [n, d]
    float32. Every held expert's SwiGLU over all n tokens as one batched
    product, weighted before the down projection so that experts and
    their width contract in ONE product: at a decode step's token counts
    the layer's time is the reading of its weights, which this form reads
    once."""
    a = jax.nn.silu(_mm("nd,edf->nef", x, w_gate)) * \
        _mm("nd,edf->nef", x, w_up)
    return _mm("nef,efd->nd", a * comb[:, :, None], w_down)


def routed_experts_ffn(h, router_w, router_bias, experts, shared=None, *,
                       k: int, scale: float, rank: int = 0,
                       eps: float = 1e-20, active=None):
    """A chip's share of a sigmoid-routed expert layer, the one function
    every description with such a layer calls (models/block.py): h [n, d]
    float32, the layer's normalised input; the router over ALL its
    outputs (:func:`sigmoid_topk_route` with the family's ``eps``);
    ``experts`` (gate, up [n_held, d, f], down [n_held, f, d]), the held
    ones, which are ``[n_held * rank, n_held * (rank + 1))`` of the
    router's outputs; ``shared`` (gate, up, down) a shared expert every
    token passes whole, or None where the family has none; ``active`` [n]
    bool masks the load count, never the result. -> (y [n, d] float32,
    the held load int32 [2] of :func:`held_load`)."""
    n_held = experts[0].shape[0]
    lo = n_held * rank
    with jax.named_scope("router"):
        idx, wts = sigmoid_topk_route(h, router_w, router_bias, k=k,
                                      scale=scale, eps=eps)
        comb = held_combine(idx, wts, lo=lo, n_held=n_held)
        act = jnp.ones((h.shape[0],), jnp.bool_) if active is None \
            else active.reshape(-1)
        load = held_load(idx, act, lo=lo, n_held=n_held)
    with jax.named_scope("experts"):
        y = held_experts_ffn(h, comb, *experts)
    if shared is not None:
        with jax.named_scope("shared_expert"):
            y = y + swiglu(h, *shared)
    return y, load
