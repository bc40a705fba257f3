"""Block descriptions and their cache kinds: what a decoder computes.

The decoders (models/decode.py) and ``DecodeEngine`` hold a parameter
table ``p`` and ONE description of the block that reads it. What a block
computes, what it caches a token and which paged attention reads that
cache are stated here and nowhere else: the decoders call the description
and never ask which one it is. The protocol is informal (four descriptions,
four cache kinds: no base class, no registry); ``pre`` is ``_<name>_``.

A description (a frozen object of pure functions over the table):

    cache                          its cache kind, a class below
    positions(p, pre) -> int       positions the model can address
    table_dtype(p, pre), vocab_size(p, pre)
    embed(p, pre, ids, pos)        ids [B, t] at positions pos -> [B, t, d]
    ffn(p, pre, i, x, active=None) -> (x + FFN_i(norm(x)), held load int32
                                   [2] or None); ``active`` [B, t] masks
                                   the load count, never the result
    logits(p, pre, x)              final norm and head -> [B, t, V]
    n_expert_layers(n_layers)      layers whose held load the step sums
    ... and what its cache kind asks of it (the kind's docstring)

A cache kind:

    refuses                        {"kv_quant" | "draft" | "speculation" |
                                   "spill": why this kind cannot}
    dense_init(block, p, pre, b, max_len, i)    layer i's dense caches
    dense_layer(block, p, pre, i, x, cache, positions, pos, kv_len)
                                   -> (x, cache): generate / beam_search
    Kind(block, p, pre, n_layers=, num_slots=, window=, page_size=,
         num_pages=, max_pages_per_slot=, kv_quant=)    the paged pools
         (and ``state_snapshots=`` where the engine is given one):
      .dtype, .plan (the artifact fingerprints' facts), .init_pools(),
      .kernel_supported(), .page_payload(page) (the spill codec's shape),
      .lanes() -> (lanes, tokens a lane): the prefill group its paged read
                                   takes beside the slots; (0, 0) is none
      .init_pools() -> (a, b): the kind's TWO pools, each whatever pytree
                                   it says (K and V; their int8 pytrees; a
                                   latent pool and nothing; a latent pool
                                   and a state; {"k", "v"} page pools and a
                                   state). The decoders and the engine pass
                                   both through whole, to the step and to
                                   the page and row programs, and never look
                                   inside: ``map_pages`` / ``map_state``
                                   choose the leaves
      .layer(p, i, x, a, b, toks, use_kernel=, interpret=)
                                   -> (x, a, b, held load or None)
      .layer_operand               True where ``layer`` also takes ``at=``,
                                   the pool's layer index as an OPERAND (a
                                   traced number) beside the ``i`` that names
                                   the parameters: :func:`shared_layers`
      .map_pages(fn, pool, *rest)  the page programs' map over a pool's
                                   leaves (``jax.tree_util.tree_map`` where
                                   every leaf is laid out by page)
      .state_rows                  rows of recurrent state it keeps a slot
                                   beside the pages, 0 for none. With rows
                                   (:class:`StateLatentCache`,
                                   :class:`StatePerHeadCache`): slot s's
                                   state is row s, ``.snapshot_rows`` are the
                                   prefix index's to give out, ``.zero_row``
                                   is what a slot without a snapshot starts
                                   from, and ``.map_state(fn, pool)`` maps a
                                   row program (the engine's row copy)

The paged step feeds its rows in GROUPS (``toks``, a tuple of
:class:`PagedTokens`): FIRST the [S, W] slot windows, row s slot s's, and
then, in the lane program, [Sp, C] prefill lanes, each lane a chunk of
one slot's prompt read and written through that slot's page-table row
(``slots`` names each row's slot; a slot's lanes lie one after the other
in position order and all but its last are full). ``x`` holds the rows of all
groups: one group's own [S, W, d], or every group's rows side by side as
[1, R, d] (:func:`join_rows`, :func:`split_rows`). A ``layer`` runs what
reads weights ONCE over ``x``, scatters the rows of every group into the
pool, and only then reads the cache, once a group with that group's page
tables and lengths: a lane's rows are causal among themselves, over other
lanes of its slot at earlier positions and over what the slot cached
before, because every row is written before any is read and masks by its
own length.

Layer ``i`` of a description is a function of its OWN parameters, named
``{pre}l{i}_...``, and of the table's shared ones (it may read widths off
layer 0's shapes): layers whose own parameters have equal names, shapes
and dtypes compute the same function.
The lane program traces and lowers one layer for all of such a kind
(:func:`shared_layers`); the plain program, held to its text, unrolls them.

:class:`DefaultBlock` is the block the ``layer`` DSL trains
(models/transformer.py keeps its own copy of the names): pre-LayerNorm,
learned positions, MHA/GQA, ReLU FFN or capacity-routed experts, tied or
untied head, over per-head K/V.

:class:`LatentBlock` is the DeepSeek-V3 / Kimi-K2 block: RMSNorm, rotary
positions (YaRN) on part of each query head, a low-rank query, multi-head
LATENT attention whose cache row is one ``[c_kv | k_rope]`` per token and
layer (not per head), SwiGLU, leading dense layers, then sigmoid-routed
experts of which this chip holds a share, a shared expert, an untied head.

:class:`DeltaLatentBlock` is the Kimi-Linear block: that block's stream,
experts and head over layers of two kinds by index, most of them keeping a
recurrent state a slot (its docstring, and :class:`StateLatentCache`'s).

:class:`ShortConvBlock` is the LFM2 block: RMSNorm, layers of two kinds by
index, gated short convolutions (two float32 tails a sequence) three to
one grouped-query attention layer with a norm a head on q and k and rotary
positions, over :class:`StatePerHeadCache` (per-head K/V pages over the
attention layers alone, the tails as rows of a state pool); leading dense
layers, then sigmoid-routed experts of which this chip holds a share and no
shared expert.

Its precision. The weights and the cache are what the table holds
(bfloat16 as served); the residual stream, the norms, the router and every
elementwise step are float32, and a product with a stored weight takes
its float32 activation as two terms of the weight's dtype in one pass
(ops/linear.einsum_two_terms). That is what keeps the router's top-k the
reference's: rounding the stream to bfloat16 moved a 384-way top-8 choice
at one position in ten a layer, and a moved choice on a held expert adds
or drops a whole expert's term.

Its table (every norm a gain, no bias):

    <pre>tok_emb.w0 [V, d]   <pre>lm_head.w0 [V, d]   <pre>norm_f.w0 [d]
    <pre>l<i>_attn_norm.w0 [d]     <pre>l<i>_ffn_norm.w0 [d]
    <pre>l<i>_q_down.w0 [d, rq]    <pre>l<i>_q_norm.w0 [rq]
    <pre>l<i>_q_up.w0 [rq, H*(dn+dr)]   <pre>l<i>_proj.w0 [H*dv, d]
    <pre>l<i>_kv_down.w0 [d, rkv+dr]   <pre>l<i>_kv_norm.w0 [rkv]
    <pre>l<i>_kv_up.w0 [rkv, H*(dn+dv)]
    dense layers:  <pre>l<i>_gate.w0, _up.w0 [d, f], _down.w0 [f, d]
    expert layers: <pre>l<i>_router.w0 [d, E], _router.wbias [E],
                   _experts.gate, _experts.up [held, d, fm],
                   _experts.down [held, fm, d],
                   _shared.gate, _shared.up [d, fs], _shared.down [fs, d]
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import pallas_decode as paged_ops
from paddle_tpu.ops import pallas_kda as kda_ops
from paddle_tpu.ops.linear import einsum_two_terms as mm

NEG_INF = -1e30


def layer_norm(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      - mean * mean, 0.0)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * g + b).astype(x.dtype)


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


def split_heads(x, h):
    return x.reshape(x.shape[:-1] + (h, x.shape[-1] // h))


def use_flash_prefill(t, pos, dh) -> bool:
    """Flash-prefill gate: a long (>=256) prompt on TPU with a
    tile-friendly head dim, and the cache empty before this call (pos is
    the static int 0 at prefill; a decode step's is a traced scalar)."""
    from paddle_tpu.config import global_config
    from paddle_tpu.ops import pallas_attention as flash
    probe = jax.ShapeDtypeStruct((1, t, 1, dh), jnp.float32)
    return (isinstance(pos, int) and pos == 0 and t >= 256
            and flash.flash_supported(probe, probe)
            and global_config().use_flash_attention
            and jax.default_backend() == "tpu")


class PagedTokens(NamedTuple):
    """What PagedDecoder's step knows of one group of its tokens: the
    [S, W] slot windows, or the [Sp, C] prefill lanes."""
    positions: jax.Array
    active: jax.Array        # bool: a masked token writes to the null page
    page_idx: jax.Array      # the physical page each token's row goes to
    offs: jax.Array          # and the row inside it
    page_tables: jax.Array   # [S, P]
    kv_lens: jax.Array       # positions + 1
    live_lens: jax.Array     # 0 where not active: such a token attends to
    #                          nothing, an all-masked slot copies no page
    slots: jax.Array         # [S]: the slot each row of the group is of


def join_rows(parts):
    """Every group's [B_g, t_g, ...] rows side by side as [1, R, ...]; a
    single group stays as it is."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(
        [a.reshape((1, -1) + a.shape[2:]) for a in parts], axis=1)


def split_rows(toks, a):
    """:func:`join_rows` undone: a [1, R, ...] -> each group's
    [B_g, t_g, ...]."""
    if len(toks) == 1:
        return [a]
    parts, at = [], 0
    for t in toks:
        n = t.positions.size
        parts.append(a[0, at:at + n].reshape(t.positions.shape + a.shape[2:]))
        at += n
    return parts


def _rows(toks, field):
    """``field`` of every group's tokens, in x's row layout."""
    return join_rows([getattr(t, field) for t in toks])


def _lane_width(takes, width: int) -> int:
    """The widest window, ``width`` halved as often as it must be, that
    the kernel's gate ``takes``."""
    while width > 1 and not takes(width):
        width //= 2
    return width


def _state_rows_of(n: int, tok: PagedTokens) -> tuple:
    """Group ``n``'s rows as a state kernel takes them: (the state row of
    each, which is its slot's; whether it starts from the pool; how many
    of its tokens are fed). The first group is the slots' own: every row
    of it starts from the pool (None). A lane goes on from the lane before
    it where that is the same slot's and both are fed (one longer
    chunk)."""
    slots = tok.slots
    fed = jnp.sum(tok.active, axis=1, dtype=jnp.int32)
    first = None if n == 0 else jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (slots[1:] != slots[:-1]) | (fed[1:] == 0) | (fed[:-1] == 0)])
    return slots, first, fed


def swiglu_or_experts(p, lp: str, x, *, rms_eps: float, expert: bool,
                      shared: bool, active=None, **route):
    """x [B, t, d] -> (x + FFN(RMSNorm(x; ``{lp}ffn_norm``)), held load
    int32 [2] or None): a dense SwiGLU (``{lp}gate`` / ``up`` / ``down``),
    or where ``expert`` the chip's share of a sigmoid-routed expert layer
    (ops/moe.py ``routed_experts_ffn``, which takes ``route``: k, scale,
    rank, eps), with the ``{lp}shared`` expert where the family has one.
    ``active`` [B, t] bool masks the load count (never the result)."""
    shape = x.shape
    h = rms_norm(x, p[f"{lp}ffn_norm.w0"], rms_eps)
    h = h.reshape(-1, shape[-1])
    if not expert:
        y = moe_ops.swiglu(h, p[f"{lp}gate.w0"], p[f"{lp}up.w0"],
                           p[f"{lp}down.w0"])
        return x + y.reshape(shape), None
    three = lambda name: tuple(p[f"{lp}{name}.{n}"]
                               for n in ("gate", "up", "down"))
    y, load = moe_ops.routed_experts_ffn(
        h, p[f"{lp}router.w0"], p[f"{lp}router.wbias"], three("experts"),
        three("shared") if shared else None, active=active, **route)
    return x + y.reshape(shape), load


def shared_layers(layer, pre: str):
    """``layer(p, i, x, k_pool, v_pool, toks)`` for a program that traces
    and lowers a layer once a KIND of layer, not once a layer (a second
    step program is seconds of set-up, most of it the Python that builds
    24 copies of one layer): layers whose own parameters are alike run ONE
    jitted function, each on its own parameters under the first such
    layer's names, with the pool's layer index an operand (``at``). XLA
    inlines the calls, so the executable is the unrolled layers'. A layer
    with no parameter of its own under ``{pre}l{i}_`` runs as it is."""
    one = jax.jit(lambda lp, i0, at, x, k_pool, v_pool, toks:
                  layer(lp, i0, x, k_pool, v_pool, toks, at=at),
                  static_argnums=1)
    of_a_layer = re.compile(re.escape(pre) + r"l\d+_")
    first: dict = {}

    def run(p, i, x, k_pool, v_pool, toks):
        own = f"{pre}l{i}_"
        mine = {n[len(own):]: v for n, v in p.items() if n.startswith(own)}
        if not mine:
            return layer(p, i, x, k_pool, v_pool, toks)
        i0 = first.setdefault(tuple(sorted(
            (n, v.shape, str(v.dtype)) for n, v in mine.items())), i)
        # the table's shared parameters, layer 0's (a description reads
        # its widths off them), and this layer's under the first's names
        lp = {n: v for n, v in p.items()
              if not of_a_layer.match(n) or n.startswith(f"{pre}l0_")}
        lp.update({f"{pre}l{i0}_{n}": v for n, v in mine.items()})
        return one(lp, i0, np.int32(i), x, k_pool, v_pool, toks)

    return run


# ------------------------------------------------------------ cache kinds
class PerHeadCache:
    """K and V, a row per token and kv head. Asks of its block:
    ``heads(p, pre) -> (h, g, dh)``; ``qkv(p, pre, i, x, pos, flat=False)
    -> q [B, t, h, dh], k, v [B, t, g, dh]`` (``flat``: k, v as the pool
    stores a token's row, [B*t, g*dh]); ``project(p, pre, i, attn)``.

    Dense: a [b, T, g, dh] pair a layer, read at stored width (GQA never
    repeats the cache). Paged: two pools [L, n_pages, page_size, g*dh],
    the kv heads of a token side by side on the lane axis (whole tiles at
    16 x 2048 bf16), in the layout paged_window_attention reads, in place.
    ``kv_quant="int8"`` makes each pool ``{"q": int8 values in that
    layout, "s": float32 [L, n_pages, page_size, g]}``: the scatter
    quantizes a row per (token, kv head) with ``quantize_kv`` (a pure
    function of the row, so prefix-shared pages stay bit-identical) and
    attention dequantizes as it reads; ~4x pages per byte at an fp32
    base, greedy output prefix-identical under the INT8_KV_* contract."""

    #: the stored layout, as the artifact fingerprints name it: an
    #: executable built for another layout can never be resolved
    LAYOUT = "L,N,page,g*dh"
    refuses: dict = {}
    #: the scatter's and the gather's index and the kernel's operand
    layer_operand = True
    #: prompt tokens a step takes through its lanes beside the slots: ONE
    #: deployment's reading, not derived from the decoder's widths or its
    #: slot count (PERF.md section 7). Beside OPT-1.3B's 32 slots at 340
    #: cached tokens a lane step cost 1.0 / 1.8 / 2.4 / 4.3 ms more than a
    #: plain one at 32 / 64 / 96 / 128 lane tokens (v5e, PERF.md section
    #: 6, PR 38: past 128 rows in all XLA computes the products that read
    #: the weights as copies into VMEM plus convolutions, which overlap
    #: the read worse), and the steps that carry a lane are the steps that
    #: carry an admission, which set the 95th-percentile gap. 64 is one
    #: step for a turn's suffix and five for the longest chat prompt
    LANE_TOKENS = 64

    #: rows of recurrent state a slot (none), and what the page programs
    #: map over (every leaf of a pool is laid out by page)
    state_rows = 0
    map_pages = staticmethod(jax.tree_util.tree_map)

    @staticmethod
    def dense_init(block, p, pre, b, max_len, i=0):
        _, g, dh = block.heads(p, pre)
        dtype = block.table_dtype(p, pre)
        return (jnp.zeros((b, max_len, g, dh), dtype),
                jnp.zeros((b, max_len, g, dh), dtype))

    @staticmethod
    def dense_layer(block, p, pre, i, x, cache, positions, pos, kv_len):
        """One block over a [b, t, d] slice; reads/extends the caches at
        positions [pos, pos+t)."""
        k_cache, v_cache = cache
        q, k, v = block.qkv(p, pre, i, x, positions)
        h, dh = q.shape[2:]
        kv_h = k_cache.shape[2]
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
        t = x.shape[1]
        T = k_cache.shape[1]
        scale = dh ** -0.5
        rep = h // kv_h
        if use_flash_prefill(t, pos, dh):
            # LONG-prompt prefill: the einsum path materializes a
            # [b,g,rep,t,t] score tensor (quadratic HBM); the flash kernel
            # streams K/V blocks instead (the cache is empty before this
            # call). GQA repeats K/V here: once, never per decode step.
            from paddle_tpu.ops import pallas_attention as flash
            kq = k if rep == 1 else jnp.repeat(k, rep, axis=2)
            vq = v if rep == 1 else jnp.repeat(v, rep, axis=2)
            lens = jnp.minimum(jnp.full((x.shape[0],), t, jnp.int32),
                               kv_len)
            attn = flash.flash_attention(
                q.astype(x.dtype), kq.astype(x.dtype),
                vq.astype(x.dtype), q_lens=lens, kv_lens=lens,
                causal=True, scale=scale,
                interpret=jax.default_backend() == "cpu")
        else:
            q5 = q.reshape(q.shape[0], t, kv_h, rep, dh)
            logits = jnp.einsum("bqgrd,bkgd->bgrqk", q5,
                                k_cache.astype(q.dtype)) * scale
            # causal against absolute positions: query row j is at pos+j
            qpos = pos + jnp.arange(t)[:, None]
            kpos = jnp.arange(T)[None, :]
            mask = (kpos <= qpos) & (kpos < kv_len)
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            w = jax.nn.softmax(logits, axis=-1)
            attn = jnp.einsum("bgrqk,bkgd->bqgrd", w,
                              v_cache.astype(q.dtype))
        x = x + block.project(p, pre, i, attn.reshape(x.shape))
        return block.ffn(p, pre, i, x)[0], (k_cache, v_cache)

    def __init__(self, block, p, pre, *, n_layers, num_slots, window,
                 page_size, num_pages, max_pages_per_slot, kv_quant):
        self.block, self.pre, self.kv_quant = block, pre, kv_quant
        self.dtype = block.table_dtype(p, pre)
        self.n_heads, self.kv_heads, self.head_dim = block.heads(p, pre)
        self.rows = (n_layers, num_pages, page_size)
        self._query = (num_slots, window, max_pages_per_slot)
        self.plan = {"pool_layout": self.LAYOUT, "kv_heads": self.kv_heads,
                     "head_dim": self.head_dim}

    @property
    def query_dtype(self):
        """What the block's ``qkv`` hands the kernel as q."""
        return self.dtype

    def kernel_supported(self, group=None) -> bool:
        """Does the kernel take the slot group's [S, W] queries (or
        ``group``'s, another (rows, window))?"""
        (S, W, P), (_, N, ps) = self._query, self.rows
        S, W = group or (S, W)
        g, dh, int8 = self.kv_heads, self.head_dim, self.kv_quant == "int8"
        return paged_ops.paged_kernel_supported(
            jax.ShapeDtypeStruct((S, W, self.n_heads, dh), self.query_dtype),
            jax.ShapeDtypeStruct((N, ps, g * dh),
                                 jnp.int8 if int8 else self.dtype),
            jax.ShapeDtypeStruct((N, ps, g), jnp.float32) if int8 else None,
            pages_per_slot=P)

    def lanes(self) -> tuple:
        """(lanes, tokens a lane). A lane is as wide as fills the
        kernel's q block, one MXU tile of query rows a lane chunk (64
        tokens at two heads of 64 a chunk; the one-token step pays for
        that tile with two rows), narrower where the gate says so; the
        lanes together take ``LANE_TOKENS`` tokens a step."""
        n = self.LANE_TOKENS
        width = min(n, paged_ops.window_tile_tokens(
            self.n_heads, self.kv_heads, self.head_dim))
        if self.kernel_supported():
            width = _lane_width(
                lambda w: self.kernel_supported((n // w, w)), width)
        return n // width, width

    def init_pools(self):
        """Zeroed (k_pool, v_pool) in the stored layout."""
        row = self.kv_heads * self.head_dim

        def one():
            if self.kv_quant == "int8":
                return {"q": jnp.zeros(self.rows + (row,), jnp.int8),
                        "s": jnp.zeros(self.rows + (self.kv_heads,),
                                       jnp.float32)}
            return jnp.zeros(self.rows + (row,), self.dtype)

        return one(), one()

    def page_payload(self, page):
        """Value leaves [L, 1, ps, g*dh] -> [L, 1, ps, g, dh]: the shape
        the spill payload has always had (the codec and its checksums do
        not know the stored layout; scale leaves pass as they are)."""
        def heads(v):
            return split_heads(v, self.kv_heads)

        if self.kv_quant == "int8":
            return {"q": heads(page["q"]), "s": page["s"]}
        return heads(page)

    def layer(self, p, i, x, k_pool, v_pool, toks, *, use_kernel,
              interpret, at=None):
        """``at``: the pools' layer index where it is not the Python
        ``i`` that names the parameters (:func:`shared_layers`)."""
        blk, pre, g = self.block, self.pre, self.kv_heads
        at = i if at is None else at
        n = x.shape[0] * x.shape[1]
        q, k, v = blk.qkv(p, pre, i, x, _rows(toks, "positions"), flat=True)
        # unconditional scatter: every token of every group writes its
        # K/V at (layer, page, offset) of the donated pool, in place and
        # BEFORE any attention, so later tokens of a window or of a slot's
        # lanes attend to earlier ones; the caller routed masked tokens to
        # the null page
        rows_p = _rows(toks, "page_idx").reshape(-1)
        rows_o = _rows(toks, "offs").reshape(-1)

        def put(pool, rows):
            return pool.at[at, rows_p, rows_o].set(rows.astype(pool.dtype))

        # the scopes name the regions in a device trace (PERF.md section 3)
        scales = {}
        if self.kv_quant == "int8":
            with jax.named_scope("kv_write"):
                kq, ks = paged_ops.quantize_kv(k.reshape(n, g, -1))
                vq, vs = paged_ops.quantize_kv(v.reshape(n, g, -1))
                k_pool = {"q": put(k_pool["q"], kq.reshape(n, -1)),
                          "s": put(k_pool["s"], ks)}
                v_pool = {"q": put(v_pool["q"], vq.reshape(n, -1)),
                          "s": put(v_pool["s"], vs)}
            k_pages, v_pages = k_pool["q"], v_pool["q"]
            scales = dict(k_scales=k_pool["s"], v_scales=v_pool["s"])
        else:
            with jax.named_scope("kv_write"):
                k_pool, v_pool = put(k_pool, k), put(v_pool, v)
            k_pages, v_pages = k_pool, v_pool
        with jax.named_scope("paged_attn"):
            attn = join_rows([paged_ops.paged_window_attention(
                q_g, k_pages, v_pages, tok.page_tables, tok.live_lens,
                layer=at, use_kernel=use_kernel, interpret=interpret,
                **scales) for q_g, tok in zip(split_rows(toks, q), toks)])
        x = x + blk.project(p, pre, i, attn.reshape(x.shape))
        with jax.named_scope("ffn"):
            x, load = blk.ffn(p, pre, i, x, _rows(toks, "active"))
        return x, k_pool, v_pool, load


class LatentCache:
    """One ``[c_kv | k_rope]`` row a token and layer (no heads). Asks of
    its block: ``cache_widths``, ``sizes``, ``qkv`` (-> q_nope, q_rope,
    c_kv, k_rope), ``absorb_q``, ``expand_o``, ``project``, ``attend``,
    ``softmax_scale``.

    Dense: a ([b, T, rkv], [b, T, dr]) pair a layer, read by the expanded
    (unabsorbed) attention. Paged: ONE pool [L, n_pages, page_size, lanes],
    a row padded with zero lanes to whole 128-lane tiles (576 -> 640 at the
    published widths: a pool whose rows are not whole tiles reaches the
    kernel through a pool-sized relayout copy every layer), read by the
    absorbed attention of ops/pallas_decode.paged_latent_attention, where
    one latent row serves as key and as value (tests hold the two forms
    to each other). The engine knows two pool names, so the second is an
    empty pytree: every page program and donation maps over nothing."""

    LAYOUT = "L,N,page,c_kv|k_rope|0"
    #: ``layer`` traces a layer under its own index. The kernel has taken
    #: the layer as an operand since PR 42; the lane program's text (and
    #: a start's trace) change with this flag, which ROADMAP S7 flips
    layer_operand = False
    refuses = {
        "kv_quant": "kv_quant is not supported on a latent (MLA) cache: the "
        "int8 layout packs per-head scales, and a latent row has no heads",
        "draft": "a draft over a latent (MLA) block is not supported: "
        "DraftDecoder's slot-private caches are per-head K/V",
        "speculation": "speculative decoding (draft / spec_k) is not "
        "supported on a latent (MLA) block"}
    #: prompt tokens a step takes through its lanes beside the slots: a
    #: reckoning at Kimi-K2's widths, measured at that one deployment
    #: alone (PERF.md section 7). The absorbed read costs a token heads x
    #: (row + c_kv) products a cached row, two terms each (0.6 GFLOP a
    #: token and layer at 64 heads over 2k rows): 32 tokens are 0.6 ms of
    #: the chip's peak over six layers, 128 would be a quarter of the step
    LANE_TOKENS = 32
    state_rows = 0
    map_pages = staticmethod(jax.tree_util.tree_map)

    @staticmethod
    def dense_init(block, p, pre, b, max_len, i=0):
        dtype = block.table_dtype(p, pre)
        return tuple(jnp.zeros((b, max_len, w), dtype)
                     for w in block.cache_widths(p, pre))

    @staticmethod
    def dense_layer(block, p, pre, i, x, cache, positions, pos, kv_len):
        c_cache, r_cache = cache
        t, T = x.shape[1], c_cache.shape[1]
        qpos = pos + jnp.arange(t)
        q_nope, q_rope, c_kv, k_rope = block.qkv(
            p, pre, i, x, jnp.broadcast_to(qpos[None], x.shape[:2]))
        c_cache = jax.lax.dynamic_update_slice(
            c_cache, c_kv.astype(c_cache.dtype), (0, pos, 0))
        r_cache = jax.lax.dynamic_update_slice(
            r_cache, k_rope.astype(r_cache.dtype), (0, pos, 0))
        kpos = jnp.arange(T)[None, :]
        mask = (kpos <= qpos[:, None]) & (kpos < kv_len)
        attn = block.attend(p, pre, i, q_nope, q_rope, c_cache, r_cache,
                            jnp.broadcast_to(mask[None], (x.shape[0], t, T)),
                            absorbed=False)
        x = x + block.project(p, pre, i, attn)
        return block.ffn(p, pre, i, x)[0], (c_cache, r_cache)

    def __init__(self, block, p, pre, *, n_layers, num_slots, window,
                 page_size, num_pages, max_pages_per_slot, kv_quant):
        self.block, self.pre = block, pre
        self.dtype = block.table_dtype(p, pre)
        rkv, dr = block.cache_widths(p, pre)
        self.row_lanes = -(-(rkv + dr) // 128) * 128
        self.shape = (n_layers, num_pages, page_size, self.row_lanes)
        self._query = (num_slots, window, block.sizes(p, pre)["H"],
                       self.row_lanes, rkv, page_size, max_pages_per_slot)
        self.plan = {"pool_layout": self.LAYOUT,
                     "row_lanes": self.row_lanes}

    def kernel_supported(self, group=None) -> bool:
        """Does the kernel take the slot group's queries (or ``group``'s,
        another (rows, window))?"""
        S, W, H, *rest = self._query
        S, W = group or (S, W)
        return paged_ops.latent_kernel_supported(S, W * H, *rest, self.dtype)

    def lanes(self) -> tuple:
        """(lanes, tokens a lane). A lane's window x heads rows, two
        terms each, stand in the kernel's VMEM beside a step's pages and
        the [rows, rkv] accumulator: the lane is as wide as that gate
        takes (4 tokens at 64 heads), and ``LANE_TOKENS`` a step over as
        many lanes. Where the gate takes nothing (toy shapes, run by
        gather or interpreted) one lane holds them all."""
        n = self.LANE_TOKENS
        width = _lane_width(
            lambda w: self.kernel_supported((n // w, w)), n) \
            if self.kernel_supported() else n
        return n // width, width

    def init_pools(self):
        return jnp.zeros(self.shape, self.dtype), {}

    def page_payload(self, page):
        return page                # a latent row has no heads to split

    def layer(self, p, i, x, pool, none, toks, *, use_kernel, interpret):
        """Every token's row scattered into the donated pool in place, the
        absorbed attention of each group over its slots' pages, the
        block's own FFN."""
        x, pool, load = self._latent_layer(p, i, i, x, pool, toks,
                                           use_kernel, interpret)
        return x, pool, none, load

    def _latent_layer(self, p, i, at, x, pool, toks, use_kernel, interpret):
        """Layer ``i``'s parameters over layer ``at`` of the pool."""
        blk, pre = self.block, self.pre
        q_nope, q_rope, c_kv, k_rope = blk.qkv(p, pre, i, x,
                                               _rows(toks, "positions"))
        with jax.named_scope("latent_kv_write"):
            row = jnp.concatenate([c_kv, k_rope], axis=-1)
            row = row.reshape(-1, row.shape[-1]).astype(pool.dtype)
            pool = pool.at[at, _rows(toks, "page_idx").reshape(-1),
                           _rows(toks, "offs").reshape(-1)].set(
                jnp.pad(row, ((0, 0), (0, pool.shape[-1] - row.shape[-1]))))
        with jax.named_scope("latent_attn"):
            q_lat = blk.absorb_q(p, pre, i, q_nope)
            o_lat = join_rows([paged_ops.paged_latent_attention(
                ql, qr, pool, tok.page_tables, tok.kv_lens, layer=at,
                scale=blk.softmax_scale, use_kernel=use_kernel,
                interpret=interpret)
                for ql, qr, tok in zip(split_rows(toks, q_lat),
                                       split_rows(toks, q_rope), toks)])
            attn = blk.expand_o(p, pre, i, o_lat)
        x = x + blk.project(p, pre, i, attn.reshape(x.shape[:2] + (-1,)))
        with jax.named_scope("ffn"):
            x, load = blk.ffn(p, pre, i, x, _rows(toks, "active"))
        return x, pool, load


class _StateRows:
    """What the kinds with a recurrent state a slot share: the state
    pool's row scheme and the two maps that tell its leaves from the
    pages'. The state pool is a dict with a ``"conv"`` leaf (both kinds
    keep a short convolution's tails); no page pool is."""

    def _set_state_rows(self, num_slots: int, state_snapshots) -> None:
        """The pool's rows: slots, snapshots, the zero row, the junk
        row."""
        n = num_slots if state_snapshots is None else int(state_snapshots)
        self.snapshot_rows = range(num_slots, num_slots + n)
        self.zero_row, self.junk_row = num_slots + n, num_slots + n + 1
        self.state_rows = num_slots + n + 2

    @staticmethod
    def _is_state(pool) -> bool:
        return isinstance(pool, dict) and "conv" in pool

    def map_pages(self, fn, pool, *rest):
        """The page programs' map: the state pool has no pages and passes
        as it is."""
        if self._is_state(pool):
            return pool
        return jax.tree_util.tree_map(fn, pool, *rest)

    def map_state(self, fn, pool):
        """A row program's map: over the state pool's leaves alone."""
        if self._is_state(pool):
            return jax.tree_util.tree_map(fn, pool)
        return pool


class StateLatentCache(_StateRows, LatentCache):
    """Two pools for a block whose layers are of two kinds by index
    (:class:`DeltaLatentBlock`): the latent page pool of
    :class:`LatentCache` over the block's latent layers ALONE
    ([latent layers, n_pages, page_size, lanes]), and beside it a STATE
    pool over its recurrent (delta-rule) layers, rows not pages:

        {"S":    [state layers, rows, H, dk, dv] float32, a head's state,
         "conv": [state layers, rows, 3 * 3*H*dk / 128, 128] float32, the
                 last three inputs of the short convolutions, one after
                 the other in whole lane tiles (ops/pallas_kda.py
                 ``kda_short_conv`` reads and writes them; 3*H*dk is whole
                 lane tiles)}

    Row s < num_slots is slot s's own, from admission to finish; the
    ``state_snapshots`` rows after them hold SNAPSHOTS (the state of all
    layers after exactly n tokens of some sequence, n a page boundary:
    the engine owns which); then one row that stays zero (what a slot
    with no snapshot starts from) and one that holds nothing (where a row
    that is fed nothing writes). The state pool is the engine's second
    pool: the page programs pass it by (``map_pages``), ``map_state``
    maps a row program over it.

    Asks of its block what :class:`LatentCache` asks, and
    ``state_layers``, ``state_sizes``, ``state_inputs``,
    ``state_conv_weights``, ``state_qkv``, ``state_output``."""

    LAYOUT = "latent layers,N,page,c_kv|k_rope|0 + " \
        "state layers,rows,H,dk,dv f32 | rows,3*3*H*dk/128,128 f32"
    refuses = {
        "kv_quant": "kv_quant is not supported on a latent (MLA) cache "
        "beside a recurrent state: a latent row has no heads to scale, "
        "and the state is float32",
        "draft": "a draft over a latent (MLA) block with a recurrent "
        "state is not supported: DraftDecoder's caches are per-head K/V",
        "speculation": "speculative decoding (draft / spec_k) is not "
        "supported over a recurrent state: a rejected token of a window "
        "would have to be rolled back out of it",
        "spill": "kv_spill_pages is not supported over a recurrent state: "
        "a spilled page's snapshot would have to travel with it"}

    #: prompt tokens a step takes through its lanes beside the slots, for
    #: this kind 128 (16 lanes of 8 at 32 heads), four times the latent
    #: kind's: most of its layers read no cached rows for a prompt token
    #: (a lane costs the state kernel 0.09 ms a layer), and at the latent
    #: kind's 32 the lanes of `kimilinear_agent_2k` ran half full, so that
    #: a turn's suffix QUEUED for them: time to first token 4 steps at the
    #: median and 7-10 at the 95th percentile, which spread by 25 % over
    #: six seeds (my chip runs, PR 39). One deployment's reading, like the
    #: others' (PERF.md section 7)
    LANE_TOKENS = 128

    @staticmethod
    def dense_init(block, p, pre, b, max_len, i=0):
        if i not in block.state_layers:
            return LatentCache.dense_init(block, p, pre, b, max_len)
        H, dk, dv = block.state_sizes(p, pre)
        return (jnp.zeros((b, H, dk, dv), jnp.float32),
                jnp.zeros((b, 3, 3 * H * dk), jnp.float32))

    @staticmethod
    def dense_layer(block, p, pre, i, x, cache, positions, pos, kv_len):
        """A state layer's dense cache is its state: (S, the conv tail);
        the t tokens of x go through it in order."""
        if i not in block.state_layers:
            return LatentCache.dense_layer(block, p, pre, i, x, cache,
                                           positions, pos, kv_len)
        St, tail = cache
        t = x.shape[1]
        pre_conv, g, beta, z = block.state_inputs(p, pre, i, x)
        ext = jnp.concatenate([tail, pre_conv], axis=1)      # [b, 3 + t, .]
        win = jnp.stack([ext[:, j:j + t] for j in range(4)], axis=2)
        q, k, v = block.state_qkv(kda_ops.conv_of_windows(
            win, block.state_conv_weights(p, pre, i)))
        with jax.named_scope("kda_state"):
            o, St = kda_ops.recurrent_kda(St, q, k, v, g, beta)
        x = x + block.state_output(p, pre, i, o, z)
        return block.ffn(p, pre, i, x)[0], (St, ext[:, t:])

    def __init__(self, block, p, pre, *, n_layers, num_slots, window,
                 page_size, num_pages, max_pages_per_slot, kv_quant,
                 state_snapshots=None):
        #: model layer -> its layer of the latent pool, or of the state pool
        self.latent_at = {i: j for j, i in enumerate(
            i for i in range(n_layers) if i not in block.state_layers)}
        self.state_at = {i: j for j, i in enumerate(
            i for i in range(n_layers) if i in block.state_layers)}
        super().__init__(block, p, pre, n_layers=len(self.latent_at),
                         num_slots=num_slots, window=window,
                         page_size=page_size, num_pages=num_pages,
                         max_pages_per_slot=max_pages_per_slot,
                         kv_quant=kv_quant)
        self._set_state_rows(num_slots, state_snapshots)
        H, dk, dv = block.state_sizes(p, pre)
        self.state_shapes = {
            "S": (len(self.state_at), self.state_rows, H, dk, dv),
            "conv": (len(self.state_at), self.state_rows,
                     9 * H * dk // 128, 128)}
        assert 3 * H * dk % 128 == 0, (H, dk)
        self.state_kernel = kda_ops.state_kernel_supported(H, dk, dv)
        self.plan = dict(self.plan, state_rows=self.state_rows,
                         state_layers=tuple(self.state_at))

    def init_pools(self):
        return jnp.zeros(self.shape, self.dtype), {
            k: jnp.zeros(v, jnp.float32)
            for k, v in self.state_shapes.items()}

    def layer(self, p, i, x, pool, state, toks, *, use_kernel, interpret):
        if i in self.latent_at:
            x, pool, load = self._latent_layer(
                p, i, self.latent_at[i], x, pool, toks, use_kernel, interpret)
            return x, pool, state, load
        blk, pre, at = self.block, self.pre, self.state_at[i]
        pre_conv, g, beta, z = blk.state_inputs(p, pre, i, x)
        outs = []
        for n, (tok, pc, g_, b_) in enumerate(zip(
                toks, split_rows(toks, pre_conv), split_rows(toks, g),
                split_rows(toks, beta))):
            slots, first, fed = _state_rows_of(n, tok)
            kernel = dict(use_kernel=use_kernel and self.state_kernel,
                          interpret=interpret, junk_row=self.junk_row,
                          layer=at)
            with jax.named_scope("kda_conv"):
                y, conv = kda_ops.kda_short_conv(
                    state["conv"], pc, blk.state_conv_weights(p, pre, i),
                    slots, first, fed, **kernel)
                q, k, v = blk.state_qkv(y)
            with jax.named_scope("kda_state"):
                o, S = kda_ops.kda_state_update(
                    state["S"], q, k, v, g_, b_, slots, first, fed, **kernel)
                state = dict(S=S, conv=conv)
            outs.append(o)
        x = x + blk.state_output(p, pre, i, join_rows(outs), z)
        with jax.named_scope("ffn"):
            x, load = blk.ffn(p, pre, i, x, _rows(toks, "active"))
        return x, pool, state, load


class StatePerHeadCache(_StateRows, PerHeadCache):
    """Per-head K/V pages beside a recurrent state, for a block whose
    layers are of two kinds by index (:class:`ShortConvBlock`):
    :class:`PerHeadCache`'s pools over the block's attention layers ALONE,
    and a STATE pool of convolution tails over its ``state_layers``, with
    :class:`StateLatentCache`'s row scheme. The engine's two pools are

        pages: {"k", "v": [attention layers, n_pages, page_size, g*dh]},
               in the layout paged_window_attention reads, in place
        state: {"conv": [state layers, rows, (K-1) * d / 128, 128] float32,
                the last K - 1 inputs of a layer's short convolution, one
                after the other in whole lane tiles (ops/pallas_kda.py
                ``short_conv`` reads and writes them)}

    Row s < num_slots of the state is slot s's own; then
    ``state_snapshots`` snapshot rows, the zero row and the junk row, as
    :class:`StateLatentCache` has them. The page programs map over
    ``pages`` alone (``map_pages``), the row copy over ``state`` alone
    (``map_state``).

    Asks of its block what :class:`PerHeadCache` asks (``heads``, ``qkv``
    with q float32, ``project``), and ``state_layers``, ``state_width``,
    ``state_inputs``, ``state_conv_weights``, ``state_output``."""

    LAYOUT = "attention layers,N,page,g*dh x {k,v} + " \
        "state layers,rows,(K-1)*d/128,128 f32"
    #: two kinds of layer, two pools with layer axes of their own: a layer
    #: is traced under its own index
    layer_operand = False
    refuses = {
        "kv_quant": "kv_quant is not supported on per-head pages beside a "
        "recurrent state: this kind's query is float32 against bfloat16 "
        "pages, and the int8 layout has no such path",
        "draft": "a draft over a block with a recurrent state is not "
        "supported: DraftDecoder's slot-private caches are per-head K/V "
        "alone",
        "speculation": "speculative decoding (draft / spec_k) is not "
        "supported over a recurrent state: a rejected token of a window "
        "would have to be rolled back out of it",
        "spill": "kv_spill_pages is not supported over a recurrent state: "
        "a spilled page's snapshot would have to travel with it"}

    #: prompt tokens a step takes through its lanes beside the slots: 8
    #: lanes of 16 tokens (``window_tile_tokens(32, 8, 64)``: a lane's
    #: 2 x 16 x 4 query rows a chunk fill the kernel's q tile), twice
    #: :class:`PerHeadCache`'s, because three of this block's four layers
    #: read no cached row for a prompt token. One deployment's reading, as
    #: the others' (LFM2-24B-A2B, 32 slots of 8.4k cached tokens, turns of
    #: 64-128 new tokens; v5e, PERF.md section 6, PR 41): at 128 a turn's
    #: suffix is ONE lane step of 33 ms beside plain steps of 22; at 256 a
    #: lane step is 43 ms, which is the 95th-percentile gap, and throughput
    #: 10 % lower, for a set-up's 262k-token prefill 33 s in place of 40
    LANE_TOKENS = 128

    @staticmethod
    def dense_init(block, p, pre, b, max_len, i=0):
        if i not in block.state_layers:
            return PerHeadCache.dense_init(block, p, pre, b, max_len)
        taps, ch = block.state_width(p, pre)
        return (jnp.zeros((b, taps - 1, ch), jnp.float32),)

    @staticmethod
    def dense_layer(block, p, pre, i, x, cache, positions, pos, kv_len):
        """A state layer's dense cache is its tail; the t tokens of x go
        through the convolution in order."""
        if i not in block.state_layers:
            return PerHeadCache.dense_layer(block, p, pre, i, x, cache,
                                            positions, pos, kv_len)
        (tail,) = cache
        t = x.shape[1]
        v, gate = block.state_inputs(p, pre, i, x)
        ext = jnp.concatenate([tail, v], axis=1)          # [b, K-1 + t, .]
        win = jnp.stack([ext[:, j:j + t]
                         for j in range(tail.shape[1] + 1)], axis=2)
        with jax.named_scope("short_conv"):
            y = kda_ops.conv_of_windows(
                win, block.state_conv_weights(p, pre, i), silu=False)
        x = x + block.state_output(p, pre, i, y, gate)
        return block.ffn(p, pre, i, x)[0], (ext[:, t:],)

    def __init__(self, block, p, pre, *, n_layers, num_slots, window,
                 page_size, num_pages, max_pages_per_slot, kv_quant,
                 state_snapshots=None):
        #: model layer -> its layer of the page pools, or of the state pool
        self.pages_at = {i: j for j, i in enumerate(
            i for i in range(n_layers) if i not in block.state_layers)}
        self.state_at = {i: j for j, i in enumerate(
            i for i in range(n_layers) if i in block.state_layers)}
        super().__init__(block, p, pre, n_layers=len(self.pages_at),
                         num_slots=num_slots, window=window,
                         page_size=page_size, num_pages=num_pages,
                         max_pages_per_slot=max_pages_per_slot,
                         kv_quant=kv_quant)
        self._set_state_rows(num_slots, state_snapshots)
        taps, ch = block.state_width(p, pre)
        #: whole lane tiles a tap: what the kernel's blocks are cut to
        self.state_kernel = ch % 128 == 0
        self.state_shape = (len(self.state_at), self.state_rows) + (
            ((taps - 1) * ch // 128, 128) if self.state_kernel
            else ((taps - 1), ch))
        self.plan = dict(self.plan, state_rows=self.state_rows,
                         state_layers=tuple(self.state_at))

    @property
    def query_dtype(self):
        return jnp.float32

    def init_pools(self):
        k, v = super().init_pools()
        return {"k": k, "v": v}, {
            "conv": jnp.zeros(self.state_shape, jnp.float32)}

    def layer(self, p, i, x, pages, state, toks, *, use_kernel, interpret):
        if i in self.pages_at:
            x, k, v, load = super().layer(
                p, i, x, pages["k"], pages["v"], toks,
                use_kernel=use_kernel, interpret=interpret,
                at=self.pages_at[i])
            return x, {"k": k, "v": v}, state, load
        blk, pre = self.block, self.pre
        v, gate = blk.state_inputs(p, pre, i, x)
        tails, outs = state["conv"], []
        for n, (tok, v_g) in enumerate(zip(toks, split_rows(toks, v))):
            slots, first, fed = _state_rows_of(n, tok)
            with jax.named_scope("short_conv"):
                y, tails = kda_ops.short_conv(
                    tails, v_g, blk.state_conv_weights(p, pre, i), slots,
                    first, fed, layer=self.state_at[i],
                    junk_row=self.junk_row, silu=False,
                    use_kernel=use_kernel and self.state_kernel,
                    interpret=interpret)
            outs.append(y)
        x = x + blk.state_output(p, pre, i, join_rows(outs), gate)
        with jax.named_scope("ffn"):
            x, load = blk.ffn(p, pre, i, x, _rows(toks, "active"))
        return x, pages, {"conv": tails}, load


# ----------------------------------------------------------- descriptions
@dataclasses.dataclass(frozen=True)
class DefaultBlock:
    """The ``layer`` DSL's block. Expert layers and their count are read
    from the table, but ``moe_k`` is NOT recoverable from it: it MUST
    match the training config or decode silently diverges.
    ``moe_capacity_factor=None`` routes DROP-FREE (capacity = each call's
    token count), so decode matches the training forward whenever training
    dropped nothing; a float reproduces a training capacity limit exactly."""

    n_heads: int
    moe_k: int = 2
    moe_capacity_factor: Optional[float] = None

    cache = PerHeadCache

    def positions(self, p, pre) -> int:
        return p[f"{pre}pos_emb.w0"].shape[0]    # the learned table's rows

    def table_dtype(self, p, pre):
        return p[f"{pre}tok_emb.w0"].dtype

    def vocab_size(self, p, pre) -> int:
        if f"{pre}head.w0" in p:
            return p[f"{pre}head.w0"].shape[1]
        return p[f"{pre}tok_emb.w0"].shape[0]

    def heads(self, p, pre) -> tuple:
        """(h, g, dh): the kv head count from the k projection's width
        (GQA stores g-sized caches — THE decode win of GQA)."""
        dh = p[f"{pre}tok_emb.w0"].shape[1] // self.n_heads
        return self.n_heads, p[f"{pre}l0_k.w0"].shape[1] // dh, dh

    def n_expert_layers(self, n_layers: int) -> int:
        return 0          # its experts are all here: no held load to count

    def embed(self, p, pre, ids, pos):
        return p[f"{pre}tok_emb.w0"][ids] + p[f"{pre}pos_emb.w0"][pos]

    def qkv(self, p, pre, i, x, pos, flat: bool = False):
        n = f"{pre}l{i}"
        h, g, _ = self.heads(p, pre)
        rows = (lambda a: a.reshape(x.shape[0] * x.shape[1], -1)) if flat \
            else (lambda a: split_heads(a, g))
        ln1 = layer_norm(x, p[f"{n}_ln1.w0"], p[f"{n}_ln1.wbias"])
        return (split_heads(ln1 @ p[f"{n}_q.w0"], h),
                rows(ln1 @ p[f"{n}_k.w0"]), rows(ln1 @ p[f"{n}_v.w0"]))

    def project(self, p, pre, i, attn):
        return attn @ p[f"{pre}l{i}_proj.w0"]

    def ffn(self, p, pre, i, x, active=None):
        n = f"{pre}l{i}"
        ln2 = layer_norm(x, p[f"{n}_ln2.w0"], p[f"{n}_ln2.wbias"])
        if f"{n}_moe.gate" not in p:
            up = jax.nn.relu(ln2 @ p[f"{n}_up.w0"] + p[f"{n}_up.wbias"])
            return x + up @ p[f"{n}_down.w0"], None
        b_, t_, d_ = ln2.shape
        gate = p[f"{n}_moe.gate"]
        cf, cap = self.moe_capacity_factor, None
        if cf is None:
            cap = b_ * t_
            # drop-free routing materializes [n, E, C=n] dispatch tensors
            # — quadratic in tokens. Cheap for the per-step call (n =
            # batch); for a LARGE prefill fall back to a generous factor
            # instead of OOMing the chip.
            if cap * cap * gate.shape[-1] > (1 << 27):
                import warnings
                warnings.warn(
                    f"moe prefill with {cap} tokens: drop-free routing "
                    f"would need a [{cap},{gate.shape[-1]},{cap}] dispatch "
                    "tensor; falling back to capacity_factor=2.0 (set "
                    "moe_capacity_factor explicitly to choose)",
                    stacklevel=2)
                cap, cf = None, 2.0
        y2d, _ = moe_ops.moe_ffn(
            ln2.reshape(b_ * t_, d_), None, gate, p[f"{n}_moe.moe_up"],
            p[f"{n}_moe.moe_down"], k=self.moe_k,
            capacity_factor=cf if cf is not None else 1.25,
            capacity=cap, dispatch_mode="auto")
        return x + y2d.reshape(b_, t_, d_), None

    def logits(self, p, pre, x):
        x = layer_norm(x, p[f"{pre}lnf.w0"], p[f"{pre}lnf.wbias"])
        if f"{pre}head.w0" in p:
            logits = x @ p[f"{pre}head.w0"]
        else:  # tie_embeddings: the head IS the token table, transposed
            logits = x @ p[f"{pre}tok_emb.w0"].T
        if f"{pre}head.wbias" in p:  # older checkpoints carried a bias
            logits = logits + p[f"{pre}head.wbias"]
        return logits


@dataclasses.dataclass(frozen=True)
class LatentBlock:
    """The block's configuration; sizes the table fixes (hidden size,
    ranks, widths, experts held) are read from the table."""

    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    max_positions: int
    first_dense_layers: int = 1
    experts_per_token: int = 8
    routed_scaling_factor: float = 1.0
    expert_rank: int = 0            # this chip among those sharing a layer
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0        # YaRN; 1 is plain RoPE
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rotary: bool = True             # False: no rotation anywhere (NoPE)

    cache = LatentCache
    #: the first latent layer: where the table's latent sizes are read
    latent0 = 0

    def positions(self, p, pre) -> int:
        return self.max_positions        # rotary: what the table was cut to

    def table_dtype(self, p, pre):
        return p[f"{pre}tok_emb.w0"].dtype

    def vocab_size(self, p, pre) -> int:
        return p[f"{pre}lm_head.w0"].shape[0]

    # ----------------------------------------------------------- rotary
    def inv_freq(self) -> np.ndarray:
        """[dr / 2] float64: ``theta^(-2j/dr)``, divided by ``factor``
        for the lanes past YaRN's ramp."""
        dr = self.qk_rope_head_dim
        f = self.rope_theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
        if self.rope_factor <= 1.0:
            return f

        def lane(beta):
            return dr * math.log(self.rope_original_max
                                 / (beta * 2 * math.pi)) / (
                2 * math.log(self.rope_theta))
        low = max(math.floor(lane(self.rope_beta_fast)), 0)
        high = min(math.ceil(lane(self.rope_beta_slow)), dr - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0.0, 1.0)
        return f / self.rope_factor * ramp + f * (1.0 - ramp)

    def _yarn_m(self, m: float) -> float:
        if self.rope_factor <= 1.0:
            return 1.0
        return 0.1 * m * math.log(self.rope_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        m = self._yarn_m(self.rope_mscale_all_dim) \
            if self.rope_mscale_all_dim else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    @functools.cache
    def rope_table(self) -> np.ndarray:
        """[2, max_positions, dr / 2] float32: cos and sin of position x
        frequency, times the rotary's own factor (mscale over
        mscale_all_dim), made on the host in float64. A table, not
        ``jnp.cos`` in the step: the chip's float32 cosine of an angle of
        a thousand radians is off in the fourth digit, which reached the
        attention's output at half a percent (PERF.md, PR 35)."""
        ang = np.outer(np.arange(self.max_positions, dtype=np.float64),
                       self.inv_freq())
        m = self._yarn_m(self.rope_mscale) / \
            self._yarn_m(self.rope_mscale_all_dim or 0.0)
        return np.stack([np.cos(ang), np.sin(ang)]).astype(np.float32) * \
            np.float32(m)

    def rope(self, x, pos):
        """x [..., dr] at positions ``pos`` (x's leading dims, or those
        without the heads axis when x is [..., H, dr]); rotate-half
        pairing (j, j + dr/2), float32 inside. The lanes pass as they
        are where the block does not rotate."""
        if not self.rotary:
            return x
        cos, sin = jnp.asarray(self.rope_table())[:, pos]
        if x.ndim == cos.ndim + 1:                  # a heads axis
            cos, sin = cos[..., None, :], sin[..., None, :]
        xf = x.astype(jnp.float32)
        a, b = jnp.split(xf, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(x.dtype)

    # ------------------------------------------------------------- sizes
    def sizes(self, p, pre: str) -> dict:
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        l0 = f"{pre}l{self.latent0}_"
        rkv = p[f"{l0}kv_norm.w0"].shape[0]
        return {"dn": dn, "dr": dr, "dv": dv, "rkv": rkv,
                "H": p[f"{l0}kv_up.w0"].shape[1] // (dn + dv)}

    def cache_widths(self, p, pre: str) -> tuple:
        """(c_kv lanes, k_rope lanes) of a token's cache row."""
        return (p[f"{pre}l{self.latent0}_kv_norm.w0"].shape[0],
                self.qk_rope_head_dim)

    # ------------------------------------------------------------- parts
    def embed(self, p, pre, ids, pos=None):
        return p[f"{pre}tok_emb.w0"][ids].astype(jnp.float32)

    def logits(self, p, pre, x):
        """x [B, t, d] -> float32 logits [B, t, V]."""
        h = rms_norm(x, p[f"{pre}norm_f.w0"], self.rms_eps)
        return mm("btd,vd->btv", h, p[f"{pre}lm_head.w0"])

    def qkv(self, p, pre, i, x, pos):
        """x [B, t, d] at positions pos [B, t] -> (q_nope [B, t, H, dn],
        q_rope [B, t, H, dr] rotated, c_kv [B, t, rkv], k_rope [B, t, dr]
        rotated), float32: a token's cache row is the last two. A table
        with no ``q_down`` has no low-rank query: ``q = h W_q``."""
        lp = f"{pre}l{i}_"
        z = self.sizes(p, pre)
        h = rms_norm(x, p[f"{lp}attn_norm.w0"], self.rms_eps)
        if f"{lp}q_down.w0" in p:
            c_q = rms_norm(mm("btd,dr->btr", h, p[f"{lp}q_down.w0"]),
                           p[f"{lp}q_norm.w0"], self.rms_eps)
            q = mm("btr,rf->btf", c_q, p[f"{lp}q_up.w0"])
        else:
            q = mm("btd,df->btf", h, p[f"{lp}q.w0"])
        q = q.reshape(x.shape[:-1] + (z["H"], z["dn"] + z["dr"]))
        ckr = mm("btd,df->btf", h, p[f"{lp}kv_down.w0"])
        c_kv = rms_norm(ckr[..., :z["rkv"]], p[f"{lp}kv_norm.w0"],
                        self.rms_eps)
        return (q[..., :z["dn"]], self.rope(q[..., z["dn"]:], pos), c_kv,
                self.rope(ckr[..., z["rkv"]:], pos))

    def _kv_up(self, p, pre, i):
        """W_uk, W_uv: [rkv, H, dn], [rkv, H, dv]."""
        z = self.sizes(p, pre)
        w = p[f"{pre}l{i}_kv_up.w0"].reshape(
            z["rkv"], z["H"], z["dn"] + z["dv"])
        return w[..., :z["dn"]], w[..., z["dn"]:]

    def absorb_q(self, p, pre, i, q_nope):
        """q_lat_i = q_nope_i W_uk_i^T: [..., H, dn] -> [..., H, rkv]."""
        w_uk, _ = self._kv_up(p, pre, i)
        return mm("bthd,rhd->bthr", q_nope, w_uk)

    def expand_o(self, p, pre, i, o_lat):
        """o_i = o_lat_i W_uv_i: [..., H, rkv] -> [..., H, dv]."""
        _, w_uv = self._kv_up(p, pre, i)
        return mm("bthr,rhd->bthd", o_lat, w_uv)

    def project(self, p, pre, i, attn):
        """concat_i(o_i) W_o: [B, t, H*dv] -> [B, t, d]."""
        return mm("btf,fd->btd", attn, p[f"{pre}l{i}_proj.w0"])

    def attend(self, p, pre, i, q_nope, q_rope, c, kr, mask,
               absorbed: bool = True):
        """Attention of q [B, t, H, .] over the cached rows c [B, T, rkv],
        kr [B, T, dr] under mask [B, t, T] -> [B, t, H*dv]. Both forms
        are the same mathematics: ``absorbed`` scores q_nope W_uk^T
        against c_kv itself and expands the output once; the other
        expands every cached row to per-head keys and values first."""
        sigma = self.softmax_scale
        hp = jax.lax.Precision.HIGHEST
        c, kr = c.astype(jnp.float32), kr.astype(jnp.float32)
        s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, kr, precision=hp)
        if absorbed:
            q_lat = self.absorb_q(p, pre, i, q_nope)
            s = jnp.einsum("bqhr,bkr->bhqk", q_lat, c, precision=hp) + s_rope
        else:
            k_nope = mm("bkr,rhd->bkhd", c, self._kv_up(p, pre, i)[0])
            s = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                           precision=hp) + s_rope
        s = jnp.where(mask[:, None], s * sigma, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        if absorbed:
            o = self.expand_o(p, pre, i, jnp.einsum(
                "bhqk,bkr->bqhr", w, c, precision=hp))
        else:
            o = jnp.einsum("bhqk,bkhd->bqhd", w, mm(
                "bkr,rhd->bkhd", c, self._kv_up(p, pre, i)[1]),
                precision=hp)
        return o.reshape(o.shape[:2] + (-1,))

    def is_expert_layer(self, i: int) -> bool:
        return i >= self.first_dense_layers

    def n_expert_layers(self, n_layers: int) -> int:
        return max(n_layers - self.first_dense_layers, 0)

    def ffn(self, p, pre, i, x, active=None):
        """x [B, t, d] -> (x + FFN_i(RMSNorm(x)), held load int32 [2] or
        None for a dense layer). ``active`` [B, t] bool masks the load
        count (never the result)."""
        return swiglu_or_experts(
            p, f"{pre}l{i}_", x, rms_eps=self.rms_eps,
            expert=self.is_expert_layer(i), shared=True, active=active,
            k=self.experts_per_token, scale=self.routed_scaling_factor,
            rank=self.expert_rank)


@dataclasses.dataclass(frozen=True)
class DeltaLatentBlock(LatentBlock):
    """The Kimi-Linear block: :class:`LatentBlock`'s residual stream,
    norms, expert FFN and head, with layers of two kinds by index. A layer
    in ``state_layers`` (0-based) is a Kimi Delta Attention (KDA) layer,
    which keeps no keys and values but a recurrent state a head
    (ops/pallas_kda.py) and the last three inputs of three short
    convolutions; every other layer is :class:`LatentBlock`'s latent
    attention, here with a plain query (no ``q_down`` in the table) and
    ``rotary=False``. Precision as the latent block's: stored matrices in
    the table's dtype, products two-term, the convolution, the gates, the
    state and the output norm float32.

    A KDA layer's table (H heads of dk = dv):

        <pre>l<i>_attn_norm.w0 [d]
        <pre>l<i>_kda_q.w0, _kda_k.w0, _kda_v.w0 [d, H*dk]
        <pre>l<i>_kda_q_conv.w0, _kda_k_conv.w0, _kda_v_conv.w0 [4, H*dk]
        <pre>l<i>_kda_f_a.w0 [d, r], _kda_f_b.w0 [r, H*dk]    (the decay)
        <pre>l<i>_kda_a_log.w0 [H], _kda_dt_bias.w0 [H*dk]
        <pre>l<i>_kda_beta.w0 [d, H]
        <pre>l<i>_kda_g_a.w0 [d, r], _kda_g_b.w0 [r, H*dv]    (output gate)
        <pre>l<i>_kda_o_norm.w0 [dv],  <pre>l<i>_proj.w0 [H*dv, d]"""

    state_layers: tuple = ()
    state_heads: int = 1

    cache = StateLatentCache

    @property
    def latent0(self) -> int:
        return next(i for i in range(len(self.state_layers) + 1)
                    if i not in self.state_layers)

    def state_sizes(self, p, pre) -> tuple:
        """(H, dk, dv) of a state layer."""
        lp = f"{pre}l{self.state_layers[0]}_"
        H = self.state_heads
        return (H, p[f"{lp}kda_q.w0"].shape[1] // H,
                p[f"{lp}kda_o_norm.w0"].shape[0])

    def state_inputs(self, p, pre, i, x):
        """x [B, t, d] -> (the three convolutions' inputs side by side
        [B, t, 3*H*dk] (q, k, v), the log-decay g [B, t, H, dk] <= 0,
        beta [B, t, H] in (0, 1), the output gate's logits
        [B, t, H*dv]), float32."""
        lp = f"{pre}l{i}_"
        H = self.state_heads
        h = rms_norm(x, p[f"{lp}attn_norm.w0"], self.rms_eps)
        pre_conv = jnp.concatenate(
            [mm("btd,df->btf", h, p[f"{lp}kda_{n}.w0"]) for n in "qkv"],
            axis=-1)
        with jax.named_scope("kda_gates"):
            a = mm("btr,rf->btf", mm("btd,dr->btr", h, p[f"{lp}kda_f_a.w0"]),
                   p[f"{lp}kda_f_b.w0"])
            a = split_heads(a + p[f"{lp}kda_dt_bias.w0"].astype(jnp.float32),
                            H)
            g = -jnp.exp(p[f"{lp}kda_a_log.w0"].astype(jnp.float32)
                         )[:, None] * jax.nn.softplus(a)
            beta = jax.nn.sigmoid(mm("btd,dh->bth", h, p[f"{lp}kda_beta.w0"]))
            z = mm("btr,rf->btf", mm("btd,dr->btr", h, p[f"{lp}kda_g_a.w0"]),
                   p[f"{lp}kda_g_b.w0"])
        return pre_conv, g, beta, z

    def state_conv_weights(self, p, pre, i):
        """The three depthwise kernels side by side, [4, 3*H*dk]: a
        token's convolution is ``silu(sum_j w[j] * input three back + j)``."""
        lp = f"{pre}l{i}_"
        return jnp.concatenate([p[f"{lp}kda_{n}_conv.w0"] for n in "qkv"],
                               axis=-1).astype(jnp.float32)

    def state_qkv(self, y):
        """The convolutions' outputs y [B, t, 3*H*dk] -> q, k, v
        [B, t, H, dk]: q and k to unit length a head, q scaled by
        dk^-0.5."""
        q, k, v = (split_heads(a, self.state_heads)
                   for a in jnp.split(y, 3, axis=-1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        return unit(q) * q.shape[-1] ** -0.5, unit(k), v

    def state_output(self, p, pre, i, o, z):
        """o [B, t, H, dv], gate logits z [B, t, H*dv] ->
        (RMSNorm(o) * sigmoid(z)) W_o, [B, t, d]."""
        lp = f"{pre}l{i}_"
        y = rms_norm(o, p[f"{lp}kda_o_norm.w0"], self.rms_eps) * \
            jax.nn.sigmoid(split_heads(z, o.shape[-2]))
        return mm("btf,fd->btd", y.reshape(y.shape[:2] + (-1,)),
                  p[f"{lp}proj.w0"])


@dataclasses.dataclass(frozen=True)
class ShortConvBlock:
    """The LFM2 (``lfm2_moe``) block: RMSNorm with a gain and no bias
    anywhere, layers of two kinds by index. A layer in ``conv_layers``
    (0-based) is a GATED SHORT CONVOLUTION, which keeps the last taps - 1
    of its inputs a sequence and no keys or values:

        [B | C | u] = h W_in;  v = B * u;  c_t = sum_j w_j v_{t-K+1+j};
        y = (C * c) W_out

    every other layer is full attention over per-head K/V: grouped-query
    (``n_heads`` on ``n_kv_heads``), RMSNorm a head on q and k, rotary
    positions over the whole head (halves paired). The first
    ``first_dense_layers`` layers carry a dense SwiGLU, the rest
    sigmoid-routed experts of which this chip holds a share and NO shared
    expert (ops/moe.py ``routed_experts_ffn``, :class:`LatentBlock`'s
    too). The head is ``lm_head`` where the table has one, else the
    embedding (tied). Precision as the file's head says: stored matrices
    and K/V in the table's dtype, products two-term, the stream, norms,
    router, convolution and tails float32.

    Its table:

        <pre>tok_emb.w0 [V, d]   <pre>norm_f.w0 [d]   (<pre>lm_head.w0 [V, d])
        <pre>l<i>_op_norm.w0 [d]     <pre>l<i>_ffn_norm.w0 [d]
        conv:      <pre>l<i>_conv_in.w0 [d, 3d], _conv.w0 [K, d],
                   _conv_out.w0 [d, d]
        attention: <pre>l<i>_q.w0 [d, h*dh], _k.w0, _v.w0 [d, g*dh],
                   _q_norm.w0, _k_norm.w0 [dh], _proj.w0 [h*dh, d]
        dense and expert layers: as :class:`LatentBlock`'s, no ``_shared``"""

    n_heads: int
    head_dim: int
    max_positions: int
    conv_layers: tuple = ()
    first_dense_layers: int = 2
    experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    route_eps: float = 1e-6
    expert_rank: int = 0            # this chip among those sharing a layer
    rms_eps: float = 1e-5
    rope_theta: float = 1e6

    cache = StatePerHeadCache

    @property
    def state_layers(self) -> tuple:
        return self.conv_layers

    def positions(self, p, pre) -> int:
        return self.max_positions        # rotary: what the table was cut to

    def table_dtype(self, p, pre):
        return p[f"{pre}tok_emb.w0"].dtype

    def vocab_size(self, p, pre) -> int:
        return p[f"{pre}tok_emb.w0"].shape[0]

    def _first(self, conv: bool) -> int:
        """The first layer of a kind: where the table's sizes are read."""
        return next(i for i in range(len(self.conv_layers) + 1)
                    if (i in self.conv_layers) == conv)

    def heads(self, p, pre) -> tuple:
        """(h, g, dh), the kv heads from the k projection's width."""
        k = p[f"{pre}l{self._first(False)}_k.w0"]
        return self.n_heads, k.shape[1] // self.head_dim, self.head_dim

    def n_expert_layers(self, n_layers: int) -> int:
        return max(n_layers - self.first_dense_layers, 0)

    @functools.cache
    def rope_table(self) -> np.ndarray:
        """[2, max_positions, dh / 2] float32: cos and sin of position x
        ``theta^(-2j/dh)``, made on the host in float64 (a table for the
        reason :meth:`LatentBlock.rope_table` gives)."""
        dh = self.head_dim
        ang = np.outer(np.arange(self.max_positions, dtype=np.float64),
                       self.rope_theta ** (
                           -np.arange(0, dh, 2, dtype=np.float64) / dh))
        return np.stack([np.cos(ang), np.sin(ang)]).astype(np.float32)

    def rope(self, x, pos):
        """x [B, t, H, dh] float32 at positions pos [B, t]: rotate-half
        pairing (j, j + dh/2) over the whole head."""
        cos, sin = jnp.asarray(self.rope_table())[:, pos][..., None, :]
        a, b = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1)

    # ------------------------------------------------------------- parts
    def embed(self, p, pre, ids, pos=None):
        return p[f"{pre}tok_emb.w0"][ids].astype(jnp.float32)

    def logits(self, p, pre, x):
        """x [B, t, d] -> float32 logits [B, t, V]."""
        h = rms_norm(x, p[f"{pre}norm_f.w0"], self.rms_eps)
        head = p.get(f"{pre}lm_head.w0", p[f"{pre}tok_emb.w0"])
        return mm("btd,vd->btv", h, head)

    def qkv(self, p, pre, i, x, pos, flat: bool = False):
        """x [B, t, d] at positions pos [B, t] -> q [B, t, h, dh], k, v
        [B, t, g, dh] float32, q and k normalised a head and rotated
        (``flat``: k, v as the pool stores a token's row, [B*t, g*dh])."""
        lp = f"{pre}l{i}_"
        h_, g, _ = self.heads(p, pre)
        h = rms_norm(x, p[f"{lp}op_norm.w0"], self.rms_eps)
        q, k, v = (split_heads(mm("btd,df->btf", h, p[f"{lp}{n}.w0"]), m)
                   for n, m in (("q", h_), ("k", g), ("v", g)))
        with jax.named_scope("qk_norm_rope"):
            q = self.rope(rms_norm(q, p[f"{lp}q_norm.w0"], self.rms_eps), pos)
            k = self.rope(rms_norm(k, p[f"{lp}k_norm.w0"], self.rms_eps), pos)
        if flat:
            k, v = (a.reshape(x.shape[0] * x.shape[1], -1) for a in (k, v))
        return q, k, v

    def project(self, p, pre, i, attn):
        return mm("btf,fd->btd", attn, p[f"{pre}l{i}_proj.w0"])

    def state_width(self, p, pre) -> tuple:
        """(taps, channels) of a conv layer's convolution."""
        return p[f"{pre}l{self._first(True)}_conv.w0"].shape

    def state_inputs(self, p, pre, i, x):
        """x [B, t, d] -> (the convolution's input v = B * u, the output
        gate C), both [B, t, d] float32."""
        lp = f"{pre}l{i}_"
        h = rms_norm(x, p[f"{lp}op_norm.w0"], self.rms_eps)
        bcu = mm("btd,df->btf", h, p[f"{lp}conv_in.w0"])
        with jax.named_scope("conv_gates"):
            b, c, u = jnp.split(bcu, 3, axis=-1)
            return b * u, c

    def state_conv_weights(self, p, pre, i):
        """[K, d] float32: ``c_t = sum_j w[j] * v_{t-K+1+j}``."""
        return p[f"{pre}l{i}_conv.w0"].astype(jnp.float32)

    def state_output(self, p, pre, i, y, gate):
        """(C * conv) W_out: [B, t, d] -> [B, t, d]."""
        with jax.named_scope("conv_gates"):
            y = gate * y
        return mm("btd,df->btf", y, p[f"{pre}l{i}_conv_out.w0"])

    def ffn(self, p, pre, i, x, active=None):
        """x [B, t, d] -> (x + FFN_i(RMSNorm(x)), held load int32 [2] or
        None for a dense layer)."""
        return swiglu_or_experts(
            p, f"{pre}l{i}_", x, rms_eps=self.rms_eps,
            expert=i >= self.first_dense_layers, shared=False,
            active=active, k=self.experts_per_token,
            scale=self.routed_scaling_factor, rank=self.expert_rank,
            eps=self.route_eps)
