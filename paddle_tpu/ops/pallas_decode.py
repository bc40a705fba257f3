"""Paged decode attention: the gather reference and the live-pages kernels.

The serving step (models/decode.py PagedDecoder, through the cache kinds
of models/block.py) reads its page pools through two entry points:

:func:`paged_window_attention` over per-head K/V pools. Its kernel
(:func:`_paged_window_kernel`) walks the LIVE pages itself. The pools
stay in HBM whole; the grid is over slots alone; inside a slot's step a
loop of ``ceil(used[s] / K)`` compute blocks (:func:`_walk_live_pages`,
which knows nothing of heads or softmax: both kernels of this file are
bodies over it) copies each live page with a DMA of its own into its rows
of a double-buffered [K*page_size, g*dh] tile while the block before is
computed, and starts the NEXT live slot's first block before this slot's
last one ends. A page past a slot's allocation is never named, copied or
visited, and an idle slot costs no DMA: a call's time follows the cached
tokens (0.13 ms at one token a slot, 1.3 ms at 2,048;
tests/test_tpu_smoke.py holds the ratio on the chip). The kernel it
replaced let Pallas's pipeline walk the table, grid (S, P), and paid
~65 ns a page operand visited, slots x table width whatever was cached
(PERF.md, PR 35 and PR 36). The query carries a W-token verify window
per slot (speculative decoding + multi-token prefill,
serving/engine.py), accumulated with the online-softmax recurrence
across blocks.

:func:`paged_latent_attention` over a latent (MLA) pool, one
[c_kv | k_rope] row a token. Its kernel (:func:`_paged_latent_kernel`)
is a second body over the same walk (PR 42): ONE pool, the row's 640
lanes whole tiles, every head's query rows against a block's tile in one
product, the tile used as key and as value.

:func:`paged_attention` is the GATHER REFERENCE both fall back to and
are held to: the page gather produces the contiguous [b, T, g, dh] view
of each slot's whole table width and runs the dense decoder's exact
einsum over it, so it is token-identical to the dense cache by
construction and reads ``max_seq_len`` of traffic a slot whatever is
cached. Parity of the kernels against it is pinned in
tests/test_paged_decode.py (GQA/MQA, ragged lengths, W > 1, idle slots,
block edges, repeated pages) and tests/test_latent_decode.py.

All paths read the pools AS STORED: a page is [page_size, g*dh], the kv
heads of a token side by side on the lane axis, and a pool that keeps
its layer axis is handed over whole with ``layer=`` (the comment above
:func:`gather_pages`). The window kernel walks a block's tile in
128-lane chunks against a block-diagonal q, so nothing is padded in HBM
or relaid out in VMEM at the serving widths."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LOG2E = 1.4426950408889634

# ---- int8 KV token-identity contract (the two-tier KV plane) ----
# The int8 paged path must be GREEDY-PREFIX-IDENTICAL to the fp
# single-tier baseline on the pinned suite (mirroring speculation's
# acceptance rule, serving/engine.py) and its attention output within
# this tolerance of the exact-einsum reference. These constants ARE
# the contract — tests/test_paged_decode.py pins against them, and a
# change here is a semantics change, not a tuning knob.
INT8_KV_RTOL = 2e-2
INT8_KV_ATOL = 2e-2
# smallest representable per-row scale: keeps all-zero K/V rows (the
# null page, unwritten pool rows) exactly zero after dequant while
# never dividing by zero in the quantizer
INT8_KV_SCALE_EPS = 1e-12


def quantize_kv(x):
    """Symmetric per-(row, kv-head) int8 quantization of K/V rows:
    ``x`` [..., dh] -> (int8 values [..., dh], float32 scales [...]).
    absmax/127 scaling with deterministic round-half-even — the paged
    scatter must be a pure function of the token run for prefix-reuse
    token identity to survive quantization (serving/prefix.py)."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1),
                    INT8_KV_SCALE_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def dequantize_kv(q, scales, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv`: int8 values [..., dh] * scales
    [...] -> ``dtype`` values [..., dh]."""
    return (q.astype(jnp.float32)
            * scales.astype(jnp.float32)[..., None]).astype(dtype)


# --------------------------------------------------------------- paged
# The pool layout (one contract for the kernel, the gather path and
# models/decode.PagedDecoder, which stores it): a page is
# [page_size, g*dh] — every kv head of a token side by side on the lane
# axis, so a page of the serving widths (16 x 2048 bf16) is whole
# (16, 128) tiles with nothing padded. Pools are [n_pages, page_size,
# g*dh], or [L, n_pages, page_size, g*dh] with ``layer=`` naming the
# layer to read: the layer goes into the gather's index / the index of
# the kernel's page copies, and no array op ever takes ``pool[i]`` out of
# the pool (a slice of a pool is a pool-sized copy on the chip). The int8
# layout's scales are [..., n_pages, page_size, g].
def gather_pages(pages, page_table, layer=None):
    """Contiguous per-sequence view of a paged pool: ``pages``
    [n_pages, page_size, g*dh] (or [L, n_pages, page_size, g*dh] with
    ``layer``) gathered through ``page_table`` [b, P] ->
    [b, P*page_size, g*dh]; the int8 layout's scales
    [..., n_pages, page_size, g] gather the same way. Rows of the table
    beyond a sequence's allocation point at the reserved null page (0);
    the caller's length mask keeps those positions out of the
    softmax."""
    b, pp = page_table.shape
    ps, width = pages.shape[-2:]
    rows = pages[page_table] if layer is None else pages[layer, page_table]
    return rows.reshape(b, pp * ps, width)


def paged_attention(q, k_pages, v_pages, page_table, kv_lens, *,
                    scale=None, k_scales=None, v_scales=None, layer=None):
    """Decode attention over a PAGED KV cache by gather: the reference
    :func:`paged_window_attention` falls back to and is held to.

    q [b, h, dh]: one query token per sequence (slot batch);
    k_pages/v_pages [n_pages, page_size, g*dh]: the shared page pools
    in the stored lane-dense layout (h % g == 0 — GQA reads the cache
    at stored width), or [L, n_pages, page_size, g*dh] with ``layer``;
    page_table [b, P] int32: each row maps the sequence's logical pages
    to physical pages (entries past the allocation = the null page 0);
    kv_lens [b] int32: per-row valid positions — position kv_lens[i]-1
    is row i's query, so the mask is both the causal mask AND the
    ragged-length mask. Returns [b, h, dh].

    The gather materializes the same [b, T, g, dh] view the dense cache
    stores, then runs the dense decoder's exact einsum formulation
    (models/block.py PerHeadCache.dense_layer; the measured optimum of
    five — docs/perf.md), so paged decode is token-identical to the
    dense path."""
    b, h, dh = q.shape
    g = k_pages.shape[-1] // dh
    assert g * dh == k_pages.shape[-1] and h % g == 0, (
        h, dh, k_pages.shape)
    rep = h // g
    if scale is None:
        scale = dh ** -0.5
    k = gather_pages(k_pages, page_table, layer).reshape(b, -1, g, dh)
    v = gather_pages(v_pages, page_table, layer).reshape(b, -1, g, dh)
    if k_scales is not None:
        # int8 pools: dequantize the GATHERED view (T rows, not the
        # whole pool) and fall through to the identical exact-einsum
        # formulation
        k = dequantize_kv(k, gather_pages(k_scales, page_table, layer),
                          q.dtype)
        v = dequantize_kv(v, gather_pages(v_scales, page_table, layer),
                          q.dtype)
    lens = jnp.asarray(kv_lens, jnp.int32).reshape(-1)
    t = k.shape[1]
    # identical formulation (einsum strings, mask value, softmax dtype
    # path) to PerHeadCache.dense_layer at t=1 — parity is structural
    q5 = q.reshape(b, 1, g, rep, dh)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", q5,
                        k.astype(q.dtype)) * scale
    mask = jnp.arange(t)[None, :] < lens[:, None]      # [b, T]
    logits = jnp.where(mask[:, None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bgrqk,bkgd->bqgrd", w, v.astype(q.dtype))
    return attn.reshape(b, h, dh)


# ------------------------------------------------ live-pages kernel
def _heads_per_chunk(g: int, dh: int) -> int:
    """How many kv heads one lane chunk of the kernel holds. A chunk is
    whole 128-lane tiles wherever the shapes allow it: 128 // dh heads
    of a narrow head (two heads of 64), one head whose dh is a multiple
    of 128. Shapes that fit neither (toy models: g*dh under 128, odd
    head dims) run the same code on ONE chunk that is the whole row,
    padded by the compiler."""
    hp = 128 // dh if 128 % dh == 0 else 1
    if (hp * dh) % 128 or g % hp:
        return g
    return hp


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# query rows that fill the MXU's tile: the kernel's q block holds
# hp x window x rep rows a lane chunk and pays for a whole tile of them
_Q_TILE_ROWS = 128


def window_tile_tokens(h: int, g: int, dh: int) -> int:
    """The window whose query rows fill one MXU tile of the kernel's q
    block (64 tokens at two heads of 64 a lane chunk): what a prefill
    lane is cut to (models/block.py PerHeadCache.lanes)."""
    return max(1, _Q_TILE_ROWS // (_heads_per_chunk(g, dh) * (h // g)))


def _walk_live_pages(tables_ref, used_ref, pools, bufs, sems, half_ref,
                     body, *, lead, page_size, pages_per_block):
    """One slot's share of the page walk: the grid is over slots, and
    this is what a grid step does about pages. It knows tables, counts,
    pools and buffers, and nothing of heads, chunks or softmax.

    ``pools`` are HBM refs [..., n_pages, page_size, width_i], whole, as
    stored (``lead`` = the layer's index, or ()); ``bufs`` their VMEM
    scratch [2, K*page_size, width_i], ``sems`` DMA semaphores
    [len(pools), 2]. Slot s holds ``used[s]`` live pages = ceil(used/K)
    compute blocks of K pages. A block's live pages are copied each by
    its own DMA into its rows of one half of every buffer while
    ``body(b, tiles)`` computes the block in the other half; a page past
    ``used[s]`` is never named, copied or waited for (its rows keep what
    an earlier block left there, finite, for the body's mask). The walk
    does not stop at a slot's end: while a slot's last block is computed
    the first block of the NEXT LIVE slot is already on its way, so a
    call pays one pipeline bubble, not one a slot, and a slot with
    ``used == 0`` issues nothing at all. ``half_ref`` (SMEM [1]) carries
    which half the next block lands in from one grid step to the
    next."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    K = pages_per_block

    def copies(slot, b, half, wait):
        def page(j, carry):
            # a wait reads only the destination's size
            src = 0 if wait else tables_ref[slot, b * K + j]
            row = 0 if wait else pl.multiple_of(j * page_size, page_size)
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                dma = pltpu.make_async_copy(
                    pool.at[lead + (src,)],
                    buf.at[half, pl.ds(row, page_size)], sems.at[i, half])
                dma.wait() if wait else dma.start()
            return carry

        live = jnp.minimum(K, used_ref[slot] - b * K)
        jax.lax.fori_loop(0, live, page, 0)

    def next_live(start):
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < n_slots, used_ref[jnp.minimum(i, n_slots - 1)] == 0),
            lambda i: i + 1, start)

    @pl.when(s == 0)
    def _first():
        # rows no DMA ever writes must not hold what VMEM held before
        # the call (a NaN times a zero probability is a NaN)
        for buf in bufs:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        half_ref[0] = 0
        first = next_live(0)

        @pl.when(first < n_slots)
        def _():
            copies(first, 0, 0, wait=False)

    n_blocks = (used_ref[s] + K - 1) // K

    @pl.when(n_blocks > 0)
    def _live():
        after = next_live(s + 1)

        def block(b, half):
            last = b + 1 == n_blocks
            slot_n = jnp.where(last, after, s)

            @pl.when(slot_n < n_slots)
            def _():
                copies(slot_n, jnp.where(last, 0, b + 1), 1 - half,
                       wait=False)

            copies(s, b, half, wait=True)
            body(b, [buf.at[half] for buf in bufs])
            return 1 - half

        half_ref[0] = jax.lax.fori_loop(0, n_blocks, block, half_ref[0])


def _paged_window_kernel(tables_ref, used_ref, lens_ref, layer_ref, q_ref,
                         *rest, scale, rep, page_size, window,
                         pages_per_block, quant):
    """Grid (S,): one step a slot, and inside it
    :func:`_walk_live_pages` over the slot's live pages, K pages a
    compute block. The K pages stand as one [K*page_size, g*dh] tile of
    cache rows; the body below is called once a block. Online softmax
    carries (m, l, acc) per (lane chunk, row) across a slot's blocks in
    VMEM scratch, updated once a block.

    The body walks the tile in lane chunks of C = hp*dh lanes
    (:func:`_heads_per_chunk` — whole 128-lane tiles at the serving
    widths, so no chunk is ever sliced or reshaped across a tile). q
    arrives BLOCK-DIAGONAL per chunk, [n_chunks, hp*W*rep, C] per slot
    (the wrapper lays it out in XLA): row (j, w, r) holds the query of
    head (chunk*hp + j)*rep + r at window token w in lanes
    [j*dh, (j+1)*dh) and zeros elsewhere, so ONE q·kT per chunk gives
    every head's scores exactly, [rows, K*page_size] with the cache rows
    on the lanes, and one p·v gives every head's output in its own lanes
    (the other lanes of a row hold another head's values weighted by
    this row's probabilities: finite, and dropped by the wrapper). The
    per-token lengths are the third scalar-prefetch operand: an [S, W]
    VMEM block of them does not tile. The layer is the fourth, a number
    the program reads and not a constant of it, so that every layer of
    a model runs ONE kernel, traced and lowered once.

    Precision: nothing is rounded below what it is stored as. Where q
    is bfloat16 and the pools bfloat16 or int8 (every int8 is a
    bfloat16), their product goes to the MXU as it is — a bf16 x bf16
    product summed in float32 is exact — and the float32 probabilities
    go as two bfloat16 terms (:func:`_two_terms`); a float32 q over
    bfloat16 pools goes as two bfloat16 terms too (``split_q``: a block
    with a float32 stream keeps 16 bits of its query, and no page is
    copied to float32); every other pairing of dtypes is cast to float32
    first. m, l and the accumulator are
    float32.

    ``quant`` is the dequant-FUSED variant over int8 pools. A row's
    scale is one number a kv head, so it multiplies that head's SCORE
    (k) and PROBABILITY (v) instead of the row's dh values: two more
    inputs carry the slot's scales with the cache rows on the lanes,
    [g, P*page_size] float32 in table order, and the int8 pages go to
    the products unscaled. The HBM read is 1 byte/element for the live
    pages + 4 bytes/row/head over the table's width for the scales
    (the wrapper says why they do not ride with their pages)."""
    if quant:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    pools, out_ref, bufs = rest[:2], rest[2], rest[3:5]
    sems, half_ref, m_ref, l_ref, acc_ref = rest[5:]
    s = pl.program_id(0)
    n_chunks, rows_n, C = acc_ref.shape
    wr = window * rep
    hp = rows_n // wr
    span = pages_per_block * page_size
    exact = q_ref.dtype == jnp.bfloat16 and pools[0].dtype in (
        jnp.bfloat16, jnp.int8)
    # a float32 query over bfloat16 pages (a block whose stream is
    # float32, models/block.py): the pages go to the MXU as they are and
    # the query as two bfloat16 terms, twice the query rows and no
    # float32 copy of a page
    split_q = q_ref.dtype == jnp.float32 and \
        pools[0].dtype == jnp.bfloat16 and not quant
    exact = exact or split_q
    cdt = jnp.bfloat16 if exact else jnp.float32

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(b, tiles):
        k_ref, v_ref = tiles                           # [span, g*dh]
        # per-token causal/ragged mask against ABSOLUTE positions:
        # block b covers [b*span, (b+1)*span); row (j, w, r) is window
        # token w and sees < lens[s, w]. Rows of the tile past the
        # slot's last live page lie past every length.
        cols = b * span + jax.lax.broadcasted_iota(
            jnp.int32, (rows_n, span), 1)
        lim = jnp.full((rows_n, span), lens_ref[s, 0], jnp.int32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (rows_n, span), 0)
        if window > 1:
            for j in range(hp):
                for w in range(window):
                    lim = jnp.where(rows >= j * wr + w * rep,
                                    lens_ref[s, w], lim)
        live = cols < lim
        here = pl.ds(pl.multiple_of(b * span, span), span)

        def row_scales(ref, c):
            # [rows_n, span]: row (j, w, r) takes head c*hp + j's scales
            sc = ref[0, c * hp:c * hp + 1, here]
            for j in range(1, hp):
                sc = jnp.where(rows >= j * wr,
                               ref[0, c * hp + j:c * hp + j + 1, here], sc)
            return sc

        for c in range(n_chunks):
            lanes = (slice(None), slice(c * C, (c + 1) * C))
            sc = jax.lax.dot_general(
                _two_terms(q_ref[0, c]) if split_q
                else q_ref[0, c].astype(cdt), k_ref[lanes].astype(cdt),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if split_q:
                sc = sc[:rows_n] + sc[rows_n:]
            sc = sc * (scale * LOG2E)
            if quant:
                sc = sc * row_scales(ks_ref, c)
            sc = jnp.where(live, sc, NEG_INF)          # [rows_n, span]
            m_prev = m_ref[c]                          # [rows_n, 1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_cur)
            pm = jnp.exp2(sc - m_cur)                  # [rows_n, span]
            l_ref[c] = l_ref[c] * alpha + \
                jnp.sum(pm, axis=1, keepdims=True)
            if quant:
                pm = pm * row_scales(vs_ref, c)
            pv = jax.lax.dot_general(
                _two_terms(pm) if exact else pm, v_ref[lanes].astype(cdt),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if exact:
                pv = pv[:rows_n] + pv[rows_n:]
            acc_ref[c] = acc_ref[c] * alpha + pv
            m_ref[c] = m_cur

    # a pool that keeps its layer axis is read at the layer the call
    # names: the index of every page copy, never a slice of the pool
    lead = (layer_ref[0],) if len(pools[0].shape) == 4 else ()
    _walk_live_pages(tables_ref, used_ref, pools, bufs, sems, half_ref,
                     body, lead=lead, page_size=page_size,
                     pages_per_block=pages_per_block)
    # a slot with no live page (idle) leaves l = 0 and acc = 0: its
    # output is zeros, finite
    l = jnp.maximum(l_ref[...], 1e-30)                 # [n_chunks, rows, 1]
    out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


# What the chip's compiler takes (v5e:2x2 described in the sandbox,
# tests/test_chip_compile.py). The kernel holds, against a 16 MiB scoped
# VMEM limit: K's and V's compute block twice (the walk's double
# buffer), [K*page_size, g*dh] each; the working set of one lane chunk
# (its float32 copies where the dtypes ask for them, the scores and
# probabilities of the block); per chunk the block-diagonal q and the
# output block (both double-buffered by Pallas) and the float32
# accumulator; and the int8 layout's per-slot scales [g, P*page_size],
# double-buffered. Past the limit the compiler answers "Ran out of
# memory in memory space vmem". The budget is kept under what was seen
# to compile, not at the limit — the compiler's own temporaries are not
# modelled. A page is copied from the pool by a DMA of its own into its
# rows of the tile, and this compiler slices an HBM ref only along whole
# tiles: g*dh must be whole 128-lane tiles ("Slice shape along dimension
# 3 must be aligned to tiling (128), but is 64" for one kv head of 64)
# and a page whole 8-row sublane tiles. Other shapes go to the gather
# path. The scalar-prefetched page tables and lengths live in 1 MiB of
# SMEM ("Ran out of memory in memory space smem" at [256, 1024]
# tables).
_PAGED_VMEM_BYTES = 12 * 1024 * 1024
_PAGED_SMEM_BYTES = 960 * 1024
# cache rows in a compute block of the window kernel: enough that a lane
# chunk's q·kT fills MXU tiles and the softmax state is touched once for
# many pages, few enough that a slot's last, part-filled block wastes
# little (a block is computed whole)
_WINDOW_ROWS_PER_BLOCK = 128
# ... times this many for rows narrower than 2,048 lanes (32 kv heads of
# 64), so that a block's tile keeps about the elements those 128 rows have
# there, up to 512 rows: a block's fixed work (its pages' DMAs started and
# waited for, the mask, a chunk's two small products) stands against its
# bytes, and at 512 lanes (8 kv heads of 64) 128 rows are a quarter of
# them. LFM2's 8.4k-token slots read 1.60 s of a 3-s trace at 128 rows and
# 1.18 s at 512 (v5e, PERF.md section 6, PR 41). Judged at 512 and 2,048
# lanes alone: a third width (1,024 lanes) is the first chip trial a later
# kernel PR owes the rule
_WINDOW_ROWS_MOST = 512


def _window_pages_per_block(page_size: int, pages_per_slot: int,
                            row_bytes: int, lanes: int) -> int:
    """Pages in one compute block of the window kernel: about
    ``_WINDOW_ROWS_PER_BLOCK`` cache rows of 2,048 ``lanes`` (g*dh) and
    in proportion more of narrower ones, no more than a slot's table
    holds, halved until K's and V's double-buffered tiles
    (``row_bytes`` a cache row of one) take at most half the VMEM
    budget. -> 0 where not even one page does."""
    rows = max(_WINDOW_ROWS_PER_BLOCK,
               min(_WINDOW_ROWS_MOST,
                   _WINDOW_ROWS_PER_BLOCK * max(1, 2048 // lanes)))
    k = max(1, min(rows // page_size, pages_per_slot))
    while k and 4 * k * page_size * row_bytes > _PAGED_VMEM_BYTES // 2:
        k //= 2
    return k


def _window_vmem(q, k_pages, quant, pages_per_slot):
    """(pages a block, modelled VMEM bytes) of the window kernel for
    these shapes (arrays or ShapeDtypeStructs)."""
    S, W, h, dh = q.shape
    ps, gd = k_pages.shape[-2:]
    g = gd // dh
    row_bytes = _round_up(gd, 128) * jnp.dtype(k_pages.dtype).itemsize
    K = _window_pages_per_block(ps, pages_per_slot, row_bytes, gd)
    span = _round_up(max(K, 1) * ps, 128)
    hp = _heads_per_chunk(g, dh)
    chunk = _round_up(hp * dh, 128)
    rows = _round_up(hp * W * (h // g), 8)
    # a chunk's K and V cast for the product, the block's mask, scores,
    # probabilities, their two terms and a row of scales
    working = 2 * span * chunk * 4 + 8 * rows * span * 4
    per_chunk = rows * chunk * (4 * jnp.dtype(q.dtype).itemsize + 4)
    vmem = 4 * K * ps * row_bytes + working + (g // hp) * per_chunk
    if quant:
        vmem += 2 * 2 * _round_up(g, 8) * 4 * _round_up(
            -(-pages_per_slot // max(K, 1)) * K * ps, 128)
    return K, vmem


def paged_kernel_supported(q, k_pages, k_scales=None,
                           pages_per_slot: int = 1) -> bool:
    """Gate for the live-pages kernel — "supported" means the kernel
    LOWERS on the chip for these shapes: a sublane-multiple head dim,
    cache rows of whole 128-lane tiles and pages of whole 8-row tiles
    (what a page's own DMA needs), the compute block's tiles
    ([K*page_size, g*dh] of K and of V as stored, twice), the chunk's
    working set and the int8 layout's per-slot scales inside the VMEM
    budget, and the [S, P] page tables plus [S, W] lengths inside SMEM.
    ``k_pages`` is a pool (or its ShapeDtypeStruct) in the stored
    layout, with or without the layer axis."""
    S, W, h, dh = q.shape
    ps, gd = k_pages.shape[-2:]
    g = gd // dh
    if dh % 8 or g * dh != gd or h % g or gd % 128 or ps % 8:
        return False
    K, vmem = _window_vmem(q, k_pages, k_scales is not None,
                           pages_per_slot)
    smem = 4 * S * (_round_up(pages_per_slot, 128) + 128 + 1)
    return K > 0 and vmem <= _PAGED_VMEM_BYTES and \
        smem <= _PAGED_SMEM_BYTES


def paged_window_attention(q, k_pages, v_pages, page_tables, kv_lens,
                           *, scale=None, use_kernel=False,
                           interpret=False, k_scales=None,
                           v_scales=None, layer=None):
    """Decode attention over the paged pool for a W-token window per
    slot (W = 1 is the classic one-token step; the speculative engine
    feeds W = spec_k + 1 — serving/engine.py).

    q [S, W, h, dh]; k_pages/v_pages [n_pages, page_size, g*dh] — the
    pools AS STORED, every kv head of a token side by side on the lane
    axis — or the whole [L, n_pages, page_size, g*dh] pools with
    ``layer`` (a Python int) naming the layer: it goes into the index
    of the kernel's page copies (the gather's index), never into a
    slice of the pool, and reaches the kernel as an operand, so every
    layer runs one program; page_tables [S, P] int32; kv_lens [S, W] int32
    per-TOKEN valid lengths (token w of slot s is the query at position
    kv_lens[s, w] - 1 — the mask is causal within the window too,
    because earlier window tokens' K/V were scattered before this
    call). Returns [S, W, h, dh].

    ``use_kernel=False`` flattens the window into the gather/einsum
    reference (:func:`paged_attention` — exact, reads the full table
    width). ``use_kernel=True`` runs the live-pages Pallas kernel
    (:func:`_paged_window_kernel`): the pools stay in HBM, page tables
    and per-slot live-page counts are scalar-prefetched, and the kernel
    copies ceil(len/page_size) pages a slot itself, so a call's time
    follows the cached tokens and not slots x table width. A slot whose
    lengths are all 0 (idle) costs no copy and returns zeros.

    ``k_scales``/``v_scales`` [n_pages, page_size, g] (or [L, ...])
    switch the pools to the INT8 two-tier layout (:func:`quantize_kv`
    rows): the gather path dequantizes the gathered view then runs the
    same exact einsum, and the kernel path runs :func:`_paged_window_kernel`
    with ``quant``, which fuses the per-row rescale into the
    online-softmax page walk — int8 K/V never round-trips through HBM
    at float width."""
    S, W, h, dh = q.shape
    ps, gd = k_pages.shape[-2:]
    g = gd // dh
    P = page_tables.shape[1]
    assert g * dh == gd and h % g == 0, (h, dh, k_pages.shape)
    assert (layer is None) == (k_pages.ndim == 3), (layer, k_pages.shape)
    rep = h // g
    if scale is None:
        scale = dh ** -0.5
    lens = jnp.asarray(kv_lens, jnp.int32).reshape(S, W)
    quant = k_scales is not None
    if not use_kernel:
        out = paged_attention(
            q.reshape(S * W, h, dh), k_pages, v_pages,
            jnp.repeat(page_tables, W, axis=0), lens.reshape(-1),
            scale=scale, k_scales=k_scales, v_scales=v_scales,
            layer=layer)
        return out.reshape(S, W, h, dh)
    # at least one page a block: shapes the gate turns away still run
    # here in interpret mode (the tests' toy pages)
    K = max(1, _window_vmem(q, k_pages, quant, P)[0])
    return _live_pages_call(
        q, k_pages, v_pages, jnp.asarray(page_tables, jnp.int32), lens,
        None if layer is None else jnp.full((1,), layer, jnp.int32),
        k_scales, v_scales, scale=float(scale), pages_per_block=K,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_block",
                                             "interpret"))
def _live_pages_call(q, k_pages, v_pages, page_tables, lens, layer,
                     k_scales, v_scales, *, scale, pages_per_block,
                     interpret):
    """:func:`paged_window_attention`'s kernel path. A program of its
    own, with the layer an operand: the 24 layers of a step call the
    same traced and lowered function, where a kernel with the layer
    built in was traced, lowered and compiled once a layer."""
    S, W, h, dh = q.shape
    ps, gd = k_pages.shape[-2:]
    g, P = gd // dh, page_tables.shape[1]
    rep = h // g
    quant = k_scales is not None
    # pages holding live KV for each slot; 0 for an idle one
    used = jnp.clip(-(-jnp.max(lens, axis=1) // ps), 0, P)
    hp = _heads_per_chunk(g, dh)
    n_chunks, C, rows_n = g // hp, hp * dh, hp * W * rep
    K = pages_per_block

    def _slot_map(si, *_):
        return (si, 0, 0, 0)

    kernel = functools.partial(
        _paged_window_kernel, scale=scale, rep=rep, page_size=ps,
        window=W, pages_per_block=K, quant=quant)
    # rows (j, w, r) of chunk c, block-diagonal over the chunk's heads:
    # [S, W, (c, j, r), dh] -> [S, c, (j, w, r), (j', dh)]
    qc = q.reshape(S, W, n_chunks, hp, rep, dh).transpose(0, 2, 3, 1, 4, 5)
    eye = jnp.eye(hp, dtype=q.dtype)[:, None, None, :, None]
    qd = (qc[:, :, :, :, :, None, :] * eye).reshape(S, n_chunks, rows_n, C)
    in_specs = [pl.BlockSpec((1, n_chunks, rows_n, C), _slot_map)]
    operands = [qd]
    if quant:
        # [page_size, g] of scales is no whole lane tile, so the kernel
        # cannot copy a page of them itself (the gate's comment): XLA
        # gathers each slot's scales over its table's width, cache rows
        # on the lanes, whole compute blocks
        t = -(-P // K) * K * ps

        def by_slot(scales):
            rows = gather_pages(scales, page_tables,
                                None if layer is None else layer[0])
            return jnp.pad(rows.astype(jnp.float32).transpose(0, 2, 1),
                           ((0, 0), (0, 0), (0, t - P * ps)))

        in_specs += [pl.BlockSpec((1, g, t), lambda si, *_: (si, 0, 0))] * 2
        operands += [by_slot(k_scales), by_slot(v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec((1, n_chunks, rows_n, C), _slot_map),
        scratch_shapes=[
            pltpu.VMEM((2, K * ps, gd), k_pages.dtype),
            pltpu.VMEM((2, K * ps, gd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_chunks, rows_n, 1), jnp.float32),
            pltpu.VMEM((n_chunks, rows_n, 1), jnp.float32),
            pltpu.VMEM((n_chunks, rows_n, C), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_chunks, rows_n, C), q.dtype),
        # the walk's state goes from one slot's grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_window_attention",
    )(page_tables, used.astype(jnp.int32), lens,
      jnp.zeros((1,), jnp.int32) if layer is None else layer,
      *operands, k_pages, v_pages)
    # head j's output sits in lanes [j*dh, (j+1)*dh) of its own rows
    out = jnp.diagonal(out.reshape(S, n_chunks, hp, W, rep, hp, dh),
                       axis1=2, axis2=5)            # [S, c, W, rep, dh, j]
    return out.transpose(0, 2, 1, 5, 3, 4).reshape(S, W, h, dh)


# ------------------------------------------------------- latent (MLA) pool
# A latent cache holds ONE row a token and layer, [c_kv | k_rope], shared
# by every query head (models/block.py LatentBlock): ONE pool
# [L, n_pages, page_size, lanes], lanes = rkv + dr rounded up to whole
# 128-lane tiles (zero lanes at the end), kept in that layout for the
# engine's life like the per-head pools above. In the absorbed form every
# head scores [q_lat | q_rope] (q_lat = q_nope W_uk^T, rkv wide) against the
# row and the probabilities weight its first rkv lanes again: a latent page
# brought from HBM once serves as key and as value, for all heads.
#
# What the walk is worth here (v5e, 64 slots x 64 heads, a table of 128
# pages of 32, bf16, six calls chained as a step chains them, each with
# its query's layout in XLA, ~0.10 ms; my chip runs, PR 42,
# tests/test_tpu_smoke.py holds the ratio): a call reads 0.22 ms with 1
# token a slot and 0.70 with 2,304, where the kernel with a grid of its
# own over (slots, table entries) read 0.65 and 0.93, ~65 ns a page
# operand visited whether its DMA was skipped or not. Of the 0.60 ms that
# are the kernel's at 2,304 tokens, 0.28 are the walk with an empty body
# (4,608 page copies; 189 MB are 0.23 ms at the HBM peak), 0.22 the scores
# and the softmax, 0.10 p . c_kv: the copies' issue and the body's
# products do not overlap, and that sum, not the bytes, is what is left
# (ROADMAP S17).
#
# Cache rows in a compute block of the latent kernel: one product
# q . tile^T over all of them, the softmax state touched once a block.
_LATENT_ROWS_PER_BLOCK = 512


def _latent_pages_per_block(page_size: int, pages_per_slot: int,
                            row_bytes: int) -> int:
    """Pages in one compute block of the latent kernel:
    ``_LATENT_ROWS_PER_BLOCK`` cache rows, no more than a slot's table
    holds, halved until the double-buffered tile (``row_bytes`` a cache
    row) takes at most half the VMEM budget. -> 0 where not even one
    page does."""
    k = max(1, min(_LATENT_ROWS_PER_BLOCK // page_size, pages_per_slot))
    while k and 2 * k * page_size * row_bytes > _PAGED_VMEM_BYTES // 2:
        k //= 2
    return k


def latent_kernel_supported(num_slots: int, rows: int, lanes: int,
                            rkv: int, page_size: int, pages_per_slot: int,
                            dtype) -> bool:
    """Does :func:`paged_latent_attention`'s kernel lower on the chip for
    these shapes: rows and their c_kv part of whole 128-lane tiles, pages
    of whole sublane tiles of the pool's dtype (what a page's own DMA
    needs), the walk's tile twice, the body's working set and the
    [rows, rkv] accumulator inside VMEM, the page tables and lengths
    inside SMEM. ``rows`` = window x heads (the kernel stacks two terms
    of each)."""
    esize = jnp.dtype(dtype).itemsize
    if lanes % 128 or rkv % 128 or page_size % (32 // esize):
        return False
    k = _latent_pages_per_block(page_size, pages_per_slot, lanes * esize)
    span = k * page_size
    rows8 = _round_up(2 * rows, 8)
    tiles = 2 * span * lanes * esize                     # the walk's halves
    # a block's rows as the products take them; q's two terms (double-
    # buffered by Pallas); the output block, the accumulator and a
    # block's p . c_kv; the scores, the mask and the probabilities
    work = span * lanes * esize + rows8 * (
        2 * lanes * esize + 3 * rkv * 4 + 3 * span * 4)
    smem = 4 * num_slots * (_round_up(pages_per_slot, 128) + 128 + 1)
    return k > 0 and tiles + work <= _PAGED_VMEM_BYTES and \
        smem <= _PAGED_SMEM_BYTES


def _two_terms(x):
    """float32 x as two bfloat16 terms stacked on the leading axis,
    [hi; lo]: hi is x with its low 16 bits cleared (exactly a bfloat16),
    lo = round(x - hi). A product with the stack, its two halves added,
    is the product with x itself to 16 mantissa bits at one pass over the
    other operand. The bits are masked, not cast there and back: a cast
    pair may be kept at float32 by the compiler, and lo is then nought."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return jnp.concatenate([hi, x - hi], axis=0).astype(jnp.bfloat16)


def _paged_latent_kernel(tables_ref, used_ref, lens_ref, layer_ref, q_ref,
                         pool, out_ref, buf, sems, half_ref, m_ref, l_ref,
                         acc_ref, *, scale, page_size, window, heads, rkv,
                         pages_per_block, split):
    """Grid (S,): one step a slot, and inside it
    :func:`_walk_live_pages` over the slot's live pages of the ONE pool,
    K pages a compute block. The K pages stand as one [K*page, lanes]
    tile of cache rows; the body below is called once a block: the
    scores of all ``window x heads`` query rows in ONE product
    ``q . tile^T`` (q is [q_lat | q_rope | 0]), online softmax in
    float32, then ``p . tile[:, :rkv]`` into the [rows, rkv] float32
    accumulator: the latent row is read from HBM once and used twice.
    The per-token lengths are the third scalar-prefetch operand and the
    layer the fourth, a number the program reads and not a constant of
    it, so that every latent layer of a model runs ONE kernel, traced and
    lowered once.

    Precision: the cache is what it is stored as; nothing else is
    rounded to it. With ``split`` (a bfloat16 pool) q
    arrives as two terms of the pool's dtype stacked on its rows and the
    probabilities are split the same way before their product (the two
    halves of each result are added: 128 rows where 64 left the MXU half
    empty), and the output stays float32. A query, a probability or an
    output rounded to bfloat16 is an error of 0.2-0.4 % of the attention
    output that no averaging over the cached tokens removes, and a router
    downstream turns it into moved top-k choices."""
    s = pl.program_id(0)
    rows_n = acc_ref.shape[0]
    span = pages_per_block * page_size

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(b, tiles):
        c = tiles[0][...]                                  # [span, lanes]
        sc = jax.lax.dot_general(
            q_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if split:
            sc = sc[:rows_n] + sc[rows_n:]
        # causal/ragged mask against ABSOLUTE positions: block b covers
        # [b*span, (b+1)*span); row (w, h) is window token w and sees
        # < lens[s, w]. Rows of the tile past the slot's last live page
        # lie past every length.
        cols = b * span + jax.lax.broadcasted_iota(
            jnp.int32, (rows_n, span), 1)
        lim = jnp.full((rows_n, span), lens_ref[s, 0], jnp.int32)
        if window > 1:
            rows = jax.lax.broadcasted_iota(jnp.int32, (rows_n, span), 0)
            for w in range(1, window):
                lim = jnp.where(rows >= w * heads, lens_ref[s, w], lim)
        sc = jnp.where(cols < lim, sc * (scale * LOG2E), NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_cur)
        pm = jnp.exp2(sc - m_cur)                          # [rows, span]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pm, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            _two_terms(pm) if split else pm, c[:, :rkv],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if split:
            pv = pv[:rows_n] + pv[rows_n:]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_cur

    # the layer is the index of every page copy, never a slice of the pool
    _walk_live_pages(tables_ref, used_ref, [pool], [buf], sems, half_ref,
                     body, lead=(layer_ref[0],), page_size=page_size,
                     pages_per_block=pages_per_block)
    # a slot with no live page (idle) leaves l = 0 and acc = 0: zeros
    out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _latent_q(q_lat, q_rope, lanes):
    """[S, W, H, lanes] float32: q as the cache row is laid out,
    [q_lat | q_rope | zero lanes where the row has them]."""
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    return jnp.pad(q, [(0, 0)] * 3 + [(0, lanes - q.shape[-1])])


def paged_latent_attention(q_lat, q_rope, pages, page_tables, kv_lens, *,
                           layer, scale, use_kernel=False, interpret=False):
    """Absorbed multi-head latent attention over the paged latent pool.

    q_lat [S, W, H, rkv] (``q_nope W_uk^T``) and q_rope [S, W, H, dr]
    (rotated); pages [L, n_pages, page_size, lanes], the pool AS STORED
    (a row is [c_kv | k_rope | zero lanes]), with ``layer`` (a Python
    int) naming the layer read: it goes into the index of the kernel's
    page copies (it reaches the kernel as an operand, so every layer runs
    one program) or the gather's index, never into a slice of the pool;
    page_tables [S, P] int32; kv_lens [S, W] per-token valid lengths
    (token w of slot s is the query at position kv_lens[s, w] - 1).
    Returns float32 o_lat [S, W, H, rkv] = softmax(scores * scale) . c_kv,
    which the caller expands by W_uv.

    ``use_kernel=False`` gathers the slot's full table width and runs the
    same mathematics as einsums (the CPU path and the kernel's test
    reference); ``use_kernel=True`` runs :func:`_paged_latent_kernel`,
    which copies ceil(len/page_size) pages a slot itself, so a call's
    time follows the cached tokens and not slots x table width. A slot
    whose lengths are all 0 (idle) costs no copy and returns zeros."""
    S, W, H, rkv = q_lat.shape
    ps, lanes = pages.shape[-2:]
    P = page_tables.shape[1]
    lens = jnp.asarray(kv_lens, jnp.int32).reshape(S, W)
    if not use_kernel:
        hp = jax.lax.Precision.HIGHEST
        q = _latent_q(q_lat, q_rope, lanes)
        c = gather_pages(pages, page_tables, layer).astype(jnp.float32)
        sc = jnp.einsum("swhr,skr->shwk", q, c, precision=hp)
        mask = jnp.arange(P * ps)[None, None, :] < lens[:, :, None]
        sc = jnp.where(mask[:, None], sc * scale, NEG_INF)
        return jnp.einsum("shwk,skr->swhr", jax.nn.softmax(sc, axis=-1),
                          c[..., :rkv], precision=hp)
    # at least one page a block: shapes the gate turns away still run
    # here in interpret mode (the tests' toy pages)
    K = max(1, _latent_pages_per_block(
        ps, P, lanes * jnp.dtype(pages.dtype).itemsize))
    return _latent_pages_call(
        q_lat, q_rope, pages, jnp.asarray(page_tables, jnp.int32), lens,
        jnp.full((1,), layer, jnp.int32), scale=float(scale),
        pages_per_block=K, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_block",
                                             "interpret"))
def _latent_pages_call(q_lat, q_rope, pages, page_tables, lens, layer, *,
                       scale, pages_per_block, interpret):
    """:func:`paged_latent_attention`'s kernel path. A program of its
    own, with the layer an operand: the latent layers of a step, and
    both groups' calls of a lane step where their shapes agree, call the
    same traced and lowered function."""
    S, W, H, rkv = q_lat.shape
    ps, lanes = pages.shape[-2:]
    P, K = page_tables.shape[1], pages_per_block
    # pages holding live rows for each slot; 0 for an idle one
    used = jnp.clip(-(-jnp.max(lens, axis=1) // ps), 0, P)
    rows_n = W * H
    split = pages.dtype == jnp.bfloat16
    q = _latent_q(q_lat, q_rope, lanes).reshape(S, rows_n, lanes)
    if split:       # [hi; lo] on each slot's rows
        q = jax.vmap(_two_terms)(q)
    else:
        q = q.astype(pages.dtype)

    def _slot_map(si, *_):
        return (si, 0, 0)

    kernel = functools.partial(
        _paged_latent_kernel, scale=scale, page_size=ps, window=W,
        heads=H, rkv=rkv, pages_per_block=K, split=split)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, q.shape[1], lanes), _slot_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows_n, rkv), _slot_map),
        scratch_shapes=[pltpu.VMEM((2, K * ps, lanes), pages.dtype),
                        pltpu.SemaphoreType.DMA((1, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((rows_n, 1), jnp.float32),
                        pltpu.VMEM((rows_n, 1), jnp.float32),
                        pltpu.VMEM((rows_n, rkv), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, rows_n, rkv), jnp.float32),
        # the walk's state goes from one slot's grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_latent_attention",
    )(page_tables, used.astype(jnp.int32), lens, layer, q, pages)
    return out.reshape(S, W, H, rkv)
