"""Decode attention: the paged serving path + a recorded Pallas
experiment.

:func:`paged_attention` (bottom) is LIVE — the continuous-batching
engine's per-step attention over the paged KV pool. The Pallas kernel
that opens this file is the round-5 recorded experiment it can compose
with.

Round-5 verdict on the kernel: measured and REJECTED. The decode trace (docs/perf.md,
"the decode gap, traced") showed XLA lowering the per-step attention
(q [b,h,dh] against cached K/V over T positions) to VPU multiply-reduce
fusions at ~160 GB/s effective — the hypothesis was that a Pallas
kernel, which dictates its own block tiling, could stream the cache
with T on the lane axis at full width. Two grid shapes were measured on
device against the einsum path inside the real decode scan (bs32,
T=544, 6 layers):

  - grid (b, h) — one step per row/head: 1.86 ms/step vs 0.92 einsum.
    TPU Pallas grids run SEQUENTIALLY on the core; b*h tiny DMAs
    serialize.
  - grid (g,) — this kernel: whole-batch [b, dh, T] K/V blocks per kv
    group, all GQA query heads inside the step: 1.50 ms/step. Fewer,
    larger DMAs, still loses: Mosaic loops the leading batch dim and
    the per-b [dh, T] reductions pipeline worse than XLA's fused
    lowering of the same math.

The einsum formulation in models/decode.py remains the measured
optimum (two cache-layout variants of it also lost — see perf.md). The
kernel stays here, correct and parity-tested
(tests/test_decode.py::TestPallasDecodeAttention), as the starting
point if a future round wants to hand-tune the Mosaic lowering.

Cache layout contract: [b, g, dh, T].

Round 6 adds the LIVE serving path: :func:`paged_attention`, decode
attention over a PAGED KV cache (fixed-size pages in a preallocated
pool, per-sequence page tables — the PagedAttention design). The page
gather produces the contiguous [b, T, g, dh] view and then runs the
exact einsum formulation above (token-identical to the dense cache by
construction, pinned in tests/test_paged_decode.py), or composes with
the recorded-experiment kernel via ``use_kernel=True`` — both paths
take PER-ROW kv lengths, which is what lets one fixed-shape jitted
step serve ragged sequences (serving/engine.py).

Round 9 replaces the gather's traffic profile with
:func:`paged_window_attention` + the ALLOCATED-PAGES kernel
(:func:`_paged_window_kernel`): the gather path reads every slot's full
page-table width (P * page_size positions — ``max_seq_len`` traffic per
slot per step regardless of actual length), which docs/perf.md "Known
headroom" names as the decode-roofline lever. The kernel walks the
page axis with the page table SCALAR-PREFETCHED: the block index map
clamps the page-axis grid index to the slot's last allocated page, so
every out-of-range grid step repeats the previous block index and
Pallas SKIPS the DMA — HBM cache reads scale with the slot's TRUE
ragged length (rounded up to a page). The query carries a W-token
verify window per slot (speculative decoding + multi-token prefill,
serving/engine.py), accumulated with the online-softmax recurrence
across pages. Parity vs. the gather/einsum reference is pinned in
tests/test_paged_decode.py (GQA/MQA, ragged lengths, W > 1).

Both paged paths read the pools AS STORED: a page is
[page_size, g*dh], the kv heads of a token side by side on the lane
axis, and a pool that keeps its layer axis is handed over whole with
``layer=`` (the comment above :func:`gather_pages`). The kernel walks
a page in 128-lane chunks against a block-diagonal q, so nothing is
padded in HBM or relaid out in VMEM at the serving widths."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LOG2E = 1.4426950408889634

# per-block VMEM budget for K+V (+ double buffering headroom): beyond
# this the caller falls back to XLA rather than risk a VMEM OOM
_VMEM_BYTES = 8 * 1024 * 1024

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


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, out_ref, *, scale,
                   rep):
    k = k_ref[...]                                    # [b, 1, dh, T]
    v = v_ref[...]
    b, _, dh, t = k.shape
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, 1, 1, t), 3)
    if lens_ref.shape[0] == 1:        # one shared length (dense decode)
        live = cols < lens_ref[0]
    else:                             # per-row lengths (ragged serving)
        live = cols < lens_ref[...].reshape(b, 1, 1, 1)
    for r in range(rep):
        q = q_ref[:, r:r + 1].astype(jnp.float32)     # [b, 1, dh, 1]
        s2 = jnp.sum(q * kf, axis=2, keepdims=True) * (scale * LOG2E)
        s2 = jnp.where(live, s2, NEG_INF)             # [b, 1, 1, T]
        m = jnp.max(s2, axis=3, keepdims=True)
        p = jnp.exp2(s2 - m)                          # [b, 1, 1, T]
        l = jnp.sum(p, axis=3, keepdims=True)
        acc = jnp.sum(vf * p, axis=3, keepdims=True)  # [b, 1, dh, 1]
        out_ref[:, r:r + 1] = (acc / l).astype(out_ref.dtype)


def decode_supported(q, k_cache) -> bool:
    """Tile-friendly and VMEM-sized? dh a sublane multiple; whole-batch
    K+V group blocks within the VMEM budget."""
    b, g, dh, t = k_cache.shape
    esize = jnp.dtype(k_cache.dtype).itemsize
    return dh % 8 == 0 and 2 * b * dh * t * esize <= _VMEM_BYTES


def decode_attention(q, k_cache, v_cache, kv_len, *, scale=None,
                     interpret=False):
    """q [b, h, dh]; k_cache/v_cache [b, g, dh, T] with h % g == 0
    (GQA: h == g*rep); kv_len: traced scalar (shared by every row) or a
    per-row [b] vector — positions >= kv_len are masked (decode calls
    always have each row's query at position kv_len-1, so this IS the
    causal mask). Returns [b, h, dh]."""
    b, h, dh = q.shape
    g = k_cache.shape[1]
    t = k_cache.shape[-1]
    assert h % g == 0, (h, g)
    rep = h // g
    if scale is None:
        scale = dh ** -0.5
    q4 = q.reshape(b, h, dh, 1)
    lens = jnp.asarray(kv_len, jnp.int32).reshape(-1)
    assert lens.shape[0] in (1, b), (lens.shape, b)

    kernel = functools.partial(_decode_kernel, scale=scale, rep=rep)
    out = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # lens [1|b]
            pl.BlockSpec((b, rep, dh, 1), lambda j: (0, j, 0, 0)),
            pl.BlockSpec((b, 1, dh, t), lambda j: (0, j, 0, 0)),
            pl.BlockSpec((b, 1, dh, t), lambda j: (0, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((b, rep, dh, 1), lambda j: (0, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh, 1), q.dtype),
        interpret=interpret, name="decode_attention",
    )(lens, q4, k_cache, v_cache)
    return out.reshape(b, h, dh)


# --------------------------------------------------------------- paged
# The pool layout (one contract for the kernel, the gather path and
# models/decode.PagedDecoder, which stores it): a page is
# [page_size, g*dh] — every kv head of a token side by side on the lane
# axis, so a page of the serving widths (16 x 2048 bf16) is whole
# (16, 128) tiles with nothing padded. Pools are [n_pages, page_size,
# g*dh], or [L, n_pages, page_size, g*dh] with ``layer=`` naming the
# layer to read: the layer goes into the gather's index / the kernel's
# block index map, and no array op ever takes ``pool[i]`` out of the
# pool (a slice of a pool is a pool-sized copy on the chip). The int8
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
                    scale=None, use_kernel=False, interpret=False,
                    k_scales=None, v_scales=None, layer=None):
    """Decode attention over a PAGED KV cache (the serving engine's hot
    path — serving/engine.py).

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
    stores, then runs models/decode.py's exact einsum formulation (the
    measured optimum of five — docs/perf.md), so paged decode is
    token-identical to the dense path. ``use_kernel=True`` instead
    transposes the view into the [b, g, dh, T] contract and composes
    with the :func:`decode_attention` GQA kernel."""
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
        # formulation — the dequant analogue of the kernel-gate
        # fallback below
        k = dequantize_kv(k, gather_pages(k_scales, page_table, layer),
                          q.dtype)
        v = dequantize_kv(v, gather_pages(v_scales, page_table, layer),
                          q.dtype)
    lens = jnp.asarray(kv_lens, jnp.int32).reshape(-1)
    if use_kernel:
        kt = k.transpose(0, 2, 3, 1)                   # [b, g, dh, T]
        vt = v.transpose(0, 2, 3, 1)
        return decode_attention(q, kt, vt, lens, scale=scale,
                                interpret=interpret)
    t = k.shape[1]
    # identical formulation (einsum strings, mask value, softmax dtype
    # path) to models/decode.py _block at t=1 — parity is structural
    q5 = q.reshape(b, 1, g, rep, dh)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", q5,
                        k.astype(q.dtype)) * scale
    mask = jnp.arange(t)[None, :] < lens[:, None]      # [b, T]
    logits = jnp.where(mask[:, None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bgrqk,bkgd->bqgrd", w, v.astype(q.dtype))
    return attn.reshape(b, h, dh)


# ------------------------------------------- allocated-pages kernel
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


def _paged_window_kernel(tables_ref, used_ref, lens_ref, q_ref, k_ref,
                         v_ref, *rest, scale, rep, page_size, window,
                         head_dim, quant):
    """Grid (S, P), page axis fastest. Block p of slot s is the page
    the CLAMPED index map selected — for p >= used[s] that is the same
    physical page as step p-1, so Pallas skips the DMA (the
    allocated-pages traffic contract) and ``pl.when`` skips the math.
    Online softmax carries (m, l, acc) per (lane chunk, row) across the
    page axis in VMEM scratch.

    A K/V block is one page as stored, [page_size, g*dh]. The body
    walks it in lane chunks of C = hp*dh lanes (:func:`_heads_per_chunk`
    — whole 128-lane tiles at the serving widths, so no chunk is ever
    sliced or reshaped across a tile). q arrives BLOCK-DIAGONAL per
    chunk, [n_chunks, hp*W*rep, C] per slot (the wrapper lays it out in
    XLA): row (j, w, r) holds the query of head (chunk*hp + j)*rep + r
    at window token w in lanes [j*dh, (j+1)*dh) and zeros elsewhere, so
    ONE q·kT per chunk gives every head's scores exactly, and one p·v
    gives every head's output in its own lanes (the other lanes of a
    row hold another head's values weighted by this row's
    probabilities: finite, and dropped by the wrapper). The per-token
    lengths are the third scalar-prefetch operand: an [S, W] VMEM block
    of them does not tile.

    ``quant`` is the dequant-FUSED variant: two more inputs carry the
    per-row scales (their blocks ride the same clamped map, so a
    skipped page DMA skips its scale DMA too) and the rescale
    ``int8 * scale`` runs in VMEM right after the K/V chunk lands, so
    the HBM read is 1 byte/element + 4 bytes/row instead of the float
    pool's 2-4 bytes/element."""
    if quant:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
    p = pl.program_id(1)
    s = pl.program_id(0)
    used = used_ref[s]
    n_chunks, rows_n, C = acc_ref.shape
    wr = window * rep
    hp = rows_n // wr
    # the pools may keep their layer axis: the block is then
    # [1, 1, page_size, g*dh] and the index map chose the layer
    page = (0,) * (len(k_ref.shape) - 2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(p < used)
    def _accumulate():
        # per-token causal/ragged mask against ABSOLUTE positions:
        # page p covers [p*ps, (p+1)*ps); row (j, w, r) is window token
        # w and sees < lens[s, w]
        cols = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows_n, page_size), 1)
        lim = jnp.full((rows_n, page_size), lens_ref[s, 0], jnp.int32)
        if window > 1:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (rows_n, page_size), 0)
            for j in range(hp):
                for w in range(window):
                    lim = jnp.where(rows >= j * wr + w * rep,
                                    lens_ref[s, w], lim)
        live = cols < lim
        if quant:
            lane = jax.lax.broadcasted_iota(jnp.int32, (page_size, C), 1)
            ks = ks_ref[page].astype(jnp.float32)      # [ps, g]
            vs = vs_ref[page].astype(jnp.float32)

        def rescale(x, sc, c):
            # fused dequant: head j of the chunk owns lanes
            # [j*dh, (j+1)*dh) and its own [ps, 1] column of scales
            col = sc[:, c * hp:c * hp + 1]
            for j in range(1, hp):
                col = jnp.where(lane >= j * head_dim,
                                sc[:, c * hp + j:c * hp + j + 1], col)
            return x * col

        for c in range(n_chunks):
            lanes = (slice(None), slice(c * C, (c + 1) * C))
            kc = k_ref[page + lanes].astype(jnp.float32)   # [ps, C]
            vc = v_ref[page + lanes].astype(jnp.float32)
            if quant:
                kc = rescale(kc, ks, c)
                vc = rescale(vc, vs, c)
            qc = q_ref[0, c].astype(jnp.float32)       # [rows_n, C]
            sc = jax.lax.dot_general(
                qc, kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * (scale * LOG2E)
            sc = jnp.where(live, sc, NEG_INF)          # [rows_n, ps]
            m_prev = m_ref[c]                          # [rows_n, 1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_cur)
            pm = jnp.exp2(sc - m_cur)                  # [rows_n, ps]
            l_ref[c] = l_ref[c] * alpha + \
                jnp.sum(pm, axis=1, keepdims=True)
            acc_ref[c] = acc_ref[c] * alpha + jax.lax.dot_general(
                pm, vc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[c] = m_cur

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        # fully-masked rows (lens 0 never happens live; engine clamps
        # masked tokens to kv_len >= 1) still divide by a finite l
        l = jnp.maximum(l_ref[...], 1e-30)             # [n_chunks, rows, 1]
        out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# What the chip's compiler takes (v5e:2x2 described in the sandbox,
# tests/test_chip_compile.py). One grid step holds every K/V block
# twice (Pallas double-buffers), each padded to whole (sublane,
# 128-lane) tiles over its (page_size, g*dh) dims, plus the float32
# working copies of one lane chunk, against a 16 MiB scoped VMEM
# limit: past it the compiler answers "Ran out of memory in memory
# space vmem" (f32 g32 dh128 pages of 256 rows: 16 MiB of K/V blocks
# alone). The budget is kept under what was seen to compile, not at
# the limit — the compiler's own temporaries are not modelled, and the
# gate turns away some shapes that would compile (int8 g8 dh64 pages of
# 2048 rows, bf16 g2 dh64 pages of 4096 rows). The scalar-prefetched
# page tables and lengths live in 1 MiB of SMEM ("Ran out of memory in
# memory space smem" at [256, 1024] tables).
_PAGED_VMEM_BYTES = 12 * 1024 * 1024
_PAGED_SMEM_BYTES = 960 * 1024


def paged_kernel_supported(q, k_pages, k_scales=None,
                           pages_per_slot: int = 1) -> bool:
    """Gate for the allocated-pages kernel — "supported" means the
    kernel LOWERS on the chip for these shapes: a sublane-multiple
    head dim, one page step's K+V blocks ([page_size, g*dh] as stored,
    + per-row scales of the int8 two-tier layout) inside the VMEM
    budget, and the [S, P] page tables plus [S, W] lengths inside
    SMEM. ``k_pages`` is a pool (or its ShapeDtypeStruct) in the
    stored layout, with or without the layer axis."""
    S, W, h, dh = q.shape
    ps, gd = k_pages.shape[-2:]
    g = gd // dh
    if dh % 8 or g * dh != gd or h % g:
        return False
    esize = jnp.dtype(k_pages.dtype).itemsize
    hp = _heads_per_chunk(g, dh)
    chunk = _round_up(hp * dh, 128)
    rows = _round_up(hp * W * (h // g), 8)
    stored = _round_up(ps, 32 // esize) * _round_up(gd, 128) * esize
    working = _round_up(ps, 8) * chunk * 4
    # K and V double-buffered, their chunk's f32 copies, and per chunk
    # the block-diagonal q (double-buffered, at q's width), the output
    # block and the f32 accumulator
    vmem = 2 * 2 * stored + 4 * working + (g // hp) * rows * chunk * (
        4 * jnp.dtype(q.dtype).itemsize + 4)
    if k_scales is not None:
        vmem += 2 * 2 * _round_up(ps, 8) * _round_up(g, 128) * \
            jnp.dtype(k_scales.dtype).itemsize
    smem = 4 * S * (_round_up(pages_per_slot, 128) + 128 + 1)
    return vmem <= _PAGED_VMEM_BYTES and smem <= _PAGED_SMEM_BYTES


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
    ``layer`` (a Python int) naming the layer: it goes into the
    kernel's block index map (the gather's index), never into a slice
    of the pool; page_tables [S, P] int32; kv_lens [S, W] int32
    per-TOKEN valid lengths (token w of slot s is the query at position
    kv_lens[s, w] - 1 — the mask is causal within the window too,
    because earlier window tokens' K/V were scattered before this
    call). Returns [S, W, h, dh].

    ``use_kernel=False`` flattens the window into the gather/einsum
    reference (:func:`paged_attention` — exact, reads the full table
    width). ``use_kernel=True`` runs the allocated-pages Pallas kernel:
    page tables and per-slot used-page counts are scalar-prefetched,
    the page-axis block index is clamped to the last allocated page so
    revisited blocks skip their DMA, and cache-read traffic is
    ceil(len/page_size) pages instead of P.

    ``k_scales``/``v_scales`` [n_pages, page_size, g] (or [L, ...])
    switch the pools to the INT8 two-tier layout (:func:`quantize_kv`
    rows): the gather path dequantizes the gathered view then runs the
    same exact einsum (the dequant analogue of the existing kernel-gate
    fallback), and the kernel path runs :func:`_paged_window_kernel`
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
    # pages actually holding live KV for each slot (>= 1 so the null
    # page still feeds the pipeline for idle slots)
    used = jnp.clip(-(-jnp.max(lens, axis=1) // ps), 1, P)
    hp = _heads_per_chunk(g, dh)
    n_chunks, C, rows_n = g // hp, hp * dh, hp * W * rep
    lead = () if layer is None else (layer,)

    def _slot_map(si, pi, tables, used_, lens_):
        return (si, 0, 0, 0)

    def _table_map(si, pi, tables, used_, lens_):
        return lead + (tables[si, jnp.minimum(pi, used_[si] - 1)], 0, 0)

    kernel = functools.partial(
        _paged_window_kernel, scale=scale, rep=rep, page_size=ps,
        window=W, head_dim=dh, quant=quant)
    one = (1,) * (len(lead) + 1)
    in_specs = [
        pl.BlockSpec((1, n_chunks, rows_n, C), _slot_map),
        pl.BlockSpec(one + (ps, gd), _table_map),
        pl.BlockSpec(one + (ps, gd), _table_map),
    ]
    # rows (j, w, r) of chunk c, block-diagonal over the chunk's heads:
    # [S, W, (c, j, r), dh] -> [S, c, (j, w, r), (j', dh)]
    qc = q.reshape(S, W, n_chunks, hp, rep, dh).transpose(0, 2, 3, 1, 4, 5)
    eye = jnp.eye(hp, dtype=q.dtype)[:, None, None, :, None]
    qd = (qc[:, :, :, :, :, None, :] * eye).reshape(S, n_chunks, rows_n, C)
    operands = [jnp.asarray(page_tables, jnp.int32),
                used.astype(jnp.int32), lens, qd, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec(one + (ps, g), _table_map),
                     pl.BlockSpec(one + (ps, g), _table_map)]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_chunks, rows_n, C), _slot_map),
        scratch_shapes=[
            pltpu.VMEM((n_chunks, rows_n, 1), jnp.float32),
            pltpu.VMEM((n_chunks, rows_n, 1), jnp.float32),
            pltpu.VMEM((n_chunks, rows_n, C), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_chunks, rows_n, C), q.dtype),
        interpret=interpret, name="paged_window_attention",
    )(*operands)
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
# What the kernel's grid costs (v5e, 64 slots x 64 heads, 4096 positions a
# slot, bf16; my chip run, PR 35): every visit of a page operand by a grid
# step costs ~65 ns of the scalar core whether its DMA is skipped or not,
# so a call's time follows slots x table width x operands a page, not the
# live tokens (an EMPTY cache read 1.89 ms where 2,300 tokens a slot read
# 2.41, with c_kv and k_rope as two pools of 16-row pages). Hence one pool
# (one operand a page), and pages of 32 rows where the deployment can
# choose (1.28 against 2.41 ms at the same tokens).
_LATENT_ROWS_PER_STEP = 512


def _pages_per_block(page_size: int, pages_per_slot: int) -> int:
    """Pages one grid step of the latent kernel walks: 512 cache rows,
    each page its own input block with its own DMA in flight."""
    return max(1, min(_LATENT_ROWS_PER_STEP // page_size, pages_per_slot))


def latent_kernel_supported(num_slots: int, rows: int, lanes: int,
                            rkv: int, page_size: int, pages_per_slot: int,
                            dtype) -> bool:
    """Does :func:`paged_latent_attention`'s kernel lower on the chip for
    these shapes: rows and their c_kv part of whole 128-lane tiles, pages
    of whole sublane tiles of the pool's dtype, a step's pages and the
    [rows, rkv] accumulator inside VMEM, the page tables inside SMEM.
    ``rows`` = window x heads (the kernel stacks two terms of each)."""
    esize = jnp.dtype(dtype).itemsize
    if lanes % 128 or rkv % 128 or page_size % (32 // esize):
        return False
    k = _pages_per_block(page_size, pages_per_slot)
    span = k * page_size
    rows8 = _round_up(2 * rows, 8)
    pages = 2 * span * lanes * esize                     # double-buffered
    work = span * lanes * esize + rows8 * (
        2 * lanes * esize + 3 * rkv * 4 + 3 * span * 4)
    smem = 4 * num_slots * (_round_up(pages_per_slot, 128) + 128 + 1)
    return pages + work <= _PAGED_VMEM_BYTES and smem <= _PAGED_SMEM_BYTES


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


def _paged_latent_kernel(tables_ref, used_ref, lens_ref, q_ref, *rest,
                         scale, page_size, window, heads, rkv,
                         pages_per_block, split):
    """Grid (S, ceil(P / K)), page blocks fastest. A step holds K pages
    of the slot, each its own input block whose index map clamped the
    page to the slot's last allocated one (a revisited page skips its
    DMA; a step wholly past the allocation skips the math). The K pages
    stand as one [K*page, lanes] tile of cache rows: the scores of all
    ``window x heads`` query rows in ONE product ``q . rows^T`` (q is
    [q_lat | q_rope | 0]), online softmax in float32, then
    ``p . rows[:, :rkv]`` into the [rows, rkv] float32 accumulator: the
    latent row is read from HBM once and used twice.

    Precision: the cache is what it is stored as; nothing else is
    rounded to it. With ``split`` (a bfloat16 pool) q
    arrives as two terms of the pool's dtype stacked on its rows and the
    probabilities are split the same way before their product (the two
    halves of each result are added: 128 rows where 64 left the MXU half
    empty), and the output stays float32. A query, a probability or an
    output rounded to bfloat16 is an error of 0.2-0.4 % of the attention
    output that no averaging over the cached tokens removes, and a router
    downstream turns it into moved top-k choices."""
    K = pages_per_block
    page_refs = rest[:K]
    out_ref, m_ref, l_ref, acc_ref = rest[K:]
    s = pl.program_id(0)
    b = pl.program_id(1)
    rows_n = acc_ref.shape[0]
    span = K * page_size
    page = (0,) * (len(page_refs[0].shape) - 2)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(b * K < used_ref[s])
    def _accumulate():
        c = jnp.concatenate([r[page] for r in page_refs], axis=0) \
            if K > 1 else page_refs[0][page]               # [span, lanes]
        sc = jax.lax.dot_general(
            q_ref[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if split:
            sc = sc[:rows_n] + sc[rows_n:]
        # causal/ragged mask against ABSOLUTE positions: row (w, h) is
        # window token w and sees < lens[s, w]; a clamped (repeated)
        # page lies past every length and is masked whole
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

    @pl.when(b == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_latent_attention(q_lat, q_rope, pages, page_tables, kv_lens, *,
                           layer, scale, use_kernel=False, interpret=False):
    """Absorbed multi-head latent attention over the paged latent pool.

    q_lat [S, W, H, rkv] (``q_nope W_uk^T``) and q_rope [S, W, H, dr]
    (rotated); pages [L, n_pages, page_size, lanes], the pool AS STORED
    (a row is [c_kv | k_rope | zero lanes]), with ``layer`` (a Python
    int) naming the layer read: it goes into the kernel's block index map
    or the gather's index, never into a slice of the pool; page_tables
    [S, P] int32; kv_lens [S, W] per-token valid lengths (token w of slot
    s is the query at position kv_lens[s, w] - 1). Returns float32 o_lat
    [S, W, H, rkv] = softmax(scores * scale) . c_kv, which the caller
    expands by W_uv.

    ``use_kernel=False`` gathers the slot's full table width and runs the
    same mathematics as einsums (the CPU path and the kernel's test
    reference); ``use_kernel=True`` runs :func:`_paged_latent_kernel`,
    whose cache reads follow the slot's allocated pages."""
    S, W, H, rkv = q_lat.shape
    ps, lanes = pages.shape[-2:]
    P = page_tables.shape[1]
    lens = jnp.asarray(kv_lens, jnp.int32).reshape(S, W)
    # q as the row is laid out: zero lanes where the row has them
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    q = jnp.pad(q, [(0, 0)] * 3 + [(0, lanes - q.shape[-1])])
    if not use_kernel:
        hp = jax.lax.Precision.HIGHEST
        c = gather_pages(pages, page_tables, layer).astype(jnp.float32)
        sc = jnp.einsum("swhr,skr->shwk", q, c, precision=hp)
        mask = jnp.arange(P * ps)[None, None, :] < lens[:, :, None]
        sc = jnp.where(mask[:, None], sc * scale, NEG_INF)
        return jnp.einsum("shwk,skr->swhr", jax.nn.softmax(sc, axis=-1),
                          c[..., :rkv], precision=hp)
    K = _pages_per_block(ps, P)
    used = jnp.clip(-(-jnp.max(lens, axis=1) // ps), 1, P)
    rows_n = W * H
    split = pages.dtype == jnp.bfloat16
    q = q.reshape(S, rows_n, lanes)
    if split:       # [hi; lo] on each slot's rows
        q = jax.vmap(_two_terms)(q)
    else:
        q = q.astype(pages.dtype)
    q_rows = q.shape[1]

    def _slot_map(si, bi, tables, used_, lens_):
        return (si, 0, 0)

    def _page_map(j):
        def index(si, bi, tables, used_, lens_):
            return (layer, tables[si, jnp.minimum(bi * K + j,
                                                  used_[si] - 1)], 0, 0)
        return index

    kernel = functools.partial(
        _paged_latent_kernel, scale=scale, page_size=ps, window=W,
        heads=H, rkv=rkv, pages_per_block=K, split=split)
    in_specs = [pl.BlockSpec((1, q_rows, lanes), _slot_map)]
    in_specs += [pl.BlockSpec((1, 1, ps, lanes), _page_map(j))
                 for j in range(K)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, -(-P // K)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows_n, rkv), _slot_map),
        scratch_shapes=[pltpu.VMEM((rows_n, 1), jnp.float32),
                        pltpu.VMEM((rows_n, 1), jnp.float32),
                        pltpu.VMEM((rows_n, rkv), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, rows_n, rkv), jnp.float32),
        interpret=interpret, name="paged_latent_attention",
    )(jnp.asarray(page_tables, jnp.int32), used.astype(jnp.int32), lens,
      q, *([pages] * K))
    return out.reshape(S, W, H, rkv)
