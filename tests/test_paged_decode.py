"""Paged-KV continuous-batching decode vs the dense-cache reference.

The engine contract (ISSUE 6): greedy decode through the paged KV
cache + fixed-shape slot batch must be TOKEN-IDENTICAL to
``TransformerDecoder.generate`` (the dense path test_decode.py already
pins against the training graph) — on ragged batches, across page
boundaries, under GQA, and through preemption/eviction replays. The
decode step must compile exactly once no matter how requests join and
leave (@recompile_budget); KV pages must always return to the pool.
"""

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.serving import DecodeEngine, PagePool, Rejected
from paddle_tpu.serving.engine import GenRequest  # noqa: F401 (re-export)

CFG = dict(vocab_size=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
           max_len=32)


def _model(seed=7, **overrides):
    paddle.init(use_tpu=False, seed=0)
    from paddle_tpu.core.registry import reset_name_counters
    reset_name_counters()
    spec = models.transformer_lm(**{**CFG, **overrides})
    costs = spec.cost if isinstance(spec.cost, list) else [spec.cost]
    topo = paddle.Topology(costs, extra_outputs=[spec.output])
    params = topo.init_params(jax.random.PRNGKey(seed))
    return params


def _decoder(params, n_heads=None):
    return models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                     n_heads=n_heads or CFG["n_heads"])


def _dense_rows(dec, prompts, max_news):
    """Reference: the dense-cache decoder, one request at a time (the
    per-request path the engine replaces)."""
    return [dec.generate(p[None, :], max_len=len(p) + mn)[0]
            for p, mn in zip(prompts, max_news)]


def _ragged(rng, n, lo=3, hi=9):
    return [rng.randint(0, CFG["vocab_size"],
                        (int(rng.randint(lo, hi)),)).astype("int32")
            for _ in range(n)]


def _stored(pages):
    """[n_pages, page_size, g, dh] pages as the pools store them:
    [n_pages, page_size, g*dh], the kv heads side by side on the lane
    axis (ops/pallas_decode.py, the pool layout)."""
    return jax.numpy.asarray(pages).reshape(pages.shape[:2] + (-1,))


def _balanced(eng):
    """Zero leaks, zero refcount drift. With the prefix cache on (the
    default) a drained engine parks finished pages in the trie, so the
    balance is free + trie-held == usable and refs == slots + trie."""
    acc = eng.page_accounting()
    assert acc["leaked"] == 0
    assert acc["free"] + acc["held_by_trie"] == acc["total_usable"]
    assert acc["refs_total"] == \
        acc["held_by_slots"] + acc["held_by_trie"]
    return acc


# What the live-pages walk of ops/pallas_decode.py can get wrong, as
# cases shared by the three kernel test classes below. K pages make a
# compute block; ``_walk_parity`` cuts the block to K = 4 pages so that
# a toy table (3 blocks wide) holds several. Each case is (per-slot
# first lengths, table kind); a length is a plain int, 0 for an idle
# slot, ("blk", n, d) = n blocks + d tokens, or "full" = the table's
# whole width.
WALK_CASES = {
    "idle_among_live": ([5, 0, ("blk", 1, 3), 0, 1], None),
    "full_table": (["full", 3, "full"], None),
    "block_edges": ([("blk", 1, -1), ("blk", 1, 0), ("blk", 1, 1), 2],
                    None),
    "repeated_pages": ([("blk", 1, 2), "full", 7], "repeated"),
    "two_blocks_and_one_page": ([("blk", 2, 0), 1, ("blk", 1, 5)], None),
}


def _walk_parity(case, *, W, h, g, dh, ps, layered, quant, seed=31,
                 interpret=True):
    """Kernel (interpret mode) against gather on one WALK_CASES entry.
    A window of W tokens starts at each slot's first length, so with
    W > 1 the ("blk", 1, -1) slot's window straddles a block's edge. Idle
    slots (length 0 for every token) must come back as zeros; the
    gather reference is not asked about them."""
    from paddle_tpu.ops import pallas_decode as pd
    K = 4
    firsts, table_kind = WALK_CASES[case]
    S, P = len(firsts), 3 * K
    span = K * ps

    def resolve(x):
        if x == "full":
            return P * ps - (W - 1)
        return x[1] * span + x[2] if isinstance(x, tuple) else x

    rng = np.random.RandomState(seed)
    npages = S * P + 1
    L = 2
    k = rng.randn(L, npages, ps, g * dh).astype(np.float32)
    v = rng.randn(L, npages, ps, g * dh).astype(np.float32)
    q = rng.randn(S, W, h, dh).astype(np.float32)
    tables = rng.permutation(np.arange(1, npages)).reshape(S, P)
    if table_kind == "repeated":
        # out of order AND the same physical page at several places of
        # one slot's table and in two slots' tables (shared prefixes)
        tables[0, :] = [7, 7, 3, 7, 1, 3, 9, 9, 2, 7, 5, 1]
        tables[1, :6] = tables[0, :6]
    base = np.array([resolve(x) for x in firsts])
    lens = np.where(base[:, None] > 0,
                    base[:, None] + np.arange(W)[None, :], 0)
    assert lens.max() <= P * ps
    for si in range(S):                 # null tail past the allocation
        tables[si, -(-int(lens[si].max()) // ps):] = 0
    kw = {}
    if quant:
        def quantize(pool):
            qv, sc = pd.quantize_kv(
                jax.numpy.asarray(pool).reshape(pool.shape[:-1] + (g, dh)))
            return qv.reshape(pool.shape), sc
        (k, ks), (v, vs) = quantize(k), quantize(v)
        kw = dict(k_scales=ks, v_scales=vs)
    k, v = jax.numpy.asarray(k), jax.numpy.asarray(v)
    if layered:
        kw["layer"] = 1
    else:
        k, v = k[1], v[1]
        kw = {n: (a[1] if n != "layer" else a) for n, a in kw.items()}
    args = (jax.numpy.asarray(q), k, v,
            jax.numpy.asarray(tables.astype(np.int32)),
            jax.numpy.asarray(lens.astype(np.int32)))
    want = np.asarray(pd.paged_window_attention(*args, **kw))
    rows_was = pd._WINDOW_ROWS_PER_BLOCK, pd._WINDOW_ROWS_MOST
    # narrow rows get no wider block either: the cases count on K pages
    pd._WINDOW_ROWS_PER_BLOCK = pd._WINDOW_ROWS_MOST = span
    try:
        got = np.asarray(pd.paged_window_attention(
            *args, use_kernel=True, interpret=interpret, **kw))
    finally:
        pd._WINDOW_ROWS_PER_BLOCK, pd._WINDOW_ROWS_MOST = rows_was
    live = base > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_array_equal(got[~live], 0.0)


class TestPagedAttentionUnit:
    """ops/pallas_decode.paged_attention vs a straight dense reference,
    including GQA widths, per-row ragged lengths, and the composition
    with the recorded-experiment Pallas kernel."""

    def _reference(self, q, k, v, lens):
        b, h, dh = q.shape
        g = k.shape[2]
        rep = h // g
        t = k.shape[1]
        q5 = q.reshape(b, 1, g, rep, dh)
        logits = np.einsum("bqgrd,bkgd->bgrqk", q5, k) * dh ** -0.5
        mask = np.arange(t)[None, :] < np.asarray(lens)[:, None]
        logits = np.where(mask[:, None, None, None], logits, -1e30)
        w = np.exp(logits - logits.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        return np.einsum("bgrqk,bkgd->bqgrd", w, v).reshape(b, h, dh)

    @pytest.mark.parametrize("h,g", [(4, 4), (4, 2), (4, 1)])
    def test_matches_dense_reference(self, h, g):
        from paddle_tpu.ops.pallas_decode import paged_attention
        rng = np.random.RandomState(0)
        b, dh, ps, npages, P = 3, 8, 4, 16, 5
        k_pages = rng.randn(npages, ps, g, dh).astype(np.float32)
        v_pages = rng.randn(npages, ps, g, dh).astype(np.float32)
        q = rng.randn(b, h, dh).astype(np.float32)
        # distinct physical pages per row, deliberately out of order
        table = np.array([[3, 1, 7, 0, 0],
                          [2, 9, 4, 11, 0],
                          [5, 6, 0, 0, 0]], np.int32)
        lens = np.array([9, 17, 5], np.int32)   # ragged, straddling
        got = np.asarray(paged_attention(
            jax.numpy.asarray(q), _stored(k_pages), _stored(v_pages),
            jax.numpy.asarray(table), jax.numpy.asarray(lens)))
        k = k_pages[table].reshape(b, P * ps, g, dh)
        v = v_pages[table].reshape(b, P * ps, g, dh)
        want = self._reference(q, k, v, lens)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


class TestPagedWindowKernel:
    """The live-pages kernel (ops/pallas_decode.py
    paged_window_attention) vs the gather/einsum reference: W-token
    verify windows, GQA/MQA widths, ragged lengths whose trailing
    page-table entries the kernel's walk must never read."""

    @pytest.mark.parametrize("h,g", [(4, 4), (4, 2), (4, 1)])
    def test_window_parity_gqa(self, h, g):
        from paddle_tpu.ops.pallas_decode import paged_window_attention
        rng = np.random.RandomState(3)
        S, W, dh, ps, npages = 3, 3, 8, 4, 12
        k_pages = rng.randn(npages, ps, g, dh).astype(np.float32)
        v_pages = rng.randn(npages, ps, g, dh).astype(np.float32)
        q = rng.randn(S, W, h, dh).astype(np.float32)
        # out-of-order physical pages; rows past the allocation point
        # are the null page and must be SKIPPED, not gathered
        tables = np.array([[3, 1, 7, 0, 0],
                           [2, 9, 4, 11, 8],
                           [5, 6, 0, 0, 0]], np.int32)
        base = np.array([9, 15, 5], np.int32)     # ragged, mid-page
        lens = (base[:, None] + np.arange(W)[None, :]).astype(np.int32)
        args = [jax.numpy.asarray(a) for a in
                (q, _stored(k_pages), _stored(v_pages), tables, lens)]
        want = np.asarray(paged_window_attention(*args))
        got = np.asarray(paged_window_attention(
            *args, use_kernel=True, interpret=True))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("h,g", [(4, 4), (4, 2), (4, 1)])
    def test_wide_window_parity_gqa(self, h, g):
        """A prefill lane's window is wide: 12 tokens a slot here,
        ragged and mid-page, each row masked by its own length, one slot
        part-fed (its last rows masked by length 0), every head width."""
        from paddle_tpu.ops import pallas_decode as pd
        rng = np.random.RandomState(5)
        S, W, dh, ps, npages = 3, 12, 8, 4, 24
        k_pages = rng.randn(npages, ps, g, dh).astype(np.float32)
        v_pages = rng.randn(npages, ps, g, dh).astype(np.float32)
        q = rng.randn(S, W, h, dh).astype(np.float32)
        tables = np.array([[3, 1, 7, 12, 13, 0, 0],
                           [2, 9, 4, 11, 8, 14, 15],
                           [5, 6, 16, 17, 0, 0, 0]], np.int32)
        base = np.array([7, 15, 1], np.int32)
        lens = (base[:, None] + np.arange(W)[None, :]).astype(np.int32)
        lens[2, 9:] = 0                           # fed 9 of its 12
        args = [jax.numpy.asarray(a) for a in
                (q, _stored(k_pages), _stored(v_pages), tables, lens)]
        want = np.asarray(pd.paged_window_attention(*args))
        got = np.asarray(pd.paged_window_attention(
            *args, use_kernel=True, interpret=True))
        np.testing.assert_allclose(got[:2], want[:2], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(got[2, :9], want[2, :9],
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("layered", [False, True],
                             ids=["pool", "layer-axis"])
    @pytest.mark.parametrize("W", [1, 3], ids=["W1", "W3"])
    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_live_page_walk(self, case, W, layered):
        """The kernel walks each slot's live pages itself: idle slots
        among live ones, a slot that fills its table, lengths at a
        compute block's edge, repeated physical pages, a window that
        straddles a block's edge — GQA at toy width."""
        _walk_parity(case, W=W, h=4, g=2, dh=8, ps=4, layered=layered,
                     quant=False)

    @pytest.mark.parametrize("case", ["idle_among_live", "block_edges"])
    def test_walk_under_the_tpu_interpreter(self, case):
        """The same walk under the interpreter that models the chip's
        memories: every byte the kernel did not write reads NaN (the
        tile's rows past a slot's last live page), a DMA lands only
        when it is waited for (a tile read before its wait reads NaN),
        and a buffer written while the other half's reader still runs
        is reported as a race."""
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
        from jax.experimental.pallas import tpu as pltpu
        _walk_parity(case, W=3, h=4, g=2, dh=8, ps=4, layered=True,
                     quant=False, interpret=pltpu.InterpretParams(
                         uninitialized_memory="nan", detect_races=True,
                         dma_execution_mode="on_wait"))
        assert not interpret_pallas_call.races.races_found

    def test_w1_matches_paged_attention(self):
        """W = 1 is the classic one-token step — same numbers as the
        round-6 paged_attention path."""
        from paddle_tpu.ops.pallas_decode import (paged_attention,
                                                  paged_window_attention)
        rng = np.random.RandomState(4)
        S, h, g, dh, ps, npages = 2, 4, 2, 8, 4, 8
        k_pages = _stored(rng.randn(npages, ps, g, dh).astype(np.float32))
        v_pages = _stored(rng.randn(npages, ps, g, dh).astype(np.float32))
        q = jax.numpy.asarray(rng.randn(S, h, dh).astype(np.float32))
        tables = jax.numpy.asarray(
            np.array([[1, 4, 2, 0], [3, 5, 0, 0]], np.int32))
        lens = jax.numpy.asarray(np.array([10, 7], np.int32))
        want = np.asarray(
            paged_attention(q, k_pages, v_pages, tables, lens))
        got = np.asarray(paged_window_attention(
            q[:, None], k_pages, v_pages, tables, lens[:, None],
            use_kernel=True, interpret=True))[:, 0]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_kernel_gate(self):
        """What the gate models: the kernel copies each live page with a
        DMA of its own, which the chip's compiler takes only for cache
        rows of whole 128-lane tiles and pages of whole 8-row tiles."""
        from paddle_tpu.ops.pallas_decode import paged_kernel_supported
        q = jax.numpy.zeros((2, 2, 4, 64), np.float32)
        k = jax.numpy.zeros((8, 8, 2 * 64), np.float32)
        assert paged_kernel_supported(q, k)
        # the gate reads the stored layout with its layer axis too
        assert paged_kernel_supported(
            q, jax.numpy.zeros((3, 8, 8, 2 * 64), np.float32))
        # head dim off the sublane multiple -> fall back to XLA
        q_odd = jax.numpy.zeros((2, 2, 4, 6), np.float32)
        k_odd = jax.numpy.zeros((8, 4, 2 * 6), np.float32)
        assert not paged_kernel_supported(q_odd, k_odd)
        # one kv head of 64 is half a lane tile; a page of 4 rows is
        # half a sublane tile: neither page can be copied by itself
        assert not paged_kernel_supported(
            q, jax.numpy.zeros((8, 8, 64), np.float32))
        assert not paged_kernel_supported(
            q, jax.numpy.zeros((8, 4, 2 * 64), np.float32))
        # the compute block is cut to what VMEM holds twice over, and a
        # page that does not fit even alone is turned away
        q_wide = jax.numpy.zeros((4, 1, 32, 128), np.float32)
        assert paged_kernel_supported(
            q_wide, jax.numpy.zeros((9, 16, 32 * 128), np.float32),
            pages_per_slot=8)
        assert not paged_kernel_supported(
            q_wide, jax.numpy.zeros((9, 256, 32 * 128), np.float32),
            pages_per_slot=8)


class TestDequantWindowKernel:
    """ISSUE 20: the dequant-fused variant of the allocated-pages
    kernel over INT8 pools (quantize_kv rows + per-(row, kv-head)
    float32 scales). Three pins: the fused kernel matches the
    dequantizing gather/einsum path bit-for-tolerance, both int8 paths
    stay within the pinned INT8_KV_RTOL/ATOL contract of the exact
    float32 attention, and the VMEM gate accounts for the scale
    blocks."""

    def _quant_pools(self, rng, npages, ps, g, dh):
        from paddle_tpu.ops.pallas_decode import quantize_kv
        k = rng.randn(npages, ps, g, dh).astype(np.float32)
        v = rng.randn(npages, ps, g, dh).astype(np.float32)
        kq, ks = quantize_kv(jax.numpy.asarray(k))
        vq, vs = quantize_kv(jax.numpy.asarray(v))
        return k, v, _stored(kq), ks, _stored(vq), vs

    @pytest.mark.parametrize("h,g", [(4, 4), (4, 2), (4, 1)])
    def test_dequant_kernel_matches_gather_path(self, h, g):
        """GQA/MQA widths, out-of-order physical pages, ragged mid-page
        lengths: the fused kernel (interpret mode) vs the dequantizing
        gather + exact einsum — same int8 inputs, same numbers."""
        from paddle_tpu.ops.pallas_decode import paged_window_attention
        rng = np.random.RandomState(13)
        S, W, dh, ps, npages = 3, 3, 8, 4, 12
        _, _, kq, ks, vq, vs = self._quant_pools(rng, npages, ps, g, dh)
        q = jax.numpy.asarray(
            rng.randn(S, W, h, dh).astype(np.float32))
        tables = jax.numpy.asarray(
            np.array([[3, 1, 7, 0, 0],
                      [2, 9, 4, 11, 8],
                      [5, 6, 0, 0, 0]], np.int32))
        base = np.array([9, 15, 5], np.int32)
        lens = jax.numpy.asarray(
            (base[:, None] + np.arange(W)[None, :]).astype(np.int32))
        want = np.asarray(paged_window_attention(
            q, kq, vq, tables, lens, k_scales=ks, v_scales=vs))
        got = np.asarray(paged_window_attention(
            q, kq, vq, tables, lens, k_scales=ks, v_scales=vs,
            use_kernel=True, interpret=True))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("W", [1, 3], ids=["W1", "W3"])
    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_live_page_walk_int8(self, case, W):
        """The walk's cases over int8 pools: the pages are copied by
        the kernel, their scales arrive per slot in table order and
        multiply the scores and the probabilities."""
        _walk_parity(case, W=W, h=4, g=2, dh=8, ps=4, layered=False,
                     quant=True)

    def test_int8_within_pinned_contract_of_fp32(self):
        """The token-identity tolerance contract: int8 attention
        outputs (gather AND fused kernel) sit within INT8_KV_RTOL/ATOL
        of the exact float32 attention over the same pre-quantization
        pages — the bound under which tiny-model greedy argmax stays
        stable (TestTwoTierChaos pins the end-to-end identity)."""
        from paddle_tpu.ops.pallas_decode import (
            INT8_KV_ATOL, INT8_KV_RTOL, paged_window_attention)
        rng = np.random.RandomState(14)
        S, W, h, g, dh, ps, npages = 2, 2, 4, 2, 8, 4, 10
        k, v, kq, ks, vq, vs = self._quant_pools(rng, npages, ps, g, dh)
        q = jax.numpy.asarray(
            rng.randn(S, W, h, dh).astype(np.float32))
        tables = jax.numpy.asarray(
            np.array([[1, 4, 2, 0], [3, 5, 7, 0]], np.int32))
        base = np.array([10, 7], np.int32)
        lens = jax.numpy.asarray(
            (base[:, None] + np.arange(W)[None, :]).astype(np.int32))
        exact = np.asarray(paged_window_attention(
            q, _stored(k), _stored(v), tables, lens))
        for use_kernel in (False, True):
            got = np.asarray(paged_window_attention(
                q, kq, vq, tables, lens, k_scales=ks, v_scales=vs,
                use_kernel=use_kernel, interpret=use_kernel))
            np.testing.assert_allclose(got, exact, rtol=INT8_KV_RTOL,
                                       atol=INT8_KV_ATOL)

    def test_quantize_roundtrip_properties(self):
        """quantize_kv is a pure per-row function (token identity
        across prefix reuse needs the same row to quantize the same
        way in any batch) and all-zero rows — the null page — stay
        exactly zero after dequant."""
        from paddle_tpu.ops.pallas_decode import (dequantize_kv,
                                                  quantize_kv)
        rng = np.random.RandomState(15)
        rows = jax.numpy.asarray(rng.randn(6, 4, 2, 8)
                                 .astype(np.float32))
        q1, s1 = quantize_kv(rows)
        q2, s2 = quantize_kv(rows[2:5])      # different batch context
        np.testing.assert_array_equal(np.asarray(q1)[2:5],
                                      np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1)[2:5],
                                      np.asarray(s2))
        zq, zs = quantize_kv(jax.numpy.zeros((1, 4, 2, 8), np.float32))
        np.testing.assert_array_equal(
            np.asarray(dequantize_kv(zq, zs)), 0.0)
        # max quantization error bounded by scale/2 per element
        back = np.asarray(dequantize_kv(q1, s1))
        err = np.abs(back - np.asarray(rows))
        bound = np.asarray(s1)[..., None] * 0.5 + 1e-7
        assert (err <= bound).all()

    def test_gate_counts_scale_blocks(self):
        """The int8 layout's scales reach the kernel per slot, over the
        table's whole width: the gate counts them."""
        from paddle_tpu.ops.pallas_decode import (_window_vmem,
                                                  paged_kernel_supported)
        q = jax.numpy.zeros((2, 2, 4, 64), np.float32)
        k8 = jax.numpy.zeros((8, 8, 2 * 64), jax.numpy.int8)
        sc = jax.numpy.zeros((8, 8, 2), np.float32)
        assert paged_kernel_supported(q, k8, sc)
        assert _window_vmem(q, k8, True, 64)[1] - \
            _window_vmem(q, k8, False, 64)[1] == 2 * 2 * 8 * 4 * 64 * 8
        # a table so wide that its scales alone fill VMEM
        assert paged_kernel_supported(q, k8, None, pages_per_slot=16384)
        assert not paged_kernel_supported(q, k8, sc, pages_per_slot=16384)
        # odd head dim still falls back, scales or not
        q_odd = jax.numpy.zeros((2, 2, 4, 6), np.float32)
        k_odd = jax.numpy.zeros((8, 4, 2 * 6), jax.numpy.int8)
        assert not paged_kernel_supported(
            q_odd, k_odd, jax.numpy.zeros((8, 4, 2), np.float32))


class TestStoredPoolLayout:
    """ISSUE 32: the pools live in ONE layout, the one the kernel's
    blocks read — [L, n_pages, page_size, g*dh], the kv heads side by
    side on the lane axis — and the layer rides in the kernel's block
    index / the gather's index, never in a slice of the pool. Pins the
    kernel's 128-lane chunk paths (two heads of 64 a chunk with q laid
    out block-diagonally; one head of 128 a chunk) that the toy widths
    above never reach, and the small page programs on the stored
    layout: copy, read, write, spill payload."""

    def _case(self, rng, S, W, h, g, dh, ps, P, L=2):
        npages = S * P + 1
        k = rng.randn(L, npages, ps, g * dh).astype(np.float32)
        v = rng.randn(L, npages, ps, g * dh).astype(np.float32)
        q = rng.randn(S, W, h, dh).astype(np.float32)
        tables = rng.permutation(np.arange(1, npages)).reshape(S, P)
        tables[0, 2:] = 0                  # a short slot: null tail
        base = rng.randint(1, P * ps - W, (S,))
        base[0] = ps + 3                   # ... that ends mid-page 1
        lens = base[:, None] + np.arange(W)[None, :]
        return [jax.numpy.asarray(a) for a in
                (q, k, v, tables.astype(np.int32), lens.astype(np.int32))]

    @pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("W", [1, 3], ids=["W1", "W3"])
    @pytest.mark.parametrize("h,g,dh", [(8, 4, 64), (4, 4, 64), (4, 2, 128),
                                        (6, 3, 64), (4, 2, 32)],
                             ids=["gqa-2x64", "mha-2x64", "gqa-1x128",
                                  "odd-g-row", "4x32"])
    def test_chunked_kernel_matches_gather_with_layer(self, h, g, dh, W,
                                                      quant):
        """Kernel (interpret mode) against gather on the whole
        [L, N, ps, g*dh] pool with ``layer=``, and both against the
        gather over that layer handed alone as [N, ps, g*dh]."""
        from paddle_tpu.ops.pallas_decode import (_heads_per_chunk,
                                                  paged_window_attention,
                                                  quantize_kv)
        # the cases cover the chunk shapes by name: 2 heads of 64, one
        # of 128, 4 of 32, and (g = 3) the whole row as one chunk
        assert _heads_per_chunk(g, dh) == {"64": 2 if g % 2 == 0 else 3,
                                           "128": 1, "32": g}[str(dh)]
        rng = np.random.RandomState(21)
        q, k, v, tables, lens = self._case(rng, 3, W, h, g, dh, 4, 5)
        kw = {}
        if quant:
            def quantize(pool):
                qv, sc = quantize_kv(pool.reshape(pool.shape[:-1] + (g, dh)))
                return qv.reshape(pool.shape), sc
            (k, ks), (v, vs) = quantize(k), quantize(v)
            kw = dict(k_scales=ks, v_scales=vs)
        for layer in (0, 1):
            want = np.asarray(paged_window_attention(
                q, k, v, tables, lens, layer=layer, **kw))
            alone = np.asarray(paged_window_attention(
                q, k[layer], v[layer], tables, lens,
                **{n: a[layer] for n, a in kw.items()}))
            np.testing.assert_array_equal(alone, want)
        got = np.asarray(paged_window_attention(
            q, k, v, tables, lens, layer=1, use_kernel=True,
            interpret=True, **kw))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_live_page_walk_at_lane_chunks(self, case, quant):
        """The walk's cases at 128-lane chunks (two heads of 64 a
        chunk, pages of 8 rows, W = 2) on the whole [L, N, ps, g*dh]
        pool with ``layer=``."""
        _walk_parity(case, W=2, h=8, g=4, dh=64, ps=8, layered=True,
                     quant=quant)

    def _paged(self, kv_quant, **over):
        params = _model(**over)
        dec = models.TransformerDecoder(
            params, n_layers=CFG["n_layers"],
            n_heads=over.get("n_heads", CFG["n_heads"]))
        paged = dec.paged(num_slots=2, page_size=4, num_pages=6,
                          max_pages_per_slot=4, warm_start=False,
                          kv_quant=kv_quant)
        rng = np.random.RandomState(22)
        pools = jax.tree_util.tree_map(
            lambda z: jax.numpy.asarray(
                rng.randint(-100, 100, z.shape).astype(z.dtype)),
            paged.init_pools())
        return paged, pools

    @pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
    def test_pool_shapes_and_bytes(self, kv_quant):
        paged, (k_pool, v_pool) = self._paged(kv_quant)
        L, g, dh = (CFG["n_layers"], paged.cache.kv_heads,
                    paged.cache.head_dim)
        values = k_pool["q"] if kv_quant else k_pool
        assert values.shape == (L, 6, 4, g * dh)
        if kv_quant:
            assert k_pool["s"].shape == (L, 6, 4, g)
        assert paged.pool_bytes() == sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(
                (k_pool, v_pool)))
        assert "g*dh" in paged.cache.LAYOUT

    @pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
    def test_read_write_page_round_trip(self, kv_quant):
        """write_page(read_page(p)) is the identity, the payload keeps
        the shape the spill codec has always had ([L, 1, ps, g, dh]
        values, [L, 1, ps, g] scales), and a page written elsewhere
        lands there and nowhere else."""
        paged, (k_pool, v_pool) = self._paged(kv_quant)
        L, g, dh = (CFG["n_layers"], paged.cache.kv_heads,
                    paged.cache.head_dim)
        before = jax.tree_util.tree_map(np.asarray, (k_pool, v_pool))
        k_page, v_page = paged.read_page(k_pool, v_pool, 3)
        values = k_page["q"] if kv_quant else k_page
        assert values.shape == (L, 1, 4, g, dh)
        if kv_quant:
            assert set(k_page) == {"q", "s"}
            assert k_page["s"].shape == (L, 1, 4, g)
        stored = before[0]["q"] if kv_quant else before[0]
        np.testing.assert_array_equal(
            np.asarray(values).reshape(L, 4, g * dh), stored[:, 3])
        same = paged.write_page(k_pool, v_pool, k_page, v_page, 3)
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               jax.tree_util.tree_map(np.asarray, same),
                               before)
        moved = jax.tree_util.tree_map(
            np.asarray, paged.write_page(k_pool, v_pool, k_page, v_page, 5))

        def check(after, was):
            np.testing.assert_array_equal(after[:, 5], was[:, 3])
            np.testing.assert_array_equal(after[:, :5], was[:, :5])

        jax.tree_util.tree_map(check, moved, before)

    @pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
    def test_copy_page_on_stored_pools(self, kv_quant):
        paged, (k_pool, v_pool) = self._paged(kv_quant)
        before = jax.tree_util.tree_map(np.asarray, (k_pool, v_pool))
        after = jax.tree_util.tree_map(
            np.asarray, paged.copy_page(k_pool, v_pool, 2, 4))

        def check(now, was):
            np.testing.assert_array_equal(now[:, 4], was[:, 2])
            keep = [0, 1, 2, 3, 5]
            np.testing.assert_array_equal(now[:, keep], was[:, keep])

        jax.tree_util.tree_map(check, after, before)

    @pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
    def test_spill_payload_shapes_unchanged(self, kv_quant):
        """Spill -> restore through the engine's own codec: the host
        payload's leaves are [L, 1, ps, g, dh] (+ [L, 1, ps, g] scales)
        as before the pools changed layout, so an entry written then
        is still one the codec accepts; the restored page reads back
        what was spilled."""
        from paddle_tpu.serving.spill import SpillEntry
        paged, (k_pool, v_pool) = self._paged(kv_quant)
        L, g, dh = (CFG["n_layers"], paged.cache.kv_heads,
                    paged.cache.head_dim)
        payload = {}
        k_page, v_page = paged.read_page(k_pool, v_pool, 2)
        DecodeEngine._flatten_page("k", k_page, payload)
        DecodeEngine._flatten_page("v", v_page, payload)
        want = {"k.q": (L, 1, 4, g, dh), "k.s": (L, 1, 4, g),
                "v.q": (L, 1, 4, g, dh), "v.s": (L, 1, 4, g)} \
            if kv_quant else {"k": (L, 1, 4, g, dh), "v": (L, 1, 4, g, dh)}
        assert {n: a.shape for n, a in payload.items()} == want
        entry = SpillEntry(payload)
        assert entry.verify()
        back_k = DecodeEngine._unflatten_page("k", k_pool, entry.payload)
        back_v = DecodeEngine._unflatten_page("v", v_pool, entry.payload)
        pools = paged.write_page(k_pool, v_pool, back_k, back_v, 1)
        again = paged.read_page(*pools, 1)
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            jax.tree_util.tree_map(np.asarray, again),
            jax.tree_util.tree_map(np.asarray, (k_page, v_page)))

    def test_fingerprints_name_the_layout(self, monkeypatch):
        """An executable stored for another pool layout can never be
        resolved for this one: the layout is in every plan."""
        from paddle_tpu.models.block import PerHeadCache

        def fingerprints():
            paged, _ = self._paged(None)
            return (paged._step_fp, paged._copy_fp, paged._read_fp,
                    paged._write_fp)

        now = fingerprints()
        monkeypatch.setattr(PerHeadCache, "LAYOUT", "L,N,page,g,dh")
        for a, b in zip(now, fingerprints()):
            assert a != b


class TestPagePool:
    def test_alloc_free_accounting(self):
        pool = PagePool(8)              # 7 usable, page 0 reserved
        assert pool.usable == 7
        pages = [pool.alloc() for _ in range(7)]
        assert 0 not in pages           # the null page is never issued
        assert pool.alloc() is None     # exhausted, not an exception
        assert pool.accounting()["leaked"] == 0
        pool.free(pages[:3])
        assert pool.free_pages == 3 and pool.used_pages == 4
        assert pool.high_water == 7
        pool.free(pages[3:])
        assert pool.accounting() == {
            "total_usable": 7, "free": 7, "allocated": 0, "leaked": 0,
            "refs_total": 0, "shared": 0, "high_water": 7, }

    def test_double_free_is_loud(self):
        pool = PagePool(4)
        p = pool.alloc()
        pool.free([p])
        with pytest.raises(ValueError, match="double free|foreign"):
            pool.free([p])
        with pytest.raises(ValueError):
            pool.free([99])

    def test_refcounted_sharing(self):
        """Round 9: alloc() hands a page out at refcount 1, ref() adds
        holders (shared-prefix attach / trie indexing), and free() only
        returns the page to the free list at zero."""
        pool = PagePool(5)
        p = pool.alloc()
        assert pool.refcount(p) == 1 and pool.shared_pages == 0
        pool.ref(p)
        pool.ref(p)
        assert pool.refcount(p) == 3 and pool.shared_pages == 1
        pool.free([p])                      # one holder lets go
        assert pool.refcount(p) == 2
        assert pool.used_pages == 1         # still allocated
        pool.free([p, p])                   # last holders release
        assert pool.refcount(p) == 0
        assert pool.free_pages == pool.usable
        acc = pool.accounting()
        assert acc["leaked"] == 0 and acc["refs_total"] == 0

    def test_refcount_underflow_is_loud(self):
        """Freeing past zero is indistinguishable from a lost page —
        both raise rather than silently corrupting shared KV."""
        pool = PagePool(5)
        p = pool.alloc()
        pool.ref(p)
        pool.free([p, p])
        with pytest.raises(ValueError, match="underflow|double free"):
            pool.free([p])
        with pytest.raises(ValueError, match="not allocated"):
            pool.ref(p)                     # ref after full release

    def test_refcount_histogram(self):
        pool = PagePool(8)
        a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
        pool.ref(b)
        pool.ref(c)
        pool.ref(c)
        assert pool.refcount_histogram() == {1: 1, 2: 1, 3: 1}
        assert pool.accounting()["refs_total"] == 6
        assert pool.accounting()["shared"] == 2
        pool.free([b, c, c])
        assert pool.refcount_histogram() == {1: 3}
        pool.free([a, b, c])
        assert pool.refcount_histogram() == {}


class TestTokenIdentity:
    """THE acceptance test: greedy paged decode == greedy dense decode,
    token for token, on ragged batches whose sequences straddle page
    boundaries — and the engine step compiles exactly once even though
    requests join and leave mid-flight."""

    def test_ragged_batch_token_identical(self):
        params = _model()
        dec = _decoder(params)
        rng = np.random.RandomState(0)
        # lengths 3..8 against page_size 4: sequences start mid-page,
        # end mid-page, and cross 1-3 page boundaries while growing
        prompts = _ragged(rng, 6, lo=3, hi=9)
        max_news = [int(rng.randint(4, 12)) for _ in prompts]
        want = _dense_rows(dec, prompts, max_news)

        eng = DecodeEngine(dec, num_slots=3, page_size=4,
                           max_seq_len=CFG["max_len"])
        # more requests than slots: joins happen mid-flight as earlier
        # sequences finish — continuous batching, not static batching
        reqs = [eng.submit(p, mn) for p, mn in zip(prompts, max_news)]
        eng.run(timeout=300)
        for i, r in enumerate(reqs):
            assert r.get(timeout=1) == [int(t) for t in want[i]], i
        _balanced(eng)
        st = eng.stats()
        assert st["finished"] == len(prompts)
        assert st["tokens_out"] == sum(max_news)

    def test_gqa_token_identical(self):
        params = _model(seed=3, n_kv_heads=1)   # MQA: cache narrower
        dec = _decoder(params)
        rng = np.random.RandomState(1)
        prompts = _ragged(rng, 4, lo=3, hi=8)
        max_news = [6, 9, 5, 8]
        want = _dense_rows(dec, prompts, max_news)
        eng = DecodeEngine(dec, num_slots=4, page_size=4,
                           max_seq_len=CFG["max_len"])
        reqs = [eng.submit(p, mn) for p, mn in zip(prompts, max_news)]
        eng.run(timeout=300)
        for i, r in enumerate(reqs):
            assert r.get(timeout=1) == [int(t) for t in want[i]], i
        assert eng.page_accounting()["leaked"] == 0

    @pytest.mark.recompile_budget(max_compiles=8)
    def test_churn_causes_zero_recompiles(self):
        """THE shape-stability pin: with the engine warm, a storm of
        mid-flight joins, a cancellation, and a pool-pressure eviction
        cause ZERO XLA compilations — the continuous-batching loop
        never retraces (the fixed-shape slot-batch contract). The
        marker budget (8) is headroom for param-init/jit of the warmup
        phase, which legitimately compiles several shape families; the
        churn phase itself is held to an exact total of 0 by the inner
        watch."""
        from paddle_tpu.analysis.sanitizer import compile_watch
        from paddle_tpu.testing import FaultPlan
        params = _model()
        dec = _decoder(params)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=20, num_pages=8)
        warm = eng.submit(np.full((3,), 7, "int32"), 2)
        eng.run(timeout=120)    # compiles the step's two programs: the
        assert warm.get(timeout=1)      # prompt's lane step, then a plain
        assert eng.stats()["prefill_lane_steps"] == 1
        assert eng.stats()["steps"] == 2
        r0 = eng.submit(np.zeros((4,), "int32"), 10)
        joined = []
        with compile_watch() as watch:
            with FaultPlan.decode_script(eng, {
                    2: lambda: joined.append(
                        eng.submit(np.ones((6,), "int32"), 9)),
                    4: lambda: joined.append(
                        eng.submit(np.full((5,), 2, "int32"), 8)),
                    7: lambda: joined[0].cancel()}) as script:
                eng.run(timeout=300)
            assert script["fired"] == [2, 4, 7]
        assert watch.total == 0, (
            f"join/evict/cancel churn recompiled: {watch.per_function}")
        assert len(r0.get(timeout=1)) == 10
        assert joined[0].state == "cancelled"
        assert len(joined[1].get(timeout=1)) == 8
        assert eng.page_accounting()["leaked"] == 0

    def test_eos_frees_slot_early(self):
        """A request that hits its eos mid-flight finishes, frees its
        pages, and its tokens still match the dense path's trim."""
        params = _model()
        dec = _decoder(params)
        prompt = np.zeros((2,), "int32")
        dense = dec.generate(prompt[None, :], max_len=14)[0]
        eos = dense[1] if len(set(dense)) > 1 else dense[0]
        dense_trim = dec.generate(prompt[None, :], max_len=14,
                                  eos_id=int(eos))[0]
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"])
        req = eng.submit(prompt, 12, eos_id=int(eos))
        eng.run(timeout=120)
        assert req.get(timeout=1) == [int(t) for t in dense_trim]
        _balanced(eng)


class TestScheduling:
    def test_preemption_under_tiny_pool_is_output_invariant(self):
        """A pool too small for both requests forces preemption: the
        youngest is evicted, its pages return, and on re-admission it
        replays prompt + generated tokens — BOTH outputs stay identical
        to undisturbed solo runs (greedy determinism survives
        eviction)."""
        params = _model()
        dec = _decoder(params)
        rng = np.random.RandomState(2)
        p1 = rng.randint(0, 40, (5,)).astype("int32")
        p2 = rng.randint(0, 40, (6,)).astype("int32")
        want1 = dec.generate(p1[None, :], max_len=5 + 12)[0]
        want2 = dec.generate(p2[None, :], max_len=6 + 12)[0]
        # each needs ceil(17/4)=5 / ceil(18/4)=5 pages; give the pool 7
        # usable so concurrent growth MUST preempt at some point
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"], num_pages=8)
        r1 = eng.submit(p1, 12)
        r2 = eng.submit(p2, 12)
        eng.run(timeout=300)
        assert r1.get(timeout=1) == [int(t) for t in want1]
        assert r2.get(timeout=1) == [int(t) for t in want2]
        st = eng.stats()
        assert st["preemptions"] >= 1, \
            "pool was sized to force at least one preemption"
        assert (r1.evictions + r2.evictions) == st["preemptions"]
        assert eng.page_accounting()["leaked"] == 0

    def test_admission_rejects_never_satisfiable(self):
        params = _model()
        eng = DecodeEngine(_decoder(params), num_slots=2, page_size=4,
                           max_seq_len=16)
        with pytest.raises(Rejected) as ei:
            eng.submit(np.zeros((8,), "int32"), 20)   # 28 > 16
        assert ei.value.reason == "kv_capacity"
        # pool smaller than the sequence cap: page check also rejects
        eng2 = DecodeEngine(_decoder(params), num_slots=2, page_size=4,
                            max_seq_len=16, num_pages=3)
        with pytest.raises(Rejected) as ei2:
            eng2.submit(np.zeros((8,), "int32"), 6)   # 4 pages > 2
        assert ei2.value.reason == "kv_capacity"

    def test_wait_queue_bound(self):
        params = _model()
        eng = DecodeEngine(_decoder(params), num_slots=1, page_size=4,
                           max_seq_len=16, max_waiting=2)
        reqs = [eng.submit(np.zeros((3,), "int32"), 2)
                for _ in range(2)]
        with pytest.raises(Rejected) as ei:
            eng.submit(np.zeros((3,), "int32"), 2)
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after > 0
        eng.run(timeout=120)
        for r in reqs:
            assert len(r.get(timeout=1)) == 2

    def test_page_aware_admission_head_waits_for_pages(self):
        """A free SLOT is not enough: the queue head only joins when
        the pool can reach its first new token — admission is scheduled
        by free KV pages, not queue depth."""
        params = _model()
        dec = _decoder(params)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=16, num_pages=5)  # 4 usable
        big = eng.submit(np.zeros((8,), "int32"), 4)     # 3 pages total
        # pages allocate lazily: march until big actually holds 3 of
        # the 4 usable pages (it is still mid-generation then)
        for _ in range(40):
            eng.step()
            if eng.page_accounting()["free"] == 1:
                break
        assert eng.page_accounting()["free"] == 1
        assert big.state == "running"
        rival = eng.submit(np.zeros((8,), "int32"), 4)
        eng.step()
        # a slot is FREE, but the head needs ceil(9/4)=3 pages and only
        # 1 is — admission waits on pages, not on queue depth
        assert eng.stats()["active_slots"] == 1
        assert eng.stats()["waiting"] == 1
        eng.run(timeout=300)
        assert len(big.get(timeout=1)) == 4
        assert len(rival.get(timeout=1)) == 4
        assert eng.page_accounting()["leaked"] == 0


class TestSpeculativeDecoding:
    """ISSUE 13 tentpole (b): a draft model proposes spec_k tokens per
    round and the target verifies them in ONE fixed-shape [S, W] paged
    step. Greedy token-identity acceptance means the OUTPUT never
    depends on the draft — only the step count does."""

    def test_same_weights_draft_multi_token_commits(self):
        params = _model()
        dec = _decoder(params)
        rng = np.random.RandomState(5)
        prompts = _ragged(rng, 3, lo=3, hi=8)
        max_news = [10, 8, 12]
        want = _dense_rows(dec, prompts, max_news)
        eng = DecodeEngine(dec, num_slots=3, page_size=4,
                           max_seq_len=CFG["max_len"],
                           draft=_decoder(_model()), spec_k=2)
        reqs = [eng.submit(p, mn) for p, mn in zip(prompts, max_news)]
        eng.run(timeout=300)
        for i, r in enumerate(reqs):
            assert r.get(timeout=1) == [int(t) for t in want[i]], i
        st = eng.stats()
        assert st["window"] == 3 and st["spec_k"] == 2
        assert st["spec_proposed_tokens"] > 0
        assert st["spec_accepted_tokens"] > 0
        # a perfect draft makes multi-token commits the norm: strictly
        # more tokens out than target dispatches (accepted/step > 1)
        assert st["tokens_out"] > st["steps"]
        assert sum(r.accepted_tokens for r in reqs) == \
            st["spec_accepted_tokens"]
        _balanced(eng)

    def test_disagreeing_draft_still_token_identical(self):
        """A draft with different weights proposes mostly-wrong tokens:
        acceptance filters them; rejected speculation rows are masked
        by kv_len and overwritten before they can be read."""
        params = _model()
        dec = _decoder(params)
        rng = np.random.RandomState(6)
        prompts = _ragged(rng, 3, lo=3, hi=8)
        max_news = [8, 10, 6]
        want = _dense_rows(dec, prompts, max_news)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"],
                           draft=_decoder(_model(seed=11)), spec_k=2)
        reqs = [eng.submit(p, mn) for p, mn in zip(prompts, max_news)]
        eng.run(timeout=300)
        for i, r in enumerate(reqs):
            assert r.get(timeout=1) == [int(t) for t in want[i]], i
        st = eng.stats()
        assert st["spec_proposed_tokens"] >= st["spec_accepted_tokens"]
        _balanced(eng)

    def test_mqa_spec_identity(self):
        """Speculation over the narrow MQA cache: the [S, W] verify
        window reads the cache at stored width."""
        params = _model(seed=3, n_kv_heads=1)
        dec = _decoder(params)
        draft = _decoder(_model(seed=3, n_kv_heads=1))
        rng = np.random.RandomState(7)
        prompts = _ragged(rng, 2, lo=3, hi=7)
        want = _dense_rows(dec, prompts, [9, 7])
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"], draft=draft,
                           spec_k=2)
        reqs = [eng.submit(p, mn) for p, mn in zip(prompts, [9, 7])]
        eng.run(timeout=300)
        for i, r in enumerate(reqs):
            assert r.get(timeout=1) == [int(t) for t in want[i]], i
        assert eng.stats()["spec_accepted_tokens"] > 0
        _balanced(eng)

    def test_speculation_requires_greedy(self):
        params = _model()
        with pytest.raises(ValueError, match="greedy|temperature"):
            DecodeEngine(_decoder(params), draft=_decoder(params),
                         spec_k=2, temperature=0.8, max_seq_len=16)


class TestPrefixReuse:
    """ISSUE 13 tentpole (a): radix-indexed shared-prefix KV attach
    with per-page refcounts and copy-on-write on divergence."""

    def test_warm_prefix_attaches_pages_and_skips_prefill(self):
        params = _model()
        dec = _decoder(params)
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, 40, (13,)).astype("int32")
        want = dec.generate(prompt[None, :], max_len=13 + 5)[0]
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"])
        cold = eng.submit(prompt, 5)
        eng.run(timeout=120)
        fed_cold = eng.stats()["prefill_tokens"]
        assert cold.get(timeout=1) == [int(t) for t in want]
        assert cold.prefix_hit_pages == 0
        warm = eng.submit(prompt, 5)
        eng.run(timeout=120)
        fed_warm = eng.stats()["prefill_tokens"] - fed_cold
        # same tokens, but the shared prefill never re-runs: the warm
        # request attaches the cached pages and feeds only the tail
        # (the step count no longer tells them apart: the cold prompt
        # is one prefill-lane step)
        assert warm.get(timeout=1) == [int(t) for t in want]
        assert warm.prefix_hit_pages >= 2
        assert fed_cold == 12 and fed_warm == 0
        st = eng.stats()
        assert st["prefix_hit_pages"] >= 2
        assert st["kv_pages_shared"] >= 0
        _balanced(eng)

    def test_page_straddling_divergence_cow_identity(self):
        """Divergence INSIDE a shared page forces a copy-on-write: the
        matched rows are copied into a private page, the source page
        keeps its other holders, and both outputs stay exact."""
        params = _model()
        dec = _decoder(params)
        rng = np.random.RandomState(9)
        shared = rng.randint(0, 40, (6,)).astype("int32")
        a = np.concatenate([shared, rng.randint(0, 40, (4,))]) \
            .astype("int32")
        b = np.concatenate([shared, rng.randint(0, 40, (4,))]) \
            .astype("int32")
        b[6] = (a[6] + 1) % 40          # diverge mid-page-1, always
        want_a = dec.generate(a[None, :], max_len=len(a) + 6)[0]
        want_b = dec.generate(b[None, :], max_len=len(b) + 6)[0]
        eng = DecodeEngine(dec, num_slots=1, page_size=4,
                           max_seq_len=CFG["max_len"])
        ra = eng.submit(a, 6)
        eng.run(timeout=120)
        rb = eng.submit(b, 6)
        eng.run(timeout=120)
        assert ra.get(timeout=1) == [int(t) for t in want_a]
        assert rb.get(timeout=1) == [int(t) for t in want_b]
        st = eng.stats()
        assert rb.prefix_hit_pages >= 1     # page 0 attached whole
        assert st["prefix_cow_copies"] >= 1  # page 1 copied on write
        _balanced(eng)

    def test_reclaimable_count_is_the_walk_it_replaced(self):
        """``PagePool`` keeps, as refcounts change, how many indexed pages
        only the index holds; an admission reads it where it used to walk
        the whole trie. Under shared-prefix churn, copy-on-write, LRU
        reclaim and a preemption it equals the walk at every step."""
        dec = _decoder(_model())
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=CFG["max_len"], num_pages=12)

        def walked():
            n, stack = 0, [eng.prefix._root]
            while stack:
                nd = stack.pop()
                n += nd.page is not None and \
                    eng.pool.refcount(nd.page) == 1
                stack.extend(nd.children.values())
            return n

        rng = np.random.RandomState(11)
        base = rng.randint(0, 40, (9,)).astype("int32")
        reqs = []
        for i in range(8):
            p = base.copy() if i % 3 else rng.randint(0, 40, (9,)) \
                .astype("int32")
            p[6 + i % 3] = (p[6 + i % 3] + i) % 40
            reqs.append(eng.submit(p, 6 + i))
        seen = set()
        while eng._has_work():
            eng.step()
            assert eng.prefix.reclaimable_pages() == walked()
            seen.add(walked())
        assert len(seen) > 3 and eng.stats()["prefix_evicted_pages"] > 0
        assert all(len(r.get(timeout=1)) == 6 + i
                   for i, r in enumerate(reqs))
        _balanced(eng)
        eng.prefix.flush()
        assert eng.prefix.reclaimable_pages() == 0 == walked()

    def test_prefix_cache_off_frees_everything(self):
        params = _model()
        dec = _decoder(params)
        eng = DecodeEngine(dec, num_slots=1, page_size=4,
                           max_seq_len=16, prefix_cache=False)
        r = eng.submit(np.zeros((5,), "int32"), 4)
        eng.run(timeout=120)
        assert len(r.get(timeout=1)) == 4
        r2 = eng.submit(np.zeros((5,), "int32"), 4)
        eng.run(timeout=120)
        assert len(r2.get(timeout=1)) == 4
        acc = eng.page_accounting()
        assert acc["held_by_trie"] == 0
        assert acc["free"] == acc["total_usable"]
        assert eng.stats()["prefix_hit_pages"] == 0

    @pytest.mark.recompile_budget(max_compiles=12)
    def test_spec_prefix_churn_zero_recompiles(self):
        """Round-9 zero-recompile pin: with the [S, W] verify step, the
        draft step AND the CoW page copy warmed, a storm of
        shared-prefix joins (each walking the radix index and copying
        on write) plus a cancel cause ZERO XLA compilations."""
        from paddle_tpu.analysis.sanitizer import compile_watch
        from paddle_tpu.testing import FaultPlan
        params = _model()
        dec = _decoder(params)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=20, draft=_decoder(_model()),
                           spec_k=2)
        rng = np.random.RandomState(10)
        base = rng.randint(0, 40, (9,)).astype("int32")

        def twin():
            t = np.concatenate([base[:6], rng.randint(0, 40, (3,))]) \
                .astype("int32")
            t[6] = (base[6] + 1 + int(rng.randint(38))) % 40
            return t

        warm = eng.submit(base, 3)
        eng.run(timeout=120)             # target + draft steps compile
        warm2 = eng.submit(twin(), 3)    # CoW warms the page copy
        eng.run(timeout=120)
        assert warm.get(timeout=1) and warm2.get(timeout=1)
        assert eng.stats()["prefix_cow_copies"] >= 1
        joined = []
        r0 = eng.submit(twin(), 8)
        with compile_watch() as watch:
            with FaultPlan.decode_script(eng, {
                    2: lambda: joined.append(eng.submit(twin(), 6)),
                    4: lambda: joined.append(eng.submit(twin(), 6)),
                    6: lambda: joined[0].cancel()}) as script:
                eng.run(timeout=300)
            assert script["fired"] == [2, 4, 6]
        assert watch.total == 0, (
            f"prefix/spec churn recompiled: {watch.per_function}")
        assert len(r0.get(timeout=1)) == 8
        assert joined[0].state in ("cancelled", "done")
        assert len(joined[1].get(timeout=1)) == 6
        _balanced(eng)


LANE_MAX_LEN = 256      # positions enough for prompts of several lanes


def _lane_decoder(**over):
    return _decoder(_model(max_len=LANE_MAX_LEN, **over),
                    n_heads=over.get("n_heads"))


def _lane_prompts(lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG["vocab_size"], (n,)).astype("int32")
            for n in lens]


def _serve(eng, prompts, max_new):
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run(timeout=600)
    return [r.get(timeout=1) for r in reqs]


class TestPrefillLanes:
    """ISSUE 38: a prompt enters the paged step a lane's width of
    tokens at a time beside the decoding slots. Greedy output through
    lanes is token-identical to one-token prefill (the same engine over
    a cache kind that states no lanes) and to ``generate``; the lanes'
    shape is the cache kind's; no slot waits for a lane; the engine's
    lifetime holds exactly the step's two programs."""

    @pytest.fixture
    def no_lanes(self, monkeypatch):
        """(0, 0) is a legal answer of a cache kind: the engine then
        prefills a window a step, as it did before there were lanes."""
        from paddle_tpu.models.block import PerHeadCache

        def off():
            monkeypatch.setattr(PerHeadCache, "lanes", lambda self: (0, 0))
        return off

    def test_the_lanes_shape_is_the_cache_kinds(self):
        """Two heads of 8 make one lane chunk of 2 x 64 query rows, the
        MXU tile: the kind's 64 tokens a step are one lane of 64. At
        rep 2 (GQA) the same tile is 32 tokens, so two lanes. No caller
        chooses either."""
        eng = DecodeEngine(_lane_decoder(), num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN)
        assert eng.paged.lanes == (1, 64)
        st = eng.stats()
        assert (st["prefill_lanes"], st["prefill_lane_width"]) == (1, 64)
        gqa = DecodeEngine(_lane_decoder(n_heads=4, n_kv_heads=2),
                           num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN)
        assert gqa.paged.lanes == (2, 32)

    @pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
    @pytest.mark.parametrize("attention", ["gather", "kernel"])
    def test_lane_prefill_is_token_identical(self, attention, kv_quant,
                                             no_lanes):
        """Prompts of 1 token to three lanes' worth, ending before, on
        and after a page edge (16) and a lane edge (64), more requests
        than slots: lanes == one-token prefill == generate."""
        dec = _lane_decoder()
        prompts = _lane_prompts([150, 5, 64, 65, 63, 1, 2, 15, 16, 17,
                                 200])
        kw = dict(num_slots=3, page_size=16, max_seq_len=LANE_MAX_LEN,
                  attention=attention, kv_quant=kv_quant,
                  prefix_cache=False)   # the counters compare exactly
        eng = DecodeEngine(dec, **kw)
        got = _serve(eng, prompts, 6)
        st = eng.stats()
        assert st["prefill_lane_steps"] > 0
        # most of it: a prompt that finds the one lane taken feeds a
        # token a step in its slot's own window meanwhile
        assert st["prefill_lane_tokens"] > st["prefill_tokens"] // 2
        assert eng.page_accounting()["leaked"] == 0
        no_lanes()
        plain = DecodeEngine(dec, **kw)
        assert plain.paged.lanes == (0, 0)
        assert got == _serve(plain, prompts, 6)
        st1 = plain.stats()
        assert st1["prefill_lane_steps"] == st1["prefill_lane_tokens"] == 0
        assert st1["prefill_tokens"] == st["prefill_tokens"]
        assert st1["cache_tokens_read"] == st["cache_tokens_read"]
        assert st1["tokens_fed"] == st["tokens_fed"] \
            == st1["active_slot_steps"]
        assert st["steps"] < st1["steps"] // 3
        assert got == [[int(t) for t in w]
                       for w in _dense_rows(dec, prompts, [6] * len(prompts))]

    def test_one_slot_over_two_lanes_in_a_step(self):
        """A GQA decoder's two lanes of 32: 80 tokens take both at
        consecutive positions in step 1 (one longer chunk), the remaining
        16 and the first token in step 2."""
        dec = _lane_decoder(n_heads=4, n_kv_heads=2)
        prompt, = _lane_prompts([80], seed=4)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN)
        assert eng.paged.lanes == (2, 32)
        req = eng.submit(prompt, 5)
        assert eng.step()
        st = eng.stats()
        assert st["tokens_fed"] == st["prefill_lane_tokens"] == 64
        assert st["prefill_lane_cache_tokens_read"] == 64 * 65 // 2
        assert req.tokens == []
        assert eng.step()
        assert eng.stats()["tokens_fed"] == 80 and len(req.tokens) == 1
        eng.run(timeout=300)
        assert req.get(timeout=1) == dec.generate(
            prompt[None, :], max_len=85)[0]
        assert eng.stats()["steps"] == 2 + 4
        _balanced(eng)

    def test_more_prompts_than_lanes_and_none_starves(self):
        """Four 70-token prompts at once against one lane of 64: the
        oldest takes it (64, then its last 6), the others feed a token
        each in the slot group, as without lanes, and take the lane in
        turn."""
        dec = _lane_decoder()
        prompts = _lane_prompts([70] * 4, seed=5)
        eng = DecodeEngine(dec, num_slots=4, page_size=4,
                           max_seq_len=LANE_MAX_LEN)
        reqs = [eng.submit(p, 6) for p in prompts]
        fed = []
        for _ in range(6):
            assert eng.step()
            fed.append([sl.pos for sl in eng.slots])
        assert fed == [[64, 1, 1, 1], [70, 2, 2, 2], [71, 66, 3, 3],
                       [72, 70, 4, 4], [73, 71, 68, 5], [74, 72, 70, 6]]
        assert eng.stats()["active_slot_steps"] == 24
        eng.run(timeout=300)
        for r, p in zip(reqs, prompts):
            assert r.get(timeout=1) == dec.generate(
                p[None, :], max_len=76)[0]
        _balanced(eng)

    def test_a_replay_that_starts_mid_page_after_a_cow_attach(self):
        """The second prompt shares 6 tokens with the first: page 0
        attached, 2 rows of page 1 copied on write, so its lane starts at
        position 6, mid-page, and writes on into the copied page."""
        dec = _lane_decoder()
        a, b = _lane_prompts([40, 70], seed=6)
        b[:6] = a[:6]
        b[6] = (a[6] + 1) % CFG["vocab_size"]
        eng = DecodeEngine(dec, num_slots=1, page_size=4,
                           max_seq_len=LANE_MAX_LEN)
        ra = eng.submit(a, 5)
        eng.run(timeout=300)
        lane0 = eng.stats()["prefill_lane_tokens"]
        rb = eng.submit(b, 5)
        eng.run(timeout=300)
        st = eng.stats()
        assert rb.prefix_hit_pages == 1 and st["prefix_cow_copies"] == 1
        assert st["prefill_lane_tokens"] - lane0 == 70 - 6 - 1
        assert ra.get(timeout=1) == dec.generate(a[None, :], max_len=45)[0]
        assert rb.get(timeout=1) == dec.generate(b[None, :], max_len=75)[0]
        _balanced(eng)

    def test_a_preempted_requests_replay_goes_through_lanes(self):
        """A pool too small for both: the younger is preempted mid-decode
        and replays prompt + generated tokens as lane chunks."""
        dec = _lane_decoder()
        p1, p2 = _lane_prompts([21, 22], seed=7)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN, num_pages=15,
                           prefix_cache=False)
        r1, r2 = eng.submit(p1, 14), eng.submit(p2, 14)
        eng.run(timeout=300)
        st = eng.stats()
        assert st["preemptions"] >= 1
        assert r1.get(timeout=1) == dec.generate(p1[None, :], max_len=35)[0]
        assert r2.get(timeout=1) == dec.generate(p2[None, :], max_len=36)[0]
        # the replays' tokens beyond the two prompts' own
        assert st["prefill_lane_tokens"] > 20 + 21
        # all but the token a prompt feeds its slot while the one lane
        # is taken
        assert st["prefill_lane_tokens"] >= st["prefill_tokens"] - 2
        assert eng.page_accounting()["leaked"] == 0

    def test_warmup_resolves_both_step_programs(self):
        """warmup() returns with the plain program AND the lane program
        resolved and dispatched once (all slots inactive, no lane fed:
        the pools read as they did); the first prompt goes through a
        lane at once, and nothing compiles after warmup()."""
        from paddle_tpu.analysis.sanitizer import compile_watch
        dec = _lane_decoder()
        prompts = _lane_prompts([40, 33], seed=12)
        want = [[int(t) for t in w]
                for w in _dense_rows(dec, prompts, [5, 5])]
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN, prefix_cache=False)
        pools = jax.tree_util.tree_map(np.asarray, (eng.k_pool, eng.v_pool))
        eng.warmup()
        assert eng.paged._step_exe is not None
        assert eng.paged._lane_step_exe is not None
        # null page apart, the pools are as they were
        for was, now in zip(jax.tree_util.tree_leaves(pools),
                            jax.tree_util.tree_leaves(
                                (eng.k_pool, eng.v_pool))):
            np.testing.assert_array_equal(was[:, 1:], np.asarray(now)[:, 1:])
        with compile_watch() as served:
            first = eng.submit(prompts[0], 5)
            assert eng.step()
            assert eng.stats()["prefill_lane_steps"] == 1
            second = eng.submit(prompts[1], 5)
            eng.run(timeout=300)
        assert not {k: v for k, v in served.per_function.items()
                    if k.startswith("_step_impl")}
        st = eng.stats()
        # each prompt in one lane step, all of it but its last token
        # (whose row commits the first token)
        assert st["prefill_lane_steps"] == 2
        assert st["prefill_lane_tokens"] == 39 + 32
        assert [first.get(timeout=1), second.get(timeout=1)] == want
        _balanced(eng)

    def test_the_lane_program_traces_one_layer_a_kind(self):
        """The plain program builds its layers one after the other (it is
        held to its text); the lane program builds ONE layer for all
        whose own parameters are alike and calls it with the pool's layer
        index as an operand. Layer 1 of 3 here has a wider FFN (the new
        columns zero: the same function, another kind by its shapes): the
        lane program traces layer 0 for layers 0 and 2, and layer 1."""
        import collections
        params = dict(_model(max_len=LANE_MAX_LEN, n_layers=3))
        pre = next(n for n in params if n.endswith("l1_up.w0"))[:-8]
        for name, axis in (("up.w0", 1), ("up.wbias", 0), ("down.w0", 0)):
            a = np.asarray(params[f"{pre}l1_{name}"])
            params[f"{pre}l1_{name}"] = jax.numpy.asarray(
                np.concatenate([a, np.zeros_like(a)], axis=axis))
        dec = models.TransformerDecoder(params, n_layers=3,
                                        n_heads=CFG["n_heads"])
        prompts = _lane_prompts([70, 9, 41], seed=13)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN, prefix_cache=False,
                           warm_start=False)
        traced, layer = [], eng.paged.cache.layer
        eng.paged.cache.layer = lambda p, i, *a, **kw: (
            traced.append((i, "at" in kw)), layer(p, i, *a, **kw))[1]
        eng.warmup()
        assert collections.Counter(traced) == {
            (0, False): 1, (1, False): 1, (2, False): 1,    # plain
            (0, True): 1, (1, True): 1}                     # lanes
        assert _serve(eng, prompts, 6) == [
            [int(t) for t in w] for w in _dense_rows(dec, prompts, [6] * 3)]
        assert eng.stats()["prefill_lane_steps"] >= 3
        assert len(traced) == 5
        _balanced(eng)

    def test_a_draft_catches_up_behind_a_lane_fed_target(self):
        """Speculation: the target's prompt goes in one lane step, the
        draft teacher-forces it window by window afterwards; output
        stays token-identical and proposals are accepted."""
        dec = _lane_decoder()
        prompts = _lane_prompts([90, 7, 33], seed=8)
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=LANE_MAX_LEN,
                           draft=_lane_decoder(), spec_k=2)
        assert eng.window == 3 and eng.paged.lanes == (1, 64)
        got = _serve(eng, prompts, 12)
        assert got == [[int(t) for t in w]
                       for w in _dense_rows(dec, prompts, [12] * 3)]
        st = eng.stats()
        assert st["prefill_lane_steps"] >= 3
        assert st["spec_accepted_tokens"] > 0
        _balanced(eng)

    @pytest.mark.recompile_budget(max_compiles=10)
    def test_an_engines_lifetime_holds_exactly_the_two_step_programs(self):
        """warmup() resolves the plain program and the lane program (an
        engine built with ``warm_start=False``, as here, so that no
        other test's executables can touch the count); a storm of long
        and short joins, a cancel mid-prompt and a pool-pressure
        preemption afterwards compile nothing."""
        from paddle_tpu.analysis.sanitizer import compile_watch
        from paddle_tpu.testing import FaultPlan
        dec = _lane_decoder()
        long_a, long_b, short = _lane_prompts([140, 100, 5], seed=9)
        with compile_watch() as life:
            eng = DecodeEngine(dec, num_slots=2, page_size=4,
                               max_seq_len=LANE_MAX_LEN, num_pages=44,
                               warm_start=False, prefix_cache=False)
            eng.warmup()
            steps = lambda w: {k: v for k, v in w.per_function.items()
                               if k.startswith("_step_impl")}
            both = {"_step_impl": 1, "_step_impl_lanes": 1}
            assert steps(life) == both
            assert _serve(eng, [long_b[:70]], 1)
            assert steps(life) == both
            r0 = eng.submit(short, 40)
            joined = []
            with compile_watch() as churn:
                with FaultPlan.decode_script(eng, {
                        1: lambda: joined.append(eng.submit(long_a, 6)),
                        2: lambda: joined[0].cancel(),
                        3: lambda: joined.append(eng.submit(long_b, 40)),
                        5: lambda: joined.append(eng.submit(short, 4)),
                        }) as script:
                    eng.run(timeout=600)
                assert script["fired"] == [1, 2, 3, 5]
            assert churn.total == 0, churn.per_function
        assert steps(life) == both, life.per_function
        assert joined[0].state == "cancelled" and joined[0].tokens == []
        assert r0.get(timeout=1) == dec.generate(
            short[None, :], max_len=45)[0]
        assert joined[1].get(timeout=1) == dec.generate(
            long_b[None, :], max_len=140)[0]
        st = eng.stats()
        assert st["preemptions"] >= 1 and st["prefill_lane_steps"] >= 3
        assert eng.page_accounting()["leaked"] == 0


class TestBenchSmoke:
    """The CPU smoke slice of the decode_continuous_* bench rows: the
    same driver code bench.py runs on TPU, at toy shape, so a harness
    regression (row stops producing tokens / latency fields vanish)
    surfaces in tier-1 rather than in the next driver capture."""

    def test_unknown_device_has_no_assumed_peak(self):
        import bench
        with pytest.raises(RuntimeError, match="no HBM peak known"):
            bench._known_hbm_gbps(jax.devices()[0])

    def test_decode_continuous_row_smoke(self, monkeypatch):
        import bench
        # the CPU is in no peak table: the row's roofline arithmetic
        # is driven against a stand-in peak, its value asserted nowhere
        monkeypatch.setattr(bench, "_device_hbm_gbps", lambda dev: 819.0)
        row = bench.bench_decode_continuous(
            num_slots=4, n_requests=6, page_size=4, d_model=16,
            n_layers=2, n_heads=2, vocab_size=40, max_len=32,
            prompt_lens=(3, 8), new_tokens=(4, 10), seed=0)
        assert row["new_tokens"] == row["tokens_out"] > 0
        assert row["tokens_per_sec"] > 0
        assert row["ms"] > 0                     # per-token p50
        assert row["p99_ms"] >= row["ms"]
        assert 0 < row["slot_utilization"] <= 1
        assert row["kv_page_high_water"] > 0
        assert row["preemptions"] == 0
        assert row["roofline_frac"] > 0
