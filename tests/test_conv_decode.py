"""The block of gated short convolutions beside per-head pages
(models/block.py ShortConvBlock, StatePerHeadCache) through the decoders
and the engine, at a small size on the CPU, against the plain float32
reference (benchmarks/reference/lfm2_moe.py): the dense-cache path, the
paged step's jnp path and its kernels in interpret mode; prompts through
prefill lanes; the convolution over any tap count with and without SiLU; a
slot's tails from zero or from a snapshot; prefix reuse by snapshot;
preemption; the ranks' shares of an expert layer; zero recompiles; what
the kind refuses.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import manifest
from paddle_tpu import models
from paddle_tpu.models.block import (PerHeadCache, ShortConvBlock,
                                     StatePerHeadCache)
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import pallas_kda as kda_ops
from paddle_tpu.serving import DecodeEngine

REF = manifest.load_module("reference", "lfm2_moe")
MODEL = manifest.load_module("models", "lfm2_moe")
CFG = MODEL.tiny()
SEED = 7
V = CFG["vocab_size"]
L = CFG["num_hidden_layers"]


@pytest.fixture(scope="module")
def ref_params():
    return jax.jit(lambda lo, hi: REF.init_params((lo, hi), CFG))(
        *REF.seed_words(SEED))


@pytest.fixture(scope="module")
def decoder():
    named = MODEL.make_weights(REF, SEED, CFG, jnp.float32)
    return models.TransformerDecoder(
        named, n_layers=L, n_heads=CFG["num_attention_heads"],
        name=MODEL.NAME, block=MODEL.block_of(CFG, 64))


def _engine(decoder, attention="gather", **kw):
    kw = {"num_slots": 3, "page_size": 4, "max_seq_len": 64,
          "state_snapshots": 4, **kw}
    return DecodeEngine(decoder, attention=attention, **kw)


def _gaps(ref_params, prompt, served):
    """How far each served token's reference logit lies under the best."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    logits = np.asarray(jax.jit(lambda p, s: REF.forward(p, s, CFG))(
        ref_params, jnp.asarray(seq)))
    rows = logits[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(served)), served]


def _served(eng, prompts, n=6):
    reqs = [eng.submit(p, n) for p in prompts]
    eng.run()
    return [r.tokens for r in reqs]


# ---------------------------------------------------------------- the block
def test_the_weights_are_the_references_leaf_by_leaf(ref_params, decoder):
    assert len(decoder.p) == len(ref_params)
    for leaf, want in ref_params.items():
        got = decoder.p[MODEL.program_name(leaf)]
        assert np.array_equal(np.asarray(got), np.asarray(want)), leaf
    blk = decoder.block
    assert isinstance(blk, ShortConvBlock)
    assert blk.conv_layers == (0, 1, 3, 4, 5, 7) == blk.state_layers
    assert blk.heads(decoder.p, decoder._pre) == (4, 2, 32)
    assert blk.state_width(decoder.p, decoder._pre) == (3, 128)
    assert blk.n_expert_layers(L) == 6 and blk.cache is StatePerHeadCache


def test_dense_cache_logits_agree_with_one_full_forward_pass(ref_params,
                                                             decoder):
    """Prefill 9 tokens, then decode 7 one at a time through the dense
    caches (a conv layer's is its tail): every position's logits against
    the reference's one pass. 2e-4 of logits of size 8: float32 rounding
    through eight layers; bfloat16 anywhere reads 1e-2."""
    rng = np.random.default_rng(1)
    seq = rng.integers(0, V, 16).astype(np.int32)
    want = np.asarray(REF.forward(ref_params, jnp.asarray(seq), CFG))
    p = decoder.p
    logits, caches = decoder._prefill(p, jnp.asarray(seq[None, :9]), 9, 24)
    assert [len(c) for c in caches] == [1, 1, 2, 1] * 2
    assert caches[0][0].shape == (1, 2, 128)             # a tail
    assert caches[2][0].shape == (1, 24, 2, 32)          # K rows
    got = [np.asarray(logits[0])]
    for t in range(9, 16):
        lg, caches = decoder._forward(
            p, jnp.asarray(seq[None, t:t + 1]),
            jnp.full((1, 1), t, jnp.int32), caches, t, t + 1)
        got.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-4)


def test_beams_reorder_the_tails_with_the_other_caches(decoder):
    prompt = np.arange(5, dtype=np.int32)[None]
    greedy = decoder.generate(prompt, max_len=11)[0]
    beams = decoder.beam_search(prompt, max_len=11, beam_size=3, eos_id=V)
    assert decoder.beam_search(prompt, max_len=11, beam_size=1,
                               eos_id=V)[0][0][1] == greedy
    assert len(beams[0]) == 3 and beams[0][0][0] >= beams[0][1][0]


# ---------------------------------------------------------------- the kernel
@pytest.mark.parametrize("taps,silu", [(3, False), (2, True), (5, False)],
                         ids=["3-plain", "2-silu", "5-plain"])
@pytest.mark.parametrize("C", [1, 4])
def test_conv_kernel_takes_any_tap_count_with_or_without_silu(C, taps, silu):
    """``short_conv`` interpreted against its jnp path and against the
    plain causal convolution of a whole chunk: rows from the pool, a row
    that goes on from the row before it, a row fed nothing (its tail
    untouched), a partly fed row; 256 channels."""
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    back = taps - 1
    tails, x, w = f(2, 7, back * 2, 128), f(5, C, 256), f(taps, 256)
    rows = jnp.asarray([2, 2, 0, 4, 5])
    first = jnp.asarray([1, 0, 1, 1, 1], bool)
    fed = jnp.minimum(jnp.asarray([4, 2, 0, 1, 3]), C)
    kw = dict(layer=1, junk_row=6, silu=silu)
    y1, t1 = jax.jit(lambda *a: kda_ops.short_conv(*a, **kw))(
        tails, x, w, rows, first, fed)
    y2, t2 = jax.jit(lambda *a: kda_ops.short_conv(
        *a, use_kernel=True, interpret=True, **kw))(
        tails, x, w, rows, first, fed)
    valid = np.arange(C)[None] < np.asarray(fed)[:, None]
    np.testing.assert_allclose(np.asarray(y2)[valid], np.asarray(y1)[valid],
                               atol=5e-6)
    keep = [0, 1, 2, 3, 4, 5]
    np.testing.assert_allclose(np.asarray(t2)[:, keep],
                               np.asarray(t1)[:, keep], atol=5e-6)
    for t in (t1, t2):
        assert np.array_equal(np.asarray(t[0]), np.asarray(tails[0]))
        assert np.array_equal(np.asarray(t[1, [0, 1, 3]]),
                              np.asarray(tails[1, [0, 1, 3]]))
    n = int(fed[0] + fed[1])
    seq = jnp.concatenate([tails[1, 2].reshape(back, 256), x[0],
                           x[1]])[:back + n]
    want = sum(w[j] * seq[j:j + n] for j in range(taps))
    want = jax.nn.silu(want) if silu else want
    got = jnp.concatenate([y1[0], y1[1]])[:n]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    np.testing.assert_allclose(np.asarray(t1[1, 2]).reshape(back, 256),
                               np.asarray(seq[n:n + back]), atol=5e-6)


def test_a_lane_of_c_tokens_is_c_one_token_steps():
    """One row fed 5 tokens at once against the same 5 fed one a step,
    each step starting from the tail the last left: outputs and the final
    tail agree (kernel interpreted, 3 taps, no activation)."""
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tails, x, w = f(1, 3, 2, 128), f(1, 5, 128), f(3, 128)
    kw = dict(layer=0, junk_row=2, silu=False, use_kernel=True,
              interpret=True)
    row, one = jnp.asarray([1]), jnp.asarray([1])
    y, t = kda_ops.short_conv(tails, x, w, row, None, jnp.asarray([5]), **kw)
    step_t, ys = tails, []
    for c in range(5):
        yc, step_t = kda_ops.short_conv(step_t, x[:, c:c + 1], w, row, None,
                                        one, **kw)
        ys.append(yc)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)),
                               np.asarray(y), atol=1e-6)
    np.testing.assert_allclose(np.asarray(step_t[0, 1]), np.asarray(t[0, 1]),
                               atol=1e-6)
    assert np.array_equal(np.asarray(t[0, 0]), np.asarray(tails[0, 0]))


def test_a_float32_query_over_bfloat16_pages_goes_as_two_terms():
    """The window kernel (interpreted) with q float32 over bfloat16 pools,
    GQA 4 on 2: against the gather path's float32 einsum over the same
    stored pages. 2e-5: the query keeps 16 bits, the probabilities too;
    q rounded to bfloat16 reads 3e-3."""
    from paddle_tpu.ops import pallas_decode as paged_ops
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    S, W, h, g, dh, ps, N, P = 3, 2, 4, 2, 64, 8, 13, 4
    q = f(S, W, h, dh)
    k = f(1, N, ps, g * dh).astype(jnp.bfloat16)
    v = f(1, N, ps, g * dh).astype(jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(N - 1)[:S * P].reshape(S, P) + 1,
                         jnp.int32)
    lens = jnp.asarray([[5, 6], [30, 31], [0, 0]], jnp.int32)
    kw = dict(layer=0)
    want = paged_ops.paged_window_attention(q, k, v, tables, lens, **kw)
    got = paged_ops.paged_window_attention(q, k, v, tables, lens,
                                           use_kernel=True, interpret=True,
                                           **kw)
    assert got.dtype == jnp.float32
    live = np.asarray(lens) > 0
    err = np.abs(np.asarray(got) - np.asarray(want))[live].max()
    assert err < 2e-5, err
    rounded = paged_ops.paged_window_attention(
        q.astype(jnp.bfloat16).astype(jnp.float32), k, v, tables, lens, **kw)
    assert np.abs(np.asarray(rounded) - np.asarray(want))[live].max() > 1e-3


# ---------------------------------------------------------------- the engine
@pytest.mark.parametrize("attention", ["gather", "kernel"])
def test_paged_step_serves_what_the_reference_puts_first(
        ref_params, decoder, attention):
    """Ragged slots that join and leave, prompts that end inside, at and
    past a page boundary, through lanes, the tails and the K/V pages: each
    served token is the dense path's and the reference's first choice."""
    eng = _engine(decoder, attention)
    assert eng.paged.use_kernel == (attention == "kernel")
    assert eng.paged.cache.state_kernel
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, V, n).astype(np.int32)
               for n in (3, 4, 5, 9, 13, 40, 33)]
    for prompt, toks in zip(prompts, _served(eng, prompts, 9)):
        served = np.asarray(toks, np.int32)
        assert toks == decoder.generate(prompt[None],
                                        max_len=len(prompt) + 9)[0]
        assert _gaps(ref_params, prompt, served).max() < 1e-4
    st = eng.stats()
    acc = eng.page_accounting()
    assert acc["leaked"] == 0 and acc["snapshot_rows_total"] == 4
    assert st["state_rows_stepped"] == st["active_slot_steps"]
    assert st["tokens_fed"] > st["active_slot_steps"]
    assert st["expert_layer_steps"] == 6 * st["steps"]
    # K and V hold the two attention layers, the state pool the six conv
    assert set(eng.k_pool) == {"k", "v"} and set(eng.v_pool) == {"conv"}
    assert eng.k_pool["k"].shape == eng.k_pool["v"].shape
    assert eng.k_pool["k"].shape[0] == 2 and eng.k_pool["k"].shape[-1] == 64
    assert eng.v_pool["conv"].shape == (6, 3 + 4 + 2, 2, 128)
    kind = eng.paged.cache
    assert not np.asarray(eng.v_pool["conv"][:, kind.zero_row]).any()


@pytest.mark.parametrize("lanes", [(1, 32), (4, 4), (3, 1)],
                         ids=["1x32", "4x4", "3x1"])
def test_prompts_through_lanes_then_decode_through_the_tails(
        ref_params, decoder, lanes, monkeypatch):
    """One lane of 32; four lanes of 4, which puts a slot's chunk over
    several lanes of a step and two slots' chunks in one step; three
    lanes of 1, narrower than the convolution's reach. Served tokens are
    the dense path's and what the same engine serves with no lanes."""
    monkeypatch.setattr(StatePerHeadCache, "lanes", lambda self: lanes)
    eng = _engine(decoder)
    assert eng.paged.lanes == lanes
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, V, n).astype(np.int32)
               for n in (40, 3, 31, 32, 33, 8, 9, 1)]
    got = _served(eng, prompts, 7)
    assert eng.stats()["prefill_lane_steps"] >= 4
    assert eng.page_accounting()["leaked"] == 0
    monkeypatch.setattr(StatePerHeadCache, "lanes", lambda self: (0, 0))
    plain = _engine(decoder)
    assert _served(plain, prompts, 7) == got
    assert plain.stats()["prefill_lane_steps"] == 0
    for prompt, toks in zip(prompts, got):
        assert toks == decoder.generate(prompt[None],
                                        max_len=len(prompt) + 7)[0]
        assert _gaps(ref_params, prompt,
                     np.asarray(toks, np.int32)).max() < 1e-4


def test_the_kinds_own_lanes_fill_the_kernels_query_tile(decoder):
    """At the published heads a lane is ``window_tile_tokens(32, 8, 64)``
    = 16 tokens and the lanes take ``LANE_TOKENS`` a step."""
    from paddle_tpu.ops import pallas_decode as paged_ops
    assert paged_ops.window_tile_tokens(32, 8, 64) == 16
    n, width = _engine(decoder).paged.lanes
    assert n * width == StatePerHeadCache.LANE_TOKENS
    assert StatePerHeadCache.LANE_TOKENS != PerHeadCache.LANE_TOKENS


def test_a_slot_reused_by_a_second_request_starts_from_zero(decoder):
    rng = np.random.default_rng(4)
    a, b = (rng.integers(0, V, n).astype(np.int32) for n in (11, 7))
    eng = _engine(decoder, num_slots=1, prefix_cache=False)
    first, second = _served(eng, [a], 5)[0], _served(eng, [b], 5)[0]
    fresh = _served(_engine(decoder, num_slots=1, prefix_cache=False),
                    [b], 5)[0]
    assert second == fresh != first
    assert second == decoder.generate(b[None], max_len=12)[0]
    assert eng.stats()["state_snapshots_taken"] == 0


def test_a_snapshot_attach_equals_feeding_the_prefix_again(ref_params,
                                                           decoder):
    """A history of 16 tokens (4 pages) leaves its pages and a snapshot of
    the tails at 16. A turn on it attaches 16 tokens by snapshot and
    serves a cold engine's tokens; with the snapshot taken away the pages
    still match and all are fed again, to the same tokens."""
    rng = np.random.default_rng(5)
    hist = rng.integers(0, V, 16).astype(np.int32)
    turn = np.concatenate([hist, rng.integers(0, V, 9).astype(np.int32)])
    cold = _served(_engine(decoder, prefix_cache=False), [turn], 6)[0]
    eng = _engine(decoder)
    _served(eng, [hist], 1)
    st = eng.stats()
    assert (st["state_snapshots_taken"], st["snapshot_attach_tokens"],
            st["snapshot_miss_tokens"]) == (1, 0, 0)
    req = eng.submit(turn, 6)
    eng.run()
    st1 = eng.stats()
    assert req.tokens == cold and req.prefix_hit_pages == 4
    assert st1["snapshot_attach_tokens"] == 16
    assert st1["snapshot_miss_tokens"] == 0
    assert _gaps(ref_params, turn, np.asarray(cold, np.int32)).max() < 1e-4
    taken = [eng.prefix.take_snapshot_row() for _ in range(4)]
    assert None not in taken
    for row in taken:
        eng.prefix.free_snapshot_row(row)
    st3 = eng.stats()
    third = eng.submit(turn, 6)
    eng.run()
    st4 = eng.stats()
    assert third.tokens == cold and third.prefix_hit_pages == 0
    assert st4["snapshot_attach_tokens"] == st3["snapshot_attach_tokens"]
    assert st4["snapshot_miss_tokens"] - st3["snapshot_miss_tokens"] == 24
    acc = eng.page_accounting()
    assert acc["leaked"] == 0
    assert acc["snapshot_rows_free"] + acc["snapshot_rows_held"] == 4


def test_a_preempted_request_resumes_by_snapshot_to_the_same_tokens(decoder):
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, V, n).astype(np.int32) for n in (12, 13, 14)]
    want = _served(_engine(decoder), prompts, 14)
    eng = _engine(decoder, num_pages=15, max_seq_len=32)
    got = _served(eng, prompts, 14)
    assert eng.stats()["preemptions"] >= 1 and got == want
    assert eng.page_accounting()["leaked"] == 0
    # on a boundary: 6 prompt tokens + 6 generated and fed = position 12
    eng = _engine(decoder, num_slots=1)
    req = eng.submit(prompts[0][:6], 12)
    while eng.slots[0] is None or eng.slots[0].pos < 12:
        eng.step()
    eng._evict(0)
    assert eng.stats()["state_snapshots_taken"] == 2     # at 4, and at 12
    eng.run()
    st = eng.stats()
    assert st["preemptions"] == 1 and st["snapshot_attach_tokens"] == 12
    assert req.tokens == decoder.generate(prompts[0][None, :6],
                                          max_len=18)[0]


def test_snapshot_rows_never_leak_under_churn(decoder):
    rng = np.random.default_rng(7)
    eng = _engine(decoder, num_pages=24, state_snapshots=3)
    base = rng.integers(0, V, 12).astype(np.int32)
    for wave in range(4):
        reqs = [eng.submit(np.concatenate(
            [base[:4 * (1 + (i + wave) % 3)],
             rng.integers(0, V, 1 + i).astype(np.int32)]), 3 + i)
            for i in range(5)]
        eng.step()
        eng.step()
        reqs[wave].cancel()
        eng.run()
        acc = eng.page_accounting()
        assert acc["leaked"] == 0, acc
        assert acc["snapshot_rows_free"] + acc["snapshot_rows_held"] == 3
        assert acc["refs_total"] == acc["held_by_slots"] + acc["held_by_trie"]
    st = eng.stats()
    assert st["state_snapshots_taken"] > 3 and st["state_snapshots_evicted"]
    eng.prefix.flush()
    acc = eng.page_accounting()
    assert (acc["snapshot_rows_free"], acc["leaked"]) == (3, 0)


@pytest.mark.recompile_budget(max_compiles=60)
def test_step_churn_causes_zero_recompiles(decoder):
    from paddle_tpu.analysis.sanitizer import compile_watch
    eng = _engine(decoder, num_slots=2)
    eng.warmup()
    eng.k_pool, eng.v_pool = eng.paged.copy_page(eng.k_pool, eng.v_pool, 0, 0)
    rng = np.random.default_rng(10)
    base = rng.integers(0, V, 40).astype(np.int32)
    with compile_watch() as watch:
        _served(eng, [base[:8]], 1)
        reqs = [eng.submit(np.concatenate([base[:n], base[:2]]), 5)
                for n in (3, 8, 9, 40, 12)]
        eng.run()
    assert all(len(r.tokens) == 5 for r in reqs)
    assert watch.total == 0, watch.events
    st = eng.stats()
    assert st["snapshot_attach_tokens"] >= 8 and st["prefill_lane_steps"]
    assert eng.page_accounting()["leaked"] == 0


def test_the_engine_thread_keeps_a_step_in_flight_over_this_kind(decoder):
    """``start()``: the loop launches ahead and serves ``run()``'s
    tokens."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, V, n).astype(np.int32) for n in (5, 17, 9)]
    want = _served(_engine(decoder), prompts, 8)
    eng = _engine(decoder)
    eng.start()
    try:
        got = [r.get(timeout=300) and r.tokens
               for r in [eng.submit(p, 8) for p in prompts]]
    finally:
        eng.shutdown(drain=False, timeout=60.0)
    assert got == want
    assert eng.stats()["steps_launched_ahead"] > 0


def test_what_tails_beside_the_pages_refuse(decoder):
    for kw, word in ((dict(kv_quant="int8"), "kv_quant"),
                     (dict(draft=decoder, spec_k=2), "speculative"),
                     (dict(kv_spill_pages=4), "kv_spill_pages")):
        with pytest.raises(ValueError, match=word):
            _engine(decoder, **kw)
    from paddle_tpu.models.decode import DraftDecoder
    with pytest.raises(ValueError, match="draft"):
        DraftDecoder(decoder, num_slots=2, max_seq_len=16)
    assert set(StatePerHeadCache.refuses) == {"kv_quant", "draft",
                                              "speculation", "spill"}


# ------------------------------------------------------------ expert shares
def test_the_ranks_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        ref_params):
    """Layer 2 of the tiny model: 8 routed experts, top-2, 4 ranks of 2.
    The four ranks' parts (program: ``routed_experts_ffn`` told its rank;
    reference: ``expert_ffn`` given that rank's slice) add up to the layer
    with all 8 experts held by one chip. There is no shared expert to
    count once. And the normaliser's epsilon is the family's 1e-6."""
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(11, CFG["hidden_size"])), jnp.float32)
    w = REF._layer_params(ref_params, 2)
    full = {k: jnp.asarray(rng.normal(size=(8,) + v.shape[1:]) * 0.2,
                           jnp.float32) for k, v in w.items()
            if k.startswith("e_")}
    one_chip = dict(CFG, num_experts=8, ep_ranks=1, ep_rank=0)
    want = REF.expert_ffn(h, dict(w, **full), REF.sizes(one_chip),
                          lambda a: a)
    prog, ref = 0.0, 0.0
    for rank in range(4):
        mine = {k: v[2 * rank:2 * rank + 2] for k, v in full.items()}
        y, load = moe_ops.routed_experts_ffn(
            h, w["router"], w["router_bias"],
            (mine["e_gate"], mine["e_up"], mine["e_down"]), k=2, scale=1.0,
            rank=rank, eps=1e-6)
        prog = prog + y
        ref = ref + REF.expert_ffn(h, dict(w, **mine), REF.sizes(
            dict(CFG, ep_rank=rank)), lambda a: a)
        assert load.shape == (2,)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want), atol=2e-5)
    idx, wts = moe_ops.sigmoid_topk_route(
        h, w["router"], w["router_bias"], k=2, scale=1.0, eps=1e-6)
    s = jax.nn.sigmoid(h @ w["router"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(wts), np.asarray(picked / (picked.sum(-1, keepdims=True)
                                              + 1e-6)), atol=1e-6)
