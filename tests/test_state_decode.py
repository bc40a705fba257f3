"""The block with a recurrent state beside its latent pages
(models/block.py DeltaLatentBlock, StateLatentCache) through the decoders
and the engine, at a small size on the CPU, against the plain float32
reference (benchmarks/reference/kimi_linear.py): the dense-cache path, the
paged step's jnp path and its kernels in interpret mode; prompts through
prefill lanes; a slot's row from zero or from a snapshot, never from its
last tenant; prefix reuse by snapshot, cut back and re-fed where there is
none; preemption; snapshot rows under churn; zero recompiles; what the
kind refuses.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import manifest
from paddle_tpu import models
from paddle_tpu.models.block import DeltaLatentBlock, StateLatentCache
from paddle_tpu.ops import pallas_kda as kda_ops
from paddle_tpu.serving import DecodeEngine

REF = manifest.load_module("reference", "kimi_linear")
MODEL = manifest.load_module("models", "kimi_linear")
CFG = MODEL.tiny()
SEED = 7
V = CFG["vocab_size"]


@pytest.fixture(scope="module")
def ref_params():
    return jax.jit(lambda lo, hi: REF.init_params((lo, hi), CFG))(
        *REF.seed_words(SEED))


@pytest.fixture(scope="module")
def decoder():
    named = MODEL.make_weights(REF, SEED, CFG, jnp.float32)
    return models.TransformerDecoder(
        named, n_layers=CFG["num_hidden_layers"],
        n_heads=CFG["num_attention_heads"], name=MODEL.NAME,
        block=MODEL.block_of(CFG, 64))


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
        got = decoder.p[MODEL.program_name(leaf, CFG)]
        assert np.array_equal(np.asarray(got), np.asarray(want)), leaf
    blk = decoder.block
    assert isinstance(blk, DeltaLatentBlock) and not blk.rotary
    assert blk.state_layers == (0, 1, 2) and blk.latent0 == 3
    assert blk.state_sizes(decoder.p, decoder._pre) == (2, 128, 128)


def test_dense_cache_logits_agree_with_one_full_forward_pass(ref_params,
                                                             decoder):
    """Prefill 9 tokens, then decode 7 one at a time through the dense
    caches (a KDA layer's is its state and conv tail): every position's
    logits against the reference's one pass. 2e-4 of logits of size 6:
    float32 rounding through four layers; bfloat16 anywhere reads 1e-2."""
    rng = np.random.default_rng(1)
    seq = rng.integers(0, V, 16).astype(np.int32)
    want = np.asarray(REF.forward(ref_params, jnp.asarray(seq), CFG))
    p = decoder.p
    logits, caches = decoder._prefill(p, jnp.asarray(seq[None, :9]), 9, 24)
    assert [len(c) for c in caches] == [2] * 4
    assert caches[0][0].shape == (1, 2, 128, 128)        # a state
    assert caches[3][0].shape == (1, 24, 16)             # latent rows
    got = [np.asarray(logits[0])]
    for t in range(9, 16):
        lg, caches = decoder._forward(p, jnp.asarray(seq[None, t:t + 1]),
                                      None, caches, t, t + 1)
        got.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-4)


def test_beams_reorder_the_state_with_the_other_caches(decoder):
    """beam_search's best beam at width 1 is greedy generate: the state
    follows its beam like every cache."""
    prompt = np.arange(5, dtype=np.int32)[None]
    greedy = decoder.generate(prompt, max_len=11)[0]
    beams = decoder.beam_search(prompt, max_len=11, beam_size=3, eos_id=V)
    assert decoder.beam_search(prompt, max_len=11, beam_size=1,
                               eos_id=V)[0][0][1] == greedy
    assert len(beams[0]) == 3 and beams[0][0][0] >= beams[0][1][0]


# ---------------------------------------------------------------- the kernel
def _state_case(rng, B, C, H=2, d=128, L=2, R=7):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (f(L, R, H, d, d) * 0.1, unit(f(B, C, H, d)) * d ** -0.5,
            unit(f(B, C, H, d)), f(B, C, H, d),
            -jnp.abs(f(B, C, H, d)) * 0.1, jax.nn.sigmoid(f(B, C, H)))


@pytest.mark.parametrize("C", [1, 3])
def test_state_kernel_agrees_with_the_jnp_path(C):
    """Interpreted, against the scan: rows that start from the pool, a row
    that goes on from the row before it (a later lane of one chunk), a
    row fed nothing (its pool row untouched), partly fed rows. Both are
    float32 sums in another order: 1e-6."""
    rng = np.random.default_rng(0)
    pool, *tok = _state_case(rng, 5, C)
    rows = jnp.asarray([2, 2, 0, 4, 5])
    first = jnp.asarray([1, 0, 1, 1, 1], bool)
    fed = jnp.minimum(jnp.asarray([3, 2, 0, 1, 3]), C)
    fed = fed.at[0].set(C)              # a row that is gone on from is full
    kw = dict(layer=1, junk_row=6)
    o1, p1 = jax.jit(lambda *a: kda_ops.kda_state_update(*a, **kw))(
        pool, *tok, rows, first, fed)
    o2, p2 = jax.jit(lambda *a: kda_ops.kda_state_update(
        *a, use_kernel=True, interpret=True, **kw))(
        pool, *tok, rows, first, fed)
    valid = np.arange(C)[None] < np.asarray(fed)[:, None]
    np.testing.assert_allclose(np.asarray(o2)[valid], np.asarray(o1)[valid],
                               atol=1e-6)
    keep = [0, 1, 2, 3, 4, 5]           # row 6 holds nothing
    np.testing.assert_allclose(np.asarray(p2)[:, keep],
                               np.asarray(p1)[:, keep], atol=1e-6)
    for p in (p1, p2):                  # what was not fed was not written
        assert np.array_equal(np.asarray(p[0]), np.asarray(pool[0]))
        assert np.array_equal(np.asarray(p[1, [0, 1, 3]]),
                              np.asarray(pool[1, [0, 1, 3]]))
    # rows 0 and 1 are one chunk of row 2's state: the plain recurrence
    n = int(fed[0] + fed[1])
    chain = [jnp.concatenate([a[0], a[1]])[None, :n] for a in tok]
    o3, S3 = kda_ops.recurrent_kda(pool[1, 2][None], *chain)
    np.testing.assert_allclose(np.asarray(p1[1, 2]), np.asarray(S3[0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(o1[1, :int(fed[1])]),
                               np.asarray(o3[0, C:n]), atol=1e-6)


def test_recurrence_is_the_references_token_by_token():
    """ops/pallas_kda.recurrent_kda against the reference's own scan body
    written out with numpy."""
    rng = np.random.default_rng(3)
    pool, q, k, v, g, beta = _state_case(rng, 1, 5, H=1, d=128)
    o, S_got = kda_ops.recurrent_kda(jnp.zeros((1, 1, 128, 128)), q, k, v,
                                     g, beta)
    S = np.zeros((128, 128), np.float64)                     # [k, v]
    for t in range(5):
        qt, kt, vt, gt = (np.asarray(a[0, t, 0], np.float64)
                          for a in (q, k, v, g))
        S = np.exp(gt)[:, None] * S
        u = float(beta[0, t, 0]) * (vt - S.T @ kt)
        S = S + np.outer(kt, u)
        np.testing.assert_allclose(np.asarray(o[0, t, 0]), S.T @ qt,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(S_got[0, 0]), S, atol=1e-6)


def test_conv_windows_follow_a_chunk_over_lanes():
    """Three lanes of 4: lanes 0 and 1 are one slot's chunk of 6 tokens
    (4 + 2), lane 2 another slot's 3. Each token's window is the three
    inputs before it, from its chunk or from the slot's tail; the new
    tail is the chunk's last three inputs (or older ones moved up)."""
    x = jnp.arange(1, 13, dtype=jnp.float32).reshape(3, 4, 1)
    tail = -jnp.arange(1, 10, dtype=jnp.float32).reshape(3, 3, 1)
    tail = tail.at[1].set(tail[0])           # lanes 0, 1: the same slot's
    fed = jnp.asarray([4, 2, 3])
    cont = jnp.asarray([False, True, False])
    win, new = kda_ops.conv_windows(x, tail, fed, cont)
    w = np.asarray(win)[..., 0]
    assert w[0, 0].tolist() == [-1, -2, -3, 1]
    assert w[0, 3].tolist() == [1, 2, 3, 4]
    assert w[1, 0].tolist() == [2, 3, 4, 5]          # over the lane's edge
    assert w[1, 1].tolist() == [3, 4, 5, 6]
    assert w[2, 0].tolist() == [-7, -8, -9, 9]
    assert w[2, 2].tolist() == [-9, 9, 10, 11]
    n = np.asarray(new)[..., 0]
    assert n[1].tolist() == [4, 5, 6]                # the chunk's last lane
    assert n[2].tolist() == [9, 10, 11]
    # a chunk of one token moves the tail up by one
    _, one = kda_ops.conv_windows(x[:1], tail[:1], jnp.asarray([1]),
                                  cont[:1])
    assert np.asarray(one)[0, :, 0].tolist() == [-2, -3, 1]


@pytest.mark.parametrize("C", [1, 4])
def test_conv_kernel_agrees_with_the_jnp_path(C):
    """``kda_short_conv`` interpreted against its jnp path: rows from the
    pool, a row that goes on from the row before it, a row fed nothing
    (its tail untouched), a partly fed row; 256 channels."""
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tails, x, w = f(2, 7, 6, 128), f(5, C, 256), f(4, 256)
    rows = jnp.asarray([2, 2, 0, 4, 5])
    first = jnp.asarray([1, 0, 1, 1, 1], bool)
    fed = jnp.minimum(jnp.asarray([4, 2, 0, 1, 3]), C)
    kw = dict(layer=1, junk_row=6)
    y1, t1 = jax.jit(lambda *a: kda_ops.kda_short_conv(*a, **kw))(
        tails, x, w, rows, first, fed)
    y2, t2 = jax.jit(lambda *a: kda_ops.kda_short_conv(
        *a, use_kernel=True, interpret=True, **kw))(
        tails, x, w, rows, first, fed)
    valid = np.arange(C)[None] < np.asarray(fed)[:, None]
    np.testing.assert_allclose(np.asarray(y2)[valid], np.asarray(y1)[valid],
                               atol=1e-6)
    keep = [0, 1, 2, 3, 4, 5]
    np.testing.assert_allclose(np.asarray(t2)[:, keep],
                               np.asarray(t1)[:, keep], atol=1e-6)
    for t in (t1, t2):
        assert np.array_equal(np.asarray(t[0]), np.asarray(tails[0]))
        assert np.array_equal(np.asarray(t[1, [0, 1, 3]]),
                              np.asarray(tails[1, [0, 1, 3]]))
    # against the plain causal convolution of row 2's whole chunk
    n = int(fed[0] + fed[1])
    seq = jnp.concatenate([tails[1, 2].reshape(3, 256), x[0],
                           x[1]])[:3 + n]
    want = jax.nn.silu(sum(w[j] * seq[j:j + n] for j in range(4)))
    got = jnp.concatenate([y1[0], y1[1]])[:n]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t1[1, 2]).reshape(3, 256),
                               np.asarray(seq[n:n + 3]), atol=1e-6)


# ---------------------------------------------------------------- the engine
@pytest.mark.parametrize("attention", ["gather", "kernel"])
def test_paged_step_serves_what_the_reference_puts_first(
        ref_params, decoder, attention):
    """Ragged slots that join and leave, prompts that end inside, at and
    past a page boundary, through lanes and through the state: each served
    token is the dense path's and the reference's first choice (a gap
    under 1e-4 of logits of size 6: float32 in another order)."""
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
    assert st["expert_layer_steps"] == 3 * st["steps"]
    # the latent pool holds the one MLA layer, the state pool the three KDA
    assert eng.k_pool.shape[0] == 1
    assert eng.v_pool["S"].shape == (3, 3 + 4 + 2, 2, 128, 128)
    assert eng.v_pool["conv"].shape == (3, 9, 9 * 2, 128)
    kind = eng.paged.cache
    assert not np.asarray(eng.v_pool["S"][:, kind.zero_row]).any()
    assert not np.asarray(eng.v_pool["conv"][:, kind.zero_row]).any()


@pytest.mark.parametrize("lanes", [(1, 32), (4, 4), (3, 2)],
                         ids=["1x32", "4x4", "3x2"])
def test_prompts_through_lanes_then_decode_through_the_state(
        ref_params, decoder, lanes, monkeypatch):
    """One lane of 32; four lanes of 4, which puts a slot's chunk over
    several lanes of a step and two slots' chunks in one step; three
    lanes of 2, narrower than the convolution's reach. Prompts end on,
    before and after a page boundary. Served tokens are the dense path's
    and what the same engine serves with no lanes at all."""
    monkeypatch.setattr(StateLatentCache, "lanes", lambda self: lanes)
    eng = _engine(decoder)
    assert eng.paged.lanes == lanes
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, V, n).astype(np.int32)
               for n in (40, 3, 31, 32, 33, 8, 9, 1)]
    got = _served(eng, prompts, 7)
    st = eng.stats()
    assert st["prefill_lane_steps"] >= 4
    assert eng.page_accounting()["leaked"] == 0
    monkeypatch.setattr(StateLatentCache, "lanes", lambda self: (0, 0))
    plain = _engine(decoder)
    assert _served(plain, prompts, 7) == got
    assert plain.stats()["prefill_lane_steps"] == 0
    for prompt, toks in zip(prompts, got):
        assert toks == decoder.generate(prompt[None],
                                        max_len=len(prompt) + 7)[0]
        assert _gaps(ref_params, prompt,
                     np.asarray(toks, np.int32)).max() < 1e-4


def test_a_slot_reused_by_a_second_request_starts_from_zero(decoder):
    """One slot, no prefix index: the second request's tokens are those of
    a fresh engine, whatever the first left in the row."""
    rng = np.random.default_rng(4)
    a, b = (rng.integers(0, V, n).astype(np.int32) for n in (11, 7))
    eng = _engine(decoder, num_slots=1, prefix_cache=False)
    first, second = _served(eng, [a], 5)[0], _served(eng, [b], 5)[0]
    fresh = _served(_engine(decoder, num_slots=1, prefix_cache=False),
                    [b], 5)[0]
    assert second == fresh != first
    assert second == decoder.generate(b[None], max_len=12)[0]
    assert eng.stats()["state_snapshots_taken"] == 0


def test_prefix_reuse_is_by_snapshot_and_the_counters_tell(ref_params,
                                                           decoder):
    """A history of 16 tokens (4 pages) served with one new token leaves
    its pages and a snapshot at 16. (1) A turn on it attaches 16 tokens
    by snapshot. (2) A turn that shares 18 tokens of an earlier TURN is
    matched 4 pages deep by snapshot although the trie holds more of it:
    the match is cut back to the deepest snapshot, the rest fed again.
    (3) With the snapshot taken away the pages still match and all of
    them are fed again. All three serve a cold engine's tokens."""
    rng = np.random.default_rng(5)
    hist = rng.integers(0, V, 16).astype(np.int32)
    turn = np.concatenate([hist, rng.integers(0, V, 9).astype(np.int32)])
    cold = _served(_engine(decoder, prefix_cache=False), [turn], 6)[0]
    eng = _engine(decoder)
    _served(eng, [hist], 1)
    st = eng.stats()
    assert (st["state_snapshots_taken"], st["snapshot_attach_tokens"],
            st["snapshot_miss_tokens"]) == (1, 0, 0)
    # (1) the hit
    req = eng.submit(turn, 6)
    eng.run()
    st1 = eng.stats()
    assert req.tokens == cold and req.prefix_hit_pages == 4
    assert st1["snapshot_attach_tokens"] == 16
    assert st1["snapshot_miss_tokens"] == 0
    assert _gaps(ref_params, turn, np.asarray(cold, np.int32)).max() < 1e-4
    # the turn left a snapshot at its prompt's last boundary (24) and its
    # pages to 28 = 25 + 6 - 1 fed tokens, whole pages only
    assert st1["state_snapshots_taken"] == 2
    # (2) the same turn again with more output: the trie matches 7 pages
    # (28 tokens), the deepest snapshot on the path is at 24
    longer = np.concatenate([turn, np.asarray(cold[:5], np.int32)])
    again = eng.submit(longer, 4)
    eng.run()
    st2 = eng.stats()
    assert again.prefix_hit_pages == 6
    assert st2["snapshot_attach_tokens"] - st1["snapshot_attach_tokens"] == 24
    assert st2["snapshot_miss_tokens"] - st1["snapshot_miss_tokens"] == 4
    assert again.tokens == _served(_engine(decoder, prefix_cache=False),
                                   [longer], 4)[0]
    # (3) every snapshot taken for newer ones: pages match, nothing skips
    taken = [eng.prefix.take_snapshot_row() for _ in range(4)]
    assert None not in taken and eng.prefix.take_snapshot_row() is None
    for row in taken:
        eng.prefix.free_snapshot_row(row)
    st3 = eng.stats()
    assert st3["state_snapshots_evicted"] >= 2
    third = eng.submit(turn, 6)
    eng.run()
    st4 = eng.stats()
    assert third.tokens == cold and third.prefix_hit_pages == 0
    assert st4["snapshot_attach_tokens"] == st3["snapshot_attach_tokens"]
    assert st4["snapshot_miss_tokens"] - st3["snapshot_miss_tokens"] == 24
    acc = eng.page_accounting()
    assert acc["leaked"] == 0
    assert acc["snapshot_rows_free"] + acc["snapshot_rows_held"] == 4


def test_a_preempted_request_resumes_to_the_same_tokens(decoder):
    """A pool too small for three long requests: the youngest is
    preempted, comes back (its pages and snapshots may be gone to the
    others by then) and ends on the tokens of an engine that never
    preempted. And a request preempted where it stands ON a page boundary
    leaves a snapshot there, which its return attaches."""
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
    assert eng.slots[0].pos == 12 and len(req.tokens) == 7
    eng._evict(0)
    assert eng.stats()["state_snapshots_taken"] == 2     # at 4, and at 12
    eng.run()
    st = eng.stats()
    assert st["preemptions"] == 1 and st["snapshot_attach_tokens"] == 12
    assert req.tokens == decoder.generate(prompts[0][None, :6],
                                          max_len=18)[0]


def test_snapshot_rows_never_leak_under_churn(decoder):
    """Many short requests over few snapshot rows and few pages, some
    cancelled mid-flight: rows and pages balance after every wave, and
    evicting the whole trie gives every row back."""
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
    assert st["state_rows_live"] == acc["snapshot_rows_held"]
    eng.prefix.flush()
    acc = eng.page_accounting()
    assert (acc["snapshot_rows_free"], acc["leaked"]) == (3, 0)


@pytest.mark.recompile_budget(max_compiles=60)
def test_state_step_churn_causes_zero_recompiles(decoder):
    """With the engine warm (both step programs, the row copy, the page
    copy), joins, leaves, snapshot attaches and snapshots compile
    nothing."""
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


def test_what_a_state_beside_the_pages_refuses(decoder):
    for kw, word in ((dict(kv_quant="int8"), "kv_quant"),
                     (dict(draft=decoder, spec_k=2), "speculative"),
                     (dict(kv_spill_pages=4), "kv_spill_pages")):
        with pytest.raises(ValueError, match=word):
            _engine(decoder, **kw)
    from paddle_tpu.models.decode import DraftDecoder
    with pytest.raises(ValueError, match="draft"):
        DraftDecoder(decoder, num_slots=2, max_seq_len=16)
    assert set(StateLatentCache.refuses) == {"kv_quant", "draft",
                                             "speculation", "spill"}
