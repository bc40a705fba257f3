"""The latent (MLA) block with a chip's share of the experts
(models/block.py LatentBlock) through the decoders and the engine, at a
small size on the CPU, against the plain float32 reference
(benchmarks/reference/kimi_k2.py): the dense-cache path, the paged step's
gather path and its kernel in interpret mode; the absorbed against the
unabsorbed attention; YaRN's frequencies and the router by hand; the
shares of all ranks adding up to the uncut layer; zero recompiles under
churn; what the block refuses.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.lib import manifest
from paddle_tpu import models
from paddle_tpu.models.block import LatentBlock
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import pallas_decode as paged_ops
from paddle_tpu.serving import DecodeEngine

REF = manifest.load_module("reference", "kimi_k2")
MODEL = manifest.load_module("models", "kimi_k2")
CFG = MODEL.tiny()
SEED = 7


def _jit_params(cfg=CFG, seed=SEED):
    return jax.jit(lambda lo, hi: REF.init_params((lo, hi), cfg))(
        *REF.seed_words(seed))


@pytest.fixture(scope="module")
def ref_params():
    return _jit_params()


@pytest.fixture(scope="module")
def decoder():
    named = MODEL.make_weights(REF, SEED, CFG, jnp.float32)
    return models.TransformerDecoder(
        named, n_layers=CFG["num_hidden_layers"],
        n_heads=CFG["num_attention_heads"], name=MODEL.NAME,
        block=MODEL.block_of(CFG, 64))


def _engine(decoder, attention, **kw):
    kw = {"num_slots": 3, "page_size": 4, "max_seq_len": 48, **kw}
    return DecodeEngine(decoder, attention=attention, **kw)


def _gaps(ref_params, prompt, served):
    """How far each served token's reference logit lies under the best."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    logits = np.asarray(jax.jit(lambda p, s: REF.forward(p, s, CFG))(
        ref_params, jnp.asarray(seq)))
    rows = logits[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(served)), served]


def test_the_weights_are_the_references_leaf_by_leaf(ref_params, decoder):
    assert len(decoder.p) == len(ref_params)
    for leaf, want in ref_params.items():
        got = decoder.p[MODEL.program_name(leaf)]
        assert np.array_equal(np.asarray(got), np.asarray(want)), leaf


def test_dense_cache_logits_agree_with_one_full_forward_pass(ref_params,
                                                             decoder):
    """Prefill 9 tokens, then decode 7 one at a time through the latent
    cache: every position's logits against the reference's one pass."""
    rng = np.random.default_rng(1)
    seq = rng.integers(0, CFG["vocab_size"], 16).astype(np.int32)
    want = np.asarray(REF.forward(ref_params, jnp.asarray(seq), CFG))
    p = decoder.p
    logits, caches = decoder._prefill(p, jnp.asarray(seq[None, :9]), 9, 24)
    got = [np.asarray(logits[0])]
    for t in range(9, 16):
        lg, caches = decoder._forward(p, jnp.asarray(seq[None, t:t + 1]),
                                      None, caches, t, t + 1)
        got.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-4)


@pytest.mark.parametrize("attention", ["gather", "kernel"])
def test_paged_step_serves_what_the_reference_puts_first(
        ref_params, decoder, attention):
    """Ragged slots that join and leave, prompts that end inside, at and
    past a page boundary, through the engine: each served token is the
    dense path's and the reference's first choice."""
    eng = _engine(decoder, attention)
    assert eng.paged.use_kernel == (attention == "kernel")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
               for n in (3, 4, 5, 9, 13)]
    reqs = [eng.submit(p, 9) for p in prompts]
    eng.run()
    for prompt, r in zip(prompts, reqs):
        served = np.asarray(r.tokens, np.int32)
        want = decoder.generate(prompt[None], max_len=len(prompt) + 9)[0]
        assert served.tolist() == want
        assert _gaps(ref_params, prompt, served).max() < 1e-4
    st = eng.stats()
    assert eng.page_accounting()["leaked"] == 0
    # two expert layers a step; every token chooses 2 of 16, 4 are held
    assert st["expert_layer_steps"] == 2 * st["steps"]
    assert 0 < st["expert_hits_held"] <= st["expert_assignments_held"] \
        <= 2 * 2 * st["tokens_fed"]
    assert st["expert_hits_held"] <= 4 * st["expert_layer_steps"]


@pytest.mark.parametrize("lanes", [None, (4, 8)], ids=["own", "4x8"])
@pytest.mark.parametrize("attention", ["gather", "kernel"])
def test_latent_prompts_through_prefill_lanes(ref_params, decoder,
                                              attention, lanes,
                                              monkeypatch):
    """A latent prompt enters through lanes of the latent read: the cache
    kind's own shape at this size (one lane of 32: the kernel's gate says
    nothing of toy rows), and four lanes of 8, which puts one slot over
    several lanes in a step and leaves others to the slot group. Served
    tokens are the dense path's, the reference's first choice, and what
    the same engine serves with no lanes at all; the expert layers' load
    counts the lanes' active rows with the slots'."""
    from paddle_tpu.models.block import LatentCache
    if lanes is not None:
        monkeypatch.setattr(LatentCache, "lanes", lambda self: lanes)
    eng = _engine(decoder, attention, max_seq_len=64)
    assert eng.paged.lanes == (lanes or (1, 32))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
               for n in (40, 3, 31, 32, 33, 8, 9, 1)]
    reqs = [eng.submit(p, 7) for p in prompts]
    eng.run()
    st = eng.stats()
    assert st["prefill_lane_steps"] >= 4
    assert st["prefill_lane_tokens"] >= st["prefill_tokens"] - len(prompts) \
        - (20 if lanes else 0)      # 4 x 8: the younger feed their slots
    assert st["tokens_fed"] > st["active_slot_steps"]
    assert 0 < st["expert_hits_held"] <= st["expert_assignments_held"] \
        <= 2 * 2 * st["tokens_fed"]
    assert eng.page_accounting()["leaked"] == 0
    monkeypatch.setattr(LatentCache, "lanes", lambda self: (0, 0))
    plain = _engine(decoder, attention, max_seq_len=64)
    again = [plain.submit(p, 7) for p in prompts]
    plain.run()
    assert plain.stats()["prefill_lane_steps"] == 0
    for prompt, r, r1 in zip(prompts, reqs, again):
        served = np.asarray(r.tokens, np.int32)
        assert r.tokens == r1.tokens
        assert r.tokens == decoder.generate(prompt[None],
                                            max_len=len(prompt) + 7)[0]
        assert _gaps(ref_params, prompt, served).max() < 1e-4


def test_the_latent_lanes_are_what_the_kernels_gate_takes():
    """At the published widths (64 heads, rows of 640 lanes, pages of 32,
    bf16) a lane is 4 tokens wide, 256 rows of two terms each: 8 tokens
    fail the kernel's VMEM gate by its own arithmetic. 8 lanes take the
    kind's 32 tokens a step."""
    assert paged_ops.latent_kernel_supported(8, 4 * 64, 640, 512, 32, 128,
                                             jnp.bfloat16)
    assert not paged_ops.latent_kernel_supported(4, 8 * 64, 640, 512, 32,
                                                 128, jnp.bfloat16)
    cell = manifest.cell(manifest.load_manifest(), "kimik2_agent_2k")
    cfg = dict(cell["config"], num_hidden_layers=2)
    p = {cell["model"].program_name(k): jax.ShapeDtypeStruct(v, jnp.bfloat16)
         for k, v in cell["reference"].leaf_shapes(cfg).items()}
    block = cell["model"].block_of(cfg, 4096)
    kind = block.cache(block, p, f"_{cell['model'].NAME}_", n_layers=2,
                       num_slots=64, window=1, page_size=32, num_pages=8192,
                       max_pages_per_slot=128, kv_quant=None)
    assert kind.kernel_supported() and kind.lanes() == (8, 4)


@pytest.mark.parametrize("attention", ["gather", "kernel"])
def test_prefix_attach_and_copy_on_write(ref_params, decoder, attention):
    """A resent prompt attaches its pages from the prefix index; one that
    parts from it inside a page copies that page first. The latent pair
    goes through the engine's page copy like any pools."""
    eng = _engine(decoder, attention)
    rng = np.random.default_rng(3)
    base = rng.integers(0, CFG["vocab_size"], 14).astype(np.int32)
    first = eng.submit(base, 6)
    eng.run()
    again = eng.submit(base, 6)
    eng.run()
    assert again.prefix_hit_pages >= 3
    assert again.tokens == first.tokens
    fork = np.concatenate([base[:10], (base[10:] + 1) % CFG["vocab_size"]])
    forked = eng.submit(fork, 6)
    eng.run()
    assert eng.stats()["prefix_cow_copies"] >= 1
    for prompt, r in ((base, again), (fork, forked)):
        served = np.asarray(r.tokens, np.int32)
        assert _gaps(ref_params, prompt, served).max() < 1e-4
    assert eng.page_accounting()["leaked"] == 0


def test_spilled_latent_pages_come_back(decoder):
    """The pair's pages through the spill store's read and write."""
    eng = _engine(decoder, "gather", num_slots=1, num_pages=8,
                  kv_spill_pages=8)
    rng = np.random.default_rng(4)
    a, b = (rng.integers(0, CFG["vocab_size"], 17).astype(np.int32)
            for _ in range(2))
    first = eng.submit(a, 4)
    eng.run()
    eng.submit(b, 4)
    eng.run()
    again = eng.submit(a, 4)
    eng.run()
    st = eng.stats()
    assert st["kv_pages_spilled"] >= 1 and st["kv_pages_restored"] >= 1
    assert again.tokens == first.tokens


def test_absorbed_attention_is_the_unabsorbed(decoder):
    blk, p, pre = decoder.block, decoder.p, decoder._pre
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 7, CFG["hidden_size"])), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(7)[None], (2, 7))
    q_nope, q_rope, c, kr = blk.qkv(p, pre, 1, x, pos)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((7, 7), bool))[None], (2, 7, 7))
    a = blk.attend(p, pre, 1, q_nope, q_rope, c, kr, mask, absorbed=True)
    b = blk.attend(p, pre, 1, q_nope, q_rope, c, kr, mask, absorbed=False)
    assert float(jnp.max(jnp.abs(b))) > 1e-2
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def _latent_case(rng, S, W, H=4, rkv=16, dr=8, ps=4, P=160, L=2, N=24):
    """(q_lat, q_rope, pool, tables): random queries and a random pool of
    [c_kv | k_rope | zero lanes] rows under a table that repeats pages
    (P entries drawn from N - 1 pages)."""
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    pool = f(L, N, ps, 128).at[..., rkv + dr:].set(0.0)
    tables = jnp.asarray(rng.integers(1, N, (S, P)), jnp.int32)
    return f(S, W, H, rkv), f(S, W, H, dr), pool, tables


def _both_paths(ql, qr, pool, tables, lens, **kw):
    kw = {"layer": 1, "scale": 0.3, **kw}
    lens = jnp.asarray(lens, jnp.int32)
    want = paged_ops.paged_latent_attention(ql, qr, pool, tables, lens, **kw)
    got = paged_ops.paged_latent_attention(
        ql, qr, pool, tables, lens, use_kernel=True, interpret=True, **kw)
    assert got.dtype == want.dtype == jnp.float32
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("lens", [
    [[5, 6], [600, 601], [1, 2]],
    [[509, 510, 511, 512], [513, 514, 515, 516], [637, 638, 639, 640]],
], ids=["W2", "W4"])
def test_latent_kernel_agrees_with_the_gather_path(lens):
    """W = 2 and W = 4 window rows with per-token lengths, ragged slots,
    more live pages than one compute block holds (128 pages of 4 rows a
    block, 160 a table; lengths on both sides of a block's edge), a table
    that repeats a page."""
    rng = np.random.default_rng(6)
    ql, qr, pool, tables = _latent_case(rng, 3, len(lens[0]))
    assert paged_ops._latent_pages_per_block(4, 160, 128 * 4) == 128
    got, want = _both_paths(ql, qr, pool, tables, lens)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_latent_block_is_sized_from_the_shapes():
    """512 cache rows a compute block at the serving widths (16 pages of
    32 rows of 640 bf16 lanes), never more than a slot's table, halved
    where the tile twice would pass half the kernel's VMEM."""
    k = paged_ops._latent_pages_per_block
    assert k(32, 128, 640 * 2) == 16 and k(16, 256, 640 * 2) == 32
    assert k(32, 6, 640 * 2) == 6
    assert k(32, 128, 8192 * 4) == 2       # rows of 32 KB: 64 rows twice
    assert k(256, 128, 8192 * 4) == 0      # one page twice is too much


@pytest.mark.parametrize("idle", [(1,), (0, 1, 2)], ids=["one", "all"])
def test_an_idle_latent_slot_returns_zeros(idle):
    """A slot whose lengths are all 0 holds no live page: its output is
    zeros, beside live slots that read what they would without it, and
    where every slot is idle the kernel copies nothing at all."""
    rng = np.random.default_rng(13)
    ql, qr, pool, tables = _latent_case(rng, 3, 2, P=12)
    lens = np.asarray([[9, 10], [30, 31], [2, 3]])
    lens[list(idle)] = 0
    got, want = _both_paths(ql, qr, pool, tables, lens)
    live = [s for s in range(3) if s not in idle]
    assert not got[list(idle)].any()
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)


def test_the_latent_kernel_reads_no_page_past_the_live_ones():
    """Pages no slot's live table entries name hold NaN: the answer is
    finite and the gather path's over the clean pool (a page past
    ``used`` is never copied, and a tile's unwritten rows are zeros, not
    what VMEM held)."""
    rng = np.random.default_rng(14)
    ql, qr, pool, tables = _latent_case(rng, 3, 2, P=12, N=40)
    lens = jnp.asarray([[9, 10], [0, 0], [41, 42]], jnp.int32)
    tables = tables.at[:, :11].set(
        jnp.asarray(rng.integers(1, 20, (3, 11)), jnp.int32))
    tables = tables.at[:, 11:].set(25)
    tables = tables.at[0, 3:].set(30).at[1, :].set(35)
    dirty = pool.at[:, 20:].set(jnp.nan)
    kw = dict(layer=1, scale=0.3)
    got = paged_ops.paged_latent_attention(
        ql, qr, dirty, tables, lens, use_kernel=True, interpret=True, **kw)
    want = paged_ops.paged_latent_attention(ql, qr, pool, tables, lens, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=2e-5)


def test_the_layers_of_a_latent_pool_share_one_traced_kernel():
    """The layer is an operand of the kernel's program: layers 0 and 1 of
    one pool run through ONE trace of it and read each its own rows."""
    rng = np.random.default_rng(15)
    ql, qr, pool, tables = _latent_case(rng, 2, 1, P=10)
    lens = [[17], [33]]
    paged_ops._latent_pages_call.clear_cache()
    outs = [_both_paths(ql, qr, pool, tables, lens, layer=i)
            for i in (0, 1)]
    assert paged_ops._latent_pages_call._cache_size() == 1
    for got, want in outs:
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(outs[0][0] - outs[1][0]).max() > 1e-2


# --------------------------------------------------------------- by hand
PUBLISHED_ROPE = dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128, max_positions=4096, rope_theta=50000.0,
                      rope_factor=32.0, rope_original_max=4096,
                      rope_beta_fast=1.0, rope_beta_slow=1.0,
                      rope_mscale=1.0, rope_mscale_all_dim=1.0)


def test_yarn_frequencies_against_a_hand_computed_table():
    """At the published numbers the ramp runs from lane 19 to lane 20:
    lanes 0..19 keep ``50000^(-2j/64)``, lanes 20..31 are that over 32."""
    blk = LatentBlock(**PUBLISHED_ROPE)
    cd = 64 * np.log(4096 / (2 * np.pi)) / (2 * np.log(50000))
    assert int(np.floor(cd)) == 19 and int(np.ceil(cd)) == 20
    base = 50000.0 ** (-2.0 * np.arange(32) / 64.0)
    want = np.where(np.arange(32) <= 19, base, base / 32.0)
    np.testing.assert_allclose(blk.inv_freq(), want, rtol=1e-12)
    z = REF.sizes(manifest.cell(manifest.load_manifest(),
                                "kimik2_agent_2k")["config"])
    np.testing.assert_allclose(REF.yarn_inv_freq(z), want, rtol=1e-12)
    m = 0.1 * np.log(32.0) + 1.0
    assert blk.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert REF.softmax_scale(z) == pytest.approx(blk.softmax_scale, rel=1e-12)
    assert m == pytest.approx(1.34657, abs=1e-5)


def test_rope_rotates_pairs_j_and_j_plus_half():
    blk = LatentBlock(**{**PUBLISHED_ROPE, "qk_rope_head_dim": 4,
                         "rope_factor": 1.0})
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0], [0.0, 1.0, 0.0, 0.0]])
    got = np.asarray(blk.rope(x, jnp.asarray([3, 3])))
    f = 50000.0 ** (-np.arange(0, 4, 2) / 4.0)
    np.testing.assert_allclose(got[0], [np.cos(3 * f[0]), 0,
                                        np.sin(3 * f[0]), 0], atol=1e-6)
    np.testing.assert_allclose(got[1], [0, np.cos(3 * f[1]), 0,
                                        np.sin(3 * f[1])], atol=1e-6)


def test_router_bias_moves_the_choice_and_not_the_weight():
    """One token, four experts, top-2, by hand: scores sigmoid(h W);
    without bias the choice is experts 0 and 1; a bias on expert 3 swaps
    it in for expert 1, and the weights still come from the scores."""
    logit = lambda s: np.log(s / (1 - s))
    s = np.array([0.8, 0.6, 0.3, 0.5])
    h = jnp.ones((1, 1), jnp.float32)
    w = jnp.asarray(logit(s)[None, :], jnp.float32)
    idx, wts = moe_ops.sigmoid_topk_route(h, w, jnp.zeros(4), k=2, scale=2.0)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.2])
    idx, wts = moe_ops.sigmoid_topk_route(h, w, bias, k=2, scale=2.0)
    assert np.asarray(idx[0]).tolist() == [0, 3]
    np.testing.assert_allclose(
        np.asarray(wts[0]), 2.0 * np.array([0.8, 0.5]) / 1.3, rtol=1e-6)
    comb = moe_ops.held_combine(idx, wts, lo=2, n_held=2)
    np.testing.assert_allclose(np.asarray(comb),
                               [[0.0, 2.0 * 0.5 / 1.3]], rtol=1e-6)
    load = moe_ops.held_load(idx, jnp.asarray([True]), lo=2, n_held=2)
    assert np.asarray(load).tolist() == [1, 1]
    load = moe_ops.held_load(idx, jnp.asarray([False]), lo=2, n_held=2)
    assert np.asarray(load).tolist() == [0, 0]
    ridx, rw = REF.route(h, {"router": w, "router_bias": bias},
                         {"k": 2, "route_scale": 2.0}, lambda x: x)
    assert np.asarray(ridx[0]).tolist() == [0, 3]
    np.testing.assert_allclose(np.asarray(rw), np.asarray(wts), rtol=1e-6)


# --------------------------------------------------------- the share test
def _share_of(w: dict, rank: int, held: int) -> dict:
    lo = rank * held
    return dict(w, **{k: w[k][lo:lo + held]
                      for k in ("e_gate", "e_up", "e_down")})


@pytest.mark.parametrize("arch,experts,ranks", [
    ("kimi_k2", "n_routed_experts", 4), ("kimi_linear", "num_experts", 8)])
def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(arch, experts,
                                                           ranks):
    """The guide's share test, for each architecture with a share-aware
    expert layer: the parts of the layer that the ranks give (each its
    held experts' terms, 16 experts over 4 ranks or over 8), with the
    shared expert counted once, add up to what the uncut reference gives;
    and the program's share-aware layer gives each rank's part."""
    ref = manifest.load_module("reference", arch)
    tiny = manifest.load_module("models", arch).tiny()
    held = 16 // ranks
    full_cfg = dict(tiny, **{experts: 16, "ep_ranks": 1, "ep_rank": 0})
    zf = ref.sizes(full_cfg)
    w = ref._layer_params(jax.jit(lambda lo, hi: ref.init_params(
        (lo, hi), full_cfg))(*ref.seed_words(SEED)), 1)
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(10, tiny["hidden_size"])), jnp.float32)
    ident = lambda x: x
    whole = ref.expert_ffn(h, w, zf, ident)
    shared = ref.swiglu(h, w["s_gate"], w["s_up"], w["s_down"], ident)
    parts, prog_parts = [], []
    for rank in range(ranks):
        cfg_r = dict(tiny, **{experts: held, "ep_ranks": ranks,
                              "ep_rank": rank})
        w_r = _share_of(w, rank, held)
        parts.append(ref.expert_ffn(h, w_r, ref.sizes(cfg_r), ident,
                                    shared=False))
        idx, wts = moe_ops.sigmoid_topk_route(
            h, w["router"], w["router_bias"], k=zf["k"],
            scale=zf["route_scale"])
        comb = moe_ops.held_combine(idx, wts, lo=held * rank, n_held=held)
        prog_parts.append(moe_ops.held_experts_ffn(
            h, comb, w_r["e_gate"], w_r["e_up"], w_r["e_down"]))
    assert float(jnp.max(jnp.abs(whole - shared))) > 1e-2
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=2e-5)
    for a, b in zip(parts, prog_parts):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(moe_ops.swiglu(h, w["s_gate"], w["s_up"], w["s_down"])),
        np.asarray(shared), atol=2e-5)


def test_the_block_holds_the_rank_it_is_told(ref_params, decoder):
    """The tiny configuration is rank 1 of 4: the decoder's expert layer
    adds experts [4, 8) and nothing of the others."""
    blk, p, pre = decoder.block, decoder.p, decoder._pre
    assert blk.expert_rank == 1
    assert p[f"{pre}l1_experts.gate"].shape[0] == 4
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(1, 6, CFG["hidden_size"])), jnp.float32)
    y, load = blk.ffn(p, pre, 1, x)
    z = REF.sizes(CFG)
    w = REF._layer_params(ref_params, 1)
    h = REF.rms_norm(x[0], w["ffn_norm_g"], z["eps"])
    want = x[0] + REF.expert_ffn(h, w, z, lambda a: a)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=2e-5)
    idx, _ = REF.route(h, w, z, lambda a: a)
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    assert np.asarray(load).tolist() == [
        int(held.sum()), len(set(np.asarray(idx)[held].tolist()))]


# ------------------------------------------------------------- the engine
@pytest.mark.recompile_budget(max_compiles=40)
def test_latent_step_churn_causes_zero_recompiles(decoder):
    """With the engine warm (the step and the page copy), joins, leaves
    and prefix attaches compile nothing."""
    from paddle_tpu.analysis.sanitizer import compile_watch
    eng = _engine(decoder, "gather", num_slots=2)
    rng = np.random.default_rng(10)
    base = rng.integers(0, CFG["vocab_size"], 10).astype(np.int32)
    eng.submit(base, 2)
    eng.run()
    eng.submit(np.concatenate([base[:6], base[:3]]), 2)      # a CoW copy
    eng.run()
    with compile_watch() as watch:
        reqs = [eng.submit(np.concatenate([base[:n], base[:2]]), 5)
                for n in (3, 6, 9, 10)]
        eng.run()
    assert all(len(r.tokens) == 5 for r in reqs)
    assert watch.total == 0, watch.events
    assert eng.page_accounting()["leaked"] == 0


def test_int8_and_a_draft_on_a_latent_block_are_refused(decoder):
    with pytest.raises(ValueError, match="latent"):
        _engine(decoder, "gather", kv_quant="int8")
    with pytest.raises(ValueError, match="latent"):
        _engine(decoder, "gather", draft=decoder, spec_k=2)
    from paddle_tpu.models.decode import DraftDecoder
    with pytest.raises(ValueError, match="latent"):
        DraftDecoder(decoder, num_slots=2, max_seq_len=16)


def test_the_latent_pool_is_one_row_a_token(decoder):
    """[c_kv 16 | k_rope 8] padded to whole 128-lane tiles, in the layout
    the kernel reads; the engine's second pool attribute is an empty
    pytree that every page program maps over."""
    eng = _engine(decoder, "gather", num_pages=20)
    assert eng.k_pool.shape == (3, 20, 4, 128) and eng.v_pool == {}
    assert eng.paged.pool_bytes() == 4 * 3 * 20 * 4 * 128
    eng.submit(np.arange(7, dtype=np.int32), 2)
    eng.run()
    row = np.asarray(eng.k_pool)[:, 1:3].reshape(3, 8, 128)[:, :7]
    assert np.abs(row[..., :24]).min() > 0 and not row[..., 24:].any()
    assert eng.max_seq_len == 48 and decoder.max_positions == 64


def test_two_term_product_keeps_the_activations_precision():
    """Against bfloat16 weights a float32 activation goes through as two
    bfloat16 terms in one product: the result is the float32 product with
    the same (bfloat16) weights to 1e-4 of its size, where rounding the
    activation to bfloat16 first is a hundred times further off."""
    from paddle_tpu.ops.linear import einsum_two_terms
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(6, 5, 96)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 96, 40)), jnp.bfloat16)
    want = jnp.einsum("btd,edf->betf", x, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    got = einsum_two_terms("btd,edf->betf", x, w)
    plain = jnp.einsum("btd,edf->betf", x.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32)
    size = float(jnp.sqrt(jnp.mean(want ** 2)))
    err = float(jnp.max(jnp.abs(got - want))) / size
    err_plain = float(jnp.max(jnp.abs(plain - want))) / size
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert err < 1e-4, err
    assert err_plain > 50 * err, (err_plain, err)
    # float32 weights: the plain product
    wf = w.astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(einsum_two_terms("btd,edf->betf", x, wf)),
        np.asarray(want), rtol=1e-6, atol=1e-6)


def test_latent_kernel_rounds_nothing_but_the_stored_cache():
    """Against a bfloat16 pool the kernel takes float32 queries as two
    bfloat16 terms and splits its probabilities the same way: its output
    is the float32 mathematics over the stored rows to 1e-4 of its size
    (queries rounded to bfloat16 alone would be thirty times further)."""
    rng = np.random.default_rng(12)
    S, W, H, rkv, dr, ps, P, L, N = 2, 1, 4, 128, 8, 16, 6, 1, 9
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    ql, qr = f(S, W, H, rkv), f(S, W, H, dr)
    pool = f(L, N, ps, 256).at[..., rkv + dr:].set(0.0).astype(jnp.bfloat16)
    tables = jnp.asarray(rng.integers(1, N, (S, P)), jnp.int32)
    lens = jnp.asarray([[70], [33]], jnp.int32)
    kw = dict(layer=0, scale=0.05)
    want = paged_ops.paged_latent_attention(ql, qr, pool, tables, lens, **kw)
    got = paged_ops.paged_latent_attention(
        ql, qr, pool, tables, lens, use_kernel=True, interpret=True, **kw)
    rounded = paged_ops.paged_latent_attention(
        ql.astype(jnp.bfloat16), qr.astype(jnp.bfloat16), pool, tables, lens,
        **kw)
    size = float(jnp.sqrt(jnp.mean(want ** 2)))
    err = float(jnp.max(jnp.abs(got - want))) / size
    err_rounded = float(jnp.max(jnp.abs(rounded - want))) / size
    assert err < 1e-4 and err_rounded > 30 * err, (err, err_rounded)
