"""The seam between the decoders and what they decode (models/block.py).

``TransformerDecoder``, ``PagedDecoder``, ``DraftDecoder`` and
``DecodeEngine`` call a block DESCRIPTION and its cache kind and never ask
which one it is. Held here: a third description that lives only in this
file is generated from, paged, drafted and served with no edit to
models/decode.py or serving/engine.py; those two files do not branch on
the block; the default block is the same program whether it is built from
the constructor's arguments or handed in; its parameter names are written
in one module; dense, paged (fp and int8) and draft steps agree token for
token through the description; every description and cache kind answers
all the decoders call.
"""

import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.models import block as blocks
from paddle_tpu.models.decode import DraftDecoder
from paddle_tpu.serving import DecodeEngine

MODELS_DIR = os.path.dirname(blocks.__file__)
DECODE_PY = os.path.join(MODELS_DIR, "decode.py")
ENGINE_PY = os.path.join(os.path.dirname(MODELS_DIR), "serving", "engine.py")


# ------------------------------------------------ a third description
@dataclasses.dataclass(frozen=True)
class TinyBlock:
    """Test-only: RMSNorm, NO position table (causality alone orders the
    tokens), GQA over the per-head cache, a bias-free GELU FFN, an untied
    head. Its table: emb [V, d], out [d, V], nf [d]; a layer's n1, n2 [d],
    wq [d, h*dh], wk, wv [d, g*dh], wo [d, d], w1 [d, f], w2 [f, d]."""

    n_heads: int
    n_kv_heads: int
    max_positions: int
    eps: float = 1e-5

    cache = blocks.PerHeadCache

    def positions(self, p, pre):
        return self.max_positions

    def table_dtype(self, p, pre):
        return p[f"{pre}emb"].dtype

    def vocab_size(self, p, pre):
        return p[f"{pre}emb"].shape[0]

    def heads(self, p, pre):
        return (self.n_heads, self.n_kv_heads,
                p[f"{pre}emb"].shape[1] // self.n_heads)

    def n_expert_layers(self, n_layers):
        return 0

    def embed(self, p, pre, ids, pos):
        return p[f"{pre}emb"][ids]

    def _norm(self, x, g):
        return blocks.rms_norm(x, g, self.eps).astype(x.dtype)

    def qkv(self, p, pre, i, x, pos, flat=False):
        h = self._norm(x, p[f"{pre}l{i}_n1"])
        q = blocks.split_heads(h @ p[f"{pre}l{i}_wq"], self.n_heads)
        k, v = h @ p[f"{pre}l{i}_wk"], h @ p[f"{pre}l{i}_wv"]
        if flat:
            return q, k.reshape(-1, k.shape[-1]), v.reshape(-1, v.shape[-1])
        return (q, blocks.split_heads(k, self.n_kv_heads),
                blocks.split_heads(v, self.n_kv_heads))

    def project(self, p, pre, i, attn):
        return attn @ p[f"{pre}l{i}_wo"]

    def ffn(self, p, pre, i, x, active=None):
        h = self._norm(x, p[f"{pre}l{i}_n2"])
        return x + jax.nn.gelu(h @ p[f"{pre}l{i}_w1"]) @ p[f"{pre}l{i}_w2"], \
            None

    def logits(self, p, pre, x):
        return self._norm(x, p[f"{pre}nf"]) @ p[f"{pre}out"]


TINY = dict(V=37, d=32, h=4, g=2, f=48, L=2, T=32)


def _tiny_table(seed=0, name="toy3"):
    z, rng = TINY, np.random.default_rng(seed)
    dh = z["d"] // z["h"]

    def w(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    p = {f"_{name}_emb": w(z["V"], z["d"], scale=1.0),
         f"_{name}_out": w(z["d"], z["V"]),
         f"_{name}_nf": 1 + w(z["d"], scale=0.1)}
    for i in range(z["L"]):
        lp = f"_{name}_l{i}_"
        p.update({lp + "n1": 1 + w(z["d"], scale=0.1),
                  lp + "n2": 1 + w(z["d"], scale=0.1),
                  lp + "wq": w(z["d"], z["h"] * dh),
                  lp + "wk": w(z["d"], z["g"] * dh),
                  lp + "wv": w(z["d"], z["g"] * dh),
                  lp + "wo": w(z["d"], z["d"]),
                  lp + "w1": w(z["d"], z["f"]),
                  lp + "w2": w(z["f"], z["d"])})
    return p


def _tiny_decoder(seed=0):
    z = TINY
    return models.TransformerDecoder(
        _tiny_table(seed), n_layers=z["L"], n_heads=z["h"], name="toy3",
        block=TinyBlock(z["h"], z["g"], z["T"]))


def _tiny_plain_logits(p, ids, name="toy3"):
    """The same model in plain jax.numpy, one whole causal pass over
    ids [t] -> logits [t, V]; nothing of paddle_tpu in it."""
    z = TINY
    dh, rep = z["d"] // z["h"], z["h"] // z["g"]

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-5) * g

    x = p[f"_{name}_emb"][ids]
    t = x.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(z["L"]):
        lp = f"_{name}_l{i}_"
        h = norm(x, p[lp + "n1"])
        q = (h @ p[lp + "wq"]).reshape(t, z["h"], dh)
        k = jnp.repeat((h @ p[lp + "wk"]).reshape(t, z["g"], dh), rep, 1)
        v = jnp.repeat((h @ p[lp + "wv"]).reshape(t, z["g"], dh), rep, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None], s, -1e30), -1)
        x = x + jnp.einsum("hqk,khd->qhd", w, v).reshape(t, -1) \
            @ p[lp + "wo"]
        x = x + jax.nn.gelu(norm(x, p[lp + "n2"]) @ p[lp + "w1"]) \
            @ p[lp + "w2"]
    return norm(x, p[f"_{name}_nf"]) @ p[f"_{name}_out"]


def _tiny_plain_greedy(p, prompt, n_new):
    ids = list(map(int, prompt))
    for _ in range(n_new):
        ids.append(int(jnp.argmax(_tiny_plain_logits(
            p, jnp.asarray(ids, jnp.int32))[-1])))
    return ids[len(prompt):]


def _prompts(n, vocab, seed=3, lo=3, hi=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (int(rng.randint(lo, hi)),))
            .astype("int32") for _ in range(n)]


def test_third_description_generates_its_own_forward():
    dec = _tiny_decoder()
    for prompt in _prompts(3, TINY["V"]):
        got = dec.generate(prompt[None, :], max_len=len(prompt) + 7)[0]
        assert got == _tiny_plain_greedy(dec.p, prompt, 7)
    beams = dec.beam_search(prompt[None, :], max_len=len(prompt) + 4,
                            beam_size=3, eos_id=TINY["V"] - 1)
    assert len(beams[0]) == 3 and beams[0][0][0] >= beams[0][1][0]


@pytest.mark.parametrize("how", ["gather", "kernel", "draft"])
def test_third_description_is_served_by_the_engine(how):
    """Ragged requests, more than slots, pages of 4 straddled; the paged
    step by gather and by the live-pages kernel (interpret mode); and
    speculation with a draft of the same description."""
    dec = _tiny_decoder()
    kw = dict(draft=_tiny_decoder(seed=1), spec_k=2) if how == "draft" \
        else dict(attention=how)
    eng = DecodeEngine(dec, num_slots=2, page_size=4, max_seq_len=TINY["T"],
                       **kw)
    prompts = _prompts(4, TINY["V"], seed=5)
    news = [6, 9, 5, 8]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
    eng.run(timeout=300)
    for r, p, n in zip(reqs, prompts, news):
        assert r.get(timeout=1) == _tiny_plain_greedy(dec.p, p, n)
    assert eng.page_accounting()["leaked"] == 0
    assert eng.stats()["expert_layer_steps"] == 0


@pytest.mark.parametrize("how", ["gather", "kernel"])
def test_third_description_prompts_go_through_prefill_lanes(how,
                                                            monkeypatch):
    """A description that reuses ``PerHeadCache`` has its prefill lanes
    with it, cut to ITS heads (4 query heads over 2 kv heads of 8: a
    128-row q block is 32 tokens, so 2 lanes), with no word about lanes
    in the description: a prompt of up to 24 tokens is one lane's chunk,
    three at once share the two lanes, and the output is the plain
    forward's and that of the same engine without lanes."""
    dec = _tiny_decoder()
    prompts = _prompts(5, TINY["V"], seed=11, lo=2, hi=25)
    serve = lambda eng: [r.get(timeout=1) for r in [
        [eng.submit(p, 6) for p in prompts], eng.run(timeout=300)][0]]
    eng = DecodeEngine(dec, num_slots=3, page_size=4, max_seq_len=TINY["T"],
                       attention=how)
    assert eng.paged.lanes == (2, 32)
    got = serve(eng)
    st = eng.stats()
    assert got == [_tiny_plain_greedy(dec.p, p, 6) for p in prompts]
    assert st["prefill_lane_tokens"] > st["prefill_tokens"] // 2 > 0
    assert eng.page_accounting()["leaked"] == 0
    monkeypatch.setattr(blocks.PerHeadCache, "lanes", lambda self: (0, 0))
    plain = DecodeEngine(dec, num_slots=3, page_size=4,
                         max_seq_len=TINY["T"], attention=how)
    assert serve(plain) == got and plain.stats()["prefill_lane_steps"] == 0
    assert st["steps"] < plain.stats()["steps"]


# ----------------------------------------------- the seam, by its sources
def test_decoders_and_engine_do_not_ask_which_block():
    asks = re.compile(r"block is (not )?None|\.latent\b"
                      r"|isinstance\([^)]*Block|counts_experts")
    for path in (DECODE_PY, ENGINE_PY):
        with open(path) as f:
            hits = [(i + 1, line.strip()) for i, line in enumerate(f)
                    if asks.search(line)]
        assert not hits, (path, hits)
    with open(DECODE_PY) as f:
        src = f.read()
    for gone in ("_paged_block", "_latent_paged_block", "_latent_block",
                 "def _ln(", "def _heads(", "def _ffn(", "def _embed(",
                 "def _logits("):
        assert gone not in src, gone


#: the default block's table, by the suffixes of its names
DEFAULT_NAMES = ("_q.w0", "_k.w0", "_v.w0", "_proj.w0", "_ln1.w0",
                 "_ln1.wbias", "_ln2.wbias", "_up.wbias", "_down.w0",
                 "_moe.gate", "_moe.moe_up", "pos_emb.w0", "tok_emb.w0",
                 "lnf.wbias", "head.w0")


@pytest.mark.parametrize("suffix", DEFAULT_NAMES)
def test_default_names_are_written_in_one_module(suffix):
    """block.py writes each name; beside it only the layer DSL's own copy
    (transformer.py, the debt that is left) may."""
    holders = set()
    for fn in sorted(os.listdir(MODELS_DIR)):
        if fn.endswith(".py"):
            with open(os.path.join(MODELS_DIR, fn)) as f:
                if suffix in f.read():
                    holders.add(fn)
    assert "block.py" in holders
    assert holders <= {"block.py", "transformer.py"}, holders


# ------------------------------------------------ the default description
CFG = dict(vocab_size=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
           max_len=32)


def _default_table(seed=7, **overrides):
    paddle.init(use_tpu=False, seed=0)
    from paddle_tpu.core.registry import reset_name_counters
    reset_name_counters()
    spec = models.transformer_lm(**{**CFG, **overrides})
    costs = spec.cost if isinstance(spec.cost, list) else [spec.cost]
    topo = paddle.Topology(costs, extra_outputs=[spec.output])
    return topo.init_params(jax.random.PRNGKey(seed))


def _step_text(dec, **kw):
    paged = dec.paged(num_slots=3, page_size=4, num_pages=20,
                      max_pages_per_slot=8, warm_start=False, **kw)
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    W = paged.window
    sw = jax.ShapeDtypeStruct((3, W), jnp.int32)
    return paged._step.lower(
        dec.p, k_pool, v_pool, sw, sw,
        jax.ShapeDtypeStruct((3, 8), jnp.int32),
        jax.ShapeDtypeStruct((3, W), jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
def test_default_block_built_or_handed_in_is_one_program(kv_quant):
    params = _default_table(n_kv_heads=1, moe_experts=4)
    built = models.TransformerDecoder(params, n_layers=2, n_heads=2,
                                      moe_k=2, moe_capacity_factor=1.5)
    assert built.block == blocks.DefaultBlock(2, 2, 1.5)
    handed = models.TransformerDecoder(
        params, n_layers=2, n_heads=2, block=blocks.DefaultBlock(
            n_heads=2, moe_k=2, moe_capacity_factor=1.5))
    kw = dict(kv_quant=kv_quant, window=2)
    assert _step_text(built, **kw) == _step_text(handed, **kw)
    # and the description is in the program's fingerprint
    other = models.TransformerDecoder(params, n_layers=2, n_heads=2,
                                      moe_k=1)
    fp = lambda d: d.paged(num_slots=3, page_size=4, num_pages=20,
                           max_pages_per_slot=8, warm_start=False)._step_fp
    assert fp(built) == fp(handed) != fp(other)


def _greedy_paged(dec, prompt, n_new, **kw):
    """PagedDecoder.step by hand: slot 1 of 2 teacher-forces the prompt a
    token a step, then feeds back its own argmax; slot 0 stays idle."""
    ps, P = 4, 8
    paged = dec.paged(num_slots=2, page_size=ps, num_pages=2 * P + 1,
                      max_pages_per_slot=P, warm_start=False, **kw)
    k_pool, v_pool = paged.init_pools()
    tables = np.zeros((2, P), np.int32)
    tables[1] = 1 + np.arange(P)
    active = np.array([False, True])
    out, tok = [], int(prompt[0])
    for pos in range(len(prompt) + n_new - 1):
        nxt, k_pool, v_pool = paged.step(
            k_pool, v_pool, np.array([0, tok], np.int32),
            np.array([0, pos], np.int32), tables, active)
        tok = int(nxt[1])
        if pos + 1 < len(prompt):
            tok = int(prompt[pos + 1])
        else:
            out.append(tok)
    return out


def _greedy_draft(dec, prompt, n_new):
    """DraftDecoder.step by hand: a window of 2, the prompt two tokens a
    step (the last window half masked when it is odd), then one."""
    draft = DraftDecoder(dec, num_slots=2, max_seq_len=24, window=2,
                         warm_start=False)
    kc, vc = draft.init_caches()
    fed, out, seq = 0, [], list(map(int, prompt))
    while len(out) < n_new:
        n = min(2, len(seq) - fed)
        toks = np.zeros((2, 2), np.int32)
        pos = np.zeros((2, 2), np.int32)
        act = np.zeros((2, 2), bool)
        toks[1, :n], pos[1, :n], act[1, :n] = \
            seq[fed:fed + n], range(fed, fed + n), True
        nxt, kc, vc = draft.step(kc, vc, toks, pos, act)
        fed += n
        if fed == len(seq):
            seq.append(int(nxt[1, n - 1]))
            out.append(seq[-1])
    return out


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("which", ["default_mha", "default_mqa_tied",
                                   "third"])
def test_dense_paged_and_draft_steps_agree(which, kv_quant):
    """One description, three decoders: generate's tokens are the paged
    step's (gather and kernel, fp and int8 pages) and the draft's."""
    if which == "third":
        dec = _tiny_decoder()
        vocab = TINY["V"]
    else:
        over = dict(n_kv_heads=1, tie_embeddings=True) \
            if which == "default_mqa_tied" else {}
        dec = models.TransformerDecoder(_default_table(**over), n_layers=2,
                                        n_heads=2)
        vocab = CFG["vocab_size"]
    for prompt in _prompts(2, vocab, seed=9, lo=4, hi=8):
        want = dec.generate(prompt[None, :], max_len=len(prompt) + 6)[0]
        for attention in ("gather", "kernel"):
            assert _greedy_paged(dec, prompt, 6, kv_quant=kv_quant,
                                 attention=attention) == want, attention
        assert _greedy_draft(dec, prompt, 6) == want


# ------------------------------------------------------------ the protocol
DESCRIPTION = ("cache", "positions", "table_dtype", "vocab_size", "embed",
               "ffn", "logits", "n_expert_layers")
CACHE_KIND = ("refuses", "LAYOUT", "dense_init", "dense_layer",
              "kernel_supported", "init_pools", "page_payload", "lanes",
              "layer", "layer_operand", "state_rows", "map_pages")
#: what a kind with ``state_rows`` answers besides
STATE_KIND = ("map_state", "snapshot_rows", "zero_row")
ASKED_BY_KIND = {
    blocks.PerHeadCache: ("heads", "qkv", "project"),
    blocks.LatentCache: ("cache_widths", "sizes", "qkv", "absorb_q",
                         "expand_o", "project", "attend", "softmax_scale")}
ASKED_BY_KIND[blocks.StateLatentCache] = ASKED_BY_KIND[blocks.LatentCache] + (
    "state_layers", "state_sizes", "state_inputs", "state_conv_weights",
    "state_qkv", "state_output")


@pytest.mark.parametrize("description", [blocks.DefaultBlock,
                                         blocks.LatentBlock,
                                         blocks.DeltaLatentBlock, TinyBlock],
                         ids=lambda c: c.__name__)
def test_every_description_answers_the_whole_protocol(description):
    for name in DESCRIPTION + ASKED_BY_KIND[description.cache]:
        assert hasattr(description, name), name
    for name in CACHE_KIND:
        assert hasattr(description.cache, name), name
    assert set(description.cache.refuses) <= {"kv_quant", "draft",
                                              "speculation", "spill"}
    assert description.cache.state_rows == 0    # of the class: none yet
    if description.cache is blocks.StateLatentCache:
        assert hasattr(description.cache, "map_state")


def test_the_decoders_call_nothing_outside_the_protocol():
    """What decode.py and engine.py read off a description or its cache
    kind is in the lists above (DraftDecoder's slot-private lanes are
    per-head K/V: it may ask what PerHeadCache asks)."""
    called = set()
    for path in (DECODE_PY, ENGINE_PY):
        with open(path) as f:
            src = f.read()
        # (not the file names block.py and cache.py in a comment)
        called |= set(re.findall(r"\b(?:blk|block|cache)\.(?!py\b)(\w+)",
                                 src))
    allowed = set(DESCRIPTION + CACHE_KIND + STATE_KIND + ("dtype", "plan")
                  + ASKED_BY_KIND[blocks.PerHeadCache])
    assert called and called <= allowed, called - allowed
