"""Incremental (KV-cache) decoding for the transformer LM.

The recurrent zoo generates through `beam_search` (the dynamic
RecurrentGradientMachine parity path); the transformer needs the modern
equivalent: a jit-compiled autoregressive loop that carries per-layer
caches instead of re-running the prefix every step. The three decoders
here (dense caches, paged pools, the draft's slot-private lanes) hold the
loops, shapes and programs; WHAT a layer computes and caches is its block
description's (models/block.py), over the SAME parameter table the layer
DSL trains (a trained `Parameters` dict drops straight in);
`tests/test_decode.py` pins step-wise logits against the training graph.

TPU shape discipline: one compilation per (batch, prompt_len, max_len,
temperature) combination — the prompt prefills in a single batched
causal pass (one big MXU matmul chain), then `lax.scan` extends one
token at a time with `dynamic_update_slice` into fixed-size caches.
Parameters are a jit argument (not trace constants), so one decoder
serves updated parameter tables without retracing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.models.block import (DefaultBlock, PagedTokens, join_rows,
                                     shared_layers, split_rows)


def _sample(logits, temperature, key):
    """Greedy argmax (temperature None) or categorical at temperature."""
    if temperature is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature).astype(jnp.int32)


class TransformerDecoder:
    """Greedy / temperature sampling with per-layer KV caches.

    params: the training-side parameter dict (Parameters.raw or
    Topology.init_params output). ``block`` is the description of what the
    table computes (models/block.py); without one the config args, which
    mirror transformer_lm, describe the block the layer DSL trains."""

    def __init__(self, params, *, n_layers: int, n_heads: int,
                 name: str = "tfm", moe_k: int = 2,
                 moe_capacity_factor: Optional[float] = None,
                 block=None):
        prefix = f"_{name}"
        self.block = block or DefaultBlock(
            n_heads=n_heads, moe_k=moe_k,
            moe_capacity_factor=moe_capacity_factor)
        self._pre = f"_{name}_"
        self.p = {k: jnp.asarray(v) for k, v in params.items()
                  if k.startswith(prefix)}
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.name = name
        self._jitted = {}

    # ---------------------------------------------------------------- core
    @property
    def max_positions(self) -> int:
        """Positions the model can address (the description says)."""
        return int(self.block.positions(self.p, self._pre))

    def require(self, what: str):
        """Raise the cache kind's reason where it cannot do ``what``
        ("kv_quant", "draft", "speculation")."""
        why = self.block.cache.refuses.get(what)
        if why:
            raise ValueError(why)

    def _forward(self, p, ids, pos, caches, cache_pos, kv_len):
        """ids [b, t] at positions pos -> (logits [b, t, V], caches')."""
        blk, pre = self.block, self._pre
        x = blk.embed(p, pre, ids, pos)
        new_caches = []
        for i, cache in enumerate(caches):
            x, cache = blk.cache.dense_layer(blk, p, pre, i, x, cache, pos,
                                             cache_pos, kv_len)
            new_caches.append(cache)
        return blk.logits(p, pre, x), new_caches

    def _prefill(self, p, prompt, plen, max_len):
        """The fixed-size caches and the one batched causal pass over the
        prompt. -> (logits [b, plen, V], caches)."""
        blk, b = self.block, prompt.shape[0]
        caches = [blk.cache.dense_init(blk, p, self._pre, b, max_len, i)
                  for i in range(self.n_layers)]
        pos = jnp.arange(plen)[None, :].repeat(b, 0)
        return self._forward(p, prompt, pos, caches, 0, plen)

    def _validate(self, prompt, max_len):
        plen = int(prompt.shape[1])
        assert max_len > plen, f"max_len {max_len} <= prompt length {plen}"
        pos_rows = self.max_positions
        assert max_len <= pos_rows, (
            f"max_len {max_len} exceeds the position table ({pos_rows} "
            "rows) — jit gathers clamp silently, so positions past the "
            "table would all reuse its last row")
        return plen

    # ------------------------------------------------------------- generate
    def _build(self, plen: int, max_len: int,
               temperature: Optional[float]):
        def run(p, prompt, rng):
            b = prompt.shape[0]
            logits, caches = self._prefill(p, prompt, plen, max_len)
            k0, rng = jax.random.split(rng)
            first = _sample(logits[:, -1], temperature, k0)

            def step(carry, key):
                caches, tok, pp = carry
                lg, caches = self._forward(
                    p, tok[:, None], jnp.full((b, 1), pp, jnp.int32),
                    caches, pp, pp + 1)
                return (caches, _sample(lg[:, -1], temperature, key),
                        pp + 1), tok

            n_steps = max_len - plen - 1
            keys = jax.random.split(rng, n_steps) if n_steps > 0 else \
                jnp.zeros((0, 2), jnp.uint32)
            (_, last_tok, _), toks = jax.lax.scan(
                step, (caches, first, jnp.int32(plen)), keys)
            return jnp.concatenate(
                [toks.transpose(1, 0), last_tok[:, None]], axis=1)

        return jax.jit(run)

    # ---------------------------------------------------------- beam search
    def _beam_feed(self, p, caches, tokens, t, plen):
        """Feed every lane's token t - 1 -> (log-probs of token t
        [b, K, V], caches')."""
        b, K = tokens.shape[:2]
        last = tokens[:, :, t - 1].reshape(b * K)
        lg, caches = self._forward(
            p, last[:, None],
            jnp.full((b * K, 1), plen + t - 1, jnp.int32),
            caches, plen + t - 1, plen + t)
        lp = jax.nn.log_softmax(lg[:, -1].astype(jnp.float32))
        return lp.reshape(b, K, -1), caches

    @staticmethod
    def _beam_follow(total, tokens, caches, t):
        """The K best continuations of total [b, K, V], histories and
        caches reordered to follow the winning parents. -> (scores,
        tokens, caches, parent [b, K], tok [b, K])."""
        b, K, V = total.shape
        scores, flat = jax.lax.top_k(total.reshape(b, K * V), K)
        parent = flat // V
        tok = (flat % V).astype(jnp.int32)
        tokens = jnp.take_along_axis(
            tokens, parent[:, :, None], axis=1).at[:, :, t].set(tok)
        pflat = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
        caches = jax.tree_util.tree_map(lambda c: c[pflat], caches)
        return scores, tokens, caches, parent, tok

    def _build_beam_gnmt(self, plen: int, max_len: int, beam_size: int,
                         eos_id: int, alpha: float):
        """Full GNMT beam semantics (beam_search's length_penalty > 0):
        an EOS hypothesis is BANKED with raw / len^alpha inside the scan
        and frees its lane, so longer raw-sum rivals cannot prune it.
        Returns (tokens [b,K,L], penalized scores [b,K]), best first."""
        K = beam_size
        L = max_len - plen

        def run(p, prompt):
            b = prompt.shape[0]
            V = self.block.vocab_size(p, self._pre)
            # live lanes exclude EOS, so K live continuations need K
            # non-EOS tokens to exist (the raw-sum path has no such
            # restriction — its EOS lanes freeze in place)
            assert K < V, \
                f"gnmt beam needs beam_size={K} < vocab_size={V}"
            vmask = jnp.arange(V) == eos_id
            logits, caches = self._prefill(p, prompt, plen, max_len)
            lp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
            # the bank: top-K finished hypotheses, penalized scores
            bank_s = jnp.full((b, K), -1e30, jnp.float32)
            bank_t = jnp.full((b, K, L), eos_id, jnp.int32)
            # immediate-EOS is the first banked candidate (length 1)
            bank_s = bank_s.at[:, 0].set(lp0[:, eos_id] / 1.0 ** alpha)
            # live lanes seed from the top-K NON-eos first tokens
            lp0m = jnp.where(vmask[None], -1e30, lp0)
            scores, tok0 = jax.lax.top_k(lp0m, K)
            caches = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, K, axis=0), caches)
            tokens = jnp.full((b, K, L), eos_id, jnp.int32)
            tokens = tokens.at[:, :, 0].set(tok0)

            def merge_bank(bank_s, bank_t, cand_s, cand_t):
                all_s = jnp.concatenate([bank_s, cand_s], axis=1)
                all_t = jnp.concatenate([bank_t, cand_t], axis=1)
                top_s, idx = jax.lax.top_k(all_s, K)
                top_t = jnp.take_along_axis(all_t, idx[:, :, None], axis=1)
                return top_s, top_t

            def step(carry, t):
                caches, tokens, scores, bank_s, bank_t = carry
                lp, caches2 = self._beam_feed(p, caches, tokens, t, plen)
                # bank each lane's EOS continuation (length t+1 with eos)
                eos_raw = scores + lp[:, :, eos_id]
                eos_pen = eos_raw / (t + 1.0) ** alpha
                cand_t = tokens.at[:, :, t].set(eos_id)
                bank_s, bank_t = merge_bank(bank_s, bank_t, eos_pen,
                                            cand_t)
                # live lanes continue over non-EOS tokens only
                lp = jnp.where(vmask[None, None], -1e30, lp)
                total = scores[:, :, None] + lp
                scores2, tokens2, caches2, _, _ = self._beam_follow(
                    total, tokens, caches2, t)
                return (caches2, tokens2, scores2, bank_s, bank_t), 0

            (caches, tokens, scores, bank_s, bank_t), _ = jax.lax.scan(
                step, (caches, tokens, scores, bank_s, bank_t),
                jnp.arange(1, L))
            # drain: still-live lanes compete at their full length L
            bank_s, bank_t = merge_bank(bank_s, bank_t,
                                        scores / float(L) ** alpha, tokens)
            return bank_t, bank_s

        return jax.jit(run)

    def _build_beam(self, plen: int, max_len: int, beam_size: int,
                    eos_id: int):
        K = beam_size

        def run(p, prompt):
            b = prompt.shape[0]
            V = self.block.vocab_size(p, self._pre)
            logits, caches = self._prefill(p, prompt, plen, max_len)
            lp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
            # seed K lanes with the top-K first tokens
            scores, tok0 = jax.lax.top_k(lp0, K)          # [b, K]
            caches = jax.tree_util.tree_map(
                lambda c: jnp.repeat(c, K, axis=0), caches)  # [b*K, ...]
            tokens = jnp.full((b, K, max_len - plen), eos_id, jnp.int32)
            tokens = tokens.at[:, :, 0].set(tok0)
            alive = tok0 != eos_id                        # [b, K]

            def step(carry, t):
                caches, tokens, scores, alive = carry
                lp, caches2 = self._beam_feed(p, caches, tokens, t, plen)
                # finished beams: only the eos continuation, at no cost —
                # the lane's score freezes and it keeps emitting eos
                frozen = jnp.full((V,), -1e30).at[eos_id].set(0.0)
                lp = jnp.where(alive[:, :, None], lp, frozen[None, None])
                total = scores[:, :, None] + lp           # [b, K, V]
                scores2, tokens2, caches2, parent, tok = self._beam_follow(
                    total, tokens, caches2, t)
                alive2 = jnp.take_along_axis(
                    alive[..., None], parent[..., None],
                    axis=1)[..., 0] & (tok != eos_id)
                return (caches2, tokens2, scores2, alive2), 0

            n_steps = max_len - plen - 1
            (caches, tokens, scores, alive), _ = jax.lax.scan(
                step, (caches, tokens, scores, alive),
                jnp.arange(1, n_steps + 1))
            return tokens, scores

        return jax.jit(run)

    def beam_search(self, prompt, max_len: int, beam_size: int = 4,
                    eos_id: int = 0, num_results: Optional[int] = None,
                    length_penalty: float = 0.0):
        """prompt [b, P] -> per-sample n-best [(score, tokens), ...],
        best first — the transformer analogue of the recurrent zoo's
        `beam_search` layer (scores are summed token log-probs; finished
        beams freeze at their EOS). Rows are trimmed at the first EOS.

        length_penalty alpha > 0 runs FULL GNMT semantics in-device
        (_build_beam_gnmt): a hypothesis that emits EOS is banked with
        its penalized score score/len^alpha inside the search, freeing
        its lane — so short high-scoring hypotheses survive the beam,
        and the returned scores are the penalized ones. alpha = 0 keeps
        the raw-sum search."""
        import numpy as np
        prompt = jnp.asarray(prompt, jnp.int32)
        plen = self._validate(prompt, max_len)
        n_keep = num_results if num_results is not None else beam_size
        assert 1 <= n_keep <= beam_size, (
            f"num_results={num_results} must be in [1, beam_size]")
        assert length_penalty >= 0.0, length_penalty
        key = ("beam", plen, int(max_len), beam_size, eos_id,
               float(length_penalty))
        if key not in self._jitted:
            if length_penalty > 0.0:
                self._jitted[key] = self._build_beam_gnmt(
                    plen, int(max_len), beam_size, eos_id,
                    float(length_penalty))
            else:
                self._jitted[key] = self._build_beam(plen, int(max_len),
                                                     beam_size, eos_id)
        toks, scores = self._jitted[key](self.p, prompt)
        toks, scores = np.asarray(toks), np.asarray(scores)
        out = []
        for bi in range(toks.shape[0]):
            rows = []
            for ki in range(toks.shape[1]):
                row = list(map(int, toks[bi, ki]))
                if eos_id in row:
                    row = row[:row.index(eos_id) + 1]
                # gnmt path returns penalized scores already
                rows.append((float(scores[bi, ki]), row))
            out.append(rows[:n_keep])
        return out

    def paged(self, **kw) -> "PagedDecoder":
        """A fixed-shape paged-KV decode step over this decoder's
        parameter table (the serving engine's hot path); the keywords
        are PagedDecoder's."""
        return PagedDecoder(self, **kw)

    def generate(self, prompt, max_len: int,
                 temperature: Optional[float] = None,
                 rng: Optional[jax.Array] = None,
                 eos_id: Optional[int] = None):
        """prompt [b, P] int32 -> per-row generated ids (length
        max_len - P, trimmed at eos_id when given).

        temperature None = greedy argmax; otherwise categorical at the
        given temperature. max_len bounds prompt + generation (the KV
        cache size)."""
        import numpy as np
        prompt = jnp.asarray(prompt, jnp.int32)
        plen = self._validate(prompt, max_len)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        key = (plen, int(max_len), temperature)
        if key not in self._jitted:
            self._jitted[key] = self._build(plen, int(max_len), temperature)
        out = np.asarray(self._jitted[key](self.p, prompt, rng))
        if eos_id is None:
            return [list(map(int, row)) for row in out]
        rows = []
        for row in out:
            hit = np.where(row == eos_id)[0]
            rows.append(list(map(int, row[:hit[0] + 1] if len(hit) else row)))
        return rows


def _run(dec, which: str, *args):
    """Dispatch ``dec``'s jitted program ``which`` through the executable
    ladder (paddle_tpu/artifacts), resolved on first use."""
    exe = getattr(dec, f"_{which}_exe")
    if exe is None:
        from paddle_tpu.artifacts import resolve
        exe = resolve(getattr(dec, f"_{which}_fp"), getattr(dec, f"_{which}"),
                      args, warm=dec.warm_start)
        setattr(dec, f"_{which}_exe", exe)
    return exe(*args)


class PagedDecoder:
    """One fixed-shape, slot-batched decode step over a PAGED KV cache.

    The dense-cache decoder above allocates its caches PER REQUEST BATCH
    and marches the whole batch in lockstep — padding every sequence's
    cache read to the longest, and recompiling per (batch, prompt_len).
    This class is the serving replacement: the cache lives in shared
    preallocated POOLS of fixed-size pages whose rows, layout, write and
    read are the block's cache kind's (``self.cache``, models/block.py),
    updated IN PLACE: the step scatters its S*W new rows into the donated
    pools and hands them whole to the kernel, so the compiled step holds
    no pool-sized copy and no per-layer slice. Each slot of a fixed-size
    slot batch owns a page-table row mapping its logical positions to
    physical pages. Requests join and leave mid-flight by editing the
    small int32 inputs (tokens / positions / page tables / active mask) —
    the jitted step's shapes NEVER change, so continuous batching costs
    zero recompiles (@recompile_budget in tests/test_paged_decode.py).

    Numerics are the dense path's, by construction (the same description,
    and the gather path's exact dense einsum over the gathered page view):
    greedy paged decode is token-identical to ``generate``
    (tests/test_paged_decode.py). Scheduling (slots, page alloc/free,
    eviction) is host-side policy in serving/engine.py; this class is only
    the device step. Physical page 0 is RESERVED as the null page:
    inactive slots write their (discarded) rows there and unassigned
    page-table entries point at it, which keeps the scatter and gather
    unconditional — no shape-changing branches.

    ``window`` > 1 widens the step to W tokens PER SLOT per dispatch —
    one fixed [S, W] shape that serves multi-token prompt
    teacher-forcing, the speculative verify window (the pending token +
    k draft proposals in, W argmaxes out — serving/engine.py) and the
    one-token step (masked columns) with zero extra compiles. In-window
    causality holds because every window token's row is scattered into
    the pool BEFORE attention and each token's kv_len masks later
    positions.

    PREFILL LANES are that same property spent on prompts: beside its
    [S, W] slot group the lane program (:meth:`_step_impl` with
    ``lanes``) takes a second fixed-shape group of rows, ``Sp`` lanes of
    ``C`` tokens, each lane a chunk of ONE slot's prompt at consecutive
    positions, read and written through that slot's page-table row (a
    slot may hold several lanes, one after the other: one longer
    chunk). What reads weights runs once over the rows of both groups,
    the cache kind's write and read once a group, and the head over the
    slot group's rows and each lane's LAST row. The lanes' shape is the
    cache kind's (``self.lanes``, the widest window its paged read
    takes), never a caller's; the plain program is untouched by them and
    is what every step without a prompt chunk runs.

    ``attention`` selects the cache-read path: "gather" (the
    exact einsum over the full page view), "kernel" (the live-pages
    Pallas kernel — ops/pallas_decode.py), or "auto" (kernel on TPU when
    supported, gather elsewhere). ``kv_quant="int8"`` asks the cache
    kind for int8 pages (PerHeadCache has them)."""

    def __init__(self, dense: TransformerDecoder, *, num_slots: int,
                 page_size: int, num_pages: int, max_pages_per_slot: int,
                 temperature: Optional[float] = None, window: int = 1,
                 attention: str = "auto", warm_start: bool = True,
                 kv_quant: Optional[str] = None,
                 state_snapshots: Optional[int] = None):
        assert num_pages >= 2, "need at least the null page + one real"
        assert max_pages_per_slot * page_size <= dense.max_positions, (
            "slot capacity exceeds the position table — positions past "
            "it would silently clamp to its last row")
        assert window >= 1, window
        assert attention in ("auto", "kernel", "gather"), attention
        assert kv_quant in (None, "int8"), kv_quant
        if kv_quant is not None:
            dense.require("kv_quant")
        self.kv_quant = kv_quant
        #: layers whose held experts' load the step sums, and the last
        #: step's sums (on the device)
        self.n_expert_layers = dense.block.n_expert_layers(dense.n_layers)
        self.expert_counts = None
        self.dense = dense
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.temperature = temperature
        self.window = int(window)
        #: the pools: their rows, layout, write and read (models/block.py)
        self.cache = dense.block.cache(
            dense.block, dense.p, dense._pre, n_layers=dense.n_layers,
            num_slots=self.num_slots, window=self.window,
            page_size=self.page_size, num_pages=self.num_pages,
            max_pages_per_slot=self.max_pages_per_slot, kv_quant=kv_quant,
            # a cache kind with a state a slot takes its snapshot rows
            **({} if state_snapshots is None
               else {"state_snapshots": state_snapshots}))
        self.dtype = self.cache.dtype
        on_tpu = jax.default_backend() == "tpu"
        self.use_kernel = attention == "kernel" or (
            attention == "auto" and on_tpu and self.cache.kernel_supported())
        self.kernel_interpret = self.use_kernel and not on_tpu
        #: (lanes, tokens a lane) of the lane program's prefill group
        self.lanes = self.cache.lanes()
        # donating the pools lets XLA update pages in place (the pools
        # ARE the device memory budget); the CPU backend has no donation
        # and would warn on every dispatch
        cpu = jax.default_backend() == "cpu"
        pools = dict(donate_argnums=() if cpu else (0, 1))
        # one step function, two programs: without and with the lane
        # group, the second under a name of its own
        donate = dict(donate_argnums=() if cpu else (1, 2))
        self._step = jax.jit(self._step_impl, **donate)
        self._lane_step = jax.jit(self._step_impl_lanes, **donate)
        # the feed, by the shape of the choices it reads: a plain step's
        # [S, W] or a lane step's flat [S*W + Sp]
        self._feed = self._lane_feed = jax.jit(self._feed_impl)
        self._copy = jax.jit(self._copy_page_impl, **pools)
        self._copy_state = jax.jit(self._copy_state_impl, **pools)
        self._read = jax.jit(self._read_page_impl)
        self._write = jax.jit(self._write_page_impl, **pools)
        # warm-start plane (paddle_tpu/artifacts): the jitted functions
        # resolve through the executable ladder on first dispatch (_run);
        # an artifact hit makes the engine's startup zero-compile. The
        # fingerprints name every knob that changes the compiled program,
        # the description and its cache kind among them
        self.warm_start = bool(warm_start)
        from paddle_tpu.artifacts import fingerprint
        what = dict(self.cache.plan, block=repr(dense.block),
                    kv_quant=self.kv_quant, page_size=self.page_size,
                    num_pages=self.num_pages)
        step_plan = dict(
            what, num_slots=self.num_slots, window=self.window,
            max_pages_per_slot=self.max_pages_per_slot,
            temperature=self.temperature, use_kernel=self.use_kernel,
            kernel_interpret=self.kernel_interpret)
        self._step_fp = fingerprint("paged_step", dense.p, plan=step_plan)
        self._lane_step_fp = fingerprint(
            "paged_lane_step", dense.p, plan=dict(step_plan,
                                                  lanes=self.lanes))
        page_plan = dict(what, n_layers=dense.n_layers,
                         dtype=str(jnp.dtype(self.dtype)))
        self._copy_fp, self._read_fp, self._write_fp, self._copy_state_fp = (
            fingerprint(f"paged_{which}", dense.p, plan=page_plan)
            for which in ("copy", "read", "write", "copy_state"))
        self._feed_fp, self._lane_feed_fp = (
            fingerprint(f"paged_{which}", dense.p, plan=dict(
                num_slots=self.num_slots, window=self.window,
                lanes=self.lanes))
            for which in ("feed", "lane_feed"))
        self._step_exe = self._lane_step_exe = self._copy_exe = None
        self._feed_exe = self._lane_feed_exe = None
        self._read_exe = self._write_exe = self._copy_state_exe = None

    def init_pools(self):
        """The cache kind's two pools, zeroed: whatever pytrees it says
        they are (K and V pages; a latent pool and nothing; page pools and
        a state's rows). Every method below names them ``k_pool, v_pool``
        after the first kind and passes both through whole: the kind's
        ``layer``, ``map_pages`` and ``map_state`` alone know the leaves."""
        return self.cache.init_pools()

    def pool_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree_util
                   .tree_leaves(jax.eval_shape(self.init_pools)))

    def _group(self, positions, active, page_tables, slots) -> PagedTokens:
        """One group's tokens as the cache kind reads them."""
        ps = self.page_size
        page_idx = jnp.take_along_axis(
            page_tables, positions // ps, axis=1)
        page_idx = jnp.where(active, page_idx, 0)       # null the dead
        offs = jnp.where(active, positions % ps, 0)
        kv_lens = positions + 1
        return PagedTokens(positions, active, page_idx, offs, page_tables,
                           kv_lens, jnp.where(active, kv_lens, 0), slots)

    def _step_impl(self, p, k_pool, v_pool, tokens, positions,
                   page_tables, active, key, lanes=None):
        """tokens/positions/active [S, W]; page_tables [S, P] int32 ->
        (next_tokens [S, W] int32, k_pool', v_pool'), or ((next_tokens,
        held load [2]), ...) from a block with expert layers. Column w is
        the model's choice after window tokens 0..w: the teacher-forced
        continuation AND the speculative verify verdict in one read.

        ``lanes`` [Sp, 3 + C] int32 makes it the lane program's body
        (:meth:`_step_impl_lanes`): a row is
        one lane, (the slot whose page-table row it reads and writes, its
        first position, how many of its C tokens are fed, the tokens).
        next_tokens is then flat, [S*W + Sp]: the slot group's choices
        row by row, then each lane's choice after its LAST fed token."""
        d0 = self.dense
        blk, pre = d0.block, d0._pre
        groups = [(tokens, positions, active, page_tables,
                   jnp.arange(tokens.shape[0], dtype=jnp.int32))]
        if lanes is not None:
            col = jnp.arange(lanes.shape[1] - 3)[None, :]
            fed = col < lanes[:, 2:3]
            groups.append((lanes[:, 3:],
                           jnp.where(fed, lanes[:, 1:2] + col, 0), fed,
                           page_tables[lanes[:, 0]], lanes[:, 0]))
        with jax.named_scope("embed"):
            x = blk.embed(p, pre, join_rows([g[0] for g in groups]),
                          join_rows([g[1] for g in groups]))   # [S, W, d]
        toks = tuple(self._group(*g[1:]) for g in groups)
        layer = functools.partial(self.cache.layer,
                                  use_kernel=self.use_kernel,
                                  interpret=self.kernel_interpret)
        if lanes is not None and self.cache.layer_operand:
            # a second program is seconds of set-up: this one traces and
            # lowers one layer for all of a kind (the plain program is
            # held to the text it had, a layer after the other)
            layer = shared_layers(layer, pre)
        loads = []
        for i in range(d0.n_layers):
            x, k_pool, v_pool, load = layer(p, i, x, k_pool, v_pool, toks)
            if load is not None:
                loads.append(load)
        if lanes is not None:
            # the head reads a lane's last fed row alone: no other row's
            # choice is ever used
            x, x_lanes = split_rows(toks, x)
            last = jnp.maximum(lanes[:, 2] - 1, 0)[:, None, None]
            x = join_rows([x, jnp.take_along_axis(x_lanes, last, axis=1)])
        with jax.named_scope("logits"):
            nxt = _sample(blk.logits(p, pre, x), self.temperature, key)
        if lanes is not None:
            nxt = nxt[0]
        if loads:
            # two small sums over the step's expert layers ride beside
            # the tokens: (assignments on held experts, held experts hit)
            nxt = (nxt, sum(loads))
        return nxt, k_pool, v_pool

    def _step_impl_lanes(self, p, k_pool, v_pool, tokens, positions,
                         page_tables, active, lanes, key):
        """The lane program, under a name of its own (two programs of one
        process are told apart by name): :meth:`_step_impl` with its
        ``lanes``."""
        return self._step_impl(p, k_pool, v_pool, tokens, positions,
                               page_tables, active, key, lanes)

    @staticmethod
    def _feed_impl(prev, src, tokens):
        """The next step's ``tokens`` [S, W], column 0 of row s taken from
        ``prev`` (the choices of the step before: [S, W], or the lane
        program's flat [S*W + Sp]) at flat row ``src[s]`` where that is
        not negative, the host's own token elsewhere. A program of its
        own: both step programs keep their text, and the choices never
        leave the device between two steps (serving/engine.py, the step
        in flight)."""
        fed = prev.reshape(-1)[jnp.maximum(src, 0)]
        return tokens.at[:, 0].set(jnp.where(src >= 0, fed, tokens[:, 0]))

    @staticmethod
    def _page_slice(leaf, page):
        """[L, 1, ...] view of one physical page in the stored layout —
        rank-generic, so it covers the value leaves [L, N, ps, g*dh]
        and the int8 layout's scale leaves [L, N, ps, g] alike."""
        start = (0, page) + (0,) * (leaf.ndim - 2)
        return jax.lax.dynamic_slice(
            leaf, start, (leaf.shape[0], 1) + leaf.shape[2:])

    @staticmethod
    def _page_update(leaf, data, page):
        start = (0, page) + (0,) * (leaf.ndim - 2)
        return jax.lax.dynamic_update_slice(
            leaf, data.reshape((leaf.shape[0], 1) + leaf.shape[2:])
            .astype(leaf.dtype), start)

    def _copy_rows(self, mapped, k_pool, v_pool, src, dst):
        """Index ``src`` of axis 1 -> index ``dst``, all layers, in every
        leaf that ``mapped`` (a map of the cache kind's) reaches."""
        def cp(pool):
            return mapped(lambda leaf: self._page_update(
                leaf, self._page_slice(leaf, src), dst), pool)

        return cp(k_pool), cp(v_pool)

    def _copy_page_impl(self, k_pool, v_pool, src, dst):
        """Device-side page copy (all layers) — the copy-on-write step
        behind partial-page prefix reuse (serving/prefix.py). src/dst
        are TRACED scalars: every pair shares ONE compilation. Mapped
        over the pool pytree: the int8 layout copies values AND scales."""
        return self._copy_rows(self.cache.map_pages, k_pool, v_pool, src,
                               dst)

    def _copy_state_impl(self, k_pool, v_pool, src, dst):
        """Row ``src`` of a cache kind's state pool -> row ``dst``, all
        layers, in place (a snapshot taken or given back; a slot's row
        zeroed from the kind's zero row). Traced rows: ONE compilation."""
        return self._copy_rows(self.cache.map_state, k_pool, v_pool, src,
                               dst)

    def _read_page_impl(self, k_pool, v_pool, page):
        """Device -> host leg of page spill (serving/spill.py): one
        physical page of both pools in the cache kind's payload shape.
        ``page`` is a traced scalar: one compilation covers every spill."""
        rd = lambda pool: self.cache.page_payload(self.cache.map_pages(
            lambda leaf: self._page_slice(leaf, page), pool))
        return rd(k_pool), rd(v_pool)

    def _write_page_impl(self, k_pool, v_pool, k_page, v_page, page):
        """Host -> device leg of page restore: the inverse of
        :meth:`_read_page_impl`."""
        wr = lambda pool, data: self.cache.map_pages(
            lambda leaf, d: self._page_update(leaf, d, page),
            pool, data)
        return wr(k_pool, k_page), wr(v_pool, v_page)

    def copy_page(self, k_pool, v_pool, src: int, dst: int):
        """Copy physical page ``src`` -> ``dst`` in both pools."""
        return _run(self, "copy", k_pool, v_pool, jnp.int32(src),
                    jnp.int32(dst))

    def copy_state(self, k_pool, v_pool, src: int, dst: int):
        """Copy state row ``src`` -> ``dst`` (a cache kind with
        ``state_rows``)."""
        import numpy as np
        # numpy scalars: a jnp scalar is a device array made by a jitted
        # convert, half a millisecond of host time each (my chip run, PR 39)
        return _run(self, "copy_state", k_pool, v_pool, np.int32(src),
                    np.int32(dst))

    def read_page(self, k_pool, v_pool, page: int):
        """One physical page of both pools as [L, 1, ...] pytrees —
        the spill store's device->host read (serving/engine.py)."""
        return _run(self, "read", k_pool, v_pool, jnp.int32(page))

    def write_page(self, k_pool, v_pool, k_page, v_page, page: int):
        """Write [L, 1, ...] page pytrees back into physical ``page``
        of both pools — the restore leg of page spill."""
        return _run(self, "write", k_pool, v_pool, k_page, v_page,
                    jnp.int32(page))

    def feed(self, prev, src, tokens):
        """``tokens`` [S, W] with the rows that ``src`` [S] names fed from
        ``prev``, the device's array of the last step's choices
        (:meth:`_feed_impl`): what :meth:`step` then takes as its
        ``tokens``, still on the device."""
        return _run(self, "feed" if prev.ndim == 2 else "lane_feed", prev,
                    jnp.asarray(src, jnp.int32),
                    jnp.asarray(tokens, jnp.int32))

    def step(self, k_pool, v_pool, tokens, positions, page_tables,
             active, key=None, lanes=None):
        """Dispatch one decode step: the classic [S] one-token arrays
        (returns next tokens [S]) or the [S, W] window contract (returns
        [S, W]); with ``lanes`` ([Sp, 3 + C], :meth:`_step_impl`) the lane
        program (returns the flat [S*W + Sp]). Each program compiles
        exactly once for the engine's lifetime — joins/evictions/window
        and lane occupancy only change VALUES."""
        if key is None:
            key = jax.random.PRNGKey(0)
        tokens = jnp.asarray(tokens, jnp.int32)
        squeeze = tokens.ndim == 1
        if squeeze:
            assert self.window == 1 and lanes is None, (
                "one-token [S] arrays only drive a window=1 decoder")
            tokens = tokens[:, None]
            positions = jnp.asarray(positions, jnp.int32)[:, None]
            active = jnp.asarray(active, jnp.bool_)[:, None]
        small = (tokens, jnp.asarray(positions, jnp.int32),
                 jnp.asarray(page_tables, jnp.int32),
                 jnp.asarray(active, jnp.bool_))
        if lanes is None:
            nxt, k_pool, v_pool = _run(
                self, "step", self.dense.p, k_pool, v_pool, *small, key)
        else:
            nxt, k_pool, v_pool = _run(
                self, "lane_step", self.dense.p, k_pool, v_pool, *small,
                jnp.asarray(lanes, jnp.int32), key)
        if self.n_expert_layers:
            # the engine fetches it with the tokens (one sync)
            nxt, self.expert_counts = nxt
        if squeeze:
            nxt = nxt[:, 0]
        return nxt, k_pool, v_pool


class DraftDecoder:
    """The DRAFT side of speculative decoding: a small decoder over
    slot-PRIVATE dense caches, window-batched like PagedDecoder.

    The draft never shares the paged pool or the prefix trie — each
    slot owns a [T+1]-row per-head K/V lane (row T is the null row,
    mirroring the paged null page), and the engine teacher-forces the
    slot's committed tokens through it before asking for proposals.
    That keeps draft-cache coherence trivially correct under prefix
    hits, CoW, eviction and rejected speculation: the engine tracks how
    many committed tokens the draft has FED (draft_pos), rolls it back
    past rejected proposals and re-feeds, so every row is rewritten
    before a query's kv_len can reach it. Greedy argmax only: the
    target's token-identity acceptance needs deterministic proposals.

    ONE jitted [S, W] step serves catch-up (feed up to W committed
    tokens) and proposal (feed 1 token, read its argmax) — zero extra
    compiles under churn, same contract as the target step."""

    def __init__(self, dense: TransformerDecoder, *, num_slots: int,
                 max_seq_len: int, window: int = 1,
                 warm_start: bool = True):
        dense.require("draft")
        assert max_seq_len <= dense.max_positions, max_seq_len
        self.dense = dense
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len)
        self.window = int(window)
        _, self.kv_heads, self.head_dim = dense.block.heads(dense.p,
                                                            dense._pre)
        self.dtype = dense.block.table_dtype(dense.p, dense._pre)
        donate = () if jax.default_backend() == "cpu" else (1, 2)
        self._step = jax.jit(self._step_impl, donate_argnums=donate)
        self.warm_start = bool(warm_start)
        from paddle_tpu.artifacts import fingerprint
        self._step_fp = fingerprint("draft_step", dense.p, plan=dict(
            block=repr(dense.block), num_slots=self.num_slots,
            max_seq_len=self.max_seq_len, window=self.window))
        self._step_exe = None

    def init_caches(self):
        """Zeroed (k, v), each [L, S, T+1, g, dh] — row T is the null
        row masked tokens write to (never read: kv_len <= T)."""
        shape = (self.dense.n_layers, self.num_slots,
                 self.max_seq_len + 1, self.kv_heads, self.head_dim)
        return jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype)

    def _step_impl(self, p, kc, vc, tokens, positions, active):
        """tokens/positions/active [S, W] -> (argmax [S, W], kc', vc').
        Norms, projections, FFN and head are the description's; the
        slot-private lanes and the attention over them are this class's."""
        d0 = self.dense
        blk, pre, g = d0.block, d0._pre, self.kv_heads
        S, W = tokens.shape
        T1 = self.max_seq_len + 1
        rows = jnp.arange(S)[:, None]
        wpos = jnp.where(active, positions, self.max_seq_len)
        fed = jnp.where(active, positions, 0)
        x = blk.embed(p, pre, tokens, fed)
        kv_lens = positions + 1                          # [S, W]
        tpos = jnp.arange(T1)
        mask = tpos[None, None, :] < kv_lens[:, :, None]  # [S, W, T1]
        for i in range(d0.n_layers):
            q, k, v = blk.qkv(p, pre, i, x, fed)         # [S, W, h | g, dh]
            kc = kc.at[i, rows, wpos].set(k.astype(kc.dtype))
            vc = vc.at[i, rows, wpos].set(v.astype(vc.dtype))
            dh = q.shape[-1]
            q5 = q.reshape(S, W, g, q.shape[2] // g, dh)
            logits = jnp.einsum("swgrd,stgd->sgrwt", q5,
                                kc[i].astype(q.dtype)) * (dh ** -0.5)
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            w_ = jax.nn.softmax(logits, axis=-1)
            attn = jnp.einsum("sgrwt,stgd->swgrd", w_,
                              vc[i].astype(q.dtype))
            x = x + blk.project(p, pre, i, attn.reshape(x.shape))
            x = blk.ffn(p, pre, i, x)[0]
        logits = blk.logits(p, pre, x)                   # [S, W, V]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kc, vc

    def step(self, kc, vc, tokens, positions, active):
        return _run(self, "step", self.dense.p, kc, vc,
                    jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(positions, jnp.int32),
                    jnp.asarray(active, jnp.bool_))
