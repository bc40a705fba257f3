"""Incremental (KV-cache) decoding for the transformer LM.

The recurrent zoo generates through `beam_search` (the dynamic
RecurrentGradientMachine parity path); the transformer needs the modern
equivalent: a jit-compiled autoregressive loop that carries per-layer
K/V caches instead of re-running the prefix every step. This module
reimplements `models.transformer.transformer_lm`'s forward functionally
over the SAME parameter table (the DSL fixes parameter names, so a
trained `Parameters` dict drops straight in); `tests/test_decode.py`
pins step-wise logits against the training graph token for token.

TPU shape discipline: one compilation per (batch, prompt_len, max_len,
temperature) combination — the prompt prefills in a single batched
causal pass (one big MXU matmul chain), then `lax.scan` extends one
token at a time with `dynamic_update_slice` into fixed-size caches.
Parameters are a jit argument (not trace constants), so one decoder
serves updated parameter tables without retracing.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.ops import moe as moe_ops


def _ln(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      - mean * mean, 0.0)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * g + b).astype(x.dtype)


def _heads(x, h):
    return x.reshape(x.shape[:-1] + (h, x.shape[-1] // h))


class TransformerDecoder:
    """Greedy / temperature sampling with per-layer KV caches.

    params: the training-side parameter dict (Parameters.raw or
    Topology.init_params output). Config args mirror transformer_lm."""

    def __init__(self, params, *, n_layers: int, n_heads: int,
                 name: str = "tfm", moe_k: int = 2,
                 moe_capacity_factor: Optional[float] = None,
                 block=None):
        prefix = f"_{name}"
        # the block description (models/block.py): None is the block the
        # layer DSL trains, written out below; a LatentBlock replaces
        # embed / attention / FFN / head here and in PagedDecoder's step
        self.block = block
        self._pre = f"_{name}_"
        self.p = {k: jnp.asarray(v) for k, v in params.items()
                  if k.startswith(prefix)}
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.name = name
        # MoE blocks are auto-detected from the parameter table (and
        # expert_num comes from the gate's shape), but k is NOT
        # recoverable from it: moe_k MUST match the training config or
        # decode silently diverges. moe_capacity_factor=None (the
        # default) routes DROP-FREE at inference — capacity = each
        # call's full token count, so decode matches the training
        # forward whenever training itself dropped nothing (the
        # capacity limit only buys memory/balance at training scale).
        # Set a float to reproduce a training capacity limit exactly.
        self.moe_k = moe_k
        self.moe_capacity_factor = moe_capacity_factor
        self._jitted = {}

    # ---------------------------------------------------------------- core
    @staticmethod
    def _use_flash_prefill(t, pos, dh) -> bool:
        """Flash-prefill gate: a long (>=256) prompt on TPU with a
        tile-friendly head dim, and the cache empty before this call
        (pos is the static int 0 at prefill; decode steps pass traced
        scalars and fall through to the einsum path)."""
        from paddle_tpu.config import global_config
        from paddle_tpu.ops import pallas_attention as flash
        probe = jax.ShapeDtypeStruct((1, t, 1, dh), jnp.float32)
        return (isinstance(pos, int) and pos == 0 and t >= 256
                and flash.flash_supported(probe, probe)
                and global_config().use_flash_attention
                and jax.default_backend() == "tpu")

    @property
    def max_positions(self) -> int:
        """Positions the model can address: the learned table's rows,
        or what a rotary block's description states."""
        if self.block is not None:
            return int(self.block.max_positions)
        return int(self.p[f"_{self.name}_pos_emb.w0"].shape[0])

    def _embed(self, p, ids, pos):
        if self.block is not None:
            return self.block.embed(p, self._pre, ids)
        n = self.name
        return (p[f"_{n}_tok_emb.w0"][ids]
                + p[f"_{n}_pos_emb.w0"][pos])

    def _block(self, p, i, x, k_cache, v_cache, pos, kv_len):
        """One decoder block over a [b, t, d] slice; reads/extends the
        [b, T, h, dh] caches at positions [pos, pos+t)."""
        if self.block is not None:
            return self._latent_block(p, i, x, k_cache, v_cache, pos,
                                      kv_len)
        n, h = self.name, self.n_heads
        ln1 = _ln(x, p[f"_{n}_l{i}_ln1.w0"], p[f"_{n}_l{i}_ln1.wbias"])
        q = _heads(ln1 @ p[f"_{n}_l{i}_q.w0"], h)
        dh = q.shape[-1]
        kv_h = k_cache.shape[2]
        k = _heads(ln1 @ p[f"_{n}_l{i}_k.w0"], kv_h)
        v = _heads(ln1 @ p[f"_{n}_l{i}_v.w0"], kv_h)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
        t = x.shape[1]
        T = k_cache.shape[1]
        scale = dh ** -0.5
        rep = h // kv_h
        if self._use_flash_prefill(t, pos, dh):
            # LONG-prompt prefill: the einsum path materializes a
            # [b,g,rep,t,t] score tensor (quadratic HBM); the flash
            # kernel streams K/V blocks instead. Only valid when the
            # cache holds nothing before this call (pos == 0), i.e.
            # attention is causal over exactly these t positions. GQA
            # repeats K/V here — a one-time prefill cost, never paid
            # per decode step.
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
            attn = attn.reshape(x.shape)
        else:
            # grouped-query: q [b,t,(kv_h, rep),dh] against kv_h-head
            # caches — the cache is read at stored width, never repeated
            q5 = q.reshape(q.shape[0], t, kv_h, rep, dh)
            logits = jnp.einsum("bqgrd,bkgd->bgrqk", q5,
                                k_cache.astype(q.dtype)) * scale
            # causal against absolute positions: query row j is at pos+j
            qpos = pos + jnp.arange(t)[:, None]
            kpos = jnp.arange(T)[None, :]
            mask = (kpos <= qpos) & (kpos < kv_len)
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            attn = jnp.einsum("bgrqk,bkgd->bqgrd", w,
                              v_cache.astype(q.dtype))
            attn = attn.reshape(x.shape)
        x = x + attn @ p[f"_{n}_l{i}_proj.w0"]
        return self._ffn(p, i, x), k_cache, v_cache

    def _latent_block(self, p, i, x, c_cache, r_cache, pos, kv_len):
        """A LatentBlock layer over [b, t, d]: the caches hold one
        [b, T, rkv] latent and one [b, T, dr] rotary-key row a token; the
        expanded (unabsorbed) attention reads them (the paged step uses
        the absorbed form; tests hold the two to each other)."""
        blk, pre = self.block, self._pre
        t, T = x.shape[1], c_cache.shape[1]
        qpos = pos + jnp.arange(t)
        q_nope, q_rope, c_kv, k_rope = blk.qkv(
            p, pre, i, x, jnp.broadcast_to(qpos[None], x.shape[:2]))
        c_cache = jax.lax.dynamic_update_slice(
            c_cache, c_kv.astype(c_cache.dtype), (0, pos, 0))
        r_cache = jax.lax.dynamic_update_slice(
            r_cache, k_rope.astype(r_cache.dtype), (0, pos, 0))
        kpos = jnp.arange(T)[None, :]
        mask = (kpos <= qpos[:, None]) & (kpos < kv_len)
        attn = blk.attend(p, pre, i, q_nope, q_rope, c_cache, r_cache,
                          jnp.broadcast_to(mask[None], (x.shape[0], t, T)),
                          absorbed=False)
        x = x + blk.project(p, pre, i, attn)
        return self._ffn(p, i, x), c_cache, r_cache

    def _ffn(self, p, i, x):
        """ln2 + FFN (dense or MoE) + residual over [b, t, d] — shared
        between the dense-cache block and the paged step (PagedDecoder),
        so the two paths cannot drift numerically."""
        if self.block is not None:
            return self.block.ffn(p, self._pre, i, x)[0]
        n = self.name
        ln2 = _ln(x, p[f"_{n}_l{i}_ln2.w0"], p[f"_{n}_l{i}_ln2.wbias"])
        if f"_{n}_l{i}_moe.gate" in p:
            b_, t_, d_ = ln2.shape
            cf = self.moe_capacity_factor
            cap = None
            if cf is None:
                gate = p[f"_{n}_l{i}_moe.gate"]
                cap = b_ * t_
                # drop-free routing materializes [n, E, C=n] dispatch
                # tensors — quadratic in tokens. Cheap for the per-step
                # call (n = batch); for a LARGE prefill fall back to a
                # generous factor instead of OOMing the chip.
                if cap * cap * gate.shape[-1] > (1 << 27):
                    import warnings
                    warnings.warn(
                        f"moe prefill with {cap} tokens: drop-free "
                        "routing would need a "
                        f"[{cap},{gate.shape[-1]},{cap}] dispatch "
                        "tensor; falling back to capacity_factor=2.0 "
                        "(set moe_capacity_factor explicitly to "
                        "choose)", stacklevel=2)
                    cap, cf = None, 2.0
            y2d, _ = moe_ops.moe_ffn(
                ln2.reshape(b_ * t_, d_), None,
                p[f"_{n}_l{i}_moe.gate"], p[f"_{n}_l{i}_moe.moe_up"],
                p[f"_{n}_l{i}_moe.moe_down"], k=self.moe_k,
                capacity_factor=cf if cf is not None else 1.25,
                capacity=cap, dispatch_mode="auto")
            x = x + y2d.reshape(b_, t_, d_)
        else:
            up = jax.nn.relu(ln2 @ p[f"_{n}_l{i}_up.w0"]
                             + p[f"_{n}_l{i}_up.wbias"])
            x = x + up @ p[f"_{n}_l{i}_down.w0"]
        return x

    def _logits(self, p, x):
        if self.block is not None:
            return self.block.logits(p, self._pre, x)
        n = self.name
        x = _ln(x, p[f"_{n}_lnf.w0"], p[f"_{n}_lnf.wbias"])
        if f"_{n}_head.w0" in p:
            logits = x @ p[f"_{n}_head.w0"]
        else:  # tie_embeddings: the head IS the token table, transposed
            logits = x @ p[f"_{n}_tok_emb.w0"].T
        if f"_{n}_head.wbias" in p:  # older checkpoints carried a bias
            logits = logits + p[f"_{n}_head.wbias"]
        return logits

    def _forward(self, p, ids, pos, caches, cache_pos, kv_len):
        """ids [b, t] -> (logits [b, t, V], caches')."""
        x = self._embed(p, ids, pos)
        new_caches = []
        for i, (kc, vc) in enumerate(caches):
            x, kc, vc = self._block(p, i, x, kc, vc, cache_pos, kv_len)
            new_caches.append((kc, vc))
        return self._logits(p, x), new_caches

    def _prefill(self, p, prompt, plen, max_len):
        """Allocate the fixed-size caches and run the one batched causal
        pass over the prompt. -> (last-position logits path input, caches)."""
        n, h = self.name, self.n_heads
        b = prompt.shape[0]
        d = p[f"_{n}_tok_emb.w0"].shape[1]
        dtype = p[f"_{n}_tok_emb.w0"].dtype
        if self.block is not None:
            caches = [tuple(jnp.zeros((b, max_len, w), dtype) for w in
                            self.block.cache_widths(p, self._pre))
                      for _ in range(self.n_layers)]
            return self._forward(p, prompt, None, caches, 0, plen)
        # kv head count from the k projection's width (grouped-query
        # attention stores kv_h-sized caches — THE decode win of GQA)
        dh = d // h
        kv_h = p[f"_{n}_l0_k.w0"].shape[1] // dh
        caches = [(jnp.zeros((b, max_len, kv_h, dh), dtype),
                   jnp.zeros((b, max_len, kv_h, dh), dtype))
                  for _ in range(self.n_layers)]
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
        def sample(lg, key):
            if temperature is None:
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, lg.astype(jnp.float32) / temperature).astype(jnp.int32)

        def run(p, prompt, rng):
            b = prompt.shape[0]
            logits, caches = self._prefill(p, prompt, plen, max_len)
            k0, rng = jax.random.split(rng)
            first = sample(logits[:, -1], k0)

            def step(carry, key):
                caches, tok, pp = carry
                lg, caches = self._forward(
                    p, tok[:, None], jnp.full((b, 1), pp, jnp.int32),
                    caches, pp, pp + 1)
                return (caches, sample(lg[:, -1], key), pp + 1), tok

            n_steps = max_len - plen - 1
            keys = jax.random.split(rng, n_steps) if n_steps > 0 else \
                jnp.zeros((0, 2), jnp.uint32)
            (_, last_tok, _), toks = jax.lax.scan(
                step, (caches, first, jnp.int32(plen)), keys)
            return jnp.concatenate(
                [toks.transpose(1, 0), last_tok[:, None]], axis=1)

        return jax.jit(run)

    # ---------------------------------------------------------- beam search
    def _build_beam_gnmt(self, plen: int, max_len: int, beam_size: int,
                         eos_id: int, alpha: float):
        """Full GNMT beam semantics: a hypothesis that emits EOS leaves
        the beam and is BANKED with its length-penalized score
        (raw / len^alpha) inside the scan, freeing its lane for live
        continuations — a short high-scoring hypothesis can therefore
        never be pruned mid-search by longer raw-sum rivals (the
        limitation of the raw-sum path below, which length_penalty=0
        keeps). Returns (tokens [b,K,L], penalized scores [b,K]),
        best first."""
        n = self.name
        K = beam_size
        L = max_len - plen

        def run(p, prompt):
            b = prompt.shape[0]
            V = p[f"_{n}_head.w0"].shape[1] if f"_{n}_head.w0" in p \
                else p[f"_{n}_tok_emb.w0"].shape[0]
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
            caches = [(jnp.repeat(kc, K, axis=0), jnp.repeat(vc, K, axis=0))
                      for kc, vc in caches]
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
                last = tokens[:, :, t - 1].reshape(b * K)
                lg, caches2 = self._forward(
                    p, last[:, None],
                    jnp.full((b * K, 1), plen + t - 1, jnp.int32),
                    caches, plen + t - 1, plen + t)
                lp = jax.nn.log_softmax(
                    lg[:, -1].astype(jnp.float32)).reshape(b, K, V)
                # bank each lane's EOS continuation (length t+1 with eos)
                eos_raw = scores + lp[:, :, eos_id]
                eos_pen = eos_raw / (t + 1.0) ** alpha
                cand_t = tokens.at[:, :, t].set(eos_id)
                bank_s, bank_t = merge_bank(bank_s, bank_t, eos_pen,
                                            cand_t)
                # live lanes continue over non-EOS tokens only
                lp = jnp.where(vmask[None, None], -1e30, lp)
                total = scores[:, :, None] + lp
                scores2, flat = jax.lax.top_k(total.reshape(b, K * V), K)
                parent = flat // V
                tok = (flat % V).astype(jnp.int32)
                tokens2 = jnp.take_along_axis(
                    tokens, parent[:, :, None], axis=1).at[:, :, t].set(tok)
                pflat = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
                caches2 = [(kc[pflat], vc[pflat]) for kc, vc in caches2]
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
        n = self.name
        K = beam_size

        def run(p, prompt):
            b = prompt.shape[0]
            V = p[f"_{n}_head.w0"].shape[1] if f"_{n}_head.w0" in p \
                else p[f"_{n}_tok_emb.w0"].shape[0]
            logits, caches = self._prefill(p, prompt, plen, max_len)
            lp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
            # seed K lanes with the top-K first tokens
            scores, tok0 = jax.lax.top_k(lp0, K)          # [b, K]
            caches = [(jnp.repeat(kc, K, axis=0), jnp.repeat(vc, K, axis=0))
                      for kc, vc in caches]               # [b*K, ...]
            tokens = jnp.full((b, K, max_len - plen), eos_id, jnp.int32)
            tokens = tokens.at[:, :, 0].set(tok0)
            alive = tok0 != eos_id                        # [b, K]

            def step(carry, t):
                caches, tokens, scores, alive = carry
                last = tokens[:, :, t - 1].reshape(b * K)
                lg, caches2 = self._forward(
                    p, last[:, None],
                    jnp.full((b * K, 1), plen + t - 1, jnp.int32),
                    caches, plen + t - 1, plen + t)
                lp = jax.nn.log_softmax(
                    lg[:, -1].astype(jnp.float32)).reshape(b, K, V)
                # finished beams: only the eos continuation, at no cost —
                # the lane's score freezes and it keeps emitting eos
                frozen = jnp.full((V,), -1e30).at[eos_id].set(0.0)
                lp = jnp.where(alive[:, :, None], lp, frozen[None, None])
                total = scores[:, :, None] + lp           # [b, K, V]
                scores2, flat = jax.lax.top_k(total.reshape(b, K * V), K)
                parent = flat // V                        # [b, K]
                tok = (flat % V).astype(jnp.int32)
                # reorder histories + caches to follow the winning parents
                gather = lambda a: jnp.take_along_axis(a, parent[..., None],
                                                       axis=1)
                tokens2 = jnp.take_along_axis(
                    tokens, parent[:, :, None], axis=1).at[:, :, t].set(tok)
                pflat = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
                caches2 = [(kc[pflat], vc[pflat]) for kc, vc in caches2]
                alive2 = gather(alive[..., None])[..., 0] & (tok != eos_id)
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

    def paged(self, *, num_slots: int, page_size: int,
              num_pages: int, max_pages_per_slot: int,
              temperature: Optional[float] = None,
              window: int = 1,
              attention: str = "auto",
              warm_start: bool = True,
              kv_quant: Optional[str] = None) -> "PagedDecoder":
        """A fixed-shape paged-KV decode step over this decoder's
        parameter table (the serving engine's hot path)."""
        return PagedDecoder(self, num_slots=num_slots,
                            page_size=page_size, num_pages=num_pages,
                            max_pages_per_slot=max_pages_per_slot,
                            temperature=temperature, window=window,
                            attention=attention, warm_start=warm_start,
                            kv_quant=kv_quant)

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


class PagedDecoder:
    """One fixed-shape, slot-batched decode step over a PAGED KV cache.

    The dense-cache decoder above allocates a [b, max_len, g, dh] cache
    PER REQUEST BATCH and marches the whole batch in lockstep — padding
    every sequence's cache read to the longest, and recompiling per
    (batch, prompt_len) combination. This class is the serving
    replacement: K/V live in a shared preallocated POOL of fixed-size
    pages, stored for the engine's whole life in the layout the paged
    kernel's blocks read — [L, n_pages, page_size, g*dh], every kv head
    of a token side by side on the lane axis (a page of 16 x 2048 bf16
    is whole tiles, nothing padded) — and updated IN PLACE: the step
    scatters its S*W new rows into the donated pools and hands them
    whole to the kernel with the layer in the block index, so the
    compiled step holds no pool-sized copy and no per-layer slice. Each
    slot of a fixed-size slot batch owns a page-table row mapping its
    logical positions to physical pages. Requests join and leave
    mid-flight by editing the
    small int32 inputs (tokens / positions / page tables / active mask)
    — the jitted step's shapes NEVER change, so continuous batching
    costs zero recompiles (pinned by @recompile_budget in
    tests/test_paged_decode.py).

    Numerics are the dense path's, by construction: token embedding,
    per-layer ln/q/k/v, the grouped-query einsum attention
    (ops/pallas_decode.paged_attention runs the exact dense einsum over
    the gathered page view), and the SHARED ``_ffn`` — so greedy paged
    decode is token-identical to ``TransformerDecoder.generate``
    (tests/test_paged_decode.py pins this on ragged,
    page-boundary-straddling batches).

    Scheduling (which slot holds which request, page alloc/free,
    eviction) is host-side policy and lives in serving/engine.py; this
    class is only the device step. Physical page 0 is RESERVED as the
    null page: inactive slots write their (discarded) K/V there and
    unassigned page-table entries point at it, which keeps the scatter
    and gather unconditional — no shape-changing branches.

    ``window`` > 1 widens the step to W tokens PER SLOT per dispatch —
    one fixed [S, W] shape that serves three schedules with zero extra
    compiles: multi-token prompt teacher-forcing, the speculative
    verify window (feed the pending token + k draft proposals, read W
    argmaxes, accept the token-identical prefix — serving/engine.py),
    and the classic one-token step (W = 1, or masked columns).
    In-window causality holds because every window token's K/V is
    scattered into the pool BEFORE attention and each token's kv_len
    masks later positions. ``attention`` selects the cache-read path:
    "gather" (the exact einsum over the full page view), "kernel" (the
    live-pages Pallas kernel — ops/pallas_decode.py), or "auto"
    (kernel on TPU when supported, gather elsewhere).

    ``kv_quant="int8"`` switches the pools to the two-tier INT8 layout:
    each pool becomes a pytree ``{"q": int8 [L, N, ps, g*dh],
    "s": float32 [L, N, ps, g]}`` — the scatter quantizes each K/V row
    per (token, kv-head) with ops/pallas_decode.quantize_kv (a pure
    function of the row, so prefix-shared pages stay bit-identical
    across owners) and attention reads through the dequant-fused
    kernel or the dequantizing gather fallback. ~4x pages per HBM
    byte at fp32 base dtype; greedy output is prefix-identical to the
    fp path under the pinned INT8_KV_* contract."""

    #: the stored pool layout, as the artifact fingerprints name it: an
    #: executable built for another layout can never be resolved
    POOL_LAYOUT = "L,N,page,g*dh"
    #: a LatentBlock's one pool: rows [c_kv | k_rope | zero lanes]
    LATENT_POOL_LAYOUT = "L,N,page,c_kv|k_rope|0"

    def __init__(self, dense: TransformerDecoder, *, num_slots: int,
                 page_size: int, num_pages: int,
                 max_pages_per_slot: int,
                 temperature: Optional[float] = None,
                 window: int = 1, attention: str = "auto",
                 warm_start: bool = True,
                 kv_quant: Optional[str] = None):
        assert num_pages >= 2, "need at least the null page + one real"
        assert max_pages_per_slot * page_size <= dense.max_positions, (
            "slot capacity exceeds the position table — positions past "
            "it would silently clamp to its last row")
        assert window >= 1, window
        assert attention in ("auto", "kernel", "gather"), attention
        assert kv_quant in (None, "int8"), kv_quant
        #: a LatentBlock has ONE pool, a [c_kv | k_rope] row a token and
        #: layer; its step also counts the held experts' load
        self.latent = dense.block is not None
        if self.latent and kv_quant is not None:
            raise ValueError(
                "kv_quant is not supported on a latent (MLA) cache: the "
                "int8 layout packs per-head scales, and a latent row has "
                "no heads")
        self.kv_quant = kv_quant
        self.expert_counts = None   # the last step's held load (device)
        self.dense = dense
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.temperature = temperature
        self.window = int(window)
        n, h = dense.name, dense.n_heads
        d = dense.p[f"_{n}_tok_emb.w0"].shape[1]
        self.dtype = dense.p[f"_{n}_tok_emb.w0"].dtype
        from paddle_tpu.ops import pallas_decode as paged_ops
        on_tpu = jax.default_backend() == "tpu"
        if self.latent:
            # a token's row [c_kv | k_rope], padded with zero lanes to
            # whole 128-lane tiles: 576 -> 640 at the published widths (a
            # pool whose rows are not whole tiles reaches the kernel
            # through a pool-sized relayout copy every layer)
            rkv, dr = dense.block.cache_widths(dense.p, dense._pre)
            self.row_lanes = -(-(rkv + dr) // 128) * 128
            self.counts_experts = \
                dense.block.n_expert_layers(dense.n_layers) > 0
            supported = paged_ops.latent_kernel_supported(
                self.num_slots, self.window * h, self.row_lanes, rkv,
                self.page_size, self.max_pages_per_slot, self.dtype)
        else:
            self.counts_experts = False
            self.head_dim = d // h
            self.kv_heads = \
                dense.p[f"_{n}_l0_k.w0"].shape[1] // self.head_dim
            probe_q = jax.ShapeDtypeStruct(
                (self.num_slots, self.window, h, self.head_dim), self.dtype)
            kv_dtype = jnp.int8 if self.kv_quant == "int8" else self.dtype
            probe_k = jax.ShapeDtypeStruct(
                (self.num_pages, self.page_size,
                 self.kv_heads * self.head_dim), kv_dtype)
            probe_s = jax.ShapeDtypeStruct(
                (self.num_pages, self.page_size, self.kv_heads),
                jnp.float32) if self.kv_quant == "int8" else None
            supported = paged_ops.paged_kernel_supported(
                probe_q, probe_k, probe_s,
                pages_per_slot=self.max_pages_per_slot)
        if attention == "kernel":
            self.use_kernel = True
        elif attention == "gather":
            self.use_kernel = False
        else:
            self.use_kernel = on_tpu and supported
        self.kernel_interpret = self.use_kernel and not on_tpu
        # donating the pools lets XLA update pages in place (the pools
        # ARE the device memory budget); the CPU backend has no donation
        # and would warn on every dispatch
        donate = () if jax.default_backend() == "cpu" else (1, 2)
        self._step = jax.jit(self._step_impl, donate_argnums=donate)
        self._copy = jax.jit(self._copy_page_impl,
                             donate_argnums=() if not donate else (0, 1))
        # warm-start plane (paddle_tpu/artifacts): both jitted
        # functions resolve through the executable ladder on first
        # dispatch — an artifact hit (in-process or on-disk) makes the
        # engine's startup zero-compile. Fingerprints capture every
        # knob that changes the compiled program.
        self.warm_start = bool(warm_start)
        from paddle_tpu.artifacts import fingerprint
        plan = {"num_slots": self.num_slots,
                "page_size": self.page_size,
                "num_pages": self.num_pages,
                "max_pages_per_slot": self.max_pages_per_slot,
                "window": self.window,
                "temperature": self.temperature,
                "use_kernel": self.use_kernel,
                "kernel_interpret": self.kernel_interpret,
                "kv_quant": self.kv_quant,
                "pool_layout": self.POOL_LAYOUT}
        page_plan = {"num_pages": self.num_pages,
                     "page_size": self.page_size,
                     "n_layers": dense.n_layers,
                     "dtype": str(jnp.dtype(self.dtype)),
                     "kv_quant": self.kv_quant,
                     "pool_layout": self.POOL_LAYOUT}
        if self.latent:
            # the description changes the program and the pools' shape
            layout = {"block": repr(dense.block),
                      "pool_layout": self.LATENT_POOL_LAYOUT}
            plan.update(layout)
            page_plan.update(layout, row_lanes=self.row_lanes)
        else:
            page_plan.update(kv_heads=self.kv_heads,
                             head_dim=self.head_dim)
        self._step_fp = fingerprint("paged_step", dense.p, plan=plan)
        self._copy_fp = fingerprint("paged_copy", dense.p,
                                    plan=page_plan)
        self._read_fp = fingerprint("paged_read", dense.p,
                                    plan=page_plan)
        self._write_fp = fingerprint("paged_write", dense.p,
                                     plan=page_plan)
        self._read = jax.jit(self._read_page_impl)
        self._write = jax.jit(self._write_page_impl,
                              donate_argnums=() if not donate
                              else (0, 1))
        self._step_exe = None
        self._copy_exe = None
        self._read_exe = None
        self._write_exe = None

    def init_pools(self):
        """Zeroed (k_pool, v_pool): each [L, n_pages, page_size, g*dh]
        at the base dtype — the layout the paged kernel's blocks read,
        kept for the pools' whole life — or, under ``kv_quant="int8"``,
        the two-tier pytrees ``{"q": int8 values in that layout,
        "s": float32 per-row scales [L, n_pages, page_size, g]}``."""
        rows = (self.dense.n_layers, self.num_pages, self.page_size)
        if self.latent:
            # one pool; the engine and its callers know two attribute
            # names, so the second is an empty pytree: every page copy,
            # read, write and donation maps over it and finds nothing
            return jnp.zeros(rows + (self.row_lanes,), self.dtype), {}
        row = self.kv_heads * self.head_dim
        if self.kv_quant == "int8":
            def one():
                return {"q": jnp.zeros(rows + (row,), jnp.int8),
                        "s": jnp.zeros(rows + (self.kv_heads,),
                                       jnp.float32)}
            return one(), one()
        return (jnp.zeros(rows + (row,), self.dtype),
                jnp.zeros(rows + (row,), self.dtype))

    def pool_bytes(self) -> int:
        if self.latent:
            return int(jnp.dtype(self.dtype).itemsize) * \
                self.dense.n_layers * self.num_pages * self.page_size * \
                self.row_lanes
        rows = self.dense.n_layers * self.num_pages * \
            self.page_size * self.kv_heads
        if self.kv_quant == "int8":
            # 1 byte/element + one float32 scale per row, per pool
            return 2 * rows * (self.head_dim + 4)
        return 2 * int(jnp.dtype(self.dtype).itemsize) * rows * \
            self.head_dim

    def _paged_block(self, p, i, x, k_pool, v_pool, page_idx, offs,
                     page_tables, kv_lens):
        from paddle_tpu.ops import pallas_decode as paged_ops
        d0 = self.dense
        n, h = d0.name, d0.n_heads
        S, W = x.shape[0], x.shape[1]
        ln1 = _ln(x, p[f"_{n}_l{i}_ln1.w0"], p[f"_{n}_l{i}_ln1.wbias"])
        q = _heads(ln1 @ p[f"_{n}_l{i}_q.w0"], h)       # [S, W, h, dh]
        g = self.kv_heads
        # K/V rows as the pool stores them: [S*W, g*dh]
        k = (ln1 @ p[f"_{n}_l{i}_k.w0"]).reshape(S * W, -1)
        v = (ln1 @ p[f"_{n}_l{i}_v.w0"]).reshape(S * W, -1)
        # unconditional scatter: every window token writes its K/V at
        # (layer, physical page, in-page offset) of the donated pool,
        # in place — BEFORE attention, so later window tokens attend to
        # earlier ones (in-window causality via each token's kv_len).
        # Masked tokens were routed to the null page by the caller.
        rows_p = page_idx.reshape(-1)
        rows_o = offs.reshape(-1)

        def put(pool, rows):
            return pool.at[i, rows_p, rows_o].set(rows.astype(pool.dtype))

        # the scopes name the regions in a device trace (PERF.md
        # section 3): the pool update and the kernel
        scales = {}
        if self.kv_quant == "int8":
            with jax.named_scope("kv_write"):
                kq, ks = paged_ops.quantize_kv(k.reshape(S * W, g, -1))
                vq, vs = paged_ops.quantize_kv(v.reshape(S * W, g, -1))
                k_pool = {"q": put(k_pool["q"], kq.reshape(S * W, -1)),
                          "s": put(k_pool["s"], ks)}
                v_pool = {"q": put(v_pool["q"], vq.reshape(S * W, -1)),
                          "s": put(v_pool["s"], vs)}
            k_pages, v_pages = k_pool["q"], v_pool["q"]
            scales = dict(k_scales=k_pool["s"], v_scales=v_pool["s"])
        else:
            with jax.named_scope("kv_write"):
                k_pool, v_pool = put(k_pool, k), put(v_pool, v)
            k_pages, v_pages = k_pool, v_pool
        with jax.named_scope("paged_attn"):
            attn = paged_ops.paged_window_attention(
                q, k_pages, v_pages, page_tables, kv_lens, layer=i,
                use_kernel=self.use_kernel,
                interpret=self.kernel_interpret, **scales)
        x = x + attn.reshape(x.shape) @ p[f"_{n}_l{i}_proj.w0"]
        with jax.named_scope("ffn"):
            x = d0._ffn(p, i, x)
        return x, k_pool, v_pool

    def _latent_paged_block(self, p, i, x, pool, page_idx, offs,
                            positions, page_tables, kv_lens, active):
        """A LatentBlock layer of the step: the token's [c_kv | k_rope]
        row scattered into the donated pool in place, then the absorbed
        attention over the slot's pages (one latent row a token serves
        as key and as value), then the block's own FFN. -> (x, pool,
        held load or None)."""
        from paddle_tpu.ops import pallas_decode as paged_ops
        d0 = self.dense
        blk, pre = d0.block, d0._pre
        q_nope, q_rope, c_kv, k_rope = blk.qkv(p, pre, i, x, positions)
        with jax.named_scope("latent_kv_write"):
            row = jnp.concatenate([c_kv, k_rope], axis=-1)
            row = row.reshape(-1, row.shape[-1]).astype(pool.dtype)
            pool = pool.at[i, page_idx.reshape(-1), offs.reshape(-1)].set(
                jnp.pad(row, ((0, 0), (0, pool.shape[-1] - row.shape[-1]))))
        with jax.named_scope("latent_attn"):
            o_lat = paged_ops.paged_latent_attention(
                blk.absorb_q(p, pre, i, q_nope), q_rope, pool,
                page_tables, kv_lens, layer=i, scale=blk.softmax_scale,
                use_kernel=self.use_kernel,
                interpret=self.kernel_interpret)
            attn = blk.expand_o(p, pre, i, o_lat)
        x = x + blk.project(p, pre, i, attn.reshape(x.shape[:2] + (-1,)))
        with jax.named_scope("ffn"):
            x, load = blk.ffn(p, pre, i, x, active)
        return x, pool, load

    def _step_impl(self, p, k_pool, v_pool, tokens, positions,
                   page_tables, active, key):
        """tokens/positions/active [S, W]; page_tables [S, P] int32 ->
        (next_tokens [S, W] int32, k_pool', v_pool'). Output column w
        is the model's next-token choice after feeding window tokens
        0..w — the teacher-forced continuation AND the speculative
        verify verdict in one read."""
        d0 = self.dense
        ps = self.page_size
        with jax.named_scope("embed"):
            x = d0._embed(p, tokens, positions)         # [S, W, d]
        page_idx = jnp.take_along_axis(
            page_tables, positions // ps, axis=1)       # [S, W]
        page_idx = jnp.where(active, page_idx, 0)       # null the dead
        offs = jnp.where(active, positions % ps, 0)
        kv_lens = positions + 1
        # a token that is not active attends to nothing: a slot whose
        # window is all masked has no live page, and the window kernel
        # then copies none for it
        live_lens = jnp.where(active, kv_lens, 0)
        loads = []
        for i in range(d0.n_layers):
            if self.latent:
                x, k_pool, load = self._latent_paged_block(
                    p, i, x, k_pool, page_idx, offs, positions,
                    page_tables, kv_lens, active)
                if load is not None:
                    loads.append(load)
                continue
            x, k_pool, v_pool = self._paged_block(
                p, i, x, k_pool, v_pool, page_idx, offs, page_tables,
                live_lens)
        with jax.named_scope("logits"):
            logits = d0._logits(p, x)                   # [S, W, V]
            if self.temperature is None:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    key, logits.astype(jnp.float32) /
                    self.temperature).astype(jnp.int32)
        if self.counts_experts:
            # two small sums over the step's expert layers ride beside
            # the tokens: (assignments on held experts, held experts hit)
            nxt = (nxt, sum(loads))
        return nxt, k_pool, v_pool

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

    def _page_payload(self, page):
        """One stored page in the shape the spill payload has always
        had: value leaves [L, 1, ps, g*dh] -> [L, 1, ps, g, dh] (the
        codec and its checksums do not know the stored layout; the int8
        layout's scale leaves pass as they are)."""
        def heads(v):
            return v.reshape(v.shape[:-1] + (self.kv_heads, self.head_dim))

        if self.latent:
            return page            # a latent row has no heads to split
        if self.kv_quant == "int8":
            return {"q": heads(page["q"]), "s": page["s"]}
        return heads(page)

    def _copy_page_impl(self, k_pool, v_pool, src, dst):
        """Device-side page copy (all layers) — the copy-on-write step
        behind partial-page prefix reuse (serving/prefix.py). src/dst
        are TRACED int32 scalars, so every (src, dst) pair shares ONE
        compilation. tree_map'd over the pool pytree, so the int8
        layout copies values AND scales."""
        def cp(pool):
            return jax.tree_util.tree_map(
                lambda leaf: self._page_update(
                    leaf, self._page_slice(leaf, src), dst), pool)

        return cp(k_pool), cp(v_pool)

    def _read_page_impl(self, k_pool, v_pool, page):
        """Device -> host leg of page spill (serving/spill.py): one
        physical page of both pools as [L, 1, ps, g, dh] value leaves
        (and [L, 1, ps, g] scale leaves). ``page`` is a traced scalar —
        one compilation covers every spill."""
        rd = lambda pool: self._page_payload(jax.tree_util.tree_map(
            lambda leaf: self._page_slice(leaf, page), pool))
        return rd(k_pool), rd(v_pool)

    def _write_page_impl(self, k_pool, v_pool, k_page, v_page, page):
        """Host -> device leg of page restore: the inverse of
        :meth:`_read_page_impl`."""
        wr = lambda pool, data: jax.tree_util.tree_map(
            lambda leaf, d: self._page_update(leaf, d, page),
            pool, data)
        return wr(k_pool, k_page), wr(v_pool, v_page)

    def copy_page(self, k_pool, v_pool, src: int, dst: int):
        """Copy physical page ``src`` -> ``dst`` in both pools."""
        args = (k_pool, v_pool, jnp.int32(src), jnp.int32(dst))
        if self._copy_exe is None:
            from paddle_tpu.artifacts import resolve
            self._copy_exe = resolve(self._copy_fp, self._copy, args,
                                     warm=self.warm_start)
        return self._copy_exe(*args)

    def read_page(self, k_pool, v_pool, page: int):
        """One physical page of both pools as [L, 1, ...] pytrees —
        the spill store's device->host read (serving/engine.py)."""
        args = (k_pool, v_pool, jnp.int32(page))
        if self._read_exe is None:
            from paddle_tpu.artifacts import resolve
            self._read_exe = resolve(self._read_fp, self._read, args,
                                     warm=self.warm_start)
        return self._read_exe(*args)

    def write_page(self, k_pool, v_pool, k_page, v_page, page: int):
        """Write [L, 1, ...] page pytrees back into physical ``page``
        of both pools — the restore leg of page spill."""
        args = (k_pool, v_pool, k_page, v_page, jnp.int32(page))
        if self._write_exe is None:
            from paddle_tpu.artifacts import resolve
            self._write_exe = resolve(self._write_fp, self._write,
                                      args, warm=self.warm_start)
        return self._write_exe(*args)

    def step(self, k_pool, v_pool, tokens, positions, page_tables,
             active, key=None):
        """Dispatch one decode step. Accepts the classic [S] one-token
        arrays (returns next tokens [S]) or the [S, W] window contract
        (returns [S, W]). Compiles exactly once for the engine's
        lifetime — joins/evictions/window occupancy only change
        VALUES."""
        if key is None:
            key = jax.random.PRNGKey(0)
        tokens = jnp.asarray(tokens, jnp.int32)
        squeeze = tokens.ndim == 1
        if squeeze:
            assert self.window == 1, (
                "one-token [S] arrays only drive a window=1 decoder")
            tokens = tokens[:, None]
            positions = jnp.asarray(positions, jnp.int32)[:, None]
            active = jnp.asarray(active, jnp.bool_)[:, None]
        args = (self.dense.p, k_pool, v_pool, tokens,
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(page_tables, jnp.int32),
                jnp.asarray(active, jnp.bool_), key)
        if self._step_exe is None:
            from paddle_tpu.artifacts import resolve
            self._step_exe = resolve(self._step_fp, self._step, args,
                                     warm=self.warm_start)
        nxt, k_pool, v_pool = self._step_exe(*args)
        if self.counts_experts:
            # the engine fetches it with the tokens (one sync)
            nxt, self.expert_counts = nxt
        if squeeze:
            nxt = nxt[:, 0]
        return nxt, k_pool, v_pool


class DraftDecoder:
    """The DRAFT side of speculative decoding: a small decoder over
    slot-PRIVATE dense caches, window-batched like PagedDecoder.

    The draft never shares the paged pool or the prefix trie — each
    slot owns a [T+1]-row dense cache lane (row T is the null row,
    mirroring the paged null page), and the engine teacher-forces the
    slot's committed tokens through it before asking for proposals.
    That keeps draft-cache coherence trivially correct under prefix
    hits, CoW, eviction and rejected speculation: the engine only
    tracks how many committed tokens the draft has FED (draft_pos),
    rolls it back past rejected proposals, and re-feeds — every cache
    row is rewritten before any query's kv_len can reach it. Greedy
    argmax only: proposals must be deterministic for the target's
    token-identity acceptance rule to compose (serving/engine.py).

    ONE jitted [S, W] step serves catch-up (feed up to W committed
    tokens) and proposal (feed 1 token, read its argmax) — zero extra
    compiles under churn, same contract as the target step."""

    def __init__(self, dense: TransformerDecoder, *, num_slots: int,
                 max_seq_len: int, window: int = 1,
                 warm_start: bool = True):
        if dense.block is not None:
            raise ValueError(
                "a draft over a latent (MLA) block is not supported: "
                "DraftDecoder's slot-private caches are per-head K/V")
        pos_rows = dense.max_positions
        assert max_seq_len <= pos_rows, (max_seq_len, pos_rows)
        self.dense = dense
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len)
        self.window = int(window)
        n, h = dense.name, dense.n_heads
        d = dense.p[f"_{n}_tok_emb.w0"].shape[1]
        self.head_dim = d // h
        self.kv_heads = dense.p[f"_{n}_l0_k.w0"].shape[1] // self.head_dim
        self.dtype = dense.p[f"_{n}_tok_emb.w0"].dtype
        donate = () if jax.default_backend() == "cpu" else (1, 2)
        self._step = jax.jit(self._step_impl, donate_argnums=donate)
        self.warm_start = bool(warm_start)
        from paddle_tpu.artifacts import fingerprint
        self._step_fp = fingerprint(
            "draft_step", dense.p,
            plan={"num_slots": self.num_slots,
                  "max_seq_len": self.max_seq_len,
                  "window": self.window})
        self._step_exe = None

    def init_caches(self):
        """Zeroed (k, v), each [L, S, T+1, g, dh] — row T is the null
        row masked tokens write to (never read: kv_len <= T)."""
        shape = (self.dense.n_layers, self.num_slots,
                 self.max_seq_len + 1, self.kv_heads, self.head_dim)
        return jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype)

    def cache_bytes(self) -> int:
        return 2 * int(jnp.dtype(self.dtype).itemsize) * \
            self.dense.n_layers * self.num_slots * \
            (self.max_seq_len + 1) * self.kv_heads * self.head_dim

    def _step_impl(self, p, kc, vc, tokens, positions, active):
        """tokens/positions/active [S, W] -> (argmax [S, W], kc', vc')."""
        d0 = self.dense
        n, h, g = d0.name, d0.n_heads, self.kv_heads
        S, W = tokens.shape
        T1 = self.max_seq_len + 1
        rep = h // g
        rows = jnp.arange(S)[:, None]
        wpos = jnp.where(active, positions, self.max_seq_len)
        x = d0._embed(p, tokens, jnp.where(active, positions, 0))
        kv_lens = positions + 1                          # [S, W]
        tpos = jnp.arange(T1)
        mask = tpos[None, None, :] < kv_lens[:, :, None]  # [S, W, T1]
        for i in range(d0.n_layers):
            ln1 = _ln(x, p[f"_{n}_l{i}_ln1.w0"],
                      p[f"_{n}_l{i}_ln1.wbias"])
            q = _heads(ln1 @ p[f"_{n}_l{i}_q.w0"], h)    # [S, W, h, dh]
            k = _heads(ln1 @ p[f"_{n}_l{i}_k.w0"], g)
            v = _heads(ln1 @ p[f"_{n}_l{i}_v.w0"], g)
            kc = kc.at[i, rows, wpos].set(k.astype(kc.dtype))
            vc = vc.at[i, rows, wpos].set(v.astype(vc.dtype))
            dh = q.shape[-1]
            q5 = q.reshape(S, W, g, rep, dh)
            logits = jnp.einsum("swgrd,stgd->sgrwt", q5,
                                kc[i].astype(q.dtype)) * (dh ** -0.5)
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            w_ = jax.nn.softmax(logits, axis=-1)
            attn = jnp.einsum("sgrwt,stgd->swgrd", w_,
                              vc[i].astype(q.dtype))
            x = x + attn.reshape(x.shape) @ p[f"_{n}_l{i}_proj.w0"]
            x = d0._ffn(p, i, x)
        logits = d0._logits(p, x)                        # [S, W, V]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kc, vc

    def step(self, kc, vc, tokens, positions, active):
        args = (self.dense.p, kc, vc,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(active, jnp.bool_))
        if self._step_exe is None:
            from paddle_tpu.artifacts import resolve
            self._step_exe = resolve(self._step_fp, self._step, args,
                                     warm=self.warm_start)
        return self._step_exe(*args)
