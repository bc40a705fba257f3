"""KV-cache transformer decoding vs the training graph.

The decoder (models/decode.py) re-derives the forward functionally from
the DSL's parameter table; these tests pin it against the training
graph token for token (greedy decode must follow the graph's argmax
chain exactly), plus cache-correctness and sampling behavior.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.core.sequence import SequenceBatch

CFG = dict(vocab_size=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
           max_len=32)


def _model(seed=7, **overrides):
    paddle.init(use_tpu=False, seed=0)
    from paddle_tpu.core.registry import reset_name_counters
    reset_name_counters()
    spec = models.transformer_lm(**{**CFG, **overrides})
    costs = spec.cost if isinstance(spec.cost, list) else [spec.cost]
    # include the (paramless) probs node so _graph_argmax can read it
    topo = paddle.Topology(costs, extra_outputs=[spec.output])
    params = topo.init_params(jax.random.PRNGKey(seed))
    return spec, topo, params


def _graph_argmax(topo, spec, params, prefix):
    """Training-graph next-token argmax for each row of `prefix` [b, t]."""
    b, t = prefix.shape
    lens = jnp.full((b,), t, jnp.int32)
    sb = lambda a: SequenceBatch(jnp.asarray(a), lens)
    pos = np.tile(np.arange(t, dtype="int32"), (b, 1))
    feed = {spec.data.name: sb(prefix), spec.positions.name: sb(pos),
            spec.label.name: sb(prefix)}
    outs, _ = topo.forward(params, topo.init_state(), feed, mode="test",
                           output_names=[spec.output.name])
    probs = outs[spec.output.name].data      # [b, t, V] softmax
    return np.asarray(jnp.argmax(probs[:, -1], axis=-1))


class TestGreedyParity:
    def test_decode_follows_graph_argmax_chain(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        rng = np.random.RandomState(0)
        b, plen, max_len = 3, 4, 10
        prompt = rng.randint(0, CFG["vocab_size"], (b, plen)).astype("int32")
        got = dec.generate(prompt, max_len=max_len)   # greedy
        assert len(got) == b and all(len(r) == max_len - plen for r in got)

        prefix = prompt.copy()
        for step in range(max_len - plen):
            want = _graph_argmax(topo, spec, params, prefix)
            for row in range(b):
                assert got[row][step] == int(want[row]), (
                    f"step {step} row {row}: decode {got[row][step]} "
                    f"!= graph {int(want[row])}")
            prefix = np.concatenate(
                [prefix, want[:, None].astype("int32")], axis=1)

    def test_prefill_matches_stepwise(self):
        """Prefilling the prompt in one batched pass must produce the
        same logits and caches as feeding it token by token (the cache
        position/mask arithmetic lines up between the two modes)."""
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        rng = np.random.RandomState(1)
        b, plen, max_len = 2, 5, 8
        prompt = jnp.asarray(
            rng.randint(0, CFG["vocab_size"], (b, plen)).astype("int32"))
        d = dec.p["_tfm_tok_emb.w0"].shape[1]
        h = CFG["n_heads"]

        def fresh():
            return [(jnp.zeros((b, max_len, h, d // h), jnp.float32),
                     jnp.zeros((b, max_len, h, d // h), jnp.float32))
                    for _ in range(CFG["n_layers"])]

        pos = jnp.arange(plen)[None, :].repeat(b, 0)
        lg_pre, caches_pre = dec._forward(dec.p, prompt, pos, fresh(),
                                          0, plen)
        caches_step = fresh()
        for t in range(plen):
            lg_step, caches_step = dec._forward(
                dec.p, prompt[:, t:t + 1],
                jnp.full((b, 1), t, jnp.int32), caches_step, t, t + 1)
        np.testing.assert_allclose(np.asarray(lg_pre[:, -1]),
                                   np.asarray(lg_step[:, -1]),
                                   rtol=1e-5, atol=1e-5)
        for (kp, vp), (ks, vs) in zip(caches_pre, caches_step):
            np.testing.assert_allclose(np.asarray(kp), np.asarray(ks),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(vp), np.asarray(vs),
                                       rtol=1e-5, atol=1e-6)

    def test_max_len_beyond_position_table_rejected(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        with pytest.raises(AssertionError):
            dec.generate(np.zeros((1, 2), "int32"),
                         max_len=CFG["max_len"] + 1)

    def test_eos_trimming(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        prompt = np.zeros((1, 2), "int32")
        rows = dec.generate(prompt, max_len=12, eos_id=None)
        eid = rows[0][1] if len(set(rows[0])) > 1 else rows[0][0]
        trimmed = dec.generate(prompt, max_len=12, eos_id=eid)
        assert trimmed[0] == rows[0][:rows[0].index(eid) + 1]

    def test_moe_decode_follows_graph_in_no_drop_regime(self):
        """MoE blocks are auto-detected from the param table. Capacity
        derives from each call's token count, so graph parity is only
        guaranteed when nothing drops — pin it there (ample factor)."""
        spec, topo, params = _model(seed=3, moe_experts=4,
                                    moe_capacity_factor=8.0)
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"],
                                        moe_capacity_factor=8.0)
        rng = np.random.RandomState(2)
        b, plen, max_len = 2, 3, 8
        prompt = rng.randint(0, CFG["vocab_size"], (b, plen)).astype("int32")
        got = dec.generate(prompt, max_len=max_len)

        # graph side: same no-drop regime needs a high factor too — the
        # graph's capacity covers b*T tokens, which is already ample
        prefix = prompt.copy()
        for step in range(max_len - plen):
            want = _graph_argmax(topo, spec, params, prefix)
            for row in range(b):
                assert got[row][step] == int(want[row]), (step, row)
            prefix = np.concatenate(
                [prefix, want[:, None].astype("int32")], axis=1)

    def test_temperature_sampling_varies(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        prompt = np.zeros((4, 2), "int32")
        a = dec.generate(prompt, max_len=16, temperature=2.0,
                         rng=jax.random.PRNGKey(0))
        bb = dec.generate(prompt, max_len=16, temperature=2.0,
                          rng=jax.random.PRNGKey(1))
        assert a != bb          # different keys explore different paths
        g = dec.generate(prompt, max_len=16)
        assert g == dec.generate(prompt, max_len=16)   # greedy is stable


class TestBeamSearch:
    def test_beam1_equals_greedy(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, CFG["vocab_size"], (2, 3)).astype("int32")
        eid = CFG["vocab_size"] - 1
        greedy = dec.generate(prompt, max_len=10, eos_id=eid)
        beam = dec.beam_search(prompt, max_len=10, beam_size=1, eos_id=eid)
        for row in range(2):
            assert beam[row][0][1] == greedy[row]

    def test_nbest_sorted_and_scores_match_graph(self):
        """Beam scores must equal the training graph's summed token
        log-probs for the returned sequence (teacher-forced recompute)."""
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        rng = np.random.RandomState(5)
        b, plen, max_len, K = 2, 3, 9, 3
        prompt = rng.randint(0, CFG["vocab_size"], (b, plen)).astype("int32")
        eid = CFG["vocab_size"] - 1
        results = dec.beam_search(prompt, max_len=max_len, beam_size=K,
                                  eos_id=eid)
        for bi in range(b):
            scores = [s for s, _ in results[bi]]
            assert scores == sorted(scores, reverse=True)
            # recompute the best row's score through the graph
            score, row = results[bi][0]
            full = np.concatenate([prompt[bi], np.array(row, "int32")])
            want = 0.0
            for t in range(len(row)):
                pre = full[None, :plen + t]
                lens = jnp.full((1,), pre.shape[1], jnp.int32)
                sb = lambda a: SequenceBatch(jnp.asarray(a), lens)
                pos = np.arange(pre.shape[1], dtype="int32")[None]
                feed = {spec.data.name: sb(pre),
                        spec.positions.name: sb(pos),
                        spec.label.name: sb(pre)}
                outs, _ = topo.forward(params, topo.init_state(), feed,
                                       mode="test",
                                       output_names=[spec.output.name])
                probs = np.asarray(outs[spec.output.name].data[0, -1])
                want += float(np.log(max(probs[row[t]], 1e-30)))
                if row[t] == eid:
                    break
            np.testing.assert_allclose(score, want, rtol=1e-3, atol=1e-3)

    def test_beams_are_distinct(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        prompt = np.zeros((1, 2), "int32")
        res = dec.beam_search(prompt, max_len=8, beam_size=4,
                              eos_id=CFG["vocab_size"] - 1)
        rows = [tuple(r) for _, r in res[0]]
        assert len(set(rows)) == len(rows)


def _teacher_forced_logprob(spec, topo, params, prompt_row, row, eid):
    """Raw summed log-prob of `row` continuing `prompt_row`, through the
    training graph (stops after eos)."""
    full = np.concatenate([prompt_row, np.array(row, "int32")])
    plen = len(prompt_row)
    want = 0.0
    for t in range(len(row)):
        pre = full[None, :plen + t]
        lens = jnp.full((1,), pre.shape[1], jnp.int32)
        sb = lambda a: SequenceBatch(jnp.asarray(a), lens)
        pos = np.arange(pre.shape[1], dtype="int32")[None]
        feed = {spec.data.name: sb(pre), spec.positions.name: sb(pos),
                spec.label.name: sb(pre)}
        outs, _ = topo.forward(params, topo.init_state(), feed,
                               mode="test",
                               output_names=[spec.output.name])
        probs = np.asarray(outs[spec.output.name].data[0, -1])
        want += float(np.log(max(probs[row[t]], 1e-30)))
        if row[t] == eid:
            break
    return want


class TestLengthPenalty:
    def test_gnmt_scores_match_graph(self):
        """length_penalty > 0 runs the in-scan GNMT bank: every returned
        score must equal the teacher-forced raw log-prob / len^alpha,
        and results arrive sorted. (No superiority assertion vs the
        raw-sum search: both beams are greedy approximations exploring
        different live sets, so neither dominates in general.)"""
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        prompt = np.zeros((1, 2), "int32")
        eid = CFG["vocab_size"] - 1
        alpha = 1.0
        norm = dec.beam_search(prompt, max_len=9, beam_size=4, eos_id=eid,
                               length_penalty=alpha)
        scores = [s for s, _ in norm[0]]
        assert scores == sorted(scores, reverse=True)
        for s, r in norm[0]:
            want = _teacher_forced_logprob(spec, topo, params, prompt[0],
                                           r, eid)
            np.testing.assert_allclose(
                s, want / max(len(r), 1) ** alpha, rtol=1e-3, atol=1e-3)

    def test_gnmt_results_distinct_and_trimmed(self):
        spec, topo, params = _model()
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        prompt = np.zeros((2, 2), "int32")
        eid = CFG["vocab_size"] - 1
        res = dec.beam_search(prompt, max_len=8, beam_size=4, eos_id=eid,
                              length_penalty=0.6)
        for bi in range(2):
            rows = [tuple(r) for _, r in res[bi]]
            assert len(set(rows)) == len(rows)
            for _, r in res[bi]:
                assert eid not in r[:-1]   # trimmed at first eos


class TestTiedEmbeddings:
    def test_tied_lm_trains_and_decodes_in_parity(self):
        """tie_embeddings=True: one vocab-sized table serves both the
        embedding and the (transposed) head; training works and greedy
        decode still follows the training graph's argmax chain."""
        spec, topo, params = _model(tie_embeddings=True)
        assert "_tfm_head.w0" not in params        # the table is shared
        assert "_tfm_tok_emb.w0" in params

        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        rng = np.random.RandomState(0)
        b, plen, max_len = 2, 3, 8
        prompt = rng.randint(0, CFG["vocab_size"],
                             (b, plen)).astype("int32")
        got = dec.generate(prompt, max_len=max_len)
        prefix = prompt.copy()
        for step in range(max_len - plen):
            want = _graph_argmax(topo, spec, params, prefix)
            for row in range(b):
                assert got[row][step] == int(want[row])
            prefix = np.concatenate(
                [prefix, want[:, None].astype("int32")], axis=1)

        # one SGD step moves the shared table with grads from BOTH uses
        ps = paddle.create_parameters(
            paddle.Topology(spec.cost, extra_outputs=[spec.output]))
        tr = paddle.SGD(cost=spec.cost, parameters=ps,
                        extra_layers=[spec.output],
                        update_equation=paddle.optimizer.Adam(
                            learning_rate=1e-3))
        T = 8
        rows = []
        for _ in range(4):
            ids = rng.randint(0, CFG["vocab_size"], T + 1)
            rows.append(([int(v) for v in ids[:T]], list(range(T)),
                         [int(v) for v in ids[1:]]))
        w0 = np.asarray(ps.raw["_tfm_tok_emb.w0"]).copy()
        losses = []
        tr.train(lambda: iter([rows]), num_passes=2,
                 event_handler=lambda e: losses.append(e.cost)
                 if isinstance(e, paddle.event.EndIteration) else None)
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        assert np.abs(np.asarray(
            tr.parameters.raw["_tfm_tok_emb.w0"]) - w0).max() > 0


class TestGroupedQueryAttention:
    def test_gqa_decode_follows_graph_argmax_chain(self):
        """n_kv_heads < n_heads: the decoder's grouped einsums over the
        kv_h-sized caches must match the training graph token for
        token (which repeats kv heads to full width)."""
        spec, topo, params = _model(n_kv_heads=1)   # MQA, 2 q heads
        assert params["_tfm_l0_k.w0"].shape[1] == \
            CFG["d_model"] // CFG["n_heads"]        # kv width = one head
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=CFG["n_heads"])
        rng = np.random.RandomState(1)
        b, plen, max_len = 3, 4, 10
        prompt = rng.randint(0, CFG["vocab_size"],
                             (b, plen)).astype("int32")
        got = dec.generate(prompt, max_len=max_len)
        prefix = prompt.copy()
        for step in range(max_len - plen):
            want = _graph_argmax(topo, spec, params, prefix)
            for row in range(b):
                assert got[row][step] == int(want[row]), (step, row)
            prefix = np.concatenate(
                [prefix, want[:, None].astype("int32")], axis=1)

    def test_gqa_trains(self):
        spec, topo, params = _model(n_kv_heads=1)
        ps = paddle.create_parameters(
            paddle.Topology(spec.cost, extra_outputs=[spec.output]))
        tr = paddle.SGD(cost=spec.cost, parameters=ps,
                        extra_layers=[spec.output],
                        update_equation=paddle.optimizer.Adam(
                            learning_rate=1e-3))
        rng = np.random.RandomState(0)
        T = 8
        rows = []
        for _ in range(8):
            ids = rng.randint(0, CFG["vocab_size"], T + 1)
            rows.append(([int(v) for v in ids[:T]], list(range(T)),
                         [int(v) for v in ids[1:]]))
        losses = []
        tr.train(lambda: iter([rows]), num_passes=3,
                 event_handler=lambda e: losses.append(e.cost)
                 if isinstance(e, paddle.event.EndIteration) else None)
        assert np.isfinite(losses).all() and losses[-1] < losses[0]

    def test_gqa_grouping_order_parity(self):
        """rep>1 AND kv_h>1 (4 q heads over 2 kv heads): detects a
        consecutive-vs-interleaved mismatch between the training path's
        jnp.repeat and the decoder's grouped q reshape, which the MQA
        case structurally cannot."""
        spec, topo, params = _model(n_heads=4, n_kv_heads=2)
        dec = models.TransformerDecoder(params, n_layers=CFG["n_layers"],
                                        n_heads=4)
        rng = np.random.RandomState(5)
        b, plen, max_len = 2, 4, 9
        prompt = rng.randint(0, CFG["vocab_size"],
                             (b, plen)).astype("int32")
        got = dec.generate(prompt, max_len=max_len)
        prefix = prompt.copy()
        for step in range(max_len - plen):
            want = _graph_argmax(topo, spec, params, prefix)
            for row in range(b):
                assert got[row][step] == int(want[row]), (step, row)
            prefix = np.concatenate(
                [prefix, want[:, None].astype("int32")], axis=1)


class TestFlashPrefill:
    """Long-prompt prefill through the flash kernel must match the
    quadratic einsum path (models/block.py use_flash_prefill gate)."""

    def test_prefill_logits_match_einsum(self, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu import models
        from paddle_tpu.models import block
        paddle.init(seed=0)
        plen, max_len, d, L = 256, 272, 64, 2
        spec = models.transformer_lm(vocab_size=97, d_model=d, n_heads=4,
                                     n_layers=L, d_ff=2 * d,
                                     max_len=max_len)
        topo = paddle.Topology(spec.cost, extra_outputs=[spec.output])
        params = topo.init_params(jax.random.PRNGKey(0))
        prompt = jnp.asarray(np.random.RandomState(0).randint(
            0, 97, (2, plen)).astype("int32"))

        dec = models.TransformerDecoder(params, n_layers=L, n_heads=4)
        lg_e, _ = dec._prefill(dec.p, prompt, plen, max_len)

        # force the flash gate on (CPU runs the kernel in interpret mode)
        monkeypatch.setattr(block, "use_flash_prefill",
                            lambda t, pos, dh:
                            isinstance(pos, int) and pos == 0 and t > 1)
        lg_f, _ = dec._prefill(dec.p, prompt, plen, max_len)
        np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_e),
                                   rtol=2e-4, atol=2e-4)

    def test_gqa_prefill_logits_match_einsum(self, monkeypatch):
        import jax.numpy as jnp
        from paddle_tpu import models
        from paddle_tpu.models import block
        paddle.init(seed=0)
        plen, max_len, d, L = 256, 272, 64, 1
        spec = models.transformer_lm(vocab_size=61, d_model=d, n_heads=4,
                                     n_layers=L, d_ff=2 * d,
                                     max_len=max_len, n_kv_heads=2)
        topo = paddle.Topology(spec.cost, extra_outputs=[spec.output])
        params = topo.init_params(jax.random.PRNGKey(1))
        prompt = jnp.asarray(np.random.RandomState(1).randint(
            0, 61, (2, plen)).astype("int32"))
        dec = models.TransformerDecoder(params, n_layers=L, n_heads=4)
        lg_e, _ = dec._prefill(dec.p, prompt, plen, max_len)
        monkeypatch.setattr(block, "use_flash_prefill",
                            lambda t, pos, dh:
                            isinstance(pos, int) and pos == 0 and t > 1)
        lg_f, _ = dec._prefill(dec.p, prompt, plen, max_len)
        np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_e),
                                   rtol=2e-4, atol=2e-4)
