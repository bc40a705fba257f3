"""Real-TPU smoke tests for the compiled Mosaic kernel paths.

The regular suite runs every Pallas kernel in interpret mode on CPU;
these tests exercise the COMPILED path on actual TPU hardware (the gap
ADVICE round 2 flagged: interpret-only coverage can hide Mosaic
compile/tiling failures). They self-skip off-TPU, so the CPU CI lane is
unaffected; run the TPU lane with:

    PADDLE_TPU_SMOKE=1 python -m pytest tests/test_tpu_smoke.py -q

(the env var tells conftest.py to keep the real backend instead of the
virtual 8-device CPU mesh).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _on_tpu():
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


pytestmark = pytest.mark.skipif(not _on_tpu(),
                                reason="needs real TPU hardware")


def _median_ms_a_call(step, args, calls):
    """Median of five timed runs of ``step(*args)`` (one warm-up before
    them), in ms a chained call."""
    import time
    step(*args).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        step(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return 1e3 * sorted(times)[2] / calls


class TestFlashAttentionCompiled:
    @pytest.mark.parametrize("tq,tk,d", [
        (512, 512, 128),
        (100, 100, 64),        # ragged T -> exercises block rounding/pad
        (1024, 256, 128),      # cross lengths
    ])
    def test_forward_matches_reference(self, tq, tk, d):
        from paddle_tpu.ops.pallas_attention import (_lens_mask, _reference,
                                                     flash_attention)
        rng = np.random.RandomState(0)
        b, h = 2, 4
        q = jnp.asarray(rng.randn(b, tq, h, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, tk, h, d).astype(np.float32))
        lens_q = jnp.asarray([tq, max(tq // 2, 1)], jnp.int32)
        lens_k = jnp.asarray([tk, max(tk // 3, 1)], jnp.int32)
        out = flash_attention(q, k, v, q_lens=lens_q, kv_lens=lens_k,
                              causal=False)
        mask = _lens_mask(lens_q, lens_k, tq, tk, False)
        want = _reference(q, k, v, mask, d ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_backward_matches_reference(self):
        from paddle_tpu.ops.pallas_attention import (_lens_mask, _reference,
                                                     flash_attention)
        rng = np.random.RandomState(1)
        b, t, h, d = 2, 256, 4, 128
        q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
        lens = jnp.asarray([t, t // 2], jnp.int32)

        def f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, kv_lens=lens,
                                           q_lens=lens, causal=True) ** 2)

        mask = _lens_mask(lens, lens, t, t, True)

        def r(q, k, v):
            return jnp.sum(_reference(q, k, v, mask, d ** -0.5)
                           .astype(jnp.float32) ** 2)

        gf = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(r, argnums=(0, 1, 2)))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-2, atol=5e-2)


class TestLstmCompiled:
    def test_train_step_matches_lax(self):
        from paddle_tpu.ops import pallas_rnn
        rng = np.random.RandomState(2)
        b, T, h = 16, 12, 128
        x4 = jnp.asarray(rng.randn(b, T, 4 * h).astype(np.float32) * 0.1)
        w = jnp.asarray(rng.randn(h, 4 * h).astype(np.float32) * 0.1)
        bias = jnp.asarray(rng.randn(4 * h).astype(np.float32) * 0.1)
        lens = jnp.asarray(rng.randint(3, T + 1, b), jnp.int32)

        def f(x4, w, bias):
            out, hT, cT = pallas_rnn.lstm_sequence(x4, lens, w, bias, None)
            return jnp.sum(out ** 2) + jnp.sum(hT) + jnp.sum(cT)

        def r(x4, w, bias):
            out, hT, cT = pallas_rnn._lstm_ref(
                x4, lens.reshape(b, 1), w, bias.reshape(1, -1),
                jnp.zeros((3, h)))
            return jnp.sum(out ** 2) + jnp.sum(hT) + jnp.sum(cT)

        vf, gf = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
            x4, w, bias)
        vr, gr = jax.jit(jax.value_and_grad(r, argnums=(0, 1, 2)))(
            x4, w, bias)
        np.testing.assert_allclose(float(vf), float(vr), rtol=1e-3)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-2, atol=1e-3)


class TestPagedKernelCompiled:
    """The live-pages decode kernel, compiled: the serving path's
    attention against the gather/einsum reference on ragged lengths,
    out-of-order pages and a verify window (the interpret-mode pins of
    tests/test_paged_decode.py, on the chip), and the property the
    kernel exists for: its time follows the cached tokens."""

    @pytest.mark.parametrize("h,g,dh,ps,lowers", [
        (8, 8, 64, 16, True), (8, 2, 64, 16, True), (8, 1, 128, 16, True),
        (8, 8, 64, 8, True), (8, 1, 64, 16, False)],
        ids=["mha", "gqa", "mqa-dh128", "mha-page8", "mqa-dh64"])
    @pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
    def test_window_matches_gather(self, h, g, dh, ps, lowers, quant):
        """On the path the gate picks: one kv head of 64 is half a lane
        tile, which the kernel's page copies cannot take, so that shape
        is served by the gather path."""
        from paddle_tpu.ops.pallas_decode import (paged_kernel_supported,
                                                  paged_window_attention,
                                                  quantize_kv)
        rng = np.random.RandomState(5)
        S, W, P = 8, 3, 34
        n_pages = S * P + 1
        k = jnp.asarray(rng.randn(n_pages, ps, g, dh), jnp.bfloat16)
        v = jnp.asarray(rng.randn(n_pages, ps, g, dh), jnp.bfloat16)
        q = jnp.asarray(rng.randn(S, W, h, dh), jnp.bfloat16)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, n_pages)).reshape(S, P), jnp.int32)
        base = rng.randint(1, P * ps - W, (S,))
        lens = jnp.asarray(base[:, None] + np.arange(W)[None, :], jnp.int32)
        kw = {}
        if quant:
            k, ks = quantize_kv(k)
            v, vs = quantize_kv(v)
            kw = dict(k_scales=ks, v_scales=vs)
        # as the pools store them: the kv heads side by side on the lanes
        k = k.reshape(n_pages, ps, g * dh)
        v = v.reshape(n_pages, ps, g * dh)
        supported = paged_kernel_supported(q, k, kw.get("k_scales"),
                                           pages_per_slot=P)
        assert supported == lowers
        want = paged_window_attention(q, k, v, tables, lens, **kw)
        got = paged_window_attention(q, k, v, tables, lens,
                                     use_kernel=supported, **kw)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_time_follows_the_cached_tokens(self):
        """A layer's call at the benchmark's shape (32 slots, a table
        of 128 pages of 16, 32 heads of 64, bf16, the pool with its
        layer axis), 24 calls chained in one program as a step chains
        them: with every slot at 1 token it takes under a fifth of what
        it takes with every slot at 2,048. The grid-walk kernel this one
        replaced read 0.55 ms against 3.42 (its grid visited 32 x 128
        table entries whatever was live; ROADMAP.md D12)."""
        from paddle_tpu.ops.pallas_decode import paged_window_attention
        S, P, h, dh, ps, L, calls = 32, 128, 32, 64, 16, 2, 24
        n_pages = S * P + 1
        key = jax.random.PRNGKey(0)
        k = jax.random.normal(key, (L, n_pages, ps, h * dh), jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 1), k.shape,
                              jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2), (S, 1, h, dh),
                              jnp.bfloat16)
        tables = jnp.asarray(np.random.RandomState(0).permutation(
            np.arange(1, n_pages)).reshape(S, P), jnp.int32)

        @jax.jit
        def step(q, k, v, tables, lens):
            x = q
            for i in range(calls):
                o = paged_window_attention(x, k, v, tables, lens,
                                           layer=i % L, use_kernel=True)
                x = (q + 0.001 * o).astype(q.dtype)
            return x

        def ms_a_call(tokens):
            lens = jnp.full((S, 1), tokens, jnp.int32)
            return _median_ms_a_call(step, (q, k, v, tables, lens), calls)

        empty, full = ms_a_call(1), ms_a_call(P * ps)
        print(f"paged_window_attention ms a call: 1 token a slot "
              f"{empty:.4f}, {P * ps} tokens a slot {full:.4f}")
        assert empty < full / 5, (empty, full)


class TestLatentKernelCompiled:
    """The latent (MLA) kernel on the same walk, compiled: against the
    gather path on the chip, and its time following the cached tokens
    (ROADMAP.md D12, D13)."""

    @pytest.mark.parametrize("S,W,H", [(8, 1, 64), (8, 4, 64), (16, 8, 32)],
                             ids=["W1", "lanes-8x4", "lanes-16x8"])
    def test_latent_matches_gather(self, S, W, H):
        """Ragged slots, an idle one, out-of-order pages, per-token
        lengths, a bfloat16 pool at the published row (512 + 64 of 640
        lanes): the kernel's float32 output is the gather path's float32
        mathematics over the stored rows to 1e-3 of its size."""
        from paddle_tpu.ops.pallas_decode import (latent_kernel_supported,
                                                  paged_latent_attention)
        rng = np.random.RandomState(6)
        ps, P, L = 32, 128, 2
        n_pages = S * P + 1
        assert latent_kernel_supported(S, W * H, 640, 512, ps, P,
                                       jnp.bfloat16)
        pool = jnp.asarray(rng.randn(L, n_pages, ps, 640), jnp.bfloat16)
        pool = pool.at[..., 576:].set(0)
        ql = jnp.asarray(rng.randn(S, W, H, 512), jnp.float32)
        qr = jnp.asarray(rng.randn(S, W, H, 64), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, n_pages)).reshape(S, P), jnp.int32)
        base = rng.randint(1, P * ps - W, (S,))
        base[:3] = (1, 511, 2304)
        lens = base[:, None] + np.arange(W)[None, :]
        lens[3] = 0
        lens = jnp.asarray(lens, jnp.int32)
        kw = dict(layer=1, scale=0.07)
        want = np.asarray(paged_latent_attention(ql, qr, pool, tables, lens,
                                                 **kw))
        got = np.asarray(paged_latent_attention(ql, qr, pool, tables, lens,
                                                use_kernel=True, **kw))
        assert not got[3].any()
        live = np.arange(S) != 3
        size = float(np.sqrt(np.mean(want[live] ** 2)))
        err = float(np.abs(got[live] - want[live]).max()) / size
        print(f"paged_latent_attention S={S} W={W} H={H}: "
              f"max error {err:.2e} of the output's size")
        assert err < 1e-3, err

    def test_time_follows_the_cached_tokens(self):
        """A layer's call at ``kimik2_agent_2k``'s shape (64 slots, 64
        heads, a table of 128 pages of 32, bf16 rows of 640 lanes), six
        calls chained in one program as a step chains them, each with
        its own query laid out and split in two terms: with every slot
        at 1 token it takes under half of what it takes with every slot
        at 2,304 (0.24 ms against 0.70; the grid-walk kernel this one
        replaced read 0.65 against 0.93 under this probe, its grid
        visiting 64 x 128 table entries whatever was live). Not a fifth,
        as the window kernel's: 0.10 ms of either reading is the query's
        layout in XLA, and a slot with one live row still computes one
        whole block of 512 (PERF.md, PR 42)."""
        from paddle_tpu.ops.pallas_decode import paged_latent_attention
        S, P, H, ps, L, calls = 64, 128, 64, 32, 2, 6
        n_pages = S * P + 1
        key = jax.random.PRNGKey(0)
        pool = jax.random.normal(key, (L, n_pages, ps, 640), jnp.bfloat16)
        ql = jax.random.normal(jax.random.fold_in(key, 1), (S, 1, H, 512),
                               jnp.float32)
        qr = jax.random.normal(jax.random.fold_in(key, 2), (S, 1, H, 64),
                               jnp.float32)
        tables = jnp.asarray(np.random.RandomState(0).permutation(
            np.arange(1, n_pages)).reshape(S, P), jnp.int32)

        @jax.jit
        def step(ql, qr, pool, tables, lens):
            x = ql
            for i in range(calls):
                o = paged_latent_attention(x, qr, pool, tables, lens,
                                           layer=i % L, scale=0.07,
                                           use_kernel=True)
                x = ql + 0.001 * o
            return x

        def ms_a_call(tokens):
            lens = jnp.full((S, 1), tokens, jnp.int32)
            return _median_ms_a_call(step, (ql, qr, pool, tables, lens),
                                     calls)

        empty, full = ms_a_call(1), ms_a_call(2304)
        print(f"paged_latent_attention ms a call: 1 token a slot "
              f"{empty:.4f}, 2304 tokens a slot {full:.4f}")
        assert empty < full / 2, (empty, full)


class TestCpuTpuParity:
    """The reference's CPU<->GPU parity discipline (test_matrixCompare.cpp,
    test_CpuGpuVector.cpp) applied for real: the SAME jitted computation
    on the TPU backend vs the in-process CPU backend, asserted allclose.
    JAX always carries a CPU backend, so this needs no process tricks."""

    def _both(self, fn, *args):
        # placement follows the committed inputs (jit's device= kwarg is
        # deprecated): default device_put -> TPU, explicit put -> CPU
        cpu = jax.devices("cpu")[0]
        on_t = jax.jit(fn)(*args)
        on_c = jax.jit(fn)(
            *jax.tree_util.tree_map(lambda a: jax.device_put(a, cpu), args))
        return (jax.tree_util.tree_map(np.asarray, on_t),
                jax.tree_util.tree_map(np.asarray, on_c))

    def test_fc_train_grads(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(32, 64).astype(np.float32))
        w = jnp.asarray(rng.randn(64, 16).astype(np.float32))

        def loss(x, w):
            from paddle_tpu.ops import linear
            return jnp.sum(jax.nn.softmax(linear.matmul(x, w)) ** 2)

        t, c = self._both(jax.grad(loss, argnums=(0, 1)), x, w)
        for a, b in zip(t, c):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)

    def test_conv_bn_forward(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(4, 16, 16, 8).astype(np.float32))
        k = jnp.asarray(rng.randn(3, 3, 8, 16).astype(np.float32) * 0.1)

        def f(x, k):
            from paddle_tpu.ops import conv as conv_ops
            from paddle_tpu.ops import norm as norm_ops
            y = conv_ops.conv2d(x, k, stride=1, padding=1)
            g = jnp.ones((16,), jnp.float32)
            b = jnp.zeros((16,), jnp.float32)
            out, _, _ = norm_ops.batch_norm_train(
                y, g, b, jnp.zeros((16,)), jnp.ones((16,)))
            return out

        t, c = self._both(f, x, k)
        np.testing.assert_allclose(t, c, rtol=2e-3, atol=2e-3)

    def test_seqpool_embedding_path(self):
        rng = np.random.RandomState(2)
        ids = jnp.asarray(rng.randint(0, 50, (8, 12)).astype(np.int32))
        table = jnp.asarray(rng.randn(50, 24).astype(np.float32))
        lens = jnp.asarray(rng.randint(1, 13, (8,)), jnp.int32)

        def f(table, ids):
            e = table[ids]                              # [b, T, d]
            m = (jnp.arange(12)[None, :] < lens[:, None]).astype(e.dtype)
            s = jnp.sum(e * m[:, :, None], axis=1)
            return s / jnp.maximum(lens[:, None].astype(e.dtype), 1.0)

        t, c = self._both(f, table, ids)
        np.testing.assert_allclose(t, c, rtol=1e-4, atol=1e-5)
