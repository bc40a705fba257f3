"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric: AlexNet bs=128 fwd+bwd+update ms/batch, the reference's
own flagship number (benchmark/README.md:37 — 334 ms/batch on a K40m,
measured by `paddle train --job=time`, parameter update included).
vs_baseline = baseline_ms / our_ms (>1 means faster than the reference).

The single stdout line also carries a `suite` object with every
single-chip BASELINE.md row (AlexNet bs128/bs512, SmallNet, GoogleNet,
LSTM h256/h1280, ResNet-50 north-star), each with achieved TFLOP/s and
MFU (model FLOPs from XLA's compiled cost analysis / device peak).
Multi-GPU rows (4xK40m) need a multi-chip slice and are listed under
`skipped`. Default numeric mode is mixed precision: f32 params, bf16
MXU passes (--dtype float32 for full-precision runs).

Per-suite lines additionally go to stderr for humans.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

BASELINES_MS = {
    # reference benchmark/README.md (1xK40m, ms/batch, update included)
    "alexnet_bs128": 334.0,
    "alexnet_bs512": 1629.0,
    "smallnet_bs128": 18.184,
    "googlenet_bs128": 1149.0,
    "lstm_bs64_h256": 83.0,
    "lstm_bs128_h1280": 1007.0,
    "resnet50_bs128": None,  # no reference number exists (BASELINE.md note)
}

# Rows that need >1 chip (4xK40m data-parallel, benchmark/README.md:68-152).
MULTICHIP_ROWS = ["alexnet_4x_bs512", "googlenet_4x_bs512", "lstm_4x_bs256"]

# The peak tables, device lookup, compiled-cost readers and roofline
# math moved to paddle_tpu/obs/profile.py so the CONTINUOUS profiler's
# live MFU/roofline gauges and these offline rows are one computation
# (the acceptance criterion is that they agree). Thin aliases keep the
# bench-side names every row below uses.
from paddle_tpu.obs.profile import (compiled_bytes as _compiled_bytes,
                                    compiled_flops as _compiled_flops,
                                    device_hbm_gbps as _device_hbm_gbps,
                                    device_peak_flops as _device_peak_flops,
                                    roofline as _roofline)


def _known_hbm_gbps(dev) -> float:
    """Peak HBM GB/s of a device in the table. A device that is not
    there is an error: a roofline against an assumed peak is a number
    about no machine."""
    bw = _device_hbm_gbps(dev)
    if bw is None:
        raise RuntimeError(
            f"no HBM peak known for device kind "
            f"{getattr(dev, 'device_kind', '?')!r} "
            "(paddle_tpu/obs/profile.py PEAK_HBM_GBPS)")
    return bw


def _add_roofline(res, bytes_acc, flops, dev):
    """The decode-row discipline generalized to every row: a step cannot
    beat its HBM traffic at peak bandwidth NOR its model FLOPs at peak
    MXU, so the BINDING bound (max of the two) is a hard per-row floor —
    `roofline_frac` drifting up is a regression, and `roofline_bound`
    says which resource certifies the row's ceiling."""
    bw = _device_hbm_gbps(dev)
    if bytes_acc and bw:
        res["hbm_gb_per_step"] = round(bytes_acc / 1e9, 4)
        res["hbm_gbps_assumed"] = bw
    from paddle_tpu.config import global_config
    rf = _roofline(res["ms"], flops=flops, bytes_acc=bytes_acc,
                   peak_flops=_device_peak_flops(dev), hbm_gbps=bw,
                   mxu=global_config().compute_dtype == "bfloat16")
    if rf.get("roofline_ms") is not None:
        res["roofline_ms"] = round(rf["roofline_ms"], 4)
        res["roofline_bound"] = rf["roofline_bound"]
        res["roofline_frac"] = round(rf["roofline_frac"], 2)
    return res


#: repetitions per bench row; the recorded ms is the MEDIAN of this many
#: independent slope measurements, with min/max kept as the spread.
N_REPS = 5


#: one slope chain must run at least this long so the jitter of its
#: one readback amortizes below ~1 ms/step of slope noise
_MIN_CHAIN_MS = 1200.0


def _slope_time(step, carry, extra, iters, warmup, reps=N_REPS):
    """Median-of-`reps` slope timings with spread, plus the live carry.

    One slope sample runs N and 2N chained steps (each chain ends in ONE
    device->host readback of the loss, the only sync every transport
    honors) and takes (T2N - TN)/N: the difference cancels the constant
    sync latency. The chain serializes on-device because each step
    consumes the previous step's params. N is grown adaptively until a
    single chain takes >= _MIN_CHAIN_MS: with short chains the slope
    inherits RTT jitter / N, which at N=5 was +-10 ms/step on the
    transformer row — worse than the effect being measured. Mirrors
    paddle --job=time (update time included). The caller's (p, o, s) are
    dead after the first call (the step donates its buffers); the live
    carry is returned."""
    feed, key, n_real = extra
    p, o, s = carry

    def chain(n):
        nonlocal p, o, s
        t0 = time.perf_counter()
        for _ in range(n):
            p, o, s, loss, *_ = step(p, o, s, feed, key, n_real)
        float(loss)
        return (time.perf_counter() - t0) * 1000.0

    for _ in range(warmup):
        chain(1)
    n = max(iters // 2, 2)
    while chain(n) < _MIN_CHAIN_MS and n < 4096:
        n = min(n * 2, 4096)
    samples = []
    for _ in range(reps):
        t1 = chain(n)
        t2 = chain(2 * n)
        samples.append(max((t2 - t1) / n, 1e-6))
    return sorted(samples), (p, o, s)


def _spread(samples):
    """{ms: median, min, max, reps} from sorted slope samples."""
    mid = len(samples) // 2
    med = (samples[mid] if len(samples) % 2 else
           (samples[mid - 1] + samples[mid]) / 2)
    return {"ms": med, "min": round(samples[0], 4),
            "max": round(samples[-1], 4), "reps": len(samples)}


def _build(name):
    from paddle_tpu import models
    if name.startswith("alexnet"):
        return models.alexnet(), 227 * 227 * 3, 1000
    if name.startswith("smallnet"):
        return models.smallnet(), 32 * 32 * 3, 10
    if name.startswith("googlenet"):
        return models.googlenet(), 224 * 224 * 3, 1000
    if name.startswith("resnet50"):
        return (models.resnet50(tpu_stem="tpustem" in name),
                224 * 224 * 3, 1000)
    if name.startswith("vgg16"):
        return models.vgg16(), 224 * 224 * 3, 1000
    raise KeyError(name)


def _measure(trainer, feed, batch, iters, warmup, extra_flops=0.0):
    """ms/batch + TFLOP/s + MFU for one trainer/feed pair. Uses the AOT
    compiled step both for cost analysis and timing (one compilation).

    extra_flops: analytic model FLOPs of Pallas custom calls, which
    XLA's cost analysis cannot see (it returns -2 for custom calls) —
    without this the flash-attention and fused-LSTM rows undercount
    their own matmuls. Callers pass the MODEL-FLOPs convention
    (forward + 2x forward for backward) — NOT the kernels' actual
    recompute FLOPs, so MFU stays the standard conservative metric."""
    import jax
    import jax.numpy as jnp

    n_real = jnp.asarray(batch, jnp.int32)
    key = jax.random.PRNGKey(0)
    p, o, s = (trainer.parameters.raw, trainer.opt_state,
               trainer.parameters.state)
    try:
        compiled = trainer._train_step.lower(p, o, s, feed, key,
                                             n_real).compile()
        step, flops = compiled, _compiled_flops(compiled)
    except Exception:
        step, flops = trainer._train_step, None
    samples, carry = _slope_time(step, (p, o, s), (feed, key, n_real),
                                 iters, warmup)
    res = _spread([max(s, 1e-3) for s in samples])  # clamp timing noise
    ms = res["ms"]
    res["ms"] = round(ms, 4)
    res["samples_per_sec"] = round(batch / (ms / 1e3), 1)
    if flops:
        flops += extra_flops
        tflops = flops / (ms / 1e3) / 1e12
        res["tflops"] = round(tflops, 2)
        peak = _device_peak_flops(jax.devices()[0])
        from paddle_tpu.config import global_config
        if peak and global_config().compute_dtype == "bfloat16":
            # the peak table is dense-bf16; an f32 run has a different
            # (pass-count-dependent) ceiling, so report tflops only there
            res["mfu"] = round(tflops * 1e12 / peak, 4)
    _add_roofline(res, _compiled_bytes(step), flops, jax.devices()[0])
    return res


def bench_image(name: str, batch: int, iters: int = 20, warmup: int = 3):
    """forward+backward+update of an image model (NHWC, mixed precision)."""
    import jax
    import paddle_tpu as paddle

    spec, in_dim, n_classes = _build(name)
    params = paddle.create_parameters(paddle.Topology(spec.cost))
    trainer = paddle.SGD(
        cost=spec.cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(
            learning_rate=0.01 / batch, momentum=0.9,
            regularization=paddle.optimizer.L2Regularization(0.0005 * batch)))
    rng = np.random.RandomState(0)
    img = rng.randn(batch, in_dim).astype("float32")
    lbl = rng.randint(0, n_classes, (batch,)).astype("int32")
    feed = {spec.data.name: jax.device_put(img),
            spec.label.name: jax.device_put(lbl)}
    return _measure(trainer, feed, batch, iters, warmup)


def bench_lstm(batch: int, hidden: int, seq_len: int = 100,
               vocab: int = 30000, iters: int = 20, warmup: int = 3):
    """IMDB stacked-LSTM benchmark (benchmark/paddle/rnn/rnn.py shape)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core.sequence import SequenceBatch

    spec = models.stacked_lstm_net(vocab_size=vocab, emb_size=128,
                                   hidden_size=hidden, lstm_num=1)
    params = paddle.create_parameters(paddle.Topology(spec.cost))
    trainer = paddle.SGD(
        cost=spec.cost, parameters=params,
        update_equation=paddle.optimizer.Adam(learning_rate=2e-3))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq_len)).astype("int32")
    lengths = np.full((batch,), seq_len, np.int32)
    feed = {spec.data.name: SequenceBatch(jax.device_put(jnp.asarray(ids)),
                                          jax.device_put(jnp.asarray(lengths))),
            spec.label.name: jax.device_put(
                rng.randint(0, 2, (batch,)).astype("int32"))}
    # the Pallas LSTM kernels hide the recurrent matmuls from XLA's cost
    # analysis: T steps of [b,h]x[h,4h] in the forward and the same chain
    # again for dh in the backward (the weight-grad matmul runs OUTSIDE
    # the kernel and is already counted)
    recurrent = 2 * seq_len * batch * hidden * 4 * hidden * 2
    return _measure(trainer, feed, batch, iters, warmup,
                    extra_flops=float(recurrent))


def bench_transformer(batch: int = 8, seq_len: int = 1024,
                      d_model: int = 512, n_layers: int = 6,
                      iters: int = 10, warmup: int = 3):
    """Decoder-only LM train step (flash-attention path end-to-end).
    No 2017 baseline exists; reported for the TPU-era model family."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core.sequence import SequenceBatch

    # tie_embeddings: the modern convention at this scale (one 32k x 512
    # table serves embedding + transposed head) — measured 21.97 vs
    # 23.03 ms untied (fewer vocab-sized optimizer passes)
    spec = models.transformer_lm(vocab_size=32000, d_model=d_model,
                                 n_heads=8, n_layers=n_layers,
                                 d_ff=4 * d_model, max_len=seq_len,
                                 tie_embeddings=True)
    params = paddle.create_parameters(paddle.Topology(spec.cost))
    trainer = paddle.SGD(cost=spec.cost, parameters=params,
                         update_equation=paddle.optimizer.Adam(
                             learning_rate=1e-4))
    rng = np.random.RandomState(0)
    lens = np.full((batch,), seq_len, np.int32)

    def seq_feed(arr):
        return SequenceBatch(jax.device_put(jnp.asarray(arr)),
                             jax.device_put(jnp.asarray(lens)))

    ids = rng.randint(0, 32000, (batch, seq_len + 1))
    feed = {spec.data.name: seq_feed(ids[:, :-1].astype("int32")),
            f"{'tfm'}_positions": seq_feed(
                np.tile(np.arange(seq_len, dtype="int32"), (batch, 1))),
            spec.label.name: seq_feed(ids[:, 1:].astype("int32"))}
    # flash attention is a Pallas custom call = invisible to XLA's cost
    # analysis; add its analytic MODEL FLOPs (causal: T^2/2 valid pairs,
    # 2 matmuls x 2d each in the forward, 2x that for the backward — the
    # kernels' score recomputation is deliberately NOT counted)
    head_dim = d_model // 8
    attn_fwd = n_layers * batch * 8 * (seq_len ** 2 / 2) * head_dim * 4
    return _measure(trainer, feed, batch, iters, warmup,
                    extra_flops=3.0 * attn_fwd)


def bench_flash_attention(batch: int = 4, seq_len: int = 4096, heads: int = 8,
                          head_dim: int = 128, iters: int = 20,
                          warmup: int = 3):
    """Fused flash attention vs plain XLA attention (causal, bf16) — the
    long-context primitive. Reports flash ms, xla ms, and their ratio;
    no 2017 baseline row exists (the reference had no attention kernel)."""
    import time

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_attention import (_lens_mask, _reference,
                                                 flash_attention)

    rng = np.random.RandomState(0)
    shape = (batch, seq_len, heads, head_dim)
    q = jax.device_put(jnp.asarray(rng.randn(*shape))).astype(jnp.bfloat16)
    k = jax.device_put(jnp.asarray(rng.randn(*shape))).astype(jnp.bfloat16)
    v = jax.device_put(jnp.asarray(rng.randn(*shape))).astype(jnp.bfloat16)
    lens = jnp.full((batch,), seq_len, jnp.int32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, kv_lens=lens,
                                                causal=True))
    mask = _lens_mask(lens, lens, seq_len, seq_len, True)
    r = jax.jit(lambda q, k, v: _reference(q, k, v, mask,
                                           head_dim ** -0.5))

    def measure(fn):
        for _ in range(warmup):
            fn(q, k, v).block_until_ready()
        samples = []
        for _ in range(N_REPS):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v)
            out.block_until_ready()
            samples.append((time.perf_counter() - t0) / iters * 1e3)
        return sorted(samples)

    flash_s = measure(f)
    xla_s = measure(r)
    flash_ms, xla_ms = _spread(flash_s)["ms"], _spread(xla_s)["ms"]

    # training step (fwd+bwd) — exercises the Pallas backward kernels
    def loss_of(fn):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))

    fg, rg = loss_of(f._fun if hasattr(f, "_fun") else (
        lambda q, k, v: flash_attention(q, k, v, kv_lens=lens,
                                        causal=True))), \
        loss_of(lambda q, k, v: _reference(q, k, v, mask, head_dim ** -0.5))

    def measure_grad(fn):
        for _ in range(warmup):
            jax.block_until_ready(fn(q, k, v))
        samples = []
        for _ in range(N_REPS):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(q, k, v)
            jax.block_until_ready(out)
            samples.append((time.perf_counter() - t0) / iters * 1e3)
        return sorted(samples)

    fg_s, rg_s = measure_grad(fg), measure_grad(rg)
    flash_grad_ms, xla_grad_ms = _spread(fg_s)["ms"], _spread(rg_s)["ms"]
    # causal forward FLOPs: two [T, d] matmuls over the T^2/2 valid pairs
    flops = batch * heads * (seq_len ** 2 / 2) * head_dim * 2 * 2
    return {"ms": round(flash_ms, 4),
            "min": round(flash_s[0], 4), "max": round(flash_s[-1], 4),
            "reps": N_REPS, "xla_ms": round(xla_ms, 4),
            "vs_xla": round(xla_ms / flash_ms, 3),
            "grad_ms": round(flash_grad_ms, 4),
            "xla_grad_ms": round(xla_grad_ms, 4),
            "grad_vs_xla": round(xla_grad_ms / flash_grad_ms, 3),
            "tflops": round(flops / flash_ms / 1e9, 2)}


def bench_decode(batch: int = 8, prompt_len: int = 32, max_len: int = 544,
                 d_model: int = 512, n_layers: int = 6, iters: int = 3,
                 n_kv_heads: int = None):
    """KV-cache autoregressive decoding throughput (tokens/sec across the
    batch) on the transformer LM. No 2017 baseline; the RNN era's
    generation analogue is beam_search. `ms` is per-token latency.
    n_kv_heads < 8 benches the GQA decoder (kv-sized caches)."""
    import time

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import models

    kv_h = n_kv_heads or 8
    spec = models.transformer_lm(vocab_size=32000, d_model=d_model,
                                 n_heads=8, n_layers=n_layers,
                                 d_ff=4 * d_model, max_len=max_len,
                                 n_kv_heads=n_kv_heads)
    topo = paddle.Topology(spec.cost, extra_outputs=[spec.output])
    params = topo.init_params(jax.random.PRNGKey(0))
    # the decoder computes in the params' dtype; cast so this row matches
    # the suite's mixed-precision mode instead of silently running f32
    from paddle_tpu.config import global_config
    cdt = global_config().compute_dtype
    if cdt != "float32":
        params = {k: v.astype(cdt) for k, v in params.items()}
    dec = models.TransformerDecoder(params, n_layers=n_layers, n_heads=8)
    # HBM roofline for one decode step: every step must read ALL params
    # (batch-independent) plus each sequence's KV cache (batch-linear,
    # kv-head-sized under GQA). Worst-case cache length = max_len;
    # bytes/elt from the cast dtype; bandwidth from the device kind.
    esize = 2 if cdt != "float32" else 4
    param_bytes = sum(int(np.prod(v.shape)) for v in params.values()) * esize
    cache_bytes = (2 * n_layers * max_len * (d_model * kv_h // 8)
                   * esize * batch)
    hbm_gb = (param_bytes + cache_bytes) / 1e9
    hbm_gbps = _known_hbm_gbps(jax.devices()[0])
    roofline_ms = hbm_gb / hbm_gbps * 1e3
    prompt = np.random.RandomState(0).randint(
        0, 32000, (batch, prompt_len)).astype("int32")
    dec.generate(prompt, max_len=max_len)        # compile
    samples = []
    for _ in range(N_REPS):
        t0 = time.perf_counter()
        for _ in range(iters):
            rows = dec.generate(prompt, max_len=max_len)
        samples.append((time.perf_counter() - t0) / iters)
    samples.sort()
    n_new = len(rows[0])
    mid = len(samples) // 2
    dt = samples[mid] if len(samples) % 2 else \
        (samples[mid - 1] + samples[mid]) / 2  # median seconds-per-generate
    ms_tok = dt / n_new * 1e3
    return {"ms": round(ms_tok, 4),
            "min": round(samples[0] / n_new * 1e3, 4),
            "max": round(samples[-1] / n_new * 1e3, 4), "reps": N_REPS,
            "tokens_per_sec": round(batch * n_new / dt, 1),
            "new_tokens": n_new, "batch": batch,
            # anchor: a per-token step cannot beat reading params + KV
            # cache once from HBM; regressions show as roofline_frac
            # drifting up
            "hbm_gb_per_step": round(hbm_gb, 4),
            "hbm_gbps_assumed": hbm_gbps,
            "roofline_ms": round(roofline_ms, 4),
            "roofline_bound": "hbm",
            "roofline_frac": round(ms_tok / roofline_ms, 2)}


def bench_decode_continuous(num_slots: int = 8, n_requests: int = 32,
                            page_size: int = 16,
                            prompt_lens=(16, 96),
                            new_tokens=(64, 256),
                            d_model: int = 512, n_layers: int = 6,
                            n_heads: int = 8, n_kv_heads: int = None,
                            vocab_size: int = 32000,
                            max_len: int = 544, seed: int = 0):
    """Continuous-batching decode engine (serving/engine.py) on a
    seeded RAGGED workload: n_requests with uniform-random prompt and
    generation lengths, more requests than slots, so sequences join and
    leave the running jitted step mid-flight (joins interleave prefill
    with other slots' decoding; finished sequences free their KV pages
    immediately).

    Metrics: `tokens_per_sec` (generated tokens / wall), per-token
    latency `ms` (p50 inter-token) + `p99_ms` + `ttft_p50_ms`, slot
    utilization, KV-page high water, preemptions. `roofline_frac` is
    throughput-based against the paged floor: every step reads all
    params once plus each ACTIVE sequence's cache at its ACTUAL length
    (the engine counts cache tokens read exactly) — a tighter floor
    than the dense rows' worst-case max_len bound, so the same frac is
    a stronger claim. The CPU smoke slice of this row runs in tier-1
    (tests/test_paged_decode.py::TestBenchSmoke)."""
    import time

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.serving import DecodeEngine

    kv_h = n_kv_heads or n_heads
    spec = models.transformer_lm(vocab_size=vocab_size, d_model=d_model,
                                 n_heads=n_heads, n_layers=n_layers,
                                 d_ff=4 * d_model, max_len=max_len,
                                 n_kv_heads=n_kv_heads)
    topo = paddle.Topology(spec.cost, extra_outputs=[spec.output])
    params = topo.init_params(jax.random.PRNGKey(0))
    from paddle_tpu.config import global_config
    cdt = global_config().compute_dtype
    if cdt != "float32":
        params = {k: v.astype(cdt) for k, v in params.items()}
    dec = models.TransformerDecoder(params, n_layers=n_layers,
                                    n_heads=n_heads)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab_size,
                           (int(rng.randint(*prompt_lens)),))
               .astype("int32") for _ in range(n_requests)]
    news = [int(rng.randint(*new_tokens)) for _ in range(n_requests)]
    eng = DecodeEngine(dec, num_slots=num_slots, page_size=page_size,
                       max_seq_len=max_len)
    # one warm token compiles the step outside the timed window
    eng.submit(prompts[0][:4], 1)
    eng.run(timeout=600)
    st0 = eng.stats()
    t0 = time.perf_counter()
    reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
    eng.run(timeout=600)
    dt = time.perf_counter() - t0
    for r in reqs:
        r.get(timeout=1)               # surface any typed failure
    st = eng.stats()
    gen = st["tokens_out"] - st0["tokens_out"]
    steps = st["steps"] - st0["steps"]
    cache_read = st["cache_tokens_read"] - st0["cache_tokens_read"]
    active_steps = st["active_slot_steps"] - st0["active_slot_steps"]
    util = active_steps / (steps * num_slots) if steps else 0.0
    esize = 2 if cdt != "float32" else 4
    param_bytes = sum(int(np.prod(v.shape))
                      for v in params.values()) * esize
    per_tok_cache = 2 * n_layers * (d_model // n_heads) * kv_h * esize
    hbm_gb = (param_bytes * steps + cache_read * per_tok_cache) / 1e9
    hbm_gbps = _known_hbm_gbps(jax.devices()[0])
    roofline_s = hbm_gb / hbm_gbps
    return {"ms": st["token_latency_p50_ms"],
            "p99_ms": st["token_latency_p99_ms"],
            "ttft_p50_ms": st["ttft_p50_ms"],
            "tokens_per_sec": round(gen / dt, 1),
            "new_tokens": gen, "tokens_out": gen,
            "prefill_tokens": st["prefill_tokens"]
            - st0["prefill_tokens"],
            "requests": n_requests, "slots": num_slots,
            "page_size": page_size,
            "slot_utilization": round(util, 4),
            "kv_page_high_water": st["kv_page_high_water"],
            "preemptions": st["preemptions"] - st0["preemptions"],
            "steps": steps,
            "hbm_gb_total": round(hbm_gb, 4),
            "hbm_gbps_assumed": hbm_gbps,
            "roofline_bound": "hbm",
            "roofline_frac": round(dt / roofline_s, 2)
            if roofline_s > 0 else None}


def bench_moe_lm(batch: int = 8, seq_len: int = 1024, d_model: int = 512,
                 n_layers: int = 6, experts: int = 8, iters: int = 10,
                 warmup: int = 3):
    """MoE transformer LM train step (sort-dispatch single-host path) —
    the beyond-parity expert-parallel leg's regression row. Same shape
    as transformer_lm_bs8_t1024 with every FFN an 8-expert top-2 MoE."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core.sequence import SequenceBatch

    spec = models.transformer_lm(vocab_size=32000, d_model=d_model,
                                 n_heads=8, n_layers=n_layers,
                                 d_ff=4 * d_model, max_len=seq_len,
                                 tie_embeddings=True, moe_experts=experts)
    params = paddle.create_parameters(
        paddle.Topology(spec.cost, extra_outputs=[spec.output]))
    # NOTE: no extra_layers — SGD computes extra layers INSIDE the timed
    # step, and spec.output is the [b, T, 32000] softmax probs side
    # branch the training forward deliberately never materializes; the
    # dense transformer row omits it too, so adding it here would skew
    # the comparison by ~2 GB/step of softmax traffic
    trainer = paddle.SGD(cost=spec.cost, parameters=params,
                         update_equation=paddle.optimizer.Adam(
                             learning_rate=1e-4))
    rng = np.random.RandomState(0)
    lens = np.full((batch,), seq_len, np.int32)

    def seq_feed(arr):
        return SequenceBatch(jax.device_put(jnp.asarray(arr)),
                             jax.device_put(jnp.asarray(lens)))

    ids = rng.randint(0, 32000, (batch, seq_len + 1))
    feed = {spec.data.name: seq_feed(ids[:, :-1].astype("int32")),
            "tfm_positions": seq_feed(
                np.tile(np.arange(seq_len, dtype="int32"), (batch, 1))),
            spec.label.name: seq_feed(ids[:, 1:].astype("int32"))}
    head_dim = d_model // 8
    attn_fwd = n_layers * batch * 8 * (seq_len ** 2 / 2) * head_dim * 4
    return _measure(trainer, feed, batch, iters, warmup,
                    extra_flops=3.0 * attn_fwd)


#: rows of the CPU smoke tier; tools/bench_gate.py gates them against
#: BENCH_SMOKE_BASELINE.json in tier-1 (docs/observability.md)
SMOKE_ROWS = ("train_tiny", "serving_infer", "decode_engine",
              "decode_prefix_hit", "decode_speculative",
              "flight_recorder_overhead", "profiler_overhead",
              "lockdep_overhead", "protocol_witness_overhead",
              "contract_check", "coord_reshard", "embed_lookup",
              "embed_update", "fleet_route", "fleet_failover",
              "cold_start_to_first_token", "fleet_deploy",
              "fleet_autoscale", "router_ha", "soak_smoke",
              "kv_capacity_multiplier", "kv_dequant_overhead",
              "kv_restore_latency")


def _smoke_trainer(batch: int = 16):
    """A CPU-trivial 2-layer classifier — the smoke tier measures the
    FRAMEWORK's step machinery (compiles, host syncs, dispatch), not
    the model."""
    import paddle_tpu as paddle
    from paddle_tpu.core.registry import reset_name_counters
    reset_name_counters()
    x = paddle.layer.data("smoke_x", paddle.data_type.dense_vector(16))
    y = paddle.layer.data("smoke_y", paddle.data_type.integer_value(4))
    h = paddle.layer.fc(x, size=8, act=paddle.activation.Relu(),
                        name="smoke_h")
    out = paddle.layer.fc(h, size=4, act=paddle.activation.Softmax(),
                          name="smoke_prob")
    cost = paddle.layer.classification_cost(out, y, name="smoke_cost")
    params = paddle.create_parameters(paddle.Topology(cost))
    trainer = paddle.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(learning_rate=1e-3,
                                                  momentum=0.9))
    rng = np.random.RandomState(0)
    data = [(rng.randn(16).astype("float32"), int(rng.randint(0, 4)))
            for _ in range(batch)]
    return trainer, data


def _smoke_decoder():
    """Tiny transformer decoder (the serving chaos suite's shape) for
    the continuous-batching engine row."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core.registry import reset_name_counters
    reset_name_counters()
    spec = models.transformer_lm(vocab_size=40, d_model=16, n_heads=2,
                                 n_layers=2, d_ff=32, max_len=32)
    costs = spec.cost if isinstance(spec.cost, list) else [spec.cost]
    topo = paddle.Topology(costs, extra_outputs=[spec.output])
    params = topo.init_params(jax.random.PRNGKey(7))
    return models.TransformerDecoder(params, n_layers=2, n_heads=2)


def bench_smoke(train_steps: int = 12, serve_requests: int = 16,
                decode_requests: int = 5, rows=SMOKE_ROWS,
                force_recompile_per_step: bool = False) -> dict:
    """The CPU smoke tier of the perf regression gate (ROADMAP item 5).

    Deliberately two-faced: COUNT metrics (XLA compiles via
    compile_watch, host syncs per step via host_sync_watch — both
    analysis/sanitizer.py) are deterministic and gated tightly, while
    TIMING metrics (steps/s, serving p50/p99, engine tokens/s) carry
    loose machine-to-machine tolerances and only catch order-of-
    magnitude regressions. ``tools/bench_gate.py`` compares the result
    against the committed BENCH_SMOKE_BASELINE.json; the tier-1 test
    (tests/test_bench_gate.py) runs both an untouched pass and a
    forced-recompile-per-step injection that must FAIL the gate.

    ``force_recompile_per_step`` is that injection seam: it rebuilds
    the jitted train step every iteration — the classic shape-drift /
    jit-in-loop regression ptlint R2 lints for, reproduced at runtime.
    """
    import paddle_tpu as paddle
    from paddle_tpu.analysis.sanitizer import compile_watch, \
        host_sync_watch

    paddle.init(seed=0)
    out = {}
    if "train_tiny" in rows:
        trainer, data = _smoke_trainer()
        with compile_watch() as cw, host_sync_watch() as hs:
            trainer.train_batch(data)           # compile + warm
            syncs0 = hs.total
            t0 = time.perf_counter()
            for _ in range(train_steps):
                if force_recompile_per_step:
                    trainer._train_step = trainer._build_train_step()
                trainer.train_batch(data)
            dt = time.perf_counter() - t0
        out["train_tiny"] = {
            "steps_per_s": round(train_steps / dt, 2),
            "step_compiles": cw.total,
            "host_syncs_per_step": round(
                (hs.total - syncs0) / train_steps, 3),
        }
    if "serving_infer" in rows:
        from paddle_tpu.serving import InferenceServer
        from paddle_tpu.trainer.inference import Inference
        from paddle_tpu.core.registry import reset_name_counters
        reset_name_counters()
        import paddle_tpu as _p
        x = _p.layer.data("smoke_sx", _p.data_type.dense_vector(8))
        o = _p.layer.fc(x, size=4, act=_p.activation.Softmax(),
                        name="smoke_sprob")
        inf = Inference(output_layer=o,
                        parameters=_p.create_parameters(_p.Topology(o)))
        rng = np.random.RandomState(0)
        reqs = [(rng.randn(8).astype("float32"),) for _ in range(2)]
        srv = InferenceServer(inf, max_queue=64, workers=2,
                              breaker=False).start()
        try:
            srv.infer(reqs)                     # compile + warm
            for _ in range(serve_requests):
                srv.infer(reqs)
            st = srv.stats()
        finally:
            srv.shutdown(drain=True)
        out["serving_infer"] = {
            "p50_ms": st["p50_ms"],
            "p99_ms": st["p99_ms"],
            "served": st["served"],
        }
    if "decode_engine" in rows:
        from paddle_tpu.analysis.sanitizer import compile_watch as _cwf
        from paddle_tpu.serving import DecodeEngine
        dec = _smoke_decoder()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 40, (int(rng.randint(3, 8)),))
                   .astype("int32") for _ in range(decode_requests)]
        news = [int(rng.randint(4, 12)) for _ in range(decode_requests)]
        with _cwf() as cw:
            eng = DecodeEngine(dec, num_slots=2, page_size=4,
                               max_seq_len=32)
            eng.submit(prompts[0][:2], 1)       # compile + warm
            eng.run(timeout=300)
            st0 = eng.stats()
            t0 = time.perf_counter()
            for p, n in zip(prompts, news):
                eng.submit(p, n)
            eng.run(timeout=300)
            dt = time.perf_counter() - t0
        st = eng.stats()
        gen = st["tokens_out"] - st0["tokens_out"]
        out["decode_engine"] = {
            "tokens_per_s": round(gen / dt, 1),
            "token_p50_ms": st["token_latency_p50_ms"],
            "decode_compiles": cw.total,
            "steps": st["steps"] - st0["steps"],
            "tokens_out": gen,
        }
    if "decode_prefix_hit" in rows:
        # ISSUE 13 tentpole (a): warm-prefix TTFT vs cold. The same
        # prompts run twice through one engine — the first pass
        # prefills and indexes its pages in the radix trie, the second
        # attaches the cached pages and feeds only the final token, so
        # the prompt tokens teacher-forced (deterministic) collapse.
        # ``warm_prefill_ratio`` and ``hit_pages`` are the gated
        # metrics: prefix reuse silently breaking drives the ratio to
        # ~1 and the hits to 0. (Until the prefill lanes it was the
        # ratio of STEPS, 13: a cold 13-token prompt is one lane step
        # now, as a warm one's last token is, so steps no longer tell
        # the two apart.)
        from paddle_tpu.serving import DecodeEngine
        dec = _smoke_decoder()
        eng = DecodeEngine(dec, num_slots=2, page_size=4,
                           max_seq_len=32)
        rng = np.random.RandomState(1)
        hit_prompts = [rng.randint(0, 40, (13,)).astype("int32")
                       for _ in range(4)]
        eng.submit(hit_prompts[0][:2], 1)       # compile + warm
        eng.run(timeout=300)
        st0 = eng.stats()
        t0 = time.perf_counter()
        cold = [eng.submit(p, 1) for p in hit_prompts]
        eng.run(timeout=300)
        dt_cold = time.perf_counter() - t0
        st1 = eng.stats()
        t0 = time.perf_counter()
        warm = [eng.submit(p, 1) for p in hit_prompts]
        eng.run(timeout=300)
        dt_warm = time.perf_counter() - t0
        st2 = eng.stats()
        for r in cold + warm:
            r.get(timeout=1)                    # surface failures
        steps_cold = st1["steps"] - st0["steps"]
        steps_warm = st2["steps"] - st1["steps"]
        fed_cold = st1["prefill_tokens"] - st0["prefill_tokens"]
        fed_warm = st2["prefill_tokens"] - st1["prefill_tokens"]
        out["decode_prefix_hit"] = {
            "ttft_cold_ms": round(dt_cold / len(hit_prompts) * 1e3, 3),
            "ttft_warm_ms": round(dt_warm / len(hit_prompts) * 1e3, 3),
            "steps_cold": steps_cold, "steps_warm": steps_warm,
            "warm_prefill_ratio": round(fed_cold / max(fed_warm, 1), 2),
            "hit_pages": sum(r.prefix_hit_pages for r in warm),
        }
    if "decode_speculative" in rows:
        # ISSUE 13 tentpole (b): speculative decoding with a
        # same-weights draft (the acceptance best case at smoke
        # scale) — spec_k proposals verified per [S, W] target step.
        # ``tokens_per_step`` is the gated acceptance metric: the
        # ISSUE contract is > 1.0 committed tokens per target
        # dispatch; a broken verify path degrades it to <= 1.
        from paddle_tpu.analysis.sanitizer import compile_watch as _cws
        from paddle_tpu.serving import DecodeEngine
        dec = _smoke_decoder()
        draft = _smoke_decoder()
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, 40, (int(rng.randint(3, 8)),))
                   .astype("int32") for _ in range(decode_requests)]
        news = [int(rng.randint(6, 12)) for _ in range(decode_requests)]
        with _cws() as cw:
            eng = DecodeEngine(dec, num_slots=2, page_size=4,
                               max_seq_len=32, draft=draft, spec_k=2)
            eng.submit(prompts[0][:2], 1)       # compile + warm
            eng.run(timeout=300)
            st0 = eng.stats()
            t0 = time.perf_counter()
            reqs = [eng.submit(p, n) for p, n in zip(prompts, news)]
            eng.run(timeout=300)
            dt = time.perf_counter() - t0
        for r in reqs:
            r.get(timeout=1)
        st = eng.stats()
        gen = st["tokens_out"] - st0["tokens_out"]
        steps = st["steps"] - st0["steps"]
        out["decode_speculative"] = {
            "tokens_per_s": round(gen / dt, 1),
            "tokens_per_step": round(gen / max(steps, 1), 2),
            "accepted_tokens": st["spec_accepted_tokens"]
            - st0["spec_accepted_tokens"],
            "proposed_tokens": st["spec_proposed_tokens"]
            - st0["spec_proposed_tokens"],
            "spec_compiles": cw.total,
            "steps": steps, "tokens_out": gen,
        }
    if "flight_recorder_overhead" in rows:
        # the always-on cost of the flight recorder (obs/flight.py):
        # same tiny train loop with the recorder off vs on. The gated
        # metric is the RATIO (off/on steps/s) — machine-independent;
        # > 2.0 means always-on recording doubled the step time and
        # the gate fails (BENCH_SMOKE_BASELINE.json).
        from paddle_tpu.obs.flight import FLIGHT
        trainer, data = _smoke_trainer()
        trainer.train_batch(data)               # compile + warm

        def _steps_per_s(n):
            t0 = time.perf_counter()
            for _ in range(n):
                trainer.train_batch(data)
            return n / (time.perf_counter() - t0)

        prev = FLIGHT.enabled
        try:
            FLIGHT.enabled = False
            _steps_per_s(4)                     # settle both modes
            off = _steps_per_s(train_steps)
            FLIGHT.enabled = True
            _steps_per_s(4)
            on = _steps_per_s(train_steps)
        finally:
            FLIGHT.enabled = prev
        out["flight_recorder_overhead"] = {
            "steps_per_s_off": round(off, 2),
            "steps_per_s_on": round(on, 2),
            "overhead_ratio": round(off / on, 3),
        }
    if "profiler_overhead" in rows:
        # the continuous step profiler's cost (obs/profile.py): the
        # same tiny train loop with PROFILER off vs on at the default
        # sampling cadence. Gated like the flight recorder — the RATIO
        # (off/on steps/s) is machine-independent; the acceptance
        # budget is a few percent of steps/s, and > 2x fails the gate
        # outright (BENCH_SMOKE_BASELINE.json).
        from paddle_tpu.obs.profile import PROFILER
        trainer, data = _smoke_trainer()
        trainer.train_batch(data)               # compile + warm

        def _steps_per_s_prof(n):
            t0 = time.perf_counter()
            for _ in range(n):
                trainer.train_batch(data)
            return n / (time.perf_counter() - t0)

        # alternating off/on reps with the MEDIAN of each: a dozen
        # sub-ms steps is a ~10 ms window, where scheduler jitter alone
        # reads as several percent — the ratio of medians is what the
        # <= few-percent acceptance budget is judged on
        offs, ons = [], []
        try:
            PROFILER.enable(sample_every=8)
            # 10 settle steps so the first sampled step (and its
            # one-time AOT cost_analysis compile) lands OUTSIDE the
            # measured window — the row gates steady-state overhead
            _steps_per_s_prof(10)
            for _ in range(5):
                PROFILER.disable()
                _steps_per_s_prof(4)            # settle the mode flip
                offs.append(_steps_per_s_prof(train_steps))
                PROFILER.enable(sample_every=8)
                _steps_per_s_prof(4)
                ons.append(_steps_per_s_prof(train_steps))
        finally:
            PROFILER.reset()
        off = sorted(offs)[len(offs) // 2]
        on = sorted(ons)[len(ons) // 2]
        out["profiler_overhead"] = {
            "steps_per_s_off": round(off, 2),
            "steps_per_s_on": round(on, 2),
            "overhead_ratio": round(off / on, 3),
        }
    if "lockdep_overhead" in rows:
        # the lockdep witness's cost (analysis/lockdep.py): an
        # uncontended with-lock loop over a raw threading.Lock vs an
        # InstrumentedLock. Every hot shared lock in the framework is
        # instrumented, so this ratio bounds what the deadlock witness
        # adds to every critical section; the RATIO is
        # machine-independent and gated like the profiler
        # (BENCH_SMOKE_BASELINE.json). Medians of alternating reps for
        # the same jitter reasons as profiler_overhead.
        import threading as _threading
        from paddle_tpu.analysis.lockdep import InstrumentedLock
        n_ops = 20000

        def _ops_per_s(lk, n=n_ops):
            t0 = time.perf_counter()
            for _ in range(n):
                with lk:
                    pass
            return n / (time.perf_counter() - t0)

        raw_lk = _threading.Lock()
        inst_lk = InstrumentedLock("bench.lockdep")
        _ops_per_s(raw_lk, 2000)                # warm both paths
        _ops_per_s(inst_lk, 2000)
        raws, insts = [], []
        for _ in range(5):
            raws.append(_ops_per_s(raw_lk))
            insts.append(_ops_per_s(inst_lk))
        raw = sorted(raws)[len(raws) // 2]
        inst = sorted(insts)[len(insts) // 2]
        out["lockdep_overhead"] = {
            "ops_per_s_raw": round(raw, 0),
            "ops_per_s_instrumented": round(inst, 0),
            "overhead_ratio": round(raw / inst, 3),
        }
    if "protocol_witness_overhead" in rows:
        # the protocol witness's cost (obs/protocol.py): a start/settle
        # emit pair into a bare journal vs one with the witness
        # observing — the witness rides the SAME observer seam in
        # production (obs/__init__.py), so this ratio bounds what ptproto
        # adds to every journaled protocol event. Medians of alternating
        # reps, ratio gated like lockdep_overhead.
        from paddle_tpu.obs.events import EventJournal
        from paddle_tpu.obs.protocol import ProtocolWitness
        n_pairs = 4000

        def _pairs_per_s(j, n=n_pairs):
            t0 = time.perf_counter()
            for i in range(n):
                t = f"bench-{i}"
                j.emit("serving", "hop", trace_id=t, phase="start")
                j.emit("serving", "hop", trace_id=t, phase="settle")
            return n / (time.perf_counter() - t0)

        bare_j = EventJournal()
        wit_j = EventJournal()
        witness = ProtocolWitness()
        wit_j.add_observer(witness.observe_journal)
        _pairs_per_s(bare_j, 500)               # warm both paths
        _pairs_per_s(wit_j, 500)
        bares, wits = [], []
        for _ in range(5):
            bares.append(_pairs_per_s(bare_j))
            wits.append(_pairs_per_s(wit_j))
        bare = sorted(bares)[len(bares) // 2]
        wit = sorted(wits)[len(wits) // 2]
        out["protocol_witness_overhead"] = {
            "pairs_per_s_bare": round(bare, 0),
            "pairs_per_s_witnessed": round(wit, 0),
            "overhead_ratio": round(bare / wit, 3),
            "violations": witness.violation_count,   # must stay 0
        }
    if "contract_check" in rows:
        # wall time of the full-repo R11/R12/R13 contract sweep (the
        # `paddle_tpu lint --contracts` view) — info only: it tracks
        # catalog growth, nothing latency-critical rides on it
        import os as _os

        from paddle_tpu.analysis.runner import (_contracts_view,
                                                load_config)
        t0 = time.perf_counter()
        res = _contracts_view(
            load_config(_os.path.dirname(_os.path.abspath(__file__))),
            use_baseline=True)
        out["contract_check"] = {
            "wall_ms": round((time.perf_counter() - t0) * 1000.0, 1),
            "files": res.files,
            "findings": len(res.new),
        }
    if "coord_reshard" in rows:
        # elastic-membership control-plane latency: time from a
        # membership change (join) to the FIRST task grant stamped with
        # the post-reshape generation — the window during which the
        # fleet is reorganizing instead of training. Tiny shapes, pure
        # control plane (no XLA), gated by the latency kind's absolute
        # floor so any machine passes unless the reshard path grows
        # real work (docs/robustness.md "Elastic training").
        from paddle_tpu.trainer.coordinator import Coordinator
        coord = Coordinator(list(range(64)), chunks_per_task=4,
                            timeout_s=60.0)
        coord.join("bench-w0")
        reshards = 8
        lat = []
        for i in range(reshards):
            wid = f"bench-w{i + 1}"
            t0 = time.perf_counter()
            gen = coord.join(wid)["generation"]
            while True:
                grant = coord.get_task(worker_id=wid)
                if grant is None or grant["generation"] >= gen:
                    break
            lat.append((time.perf_counter() - t0) * 1000.0)
            if grant is not None:
                coord.task_finished(grant["task_id"],
                                    grant["generation"])
        lat.sort()
        out["coord_reshard"] = {
            "reshard_latency_ms": round(lat[len(lat) // 2], 3),
            "reshards": reshards,
            "generation": coord.generation,
        }
    if "embed_lookup" in rows or "embed_update" in rows:
        # the sharded embedding store (paddle_tpu/embed): pure host/RPC
        # control plane, no XLA. embed_lookup gates the serving gather
        # path (rows/s + per-gather latency through the XML-RPC plane);
        # embed_update gates the async-SGD push path (acked update
        # rows/s through the exactly-once ledger). Latencies carry the
        # latency kind's absolute floor; rates are loose like every
        # timing metric here (docs/observability.md "The perf gate").
        from paddle_tpu.embed import EmbedService
        n_keys, n_dim = 256, 16
        with EmbedService(2, n_dim, seed=0) as esvc:
            with esvc.client(client_id="bench-embed") as ecl:
                if "embed_lookup" in rows:
                    rng = np.random.RandomState(0)
                    ecl.gather(np.arange(n_keys, dtype="int64"))  # warm
                    lats = []
                    t_all = time.perf_counter()
                    reps = 12
                    for _ in range(reps):
                        keys = rng.randint(0, 100000, n_keys) \
                            .astype("int64")
                        t0 = time.perf_counter()
                        ecl.gather(keys, max_stale_s=0.0)  # forced RPC
                        lats.append((time.perf_counter() - t0) * 1e3)
                    dt = time.perf_counter() - t_all
                    lats.sort()
                    out["embed_lookup"] = {
                        "rows_per_s": round(reps * n_keys / dt, 1),
                        "gather_p50_ms": round(lats[len(lats) // 2], 3),
                        "gather_p99_ms": round(lats[-1], 3),
                        "gathers": reps,
                    }
                if "embed_update" in rows:
                    rng = np.random.RandomState(1)
                    n_batches = 12
                    t0 = time.perf_counter()
                    for _ in range(n_batches):
                        keys = rng.randint(0, 100000, n_keys) \
                            .astype("int64")
                        ecl.push(keys,
                                 np.ones((n_keys, n_dim), "float32"),
                                 lr=0.1)
                    ecl.flush(timeout=60.0)
                    dt = time.perf_counter() - t0
                    st = ecl.stats()
                    out["embed_update"] = {
                        "updates_per_s": round(st["pushed_rows"] / dt, 1),
                        "push_failures": st["push_failures"],
                        "batches": n_batches,
                    }
    if "fleet_route" in rows or "fleet_failover" in rows:
        # ISSUE 15 tentpole: the serving-fleet router. fleet_route
        # gates the ROUTER'S OVERHEAD — the same request through the
        # router hop (radix-affinity choose + HTTP stream relay) vs
        # straight at the replica; both are latency metrics with the
        # absolute floor, so only a real control-plane regression
        # (scrape under the route lock, affinity scan gone quadratic)
        # fails. fleet_failover is an info row: mid-stream kill ->
        # time-to-resume on the sibling, recorded for trend reading
        # (docs/robustness.md "Serving fleet").
        import threading as _th
        import urllib.request as _rq

        from paddle_tpu.fleet import Router
        from paddle_tpu.serving import (DecodeEngine, InferenceServer,
                                        build_http_server)
        from paddle_tpu.testing import FaultPlan

        def _fleet_replica():
            eng = DecodeEngine(_smoke_decoder(), num_slots=2,
                               page_size=4, max_seq_len=32)
            srv = InferenceServer(None, max_queue=32, workers=1,
                                  breaker=False, engine=eng).start()
            httpd = build_http_server(srv, "127.0.0.1", 0)
            _th.Thread(target=httpd.serve_forever, daemon=True,
                       name="pt-bench-replica").start()
            ep = f"http://127.0.0.1:{httpd.server_address[1]}"
            return {"engine": eng, "server": srv, "httpd": httpd,
                    "endpoint": ep, "killed": False}

        def _direct(ep, prompt, n):
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": n}).encode()
            req = _rq.Request(ep + "/generate", data=body,
                              headers={"Content-Type":
                                       "application/json"})
            with _rq.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        reps = [_fleet_replica(), _fleet_replica()]
        router = Router(endpoints={f"r{i}": rep["endpoint"]
                                   for i, rep in enumerate(reps)},
                        affinity="prefix", page_size=4,
                        scrape_interval=0.1, queue_timeout=10.0)
        try:
            rng = np.random.RandomState(3)
            shared = [int(t) for t in rng.randint(0, 40, (9,))]
            for rep in reps:                    # compile + warm BOTH
                _direct(rep["endpoint"], shared, 1)
            router.refresh()
            if "fleet_route" in rows:
                reqs = 16
                direct_ms, routed_ms = [], []
                for i in range(reqs):
                    p = shared + [i % 40]
                    t0 = time.perf_counter()
                    _direct(reps[0]["endpoint"], p, 4)
                    direct_ms.append((time.perf_counter() - t0) * 1e3)
                for i in range(reqs):
                    p = shared + [i % 40]
                    t0 = time.perf_counter()
                    router.generate(p, 4)
                    routed_ms.append((time.perf_counter() - t0) * 1e3)
                direct_ms.sort()
                routed_ms.sort()
                st = router.stats()
                out["fleet_route"] = {
                    "route_p50_ms": round(
                        routed_ms[len(routed_ms) // 2], 3),
                    "route_p99_ms": round(routed_ms[-1], 3),
                    "direct_p50_ms": round(
                        direct_ms[len(direct_ms) // 2], 3),
                    "routed": st["routed"],
                    "affinity_hits": st["affinity_hits"],
                }
            if "fleet_failover" in rows:
                # pin the stream on a known victim, throttle it so the
                # kill lands MID-stream, and time kill -> first token
                # relayed off the sibling
                prime = router.generate(shared + [38], 2)
                victim_i = int(prime.replica_chain[-1][1:])
                victim = reps[victim_i]
                victim["engine"]._step_interceptor = \
                    lambda s: time.sleep(0.01)
                marks = {}

                def _kill():
                    victim["killed"] = True
                    marks["kill"] = time.perf_counter()
                    victim["httpd"].kill()

                def _tok(_t):
                    if "kill" in marks and "resume" not in marks:
                        marks["resume"] = time.perf_counter()

                with FaultPlan.kill_replica(
                        router, f"r{victim_i}", _kill, at=2):
                    res = router.generate(shared + [38], 10,
                                          on_token=_tok)
                out["fleet_failover"] = {
                    "resume_ms": round(
                        (marks["resume"] - marks["kill"]) * 1e3, 3),
                    "hops": res.hops,
                    "tokens_out": len(res.tokens),
                }
        finally:
            router.shutdown(drain=True, timeout=10)
            for rep in reps:
                if not rep["killed"]:
                    rep["httpd"].shutdown()
                    rep["httpd"].server_close()
                rep["server"].shutdown(drain=True, timeout=30)

    if "cold_start_to_first_token" in rows:
        # ISSUE 18 tentpole row: what crash recovery / autoscale-up
        # actually costs. Cold = fresh engine with NOTHING warm (jit
        # caches and the executable cache both emptied) — construction
        # plus the first token, compile included. Warm = the same
        # respawn with the warm-start plane populated: the engine
        # resolves its executable instead of compiling, so
        # warm_ttft_ms IS the autoscale-MTTR decode bound and
        # warm_step_compiles is gated at 0. The artifact store fills
        # as a side effect (artifacts_built); the cross-process disk
        # rung is proven in tests/test_artifacts.py.
        import shutil as _sh
        import tempfile as _tf

        import jax as _jax

        from paddle_tpu import artifacts as _arts
        from paddle_tpu.serving import DecodeEngine as _DEng

        _croot = _tf.mkdtemp(prefix="pt_bench_arts_")
        _cstore = _arts.configure(_croot)
        try:
            _arts.EXECUTABLES.clear()
            _jax.clear_caches()

            def _ttft_ms():
                t0 = time.perf_counter()
                eng = _DEng(_smoke_decoder(), num_slots=2, page_size=4,
                            max_seq_len=32, prefix_cache=False)
                r = eng.submit(np.array([1, 2, 3, 4], np.int32), 1)
                eng.run(timeout=300)
                assert len(r.get(timeout=1)) == 1
                return (time.perf_counter() - t0) * 1e3

            cold_ms = _ttft_ms()
            with compile_watch() as _ccw:
                warm_ms = _ttft_ms()
            out["cold_start_to_first_token"] = {
                "cold_ttft_ms": round(cold_ms, 3),
                "warm_ttft_ms": round(warm_ms, 3),
                "warm_speedup": round(cold_ms / max(warm_ms, 1e-6), 2),
                "warm_step_compiles": sum(
                    v for k, v in _ccw.per_function.items()
                    if "_step_impl" in k),
                "artifacts_built": len(_cstore.entries()),
            }
        finally:
            _arts.configure(None)
            _sh.rmtree(_croot, ignore_errors=True)

    if "fleet_deploy" in rows:
        # ISSUE 16 tentpole leg (b): the SLO-gated rolling deploy.
        # failed_requests is the GATED metric (count, slack 0): a
        # drain->restart->rejoin cycle over every replica under an
        # open-loop burst must fail NOTHING — the zero-downtime
        # contract. Wall time is a loose latency trend row.
        import threading as _th

        from paddle_tpu.fleet import Router
        from paddle_tpu.fleet.autopilot import RollingDeploy
        from paddle_tpu.serving import (DecodeEngine, InferenceServer,
                                        build_http_server)
        from paddle_tpu.testing import FaultPlan

        class _Watch:                      # no SLO pressure in a bench
            breaches = 0

        def _dep_replica():
            eng = DecodeEngine(_smoke_decoder(), num_slots=2,
                               page_size=4, max_seq_len=32)
            srv = InferenceServer(None, max_queue=32, workers=1,
                                  breaker=False, engine=eng).start()
            httpd = build_http_server(srv, "127.0.0.1", 0)
            _th.Thread(target=httpd.serve_forever, daemon=True,
                       name="pt-bench-deploy-replica").start()
            ep = f"http://127.0.0.1:{httpd.server_address[1]}"
            return {"server": srv, "httpd": httpd, "endpoint": ep}

        dreps = {f"r{i}": _dep_replica() for i in range(2)}
        drouter = Router(endpoints={rid: rep["endpoint"]
                                    for rid, rep in dreps.items()},
                         affinity="prefix", page_size=4,
                         scrape_interval=0.1, queue_timeout=10.0,
                         queue_poll=0.02, drain_timeout=5.0).start()
        try:
            # compile + warm — the same request shape as the burst,
            # twice ON EACH REPLICA'S OWN ENGINE: the first runs the
            # step's two programs (the prompt's lane step, then plain
            # ones) and caches its prefix pages, the second's prefix hit
            # resolves the CoW copy_page executable, so nothing is left
            # to compile during the rollout. (Through the router the
            # second request went to whichever replica the last scrape
            # favoured, and the copy was warmed or not by timing.)
            for rep in dreps.values():
                for _ in range(2):
                    rep["server"].engine.submit([1, 2, 3], 4).get(
                        timeout=120)
            drouter.generate([1, 2, 3], 4)
            dl = time.monotonic() + 5
            while time.monotonic() < dl and any(
                    s.last_scrape == 0 for s in
                    drouter.balancer.replicas().values()):
                time.sleep(0.05)

            def _restart(rid):
                old = dreps[rid]
                old["httpd"].shutdown()
                old["httpd"].server_close()
                old["server"].shutdown(drain=True, timeout=30)
                dreps[rid] = _dep_replica()
                return {"endpoint": dreps[rid]["endpoint"]}

            # max_compiles=0: with the warm-start plane live, a whole
            # rolling restart must not compile ANYTHING (ISSUE 18) —
            # the gate keeps rollout_compiles pinned at zero
            roll = RollingDeploy(drouter, _restart, watchdog=_Watch(),
                                 settle_timeout=30.0, max_compiles=0)
            deploy_out = {}

            def _run_deploy():
                deploy_out.update(roll.run())

            dt = _th.Thread(target=_run_deploy, daemon=True,
                            name="pt-bench-deploy")
            t0 = time.perf_counter()
            dt.start()

            def _one(i):
                res = drouter.generate([1 + i % 5, 2, 3], 4)
                assert len(res.tokens) == 4
                return res
            results, errors = FaultPlan.burst(_one, n=24, threads=4,
                                              timeout=120)
            dt.join(timeout=60)
            wall_ms = (time.perf_counter() - t0) * 1e3
            out["fleet_deploy"] = {
                "failed_requests": sum(e is not None for e in errors),
                "requests": sum(r is not None for r in results),
                "deploy_steps": len(deploy_out.get("steps", [])),
                "deploy_complete": int(
                    deploy_out.get("status") == "complete"),
                "deploy_wall_ms": round(wall_ms, 3),
                # 99 (not 0) when the deploy thread died: losing the
                # measurement must FAIL the count gate, not pass it
                "rollout_compiles": deploy_out.get(
                    "rollout_compiles", 99),
            }
        finally:
            drouter.shutdown(drain=True, timeout=10)
            for rep in dreps.values():
                rep["httpd"].shutdown()
                rep["httpd"].server_close()
                rep["server"].shutdown(drain=True, timeout=30)

    if "fleet_autoscale" in rows:
        # ISSUE 16 tentpole leg (a), info row: the hysteresis policy
        # replayed over the canonical seeded bursty trace (same replay
        # as tests/test_autopilot.py) — decision counts and how many
        # ticks the shed spike takes to turn into a spawn decision.
        from paddle_tpu.fleet.autopilot import AutopilotPolicy
        from paddle_tpu.testing import FaultPlan

        trace = FaultPlan.bursty_trace(seed=0, ticks=30)
        pol = AutopilotPolicy(min_replicas=1, max_replicas=2,
                              up_cooldown_s=2.0, down_cooldown_s=3.0,
                              down_stable_s=2.0)
        live, ups, downs, first_up = 1, 0, 0, None
        burst_edge = 8                       # bursty_trace burst_start
        for t, load in enumerate(trace):
            shed = max(0, load - 4 * live)
            sig = {"replicas_live": live, "shed_rate": float(shed),
                   "headroom_frac": 0.9 if shed == 0 else 0.2,
                   "headroom_trend_per_s": 0.0, "slo_breaches": 0}
            d = pol.decide(sig, float(t))
            if d is None:
                continue
            if d["action"] == "scale_up":
                ups += 1
                live += 1
                if first_up is None:
                    first_up = t
            else:
                downs += 1
                live -= 1
        out["fleet_autoscale"] = {
            "scale_ups": ups,
            "scale_downs": downs,
            "decisions": ups + downs,
            "ticks_to_scale_up": (first_up - burst_edge
                                  if first_up is not None else -1),
            "final_replicas": live,
        }

    if "router_ha" in rows:
        # ISSUE 16 tentpole leg (c): N independent router planes must
        # agree on cold-prompt placement (rendezvous over the stable
        # first-page key — no shared state). placement_agreement is
        # RATE-gated at >= 0.9 in BENCH_SMOKE_BASELINE.json: the HA
        # property a client retry on a sibling router depends on.
        from paddle_tpu.fleet import FleetBalancer

        planes = []
        for _ in range(2):
            bal = FleetBalancer(affinity="prefix", page_size=4)
            for i in range(3):
                bal.upsert(f"r{i}", f"http://bench:{i}")
                bal.record_scrape(f"r{i}", kv_pages_total=64,
                                  kv_pages_free=64, page_size=4)
            planes.append(bal)
        rng = np.random.RandomState(11)
        agree = total = 0
        homes = set()
        for _ in range(64):
            plen = int(rng.randint(6, 20))
            prompt = [int(v) for v in rng.randint(2, 40, (plen,))]
            picks = [b.choose(prompt, plen + 4)[0] for b in planes]
            total += 1
            agree += int(picks[0] == picks[1])
            homes.add(picks[0])
        out["router_ha"] = {
            "placement_agreement": round(agree / total, 4),
            "prompts": total,
            "replicas_spread": len(homes),
        }

    if "soak_smoke" in rows:
        # ISSUE 17 tentpole: a seconds-bounded seeded soak (mixed
        # CTR + chat, replica-kill + shard-kill fault families) whose
        # verdict counters gate CORRECTNESS, not speed: settle
        # duplicates/losses and verdict failures are count-gated at 0
        # slack in BENCH_SMOKE_BASELINE.json — one duplicated settle
        # anywhere in the fleet fails the perf gate. ttft_p99 is
        # latency-gated loosely (first streams pay XLA compile).
        from paddle_tpu.loadgen import run_soak

        report = run_soak(seed=11, duration_s=3.0, workload="mixed",
                          families="po")
        eo = report["checks"]["exactly_once"]
        ttft = report["checks"]["latency_slo"]["ttft_p99_ms"]
        out["soak_smoke"] = {
            "verdict_failures": int(not report["ok"]),
            "settle_dups": len(eo["duplicates"]),
            "settle_lost": len(eo["lost"]),
            "ttft_p99_ms": round(float(ttft), 3)
            if ttft is not None else 1e9,
            "requests": report["counts"]["requests"],
            "faults_injected": report["counts"]["faults"],
        }

    if "kv_capacity_multiplier" in rows:
        # ISSUE 20 tentpole leg (a): int8 pages with per-row scales
        # must hold >= 2x the KV tokens per HBM byte of the fp32 pools.
        # tokens_per_byte_x is RATE-gated at >= 2.0 (0.75x of the
        # 4*dh/(dh+4) = 2.67 analytic value at dh=8) — deterministic,
        # computed from the engines' REAL pool buffers, not the
        # formula. effective_pages adds the spill tier on top.
        from paddle_tpu.serving import DecodeEngine
        e32 = DecodeEngine(_smoke_decoder(), num_slots=2, page_size=4,
                           max_seq_len=32)
        e8 = DecodeEngine(_smoke_decoder(), num_slots=2, page_size=4,
                          max_seq_len=32, kv_quant="int8",
                          kv_spill_pages=16)
        b32, b8 = e32.paged.pool_bytes(), e8.paged.pool_bytes()
        acc = e8.page_accounting()
        out["kv_capacity_multiplier"] = {
            "tokens_per_byte_x": round(b32 / b8, 3),
            "fp32_pool_bytes": b32,
            "int8_pool_bytes": b8,
            "device_pages": acc["total_usable"],
            "effective_pages": acc["total_usable"]
            + acc["spill_capacity"],
        }

    if "kv_dequant_overhead" in rows:
        # the dequant read path's decode-throughput cost: int8 vs fp32
        # over identical engines and prompts. throughput_ratio is
        # ratio-gated (rate, loose floor) — it catches the dequant
        # path falling off a cliff, not CPU timing noise.
        from paddle_tpu.serving import DecodeEngine

        def _toks_per_s(kv_quant):
            eng = DecodeEngine(_smoke_decoder(), num_slots=2,
                               page_size=4, max_seq_len=32,
                               kv_quant=kv_quant)
            rng = np.random.RandomState(3)
            prompts = [[int(t) for t in rng.randint(0, 40, 6)]
                       for _ in range(4)]
            warm = eng.submit(prompts[0], 2)   # compile prefill + step
            eng.run(timeout=300)
            warm.get(timeout=1)
            t0 = time.perf_counter()
            reqs = [eng.submit(p, 8) for p in prompts]
            eng.run(timeout=300)
            toks = sum(len(r.get(timeout=1)) for r in reqs)
            return toks / (time.perf_counter() - t0)

        f32 = _toks_per_s(None)
        i8 = _toks_per_s("int8")
        out["kv_dequant_overhead"] = {
            "throughput_ratio": round(i8 / f32, 3),
            "fp32_toks_per_s": round(f32, 2),
            "int8_toks_per_s": round(i8, 2),
        }

    if "kv_restore_latency" in rows:
        # ISSUE 20 tentpole leg (b), info row: cost of bringing a
        # spilled prefix back from the host store on a revisit —
        # end-to-end revisit wall time and the per-page restore share.
        from paddle_tpu.serving import DecodeEngine
        from paddle_tpu.testing import FaultPlan as _FPk
        eng = DecodeEngine(_smoke_decoder(), num_slots=2, page_size=4,
                           max_seq_len=20, num_pages=9,
                           kv_spill_pages=16)
        plan = _FPk(seed=5)
        # revisit_from past the last wave: the storm only spills, so
        # the store still holds the early prompts' pages afterwards
        schedule, submitted = plan.spill_storm(
            eng, waves=4, per_wave=2, gap=2, prompt_len=8, max_new=3,
            vocab=40, revisit_from=4)
        with _FPk.decode_script(eng, schedule):
            eng.run(timeout=300)
        acc0 = eng.page_accounting()
        p0 = submitted[0][1]
        t0 = time.perf_counter()
        req = eng.submit(p0, 3)
        eng.run(timeout=300)
        req.get(timeout=1)
        dt_ms = (time.perf_counter() - t0) * 1e3
        acc1 = eng.page_accounting()
        restored = acc1["spill_restores"] - acc0["spill_restores"]
        out["kv_restore_latency"] = {
            "revisit_ms": round(dt_ms, 3),
            "restored_pages": restored,
            "restore_ms_per_page": round(dt_ms / max(restored, 1), 3),
        }
    return {"v": 1, "suite": "smoke", "rows": out}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=["headline", "all", "smoke"])
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", default=None,
                    help="also write the result JSON to this path "
                         "(the smoke tier's hand-off to "
                         "tools/bench_gate.py)")
    args = ap.parse_args()

    if args.suite == "smoke":
        # CPU smoke tier: f32, tiny shapes, count metrics — the perf
        # regression gate's input (tools/bench_gate.py)
        res = bench_smoke()
        blob = json.dumps(res)
        print(blob)
        if args.out:
            with open(args.out, "w") as f:
                f.write(blob + "\n")
        return 0

    import jax
    import paddle_tpu as paddle
    paddle.init(compute_dtype=args.dtype)
    dev = jax.devices()[0]

    def _emit(name, res):
        b = BASELINES_MS.get(name)
        res = dict(res)
        if b and res["ms"] > 0:
            res["vs_baseline"] = round(b / res["ms"], 3)
            lo, hi = res.get("min"), res.get("max")
            if lo and hi and (hi - lo) > 0.4 * res["ms"]:
                # spread past +-20%: self-describe the range so a
                # downstream reader never quotes the scalar alone
                res["vs_baseline_range"] = [round(b / hi, 3),
                                            round(b / lo, 3)]
        print(json.dumps({"bench": name, **res}), file=sys.stderr)
        return res

    def _row(name, thunk, retries=2):
        """One suite row, retried on transient failure: a flaky row
        must cost a retry, not the whole artifact."""
        err = None
        for attempt in range(retries + 1):
            try:
                return _emit(name, thunk())
            except Exception as e:  # noqa: BLE001 — record and move on
                err = e
                print(json.dumps({"bench": name, "attempt": attempt,
                                  "error": str(e)[:300]}), file=sys.stderr)
        return {"ms": -1.0, "error": str(err)[:300]}

    suite = {}
    suite["alexnet_bs128"] = _row(
        "alexnet_bs128",
        lambda: bench_image("alexnet_bs128", 128, iters=args.iters))

    if args.suite == "all":
        half = max(args.iters // 2, 5)
        suite["alexnet_bs512"] = _row(
            "alexnet_bs512",
            lambda: bench_image("alexnet_bs512", 512, iters=half))
        suite["smallnet_bs128"] = _row(
            "smallnet_bs128",
            lambda: bench_image("smallnet_bs128", 128, iters=args.iters))
        suite["googlenet_bs128"] = _row(
            "googlenet_bs128",
            lambda: bench_image("googlenet_bs128", 128, iters=half))
        suite["resnet50_bs128"] = _row(
            "resnet50_bs128",
            lambda: bench_image("resnet50_bs128", 128, iters=half))
        suite["resnet50_bs128_tpustem"] = _row(
            "resnet50_bs128_tpustem",
            lambda: bench_image("resnet50_bs128_tpustem", 128, iters=half))
        suite["vgg16_bs128"] = _row(
            "vgg16_bs128",
            lambda: bench_image("vgg16_bs128", 128, iters=half))
        suite["lstm_bs64_h256"] = _row(
            "lstm_bs64_h256", lambda: bench_lstm(64, 256, iters=args.iters))
        suite["lstm_bs128_h1280"] = _row(
            "lstm_bs128_h1280", lambda: bench_lstm(128, 1280, iters=half))
        suite["flash_attention_t4096"] = _row(
            "flash_attention_t4096", lambda: bench_flash_attention(iters=half))
        suite["transformer_lm_bs8_t1024"] = _row(
            "transformer_lm_bs8_t1024", lambda: bench_transformer(iters=half))
        # batch sweep anchors the claim "throughput scales with batch
        # until cache reads saturate HBM" (docs/perf.md)
        suite["decode_bs1_512tok"] = _row(
            "decode_bs1_512tok", lambda: bench_decode(batch=1))
        suite["decode_bs8_512tok"] = _row(
            "decode_bs8_512tok", lambda: bench_decode())
        suite["decode_bs32_512tok"] = _row(
            "decode_bs32_512tok", lambda: bench_decode(batch=32))
        # beyond-parity rows, driver-captured so regressions are visible
        # (VERDICT r4: the GQA/MoE claims lived only in dev captures)
        suite["decode_bs32_gqa"] = _row(
            "decode_bs32_gqa",
            lambda: bench_decode(batch=32, n_kv_heads=2))
        # continuous-batching engine rows (paged KV cache, ragged
        # workload — serving/engine.py): the roofline_frac here is
        # against the PAGED floor (actual cache lengths), the
        # ROADMAP item-1 target of < 1.3 across bs 1/8/32
        suite["decode_continuous_bs1"] = _row(
            "decode_continuous_bs1",
            lambda: bench_decode_continuous(num_slots=1, n_requests=6,
                                            new_tokens=(64, 128)))
        suite["decode_continuous_bs8"] = _row(
            "decode_continuous_bs8",
            lambda: bench_decode_continuous())
        suite["decode_continuous_bs32"] = _row(
            "decode_continuous_bs32",
            lambda: bench_decode_continuous(num_slots=32,
                                            n_requests=96))
        suite["decode_continuous_bs32_gqa"] = _row(
            "decode_continuous_bs32_gqa",
            lambda: bench_decode_continuous(num_slots=32,
                                            n_requests=96,
                                            n_kv_heads=2))
        suite["moe_lm_bs8_t1024"] = _row(
            "moe_lm_bs8_t1024", lambda: bench_moe_lm(iters=half))

    head_name = "alexnet_bs128"
    head = suite[head_name]
    if head.get("ms", -1) <= 0:  # headline row lost to a persistent flake:
        # fall back to another successful row, RENAMING the metric so a
        # consumer never records a different benchmark under the alexnet
        # label; if nothing succeeded, exit non-zero with a null value.
        head_name, head = next(
            ((n, r) for n, r in suite.items() if r.get("ms", -1) > 0),
            (head_name, head))
    ok = head.get("ms", -1) > 0
    print(json.dumps({
        "metric": f"{head_name}_train_ms_per_batch",
        "value": head["ms"] if ok else None,
        "unit": "ms/batch",
        "vs_baseline": head.get("vs_baseline"),
        "dtype": args.dtype,
        "device": getattr(dev, "device_kind", str(dev)),
        "suite": suite,
        # BASELINE.json metric: ResNet-50 samples/sec/chip >= V100
        # use_gpu throughput (~400 f32 / ~900 mixed samples/s); the row
        # runs under --suite all (the default)
        "north_star": {
            "resnet50_samples_per_sec_per_chip":
                suite.get("resnet50_bs128", {}).get("samples_per_sec"),
            "target": ">= V100 use_gpu throughput (BASELINE.json)",
        } if "resnet50_bs128" in suite else {
            "note": "run --suite all for the resnet50 north-star row"},
        "skipped": {k: "needs multi-chip slice" for k in MULTICHIP_ROWS},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
