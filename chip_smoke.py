"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the user's entry points, a small language model (vocab
32000, d_model 512, 8 heads, 6 layers, d_ff 2048, bf16: the width of the
older `bench.py` rows, NOT a configuration of the benchmark, which is
`BENCHMARK.json` / `benchmarks/run.py`), random weights from a seed:

- train: five ``paddle.SGD(...).train(...)`` steps at batch 8 x T 1024;
- serve: six requests through ``DecodeEngine`` with its default
  ``attention``, two of them checked against the dense
  ``TransformerDecoder`` (``reference_check``).

Run with no arguments it needs one TPU chip and fails anywhere else.
``--chips 4`` runs ONLY the data-parallel trainer (``trainer_count=4``)
and its one-device comparison. ``--tiny`` is the CPU rehearsal of the
control flow: it shrinks the sizes and lifts the platform check, nothing
else. Every phase may raise; nothing here catches.

The last line of standard output is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The times printed on earlier lines are one run, smoke, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

SEED = 0

FULL = dict(vocab=32000, d_model=512, n_heads=8, n_layers=6, d_ff=2048,
            batch=8, seq_len=1024,
            num_slots=8, page_size=16, max_seq_len=544,
            prompt_lens=(16, 48, 96, 144, 200, 256), new_tokens=32)
TINY = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            batch=4, seq_len=32,
            num_slots=4, page_size=4, max_seq_len=32,
            prompt_lens=(3, 5, 8, 11, 14, 16), new_tokens=6)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def cache_entries(directory) -> int:
    if directory is None or not os.path.isdir(directory):
        return 0
    return len(os.listdir(directory))


def build_trainer(cfg, on_chip: bool, trainer_count: int):
    """The LM through the v2 entry points, fed the way bench.py's
    bench_transformer feeds it. Returns (trainer, one batch of sample
    rows). On the chip the TPU is asked for by name, so that a process
    that lost it raises; the rehearsal takes what is there."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core import registry

    registry.reset_name_counters()
    paddle.init(use_tpu=True if on_chip else None,
                trainer_count=trainer_count,
                compute_dtype="bfloat16", seed=SEED)
    spec = models.transformer_lm(
        vocab_size=cfg["vocab"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_layers=cfg["n_layers"],
        d_ff=cfg["d_ff"], max_len=cfg["seq_len"], tie_embeddings=True)
    params = paddle.create_parameters(paddle.Topology(spec.cost))
    trainer = paddle.SGD(cost=spec.cost, parameters=params,
                         update_equation=paddle.optimizer.Adam(
                             learning_rate=1e-4))
    t = cfg["seq_len"]
    ids = np.random.RandomState(SEED).randint(
        0, cfg["vocab"], (cfg["batch"], t + 1)).astype("int32")
    pos = np.arange(t, dtype="int32")
    rows = [(ids[i, :-1], pos, ids[i, 1:]) for i in range(cfg["batch"])]
    return trainer, rows


def compiled_train_step(trainer, rows):
    """The train step's executable for exactly this batch: the HLO the
    kernel and collective checks read, and the shardings its inputs
    were compiled for."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.trainer.data_feeder import DataFeeder

    feed = DataFeeder(trainer.topology.data_type())(rows)
    n_real = jnp.asarray(feed.pop("__batch_size__"), jnp.int32)
    return trainer._train_step.lower(
        trainer._own_params(), trainer.opt_state, trainer.parameters.state,
        feed, jax.random.PRNGKey(SEED), n_real).compile()


def run_steps(trainer, rows, steps: int):
    """``steps`` optimizer steps on one repeated batch through
    ``SGD.train``. Returns (costs, seconds of each step); reading the
    cost waits for the device, so each stamp is after the step ended."""
    import paddle_tpu as paddle

    costs, stamps = [], [time.perf_counter()]

    def on_event(e):
        if isinstance(e, paddle.event.EndIteration):
            costs.append(float(e.cost))
            stamps.append(time.perf_counter())

    trainer.train(reader=lambda: iter([rows] * steps), num_passes=1,
                  event_handler=on_event)
    assert len(costs) == steps, (len(costs), steps)
    assert all(np.isfinite(costs)), costs
    return costs, list(np.diff(stamps))


def train_phase(cfg, on_chip: bool):
    trainer, rows = build_trainer(cfg, on_chip, trainer_count=1)
    costs, secs = run_steps(trainer, rows, steps=5)
    assert costs[4] < costs[0], costs
    flash_in_hlo = "tpu_custom_call" in \
        compiled_train_step(trainer, rows).as_text()
    assert flash_in_hlo == on_chip, (
        "the flash kernel must be in the train step on the chip, and "
        f"only there: tpu_custom_call in HLO = {flash_in_hlo}")
    steady = statistics.median(secs[2:])
    say("train", batch=cfg["batch"], seq_len=cfg["seq_len"],
        costs=[round(c, 5) for c in costs], flash_in_hlo=flash_in_hlo,
        first_step_s=round(secs[0], 3),
        compile_s=round(secs[0] - steady, 3),
        steady_step_ms=round(steady * 1e3, 3))
    return trainer


#: the repo's tolerance for bf16 results on the chip
#: (tests/test_tpu_smoke.py, ops/pallas_decode.INT8_KV_RTOL)
LOGIT_RTOL = 2e-2


def reference_check(dec, prompt, tokens):
    """Hold one served request to the dense decoder.

    ``dec.generate`` is the reference, and where the engine's tokens
    equal its tokens nothing more is asked. Greedy decoding is defined
    only up to ties, though, and in bf16 two programs that sum in a
    different order break a near-tie differently (seen on the v5e: one
    token of 32 flipped between two candidates whose logits tie). So
    each served token is also held to the dense decoder's own logits
    over the same prefix — teacher-forced through the prefill pass that
    ``generate`` runs — and must be their argmax up to LOGIT_RTOL."""
    import jax

    new = len(tokens)
    want = [int(t) for t in dec.generate(
        prompt[None, :], max_len=len(prompt) + new)[0]]
    diverge = next((i for i in range(new) if tokens[i] != want[i]), None)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], "int32")])
    logits = jax.jit(
        lambda p, ids: dec._prefill(p, ids, len(seq), len(seq))[0])(
            dec.p, seq[None, :])
    rows = np.asarray(logits[0, len(prompt) - 1:], np.float32)
    assert rows.shape[0] == new and np.isfinite(rows).all()
    best = rows.max(axis=1)
    short = best - rows[np.arange(new), tokens]
    assert (short <= LOGIT_RTOL * np.abs(best)).all(), (
        f"prompt {len(prompt)}: served tokens fall short of the dense "
        f"decoder's argmax by {short.max()} (logit scale "
        f"{np.abs(best).max()}); generate says {want}, served {tokens}")
    return {"prompt_len": len(prompt), "identical": diverge is None,
            "first_divergence": diverge,
            "argmax_of_reference": int((short == 0).sum()),
            "worst_shortfall": float(short.max())}


def serve_phase(cfg, trainer, on_chip: bool):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import models
    from paddle_tpu.serving import DecodeEngine

    params = {k: v.astype(jnp.bfloat16)
              for k, v in trainer.parameters.raw.items()}
    dec = models.TransformerDecoder(params, n_layers=cfg["n_layers"],
                                    n_heads=cfg["n_heads"])
    eng = DecodeEngine(dec, num_slots=cfg["num_slots"],
                       page_size=cfg["page_size"],
                       max_seq_len=cfg["max_seq_len"])
    paged = eng.paged
    assert paged.use_kernel == on_chip, (
        f"attention='auto' resolved to use_kernel={paged.use_kernel} "
        f"with on_chip={on_chip}")
    assert paged.kernel_interpret is False, "interpreted kernel on the path"

    t0 = time.perf_counter()
    eng.warmup()                # both step programs
    jax.block_until_ready((eng.k_pool, eng.v_pool))
    compile_s = time.perf_counter() - t0
    kernel_in_hlo = "tpu_custom_call" in paged._step_exe.as_text()
    assert kernel_in_hlo == paged.use_kernel, (kernel_in_hlo,
                                               paged.use_kernel)

    rng = np.random.RandomState(SEED + 1)
    new = cfg["new_tokens"]
    prompts = [rng.randint(0, cfg["vocab"], (n,)).astype("int32")
               for n in cfg["prompt_lens"]]
    t0 = time.perf_counter()
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run(timeout=600)
    seconds = time.perf_counter() - t0
    outs = [r.get(timeout=1) for r in reqs]      # typed failures raise
    assert all(len(o) == new for o in outs), [len(o) for o in outs]

    checked = [reference_check(dec, prompts[i], outs[i]) for i in (0, 3)]

    again = eng.submit(prompts[4], new)
    eng.run(timeout=600)
    assert again.get(timeout=1) == outs[4], "prefix reuse changed tokens"
    assert again.prefix_hit_pages >= 1, again.prefix_hit_pages

    acc = eng.page_accounting()
    assert acc["leaked"] == 0, acc
    assert acc["free"] + acc["held_by_trie"] == acc["total_usable"], acc
    assert acc["refs_total"] == acc["held_by_slots"] + acc["held_by_trie"], \
        acc
    say("serve", requests=len(reqs), prompt_lens=list(cfg["prompt_lens"]),
        use_kernel=paged.use_kernel,
        kernel_interpret=paged.kernel_interpret,
        kernel_in_hlo=kernel_in_hlo, compile_s=round(compile_s, 3),
        tokens=sum(len(o) for o in outs), seconds=round(seconds, 3),
        against_generate=checked,
        prefix_hit_pages=again.prefix_hit_pages,
        pages={k: acc[k] for k in ("total_usable", "free", "held_by_trie",
                                   "leaked")})


def data_parallel_phase(cfg, on_chip: bool):
    """trainer_count=4 against one device, same LM, same global batch:
    the batch and the gradients' all-reduce must span four devices."""
    import jax

    trainer, rows = build_trainer(cfg, on_chip, trainer_count=4)
    assert trainer.mesh is not None and trainer.mesh.size == 4, trainer.mesh
    compiled = compiled_train_step(trainer, rows)
    hlo = compiled.as_text()
    assert "all-reduce" in hlo, "no gradient all-reduce in the dp step"
    feed_shardings = jax.tree_util.tree_leaves(compiled.input_shardings[0][3])
    for sh in feed_shardings:
        assert len(sh.device_set) == 4 and not sh.is_fully_replicated, (
            f"the fed batch does not span four devices: {sh}")
    flash_in_hlo = "tpu_custom_call" in hlo
    assert flash_in_hlo == on_chip, flash_in_hlo
    dp_costs, dp_secs = run_steps(trainer, rows, steps=3)
    spans = {len(v.sharding.device_set)
             for v in trainer.parameters.raw.values()}
    assert spans == {4}, f"updated parameters live on {spans} devices"

    single, rows1 = build_trainer(cfg, on_chip, trainer_count=1)
    assert single.mesh is None
    one_costs, one_secs = run_steps(single, rows1, steps=3)
    np.testing.assert_allclose(dp_costs, one_costs, rtol=2e-2)
    say("data_parallel", trainer_count=4, batch=cfg["batch"],
        seq_len=cfg["seq_len"], all_reduce_in_hlo=True,
        flash_in_hlo=flash_in_hlo, feed_devices=4,
        dp_costs=[round(c, 5) for c in dp_costs],
        one_device_costs=[round(c, 5) for c in one_costs],
        dp_last_step_ms=round(dp_secs[-1] * 1e3, 3),
        one_device_last_step_ms=round(one_secs[-1] * 1e3, 3))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: tiny sizes, no platform check")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel trainer and its "
                         "one-device comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.tiny:
        print(f"chip_smoke: JAX found no accelerator ({device})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {device}",
              file=sys.stderr)
        return 1
    if on_chip:
        from paddle_tpu.obs import profile
        peaks = {"bf16_flops": profile.device_peak_flops(devices[0]),
                 "hbm_gbps": profile.device_hbm_gbps(devices[0])}
        if None in peaks.values():
            raise RuntimeError(
                f"device kind {device['kind']!r} is not in the peak "
                "tables of paddle_tpu/obs/profile.py")
        say("device", **device, **peaks)

    from paddle_tpu.artifacts import cache
    cache_dir = cache.enable()
    before = cache_entries(cache_dir)
    cfg = TINY if args.tiny else FULL
    if args.chips == 4:
        data_parallel_phase(cfg, on_chip)
    else:
        trainer = train_phase(cfg, on_chip)
        serve_phase(cfg, trainer, on_chip)
    say("compile_cache", dir=cache_dir, entries_before=before,
        entries_after=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
