"""Driver of the ``train_job`` mixes: ``paddle.SGD.train`` over a reader of
packed rows, reader and feeder running, one trainer object from set-up to
the end of the window.

Set-up builds the trainer, drives it from the seed through its first
three steps (through ``train``'s own call and feed, on rows that all
differ), reads what the comparison needs from its state, and hands the
same object to the window. The window's steps are stamped one step behind
the dispatch, so the device always has the next step queued; the window
closes with the first step that completes at or after ``--seconds``, and
the rate is all its tokens over all its time.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import check, harness, traffic
from benchmarks.lib import names as names_of
from benchmarks.lib.harness import now, percentile, say


def _named_to_leaves(named: dict, index: list) -> dict:
    """{program name: value} -> {reference leaf: [per layer, ...]};
    ``index`` is the model's ``leaf_index``."""
    out = {}
    for name, leaf, layer in index:
        out.setdefault(leaf, []).append(np.asarray(named[name]))
    return {k: np.stack(v) for k, v in out.items()}


def run(cell: dict, seed: int, seconds: float, trace: bool, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    cfg, job, limits = cell["config"], cell["traffic"], cell["limits"]
    chips = cell["chips"]
    reference, model = cell["reference"], cell["model"]
    compiles = env["compiles"]
    index = model.leaf_index(cfg)
    lr = float(job["learning_rate"])
    b1 = reference.ADAM["b1"]

    # ------------------------------------------------------------- set-up
    marks = {"start": env["t_start"], "imports": now()}
    named = model.make_weights(reference, seed, cfg, jnp.float32)
    jax.block_until_ready(named)
    marks["weights"] = now()
    trainer = model.build_trainer(named, cfg, job, chips, env["on_chip"])
    del named
    marks["trainer"] = now()
    gen = traffic.generate(job, seed, seconds, int(cfg["vocab_size"]), chips)
    tokens_per_step = gen["rows"] * gen["seq_len"]

    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

    # the two programs below take the seed's words as arguments
    # (check.from_seed): one program each for every seed
    def first_gradient(words, slots):
        """Norm and sketches (check.sketch_vectors) of every leaf of the
        first gradient as the optimizer got it: Adam's m after one step is
        (1 - b1) g."""
        g = {k: s["m"] / (1.0 - b1) for k, s in slots.items()}

        def one(k):
            return check.sketch_of(g, model.to_named(
                check.sketch_vectors(reference, words, cfg, k)))
        return ({k: norm(a) for k, a in g.items()},
                jax.lax.map(one, jnp.arange(check.SKETCHES)))

    def change_norms(words, p):
        return {k: norm(p[k] - v) for k, v in model.to_named(
            reference.init_params(words, cfg, jnp.float32)).items()}

    first = {"events": [], "grad": None}

    def on_first(e):
        if isinstance(e, paddle.event.EndIteration):
            if e.batch_id == 0:     # before step 2 is dispatched: Adam's m
                first["grad"] = check.from_seed(
                    first_gradient, seed, trainer.opt_state["slots"])
            first["events"].append(e)

    batches = [gen["batch"](i) for i in range(3)]
    trainer.train(reader=lambda: iter([model.rows_of(b)
                                       for b in batches]),
                  num_passes=1, event_handler=on_first)
    marks["three_steps_and_step_program"] = now()
    g_norms, g_sketch = jax.device_get(first["grad"])
    prog = {"losses": [float(e.cost) for e in first["events"]],
            "grad_norms": _named_to_leaves(g_norms, index),
            "grad_sketch": _named_to_leaves(g_sketch, index),
            "change_norms": _named_to_leaves(
                jax.device_get(check.from_seed(
                    change_norms, seed, trainer._own_params())), index)}
    if len(prog["losses"]) != 3:
        raise RuntimeError(f"set-up ran {len(prog['losses'])} steps, not 3")
    marks["norms"] = now()
    names = list(marks)
    say(phase="setup", **{f"{b}_s": marks[b] - marks[a]
                          for a, b in zip(names, names[1:])})

    # ------------------------------------------------------------- window
    stamps: list = []
    pending = [None]
    stop = [False]
    tracer = None
    if trace:
        span = float(job.get("trace_seconds", 5.0))
        tracer = harness.TraceWindow(max(0.0, 0.5 * seconds - 0.5 * span),
                                     min(span, seconds),
                                     lambda: {"steps": len(stamps)})

    def reader():
        step = 3
        while not stop[0]:
            yield model.rows_of(gen["batch"](step))
            step += 1

    def on_step(e):
        if not isinstance(e, paddle.event.EndIteration):
            return
        prev, pending[0] = pending[0], e
        if prev is not None:
            float(prev.cost)            # waits for the step before this one
            stamps.append(now())
            if stamps[-1] >= t_end:
                stop[0] = True

    compiles.open()
    t0 = now()
    setup_s = t0 - env["t_start"]
    t_end = t0 + seconds
    if tracer:
        tracer.start(t0)
    trainer.train(reader=reader, num_passes=1, event_handler=on_step)
    last_cost = float(pending[0].cost)
    stamps.append(now())
    n_compiles = compiles.close()
    if tracer:
        tracer.join()
        if env.get("dump_trace"):
            from benchmarks.lib import trace as _trace
            _trace.dump_summary(tracer.dir, env["dump_trace"])
    n_in = next(i + 1 for i, t in enumerate(stamps) if t >= t_end)
    window_s = stamps[n_in - 1] - t0
    step_ms = [1e3 * (b - a) for a, b in zip([t0] + stamps[:n_in - 1],
                                             stamps[:n_in])]
    e2e = {"train_tok_s": n_in * tokens_per_step / window_s,
           "setup_s": setup_s}
    mem_peak = harness.memory_peak_bytes(chips)
    say(phase="window", workload=cell["name"], seed=seed, window_s=window_s,
        steps=n_in, steps_dispatched=len(stamps),
        tokens_per_step=tokens_per_step, step_ms_p50=percentile(step_ms, 50),
        step_ms_p95=percentile(step_ms, 95), last_cost=last_cost,
        first_losses=prog["losses"], compiles_in_window=n_compiles)

    # ----------------------------------------- free, then the comparison
    harness.free(trainer.parameters.raw, trainer.opt_state,
                   trainer.parameters.state)
    del trainer
    t = now()
    ref = check.reference_three_steps(reference, cfg, seed, batches, lr)
    nums = check.compare_training(prog, ref)
    worst = nums.pop("_worst")
    say(phase="check", seconds=now() - t, reference_losses=ref["losses"],
        worst_leaves=worst, **nums)
    # a number with no upper reading (limits' "not_compared") is printed
    # above and not compared: it could only fail sound runs
    def held(numbers: dict) -> dict:
        checks = {k: (v, limits[k]["limit"]) for k, v in numbers.items()
                  if k in limits}
        checks["nonfinite_costs"] = (
            int(not np.isfinite(prog["losses"] + [last_cost]).all()), 0)
        checks["compiles_in_window"] = (n_compiles, 0)
        return checks

    checks = held(nums)
    correct = check.decide(checks)
    if env.get("control"):      # benchmarks/tools/readings.py, never a run
        half = list(range(gen["rows"] // 2))
        for label, kw in (("control_" + env["control"],
                           {"rounding": env["control"]}),
                          ("fault_half_batch", {"rows": half}),
                          ("fault_frozen_state", {"frozen": True})):
            other = check.compare_training(check.reference_three_steps(
                reference, cfg, seed, batches, lr, **kw), ref)
            other.pop("_worst")
            say(phase=label, seed=seed, correct=check.decide(held(other)),
                **other)
    ctx = {"config": cfg, "model": model, "traffic": job, "chips": chips,
           "window_s": window_s, "peaks": env["peaks"], "trace": None,
           "seq_len": gen["seq_len"], "rows_per_chip": gen["rows"] // chips,
           "counters": {"steps": n_in}}
    if tracer:
        ctx["trace"] = tracer.read(chips)
        say(phase="traced_steps", **ctx["trace"].steps(names_of.is_train_step))
        tracer.cleanup()
    return {"correct": correct, "attempted": n_in, "failed": 0,
            "end_to_end": e2e, "ctx": ctx, "checks": checks,
            "memory_peak_bytes": mem_peak}
