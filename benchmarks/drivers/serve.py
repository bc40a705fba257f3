"""Driver of the serving mixes (``open_loop`` and ``closed_loop``): the
window drives ``DecodeEngine.submit`` against the engine's own loop
(``start()``, the thread ``InferenceServer`` runs), from one load thread.

Open loop: every request is due at a time fixed before the run; time to
first token counts from when it was DUE, and how late the generator ran
is recorded. Closed loop: each client sends its next turn when the reply
to the last has come; its histories are prefilled through the engine
during set-up, so their pages sit in the prefix index.

The load starts ``ramp_s`` seconds before the window, as the last part of
set-up, so that the window opens on an engine in steady state. The
window's numbers are of the window alone: the requests due (or sent)
inside it, every gap stamped inside it, every token received inside it.
After the close nothing more is sent, and every request is waited for.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.lib import check, harness, traffic
from benchmarks.lib import names as names_of
from benchmarks.lib.harness import now, percentile, say


class _Sent:
    """One request as its client sees it: when it was due and sent, and
    the harness's own stamps of the tokens it has received so far."""
    __slots__ = ("req", "due", "sent", "prompt", "error", "seen", "t_first",
                 "t_last")

    def __init__(self, req, due, sent, prompt, error=None):
        self.req, self.due, self.sent = req, due, sent
        self.prompt, self.error = prompt, error
        self.seen, self.t_first, self.t_last = 0, None, None


def _submit(eng, prompt, max_new, due, log, live):
    sent = now()
    try:
        req = eng.submit(prompt, max_new)
        err = None
    except Exception as e:          # a refusal is a failed request
        req, err = None, e
    s = _Sent(req, due, sent, prompt, err)
    log.append(s)
    if req is not None:
        live.append(s)
    return s


def _observe(live: list, gaps, t: float) -> tuple:
    """Stamp, by the harness's clock, the tokens each live request has
    received since the last look; returns the requests still in flight
    and how many tokens came. ``gaps`` (None outside the window) takes the
    gaps between consecutive tokens. The engine commits one token a step
    and a look comes every millisecond, so a look sees at most one new
    token of a request; if it ever sees k, they share the time since the
    last one."""
    still, got = [], 0
    for s in live:
        done = s.req.done.is_set()      # before the count: none is missed
        n = len(s.req.tokens)
        if n > s.seen:
            k = n - s.seen
            got += k
            if s.seen == 0:
                s.t_first = t
                new = [0.0] * (k - 1)
            else:
                new = [(t - s.t_last) / k] * k
            if gaps is not None:
                gaps.extend(new)
            s.seen, s.t_last = n, t
        if not done:
            still.append(s)
    return still, got


def _warm(eng, vocab: int, seed: int):
    """Run every program the window will use once: the step (real
    tokens through the started loop) and the page copy of a partial
    prefix match. Neither leaves anything behind that a request of the
    window can match: the tokens come from a stream of their own."""
    rng = traffic.RngPlane(seed).stream("warmup")
    eng.k_pool, eng.v_pool = eng.paged.copy_page(eng.k_pool, eng.v_pool, 0, 0)
    reqs = [eng.submit(rng.integers(0, vocab, 24, dtype=np.int32), 8)
            for _ in range(2)]
    for r in reqs:
        r.get(timeout=600)


def _prefill_histories(eng, clients):
    """Each client's history through the engine, all at once; the pages
    stay in the prefix index when the request ends."""
    for r in [eng.submit(c["history"], 1) for c in clients]:
        r.get(timeout=900)


def run(cell: dict, seed: int, seconds: float, trace: bool, env: dict) -> dict:
    import jax
    import jax.numpy as jnp
    cfg, mix, limits = cell["config"], cell["traffic"], cell["limits"]
    dep = cfg["deployment"]
    reference, model = cell["reference"], cell["model"]
    vocab = int(cfg["vocab_size"])
    compiles = env["compiles"]

    # ------------------------------------------------------------- set-up
    marks = {"start": env["t_start"], "imports": now()}
    named = model.make_weights(reference, seed, cfg,
                               jnp.dtype(cfg["torch_dtype"]))
    jax.block_until_ready(named)
    marks["weights"] = now()
    dec, eng = model.build_engine(named, cfg, dep)
    del named
    if env["on_chip"] and not eng.paged.use_kernel:
        raise RuntimeError("the engine's default attention did not take the "
                           "paged kernel on the chip")
    eng.warmup()
    jax.block_until_ready((eng.k_pool, eng.v_pool))
    marks["engine_and_step_program"] = now()
    inputs = traffic.generate(mix, seed, seconds, vocab)
    marks["traffic"] = now()
    eng.start()
    _warm(eng, vocab, seed)
    marks["warm_requests"] = now()
    hist_s = 0.0
    if mix["kind"] == "closed_loop":
        t = now()
        _prefill_histories(eng, inputs["clients"])
        hist_s = now() - t
    tracer = None
    if trace:
        span = float(mix.get("trace_seconds", 3.0))
        tracer = harness.TraceWindow(max(0.0, 0.5 * seconds - 0.5 * span),
                                     min(span, seconds), eng.stats)

    # ---------------------------------------------------- ramp and window
    # One load thread: it sends what is due, looks at every live request
    # once a millisecond and stamps its new tokens. The ramp (the last of
    # set-up) and the window are one loop, so the window opens on the
    # ramp's load. After the close nothing is sent; every request is then
    # waited for, a minute past the close if need be: one that comes late
    # is late, not wrong.
    log: list = []
    live: list = []
    gaps_s: list = []
    poll = float(mix.get("poll_s", 0.001))
    ramp_s = float(mix["ramp_s"])
    open_loop = mix["kind"] == "open_loop"
    pending = (inputs["ramp_requests"] + inputs["requests"]) if open_loop \
        else []
    clients = [] if open_loop else inputs["clients"]
    turn = [0] * len(clients)
    owner = {}                          # id(_Sent) -> client index

    def send_turn(i):
        c = clients[i]
        tn = c["turns"][turn[i] % len(c["turns"])]
        turn[i] += 1
        s = _submit(eng, np.concatenate([c["history"], tn["suffix"]]),
                    tn["max_new"], None, log, live)
        owner[id(s)] = i

    t0 = now() + ramp_s                 # the window: [t0, t0 + seconds)
    t_close = t0 + seconds
    for i in range(len(clients)):
        send_turn(i)
    next_i, t_opened, t_closed = 0, None, None
    got_in_window, stats0, stats1 = 0, None, None
    while True:
        t = now()
        if t_opened is None and t >= t0:
            t_opened = t
            setup_s = t - env["t_start"]
            stats0 = eng.stats()
            compiles.open()
            if tracer:
                tracer.start(t)
        while next_i < len(pending) and t0 + pending[next_i]["due"] <= t:
            r = pending[next_i]
            _submit(eng, r["prompt"], r["max_new"], t0 + r["due"], log, live)
            next_i += 1
        before = live
        in_window = t_opened is not None and t_closed is None and t < t_close
        live, got = _observe(live, gaps_s if in_window else None, t)
        if in_window:
            got_in_window += got
        elif t_opened is not None and t_closed is None:
            t_closed = t
            stats1 = eng.stats()
        if t_closed is None:            # a client's reply came: its next turn
            for s in before:
                if s not in live and id(s) in owner:
                    send_turn(owner[id(s)])
        elif not live:
            break
        elif t > t_closed + 60.0:
            for s in live:
                s.error = TimeoutError("no reply a minute past the close")
            break
        wake = t + poll
        if next_i < len(pending):
            wake = min(wake, t0 + pending[next_i]["due"])
        time.sleep(max(0.0, wake - now()))
    t_end = now()
    n_compiles = compiles.close()
    if tracer:
        tracer.join()
        if env.get("dump_trace"):
            from benchmarks.lib import trace as _trace
            _trace.dump_summary(tracer.dir, env["dump_trace"])
    gaps_ms = [g * 1e3 for g in gaps_s]
    acc = eng.page_accounting()
    mem_peak = harness.memory_peak_bytes(cell["chips"])
    eng.shutdown(drain=False, timeout=60.0)

    # ------------------------------------------------------------ metrics
    failed = [s for s in log if s.error is not None or s.req is None
              or s.req.error is not None or s.req.state != "done"
              or s.t_first is None]
    ok = [s for s in log if s not in failed]
    base = (lambda s: s.due) if open_loop else (lambda s: s.sent)
    # the window's requests: due (open loop) or sent (closed) inside it.
    # A failed or refused request misses: it waited until the run gave up
    win = [s for s in log if t0 <= base(s) < t_close]
    ttft_ms = [((t_end if s in failed else s.t_first) - base(s)) * 1e3
               for s in win]
    late_ms = [(s.sent - s.due) * 1e3 for s in win if s.due is not None]
    window_s = t_closed - t_opened
    e2e = {"out_tok_s": got_in_window / window_s,
           "ttft_p95_ms": percentile(ttft_ms, 95),
           "gap_p95_ms": percentile(gaps_ms, 95),
           "setup_s": setup_s}
    say(phase="window", workload=cell["name"], seed=seed, window_s=window_s,
        requests=len(win), requests_of_ramp=len(log) - len(win),
        failed=len(failed), tokens_in_window=got_in_window,
        ttft_p50_ms=percentile(ttft_ms, 50), gap_p50_ms=percentile(gaps_ms, 50),
        gaps=len(gaps_ms), loadgen_late_p95_ms=percentile(late_ms, 95)
        if late_ms else None, loadgen_late_top_ms=sorted(late_ms)[-6:],
        history_prefill_s=hist_s, ramp_s=ramp_s,
        active_at_open=stats0["active_slots"],
        active_at_close=stats1["active_slots"],
        waiting_at_close=stats1["waiting"], drain_s=t_end - t_closed,
        compiles_in_window=n_compiles)
    counters = harness.delta(stats1, stats0)
    names = list(marks)
    say(phase="setup", **{f"{b}_s": marks[b] - marks[a]
                          for a, b in zip(names, names[1:])})
    say(phase="counters", window=counters, pages=acc,
        use_kernel=eng.paged.use_kernel)

    # ----------------------------------------- free, then the comparison
    finished = [(s.prompt, list(s.req.tokens)) for s in ok]
    itemsize = int(np.dtype(eng.paged.dtype).itemsize)
    num_slots = eng.num_slots
    harness.free(eng.k_pool, eng.v_pool, dec.p)
    del eng, dec
    t = now()
    sample = check.sample_finished(finished, int(limits["sample_requests"]),
                                   seed)
    res = check.served_logit_gap(reference, cfg, seed, sample,
                                 int(limits["pad_to"]))
    say(phase="check", seconds=now() - t, tokens=res["tokens"],
        requests=len(sample), per_request=res["per_request"])
    checks = {
        "served_logit_gap": (res["widest_gap"],
                             limits["served_logit_gap"]["limit"]),
        "requests_unanswered": (len(failed), 0),
        "pages_leaked": (int(acc["leaked"]), 0),
        "compiles_in_window": (n_compiles, 0),
        "requests_without_a_compared_token": (int(res["tokens"] < 1), 0),
    }
    correct = check.decide(checks)
    if env.get("control"):      # benchmarks/tools/readings.py, never a run
        ctl = check.served_logit_gap(reference, cfg, seed, sample,
                                     int(limits["pad_to"]),
                                     rounding=env["control"])
        held = dict(checks, served_logit_gap=(
            ctl["widest_gap"], limits["served_logit_gap"]["limit"]))
        say(phase="control", rounding=env["control"], seed=seed,
            program_gap=res["widest_gap"], control_gap=ctl["widest_gap"],
            control_per_request=ctl["per_request"],
            control_correct=check.decide(held))
    ctx = {"config": cfg, "model": model, "traffic": mix,
           "chips": cell["chips"], "window_s": window_s, "counters": counters,
           "kv_itemsize": itemsize, "num_slots": num_slots,
           "late_ms": late_ms, "peaks": env["peaks"], "trace": None,
           "traced_counters": None}
    if tracer:
        ctx["trace"] = tracer.read(cell["chips"])
        ctx["traced_counters"] = harness.delta(tracer.after, tracer.before)
        tracer.cleanup()
        say(phase="traced_steps", **ctx["trace"].steps(names_of.is_decode_step))
    return {"correct": correct, "attempted": len(win),
            "failed": len([s for s in win if s in failed]),
            "end_to_end": e2e, "ctx": ctx, "checks": checks,
            "memory_peak_bytes": mem_peak}
