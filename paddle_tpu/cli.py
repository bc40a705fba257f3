"""Command-line trainer — `paddle train` parity (TrainerMain.cpp:32-58).

    paddle_tpu train --config=CONF [--job=train|time|test] [flags]

CONF is either
  * a Python config script (the reference's trainer-config convention,
    config_parser.py executed user configs the same way): it must define
    ``cost`` (a cost LayerOutput or list), and may define ``optimizer``,
    ``train_reader`` / ``test_reader`` (callables yielding batches),
    ``extra_layers``, ``evaluators``, ``num_passes``, ``batch_size``; or
  * a serialized topology JSON (Topology.serialize / the ModelConfig
    contract) — enough for --job=time (synthetic feeds) and, with
    --init_model_path, --job=test over a config-provided reader.

Jobs (Trainer::{train,test,time,checkGradient}, TrainerBenchmark.cpp):
  train    : SGD over train_reader, per-pass checkpoint under --save_dir.
  test     : load parameters, evaluate test_reader, print metrics.
  time     : timed fwd+bwd+update steps on synthetic data, one JSON line.
  checkgrad: finite-difference audit of the config's gradients
             (Trainer.h:43 checkGradient).

Other subcommands:
  merge : topology + params -> one deployable artifact
          (paddle/trainer/MergeModel.cpp:23 parity).
  infer : forward a merged artifact over `infer_reader` rows or
          synthetic inputs (capi/gradient_machine.h:52's Python twin).
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import sys
import time
from typing import Any, Dict, Optional

import numpy as np


def _load_config(path: str, require_cost: bool = True) -> Dict[str, Any]:
    """Execute a .py config (namespace dict) or load a topology .json.
    Training configs must define ``cost``; serving decode configs
    (``require_cost=False``) define ``decoder`` instead."""
    if path.endswith(".py"):
        ns = runpy.run_path(path)
        if require_cost and "cost" not in ns:
            raise SystemExit(f"config {path!r} defines no `cost`")
        return ns
    with open(path) as f:
        blob = f.read()
    from paddle_tpu.core.topology import Topology
    topo = Topology.deserialize(blob)
    # outputs of a serialized topology are its cost nodes
    return {"cost": list(topo.outputs)}


def _topo_from_ns(ns: Dict[str, Any]):
    """Topology from a config namespace: cost node(s) + extra layers."""
    import paddle_tpu as paddle
    cost = ns["cost"]
    return paddle.Topology(
        cost if isinstance(cost, (list, tuple)) else [cost],
        extra_outputs=list(ns.get("extra_layers") or []))


def _build_trainer(ns: Dict[str, Any], init_model_path: Optional[str]):
    import paddle_tpu as paddle
    cost = ns["cost"]
    topo = _topo_from_ns(ns)
    if init_model_path:
        with open(init_model_path, "rb") as f:
            parameters = paddle.Parameters.from_tar(f)
    else:
        parameters = paddle.create_parameters(topo)
    optimizer = ns.get("optimizer") or paddle.optimizer.Momentum(
        learning_rate=1e-3, momentum=0.9)
    return paddle.SGD(cost=cost, parameters=parameters,
                      update_equation=optimizer,
                      extra_layers=ns.get("extra_layers"),
                      evaluators=ns.get("evaluators"))


def _synthetic_batch(trainer, batch_size: int, seq_len: int = 16):
    """One synthetic batch matching the topology's data contract (the
    --job=time mode needs shapes, not data)."""
    from paddle_tpu.core.data_type import SeqType
    rng = np.random.RandomState(0)
    samples = []
    for _ in range(batch_size):
        row = []
        for _, t in trainer.topology.data_type():
            if t.seq_type != SeqType.NO_SEQUENCE:
                n = seq_len
                if t.kind == "integer":
                    row.append([int(v) for v in rng.randint(0, t.dim, n)])
                else:
                    row.append([rng.randn(t.dim).astype("float32")
                                for _ in range(n)])
            elif t.kind == "integer":
                row.append(int(rng.randint(0, t.dim)))
            else:
                row.append(rng.randn(t.dim).astype("float32"))
        samples.append(tuple(row))
    return samples


def _job_time(trainer, batch_size: int, iters: int,
              seq_len: int = 16) -> int:
    """TrainerBenchmark.cpp parity: timed train steps, update included."""
    batch = _synthetic_batch(trainer, batch_size, seq_len)

    def reader():
        while True:
            yield batch

    times = []
    t_last = [None]

    def handler(e):
        import paddle_tpu as paddle
        if isinstance(e, paddle.event.BeginIteration):
            t_last[0] = time.perf_counter()
        elif isinstance(e, paddle.event.EndIteration):
            e.cost                   # force the device sync: this verb
            # times COMPLETED steps (TrainerBenchmark semantics), not
            # async dispatch
            times.append(time.perf_counter() - t_last[0])

    trainer.train(reader, num_passes=1, event_handler=handler,
                  num_batches_per_pass=iters + 3)
    steady = times[3:] or times              # drop compile warmup
    ms = 1000.0 * float(np.mean(steady))
    print(json.dumps({"metric": "train_ms_per_batch", "value": round(ms, 3),
                      "unit": "ms/batch", "batch_size": batch_size,
                      "seq_len": seq_len,
                      "iters": len(steady)}))
    return 0


def _train_step_text(trainer, batch) -> str:
    """The compiled text of the train step for ``batch``'s shapes: the
    program the loop runs, so its instructions are the ones a trace
    names, each with the ``jax.named_scope`` it lies in (``op_name``).
    A second compile of the same program (the AOT path does not share
    the jit's cache)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.trainer.data_feeder import DataFeeder
    feed = DataFeeder(trainer.topology.data_type(), None)(batch)
    n_real = jnp.asarray(feed.pop("__batch_size__"), jnp.int32)
    return trainer._train_step.lower(
        trainer._own_params(), trainer.opt_state, trainer.parameters.state,
        feed, jax.random.PRNGKey(0), n_real).compile().as_text()


def _job_profile(trainer, args) -> int:
    """Profile train steps into an xplane trace (--job=profile).

    The reference's profiling loop is Stat.h timers printed at pass end
    (SURVEY §5 tracing); the TPU-native loop is jax.profiler -> .xplane.pb
    -> obs/xplane.py. This verb runs warmup + traced steps on synthetic
    data shaped by the config and prints where the trace landed, then
    the reader's report: device busy and idle time, device time by
    operation and by named scope, and the idle gaps by the ``train*``
    host span open during them."""
    import jax
    batch = _synthetic_batch(trainer, args.batch_size, args.seq_len)

    def reader():
        while True:
            yield batch

    out = args.profile_dir or os.path.join(".", "profile_out")
    os.makedirs(out, exist_ok=True)
    # warmup pass outside the trace so compile time doesn't pollute it
    trainer.train(reader, num_passes=1, event_handler=lambda e: None,
                  num_batches_per_pass=2)
    with jax.profiler.trace(out):
        trainer.train(reader, num_passes=1, event_handler=lambda e: None,
                      num_batches_per_pass=args.iters)
    from paddle_tpu.obs import xplane
    try:
        rep = xplane.report(out, hlo_text=_train_step_text(trainer, batch))
    except FileNotFoundError:
        rep = None
    print(json.dumps({"job": "profile", "status": "ok",
                      "trace_dir": out,
                      "xplane": rep["xplane"] if rep else None,
                      "iters": args.iters}))
    if rep:
        print(xplane.format_report(rep))
    return 0


def _job_train(trainer, ns, args) -> int:
    import paddle_tpu as paddle
    reader = ns.get("train_reader")
    if reader is None:
        raise SystemExit("--job=train needs a `train_reader` in the config")

    if args.data_max_bad or args.data_sample_timeout or args.data_prefetch:
        # supervise the config's batch reader: bounded prefetch with
        # clean shutdown, hung-source watchdog, per-batch error budget
        # (docs/robustness.md "Data pipeline")
        from paddle_tpu.reader import ErrorBudget, supervised
        reader = supervised(
            reader,
            buffer_size=args.data_prefetch or 4,
            sample_timeout=args.data_sample_timeout or None,
            error_budget=ErrorBudget(max_bad=args.data_max_bad,
                                     on_bad=args.data_on_bad),
            name="train-feed")

    def handler(e):
        if isinstance(e, paddle.event.EndIteration) and \
                e.batch_id % max(args.log_period, 1) == 0:
            print(f"Pass {e.pass_id}, Batch {e.batch_id}, "
                  f"Cost {e.cost:.6f}, {e.evaluator}")
        elif isinstance(e, paddle.event.EndPass):
            print(f"Pass {e.pass_id} done. {e.evaluator}")
            if args.save_dir:
                trainer.save_pass(args.save_dir, e.pass_id)
        elif isinstance(e, paddle.event.FaultEvent):
            print(f"FAULT {e!r}", file=sys.stderr)

    fault_policy = None
    if args.fault_max_bad_steps:
        from paddle_tpu.trainer.fault import FaultPolicy
        fault_policy = FaultPolicy(max_bad_steps=args.fault_max_bad_steps)
    microbatch = args.microbatch
    if microbatch is not None and microbatch != "auto":
        microbatch = int(microbatch)
    num_passes = args.num_passes or int(ns.get("num_passes", 1))
    trainer.train(reader, num_passes=num_passes, event_handler=handler,
                  checkpoint_dir=args.checkpoint_dir,
                  checkpoint_period=args.checkpoint_period,
                  auto_resume=args.auto_resume, fault_policy=fault_policy,
                  microbatch=microbatch, oom_probe=args.oom_probe)
    if ns.get("test_reader") is not None:
        res = trainer.test(ns["test_reader"])
        print(f"Test: cost={res.cost:.6f} {res.evaluator}")
    return 0


def _job_test(trainer, ns) -> int:
    reader = ns.get("test_reader") or ns.get("train_reader")
    if reader is None:
        raise SystemExit("--job=test needs a `test_reader` in the config")
    res = trainer.test(reader)
    print(f"Test: cost={res.cost:.6f} {res.evaluator}")
    return 0


def _job_checkgrad(trainer, ns, args) -> int:
    """Trainer::checkGradient parity (Trainer.h:43, --job=checkgrad):
    central finite differences vs jax.grad over the config's whole
    topology, on a batch from the config's reader if present, else a
    synthetic one."""
    from paddle_tpu.trainer.data_feeder import DataFeeder
    from paddle_tpu.trainer.grad_check import check_topology_grads

    reader = ns.get("train_reader")
    if reader is not None:
        batch = next(iter(reader()))
        batch = batch[:min(len(batch), args.batch_size)]
    else:
        batch = _synthetic_batch(trainer, min(args.batch_size, 8),
                                 args.seq_len)
    feeder = DataFeeder(trainer.topology.data_type(), None)
    # the audit runs on the CPU backend even from a TPU process: central
    # differences at eps=1e-3 need deterministic f32 accumulation, and a
    # TPU batch-sum's roundoff (~1e-2 absolute on a 128-row cost) swamps
    # the 2e-3 probe. The analytic graph being checked is device-
    # independent; CPU is the universal fake device (tests/conftest.py).
    # The feed conversion happens INSIDE the context so inputs are
    # placed on CPU directly instead of TPU-then-migrated.
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        feed = feeder(batch)
        check_topology_grads(trainer.topology, feed,
                             eps=args.checkgrad_eps, seed=args.seed)
    n_params = len(trainer.topology.param_specs)
    print(json.dumps({"job": "checkgrad", "status": "ok",
                      "params_checked": n_params,
                      "batch": len(batch), "eps": args.checkgrad_eps}))
    return 0


def _cmd_merge(args) -> int:
    """MergeModel parity (paddle/trainer/MergeModel.cpp:23): one
    deployable artifact = serialized inference topology + parameters,
    loadable by load_inference_model and the C ABI
    (paddle_gradient_machine_create_for_inference_with_parameters)."""
    import paddle_tpu as paddle
    from paddle_tpu.trainer.inference import save_inference_model

    ns = _load_config(args.config)
    output = ns.get("output") or ns.get("outputs")
    if output is None:
        raise SystemExit(
            "merge needs the config to define `output` (the inference "
            "output LayerOutput) — the cost graph is a training artifact")
    with open(args.init_model_path, "rb") as f:
        parameters = paddle.Parameters.from_tar(f)
    save_inference_model(args.out, output, parameters)
    print(json.dumps({"job": "merge", "status": "ok", "out": args.out}))
    return 0


def _cmd_infer(args) -> int:
    """Forward the merged artifact: rows from the config's
    `infer_reader` if given, else synthetic inputs matching the data
    contract. Prints one JSON line with the output shape + a sample."""
    from paddle_tpu.trainer.inference import load_inference_model

    inf = load_inference_model(args.model)
    if args.config:
        ns = _load_config(args.config)
        if ns.get("infer_reader") is None:
            raise SystemExit("--config for infer must define "
                             "`infer_reader` (yields input rows)")
        rows = list(ns["infer_reader"]())
    else:
        # _synthetic_batch only touches .topology.data_type(), which the
        # loaded Inference provides too
        rows = _synthetic_batch(inf, args.batch_size, args.seq_len)
    out = inf.infer(rows, batch_size=args.batch_size)
    arr = np.asarray(out)
    print(json.dumps({"job": "infer", "status": "ok",
                      "rows": len(rows), "output_shape": list(arr.shape),
                      "row0": [round(float(v), 6)
                               for v in arr.reshape(arr.shape[0], -1)[0][:8]]}))
    return 0


def _build_engine(args):
    """--decode_config wiring for `paddle_tpu serve`: the config script
    must define ``decoder`` (a models.TransformerDecoder over merged
    params); ``--draft_config`` names a second script whose (smaller)
    ``decoder`` proposes ``--spec_k`` tokens per step, and
    ``--prefix_cache off`` disables shared-prefix KV reuse. Split from
    _build_server so tests can assert the flag plumbing without a
    model artifact (tests/test_cli.py)."""
    from paddle_tpu.serving.engine import DecodeEngine

    ns = _load_config(args.decode_config, require_cost=False)
    decoder = ns.get("decoder")
    if decoder is None:
        raise SystemExit("--decode_config must define `decoder` "
                         "(a models.TransformerDecoder)")
    draft = None
    if getattr(args, "draft_config", None):
        dns = _load_config(args.draft_config, require_cost=False)
        draft = dns.get("draft_decoder") or dns.get("decoder")
        if draft is None:
            raise SystemExit("--draft_config must define `decoder` "
                             "(or `draft_decoder`)")
    kv_quant = getattr(args, "kv_quant", "none")
    return DecodeEngine(
        decoder, num_slots=args.gen_slots,
        page_size=args.gen_page_size,
        draft=draft, spec_k=args.spec_k,
        prefix_cache=args.prefix_cache == "on",
        kv_quant=None if kv_quant == "none" else kv_quant,
        kv_spill_pages=getattr(args, "kv_spill_pages", 0))


def _build_server(args, InferenceServer, CircuitBreaker,
                  build_http_server, engine_builder=None,
                  on_quit=None):
    """serve-flag wiring, split from the signal loop so tests can
    assert the flags reach InferenceServer (tests/test_cli.py).
    ``on_quit`` arms POST /admin/quit — the rolling deploy's restart
    primitive (fleet/autopilot.py)."""
    breaker = CircuitBreaker(window=args.breaker_window,
                             failure_threshold=args.breaker_threshold,
                             cooldown=args.breaker_cooldown)
    engine = None
    if getattr(args, "decode_config", None):
        engine = (engine_builder or _build_engine)(args)
    if not args.model and engine is None:
        raise SystemExit("need --model (a merged artifact for /infer) "
                         "or --decode_config (a generate-only fleet "
                         "replica) — got neither")
    server = InferenceServer(
        args.model or None,
        max_queue=args.max_queue, workers=args.workers,
        default_deadline=(args.deadline_ms / 1e3
                          if args.deadline_ms else None),
        max_batch_memory=args.max_batch_memory or None,
        breaker=breaker, engine=engine).start()
    httpd = build_http_server(server, args.host, args.port,
                              on_quit=on_quit)
    return server, httpd


def _cmd_serve(args) -> int:
    """Serve a merged artifact over HTTP with admission control — the
    hardened twin of the C ABI's multi-threaded serving story
    (docs/robustness.md "Serving"): bounded queue + backpressure,
    per-request deadlines, circuit breaker, graceful drain on
    SIGTERM/SIGINT, /health and /stats snapshots."""
    import signal
    import threading

    from paddle_tpu.serving import (CircuitBreaker, InferenceServer,
                                    build_http_server)

    stop = []

    def _on_admin_quit():
        # POST /admin/quit rides the SIGTERM path: same postmortem,
        # same drain -> leave -> close order below
        from paddle_tpu.obs.flight import FLIGHT
        FLIGHT.maybe_autodump("admin_quit")
        stop.append(1)

    server, httpd = _build_server(args, InferenceServer, CircuitBreaker,
                                  build_http_server,
                                  on_quit=_on_admin_quit)
    if server.engine is not None:
        # resolve the decode executables BEFORE the HTTP thread starts
        # admitting: with a warm artifact store this is zero-compile
        # (the deserialized executable traces nothing); cold, the
        # compile is paid here — never inside a request — and the
        # store is backfilled for the next respawn
        server.engine.warmup()
    # fleet membership (docs/robustness.md "Serving fleet"): join the
    # coordinator directory as serve/<replica_id> publishing the HTTP
    # endpoint, so a `paddle_tpu router` discovers (and fails over)
    # this replica with no static config
    registration = None
    if getattr(args, "coordinator", None):
        from paddle_tpu.fleet import ReplicaRegistration
        from paddle_tpu.trainer.coordinator import connect
        chost, _, cport = args.coordinator.rpartition(":")
        endpoint = f"http://{args.host}:{httpd.server_address[1]}"
        replica_id = args.replica_id or \
            f"{args.host}-{httpd.server_address[1]}"
        registration = ReplicaRegistration(
            connect(chost or "127.0.0.1", int(cport)), replica_id,
            endpoint, heartbeat_s=args.heartbeat).join()

    def _on_stop_signal(*a):
        # the SIGTERM postmortem: a bundle of the last moments before
        # the drain, while the queue/slot state is still live
        from paddle_tpu.obs.flight import FLIGHT
        FLIGHT.maybe_autodump("sigterm")
        stop.append(1)

    signal.signal(signal.SIGTERM, _on_stop_signal)
    signal.signal(signal.SIGINT, _on_stop_signal)
    t = threading.Thread(target=httpd.serve_forever, daemon=True,
                         name="pt-serve-http")
    t.start()
    print(json.dumps({"job": "serve", "status": "serving",
                      "host": args.host,
                      "port": httpd.server_address[1],
                      "workers": args.workers,
                      "max_queue": args.max_queue,
                      "replica_id": registration.replica_id
                      if registration else None}), flush=True)
    while not stop:
        time.sleep(0.2)
    # orderly exit mirrors pserver: the goodbye FIRST (a router
    # mid-retry sees the directory lose the entry before the endpoint
    # stops answering), then the transport, then the drain
    if registration is not None:
        registration.stop(leave=True)
    httpd.shutdown()            # stop admissions at the transport...
    server.shutdown(drain=True)  # ...then drain the queued requests
    if args.profile_every or args.slo:
        from paddle_tpu.obs.profile import PROFILER
        PROFILER.disable()      # joins the pt-obs-profiler thread
    print(json.dumps({"job": "serve", "status": "stopped",
                      "stats": server.stats()}))
    return 0


def _cmd_artifacts(args) -> int:
    """`paddle_tpu artifacts build|verify|ls` — operate the warm-start
    store offline: a deploy pipeline builds artifacts ONCE, verifies
    them, and every replica of the rollout then cold-starts
    zero-compile from them (docs/robustness.md)."""
    from paddle_tpu.artifacts import ArtifactStore, configure
    from paddle_tpu.artifacts.runtime import ENV_STORE
    root = args.dir or os.environ.get(ENV_STORE)
    if not root:
        raise SystemExit("need --dir (or $PADDLE_TPU_ARTIFACTS)")
    if args.event_log:
        from paddle_tpu.obs.events import JOURNAL
        JOURNAL.configure(args.event_log)
    store = ArtifactStore(root)
    if args.action == "ls":
        rows = store.entries()
        print(json.dumps({"job": "artifacts", "action": "ls",
                          "dir": store.root, "count": len(rows),
                          "entries": rows}, indent=2))
        return 0
    if args.action == "verify":
        rows = store.entries()
        bad = [r for r in rows if not r["ok"]]
        for r in bad:   # same audit trail as ArtifactStore.verify()
            from paddle_tpu.obs.events import emit
            emit("artifacts", "verify_failed", name=r["name"],
                 path=r["path"], detail=r.get("error"))
        print(json.dumps({"job": "artifacts", "action": "verify",
                          "dir": store.root, "checked": len(rows),
                          "defective": bad}, indent=2))
        return 1 if bad else 0
    # build: construct the engine exactly as `paddle_tpu serve` would
    # and warm it up — resolve() backfills the store with serialized
    # executables for precisely the serving fingerprints
    if not args.decode_config:
        raise SystemExit("artifacts build needs --decode_config")
    configure(store.root)
    engine = _build_engine(args)
    stats = engine.warmup()     # both step programs' artifacts
    rows = store.entries()
    print(json.dumps({"job": "artifacts", "action": "build",
                      "dir": store.root, "executables": stats,
                      "entries": rows}, indent=2))
    return 0


def _cmd_coordinator(args) -> int:
    """Run the elastic-training coordinator as a daemon — the
    `paddle_master` binary's role (go/cmd/master/master.go): partition
    RecordIO chunks into tasks, serve GetTask/TaskFinished/TaskFailed +
    the save election over RPC, snapshot state for crash recovery."""
    import glob as _glob
    import signal

    from paddle_tpu.reader import recordio as rio
    from paddle_tpu.trainer.coordinator import (Coordinator,
                                                CoordinatorServer,
                                                FileStore, RpcStore)
    # de-dup: overlapping globs must not serve the same chunk twice
    paths = sorted({p for pat in args.data for p in _glob.glob(pat)})
    if not paths:
        raise SystemExit(f"no files match --data {args.data}")
    chunks = [d for p in paths for d in rio.chunk_descriptors(p)]
    if args.snapshot and getattr(args, "snapshot_rpc", None):
        raise SystemExit("--snapshot and --snapshot_rpc are mutually "
                         "exclusive")
    store = None
    if args.snapshot:
        store = FileStore(args.snapshot)
    elif getattr(args, "snapshot_rpc", None):
        host, _, port = args.snapshot_rpc.rpartition(":")
        store = RpcStore(host or "127.0.0.1", int(port))
    coord = Coordinator(chunks, chunks_per_task=args.chunks_per_task,
                        timeout_s=args.task_timeout,
                        failure_max=args.failure_max, store=store,
                        worker_lease_s=args.worker_lease)
    server = CoordinatorServer(coord, host=args.host, port=args.port)

    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    server.start()
    # report the coordinator's ACTUAL state: after snapshot recovery it
    # serves the recovered chunk list, not this invocation's --data
    print(json.dumps({"job": "coordinator", "status": "serving",
                      "host": args.host, "port": server.port,
                      "files": len(paths), "chunks": len(coord.chunks),
                      "chunks_per_task": coord.chunks_per_task,
                      "recovered": coord.recovered,
                      "generation": coord.generation}), flush=True)
    while not stop:
        time.sleep(0.2)
    server.stop()
    # final membership/queue picture (workers, generation, stale_grants
    # …) — the same dict the /metrics collector exports
    print(json.dumps({"job": "coordinator", "status": "stopped",
                      "stats": coord.stats()}))
    return 0


def _cmd_pserver(args) -> int:
    """Run one embedding shard as a daemon — the 2017 `paddle pserver`
    binary's role reborn (docs/robustness.md "Sharded embedding
    service"): serve row-gather/scatter-update RPCs for this shard's
    key range, keep a membership lease on the coordinator so clients
    resolve (and fail over) through the directory, and persist
    WAL+snapshots to --snapshot_dir so a replacement started with the
    same flags restores the range digest-stable. SIGTERM snapshots,
    leaves the membership plane, and drains cleanly."""
    import signal

    from paddle_tpu.embed import (EmbeddingShard, EmbeddingShardServer,
                                  ShardRegistration)
    from paddle_tpu.trainer.coordinator import FileStore, connect

    store = FileStore(args.snapshot_dir) if args.snapshot_dir else None
    shard = EmbeddingShard(args.shard_id, args.shards, args.dim,
                           seed=args.seed, store=store)
    restored = shard.restore_from_store() if store is not None else False
    server = EmbeddingShardServer(shard, host=args.host,
                                  port=args.port).start()
    registration = None
    if args.coordinator:
        host, _, port = args.coordinator.rpartition(":")
        registration = ShardRegistration(
            connect(host or "127.0.0.1", int(port)), shard,
            server.endpoint, heartbeat_s=args.heartbeat).join()

    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    print(json.dumps({"job": "pserver", "status": "serving",
                      "shard_id": shard.shard_id, "shards": shard.num_shards,
                      "dim": shard.dim, "endpoint": server.endpoint,
                      "port": server.port, "restored": restored,
                      "generation": registration.generation
                      if registration else None}), flush=True)
    while not stop:
        time.sleep(0.2)
    # orderly exit: durable state first, then the goodbye, then the
    # socket — a client mid-retry sees the directory lose the entry
    # before the endpoint stops answering
    if store is not None:
        shard.save_snapshot()
    if registration is not None:
        registration.stop(leave=True)
    server.stop()
    print(json.dumps({"job": "pserver", "status": "stopped",
                      "stats": shard.stats()}))
    return 0


def _build_router(args, Router, build_router_http_server, connect):
    """router-flag wiring, split from the signal loop so tests can
    assert the flags reach Router (and the autopilot, when enabled)
    without a live coordinator (tests/test_cli.py)."""
    chost, _, cport = args.coordinator.rpartition(":")
    coord = connect(chost or "127.0.0.1", int(cport))
    router = Router(coordinator=coord, affinity=args.affinity,
                    page_size=args.page_size,
                    scrape_interval=args.scrape_interval,
                    queue_timeout=args.queue_timeout,
                    drain_timeout=args.drain_timeout).start()
    autopilot = None
    if getattr(args, "autopilot", False) or \
            getattr(args, "spawn_cmd", None):
        autopilot = _build_autopilot(args, router)
    httpd = build_router_http_server(router, args.host, args.port,
                                     autopilot=autopilot)
    return router, httpd, coord, autopilot


def _build_autopilot(args, router):
    """autopilot-flag wiring (fleet/autopilot.py): with --spawn_cmd
    the provisioner runs one subprocess per replica (the {replica_id}
    template); without, spawning is impossible (journaled
    ``autopilot/spawn_failed``) but the ROLLING DEPLOY still works —
    restart asks each replica to POST /admin/quit itself and its
    supervisor to respawn it (the fresh boot_id rejoin re-admits)."""
    import shlex

    from paddle_tpu.fleet.autopilot import (Autopilot, AutopilotPolicy,
                                            CallbackProvisioner,
                                            SubprocessProvisioner)
    policy = AutopilotPolicy(min_replicas=args.min_replicas,
                             max_replicas=args.max_replicas)
    if getattr(args, "spawn_cmd", None):
        cmd = shlex.split(args.spawn_cmd)
        # fleet KV mode rides into every autoscaled replica: a spawn
        # that comes up single-tier/fp32 in an int8+spill fleet would
        # scrape mismatched capacity and break restore-path affinity
        if getattr(args, "kv_quant", "none") not in (None, "none"):
            cmd += ["--kv_quant", args.kv_quant]
        if getattr(args, "kv_spill_pages", 0):
            cmd += ["--kv_spill_pages", str(args.kv_spill_pages)]
        prov = SubprocessProvisioner(cmd)
    else:
        def _no_spawn(rid):
            raise RuntimeError("no --spawn_cmd: this autopilot can "
                               "deploy but not spawn")

        def _quit_restart(rid):
            # supervisor-managed replica: ask it to exit cleanly; the
            # supervisor respawns it and the fresh boot_id rejoins
            st = router.balancer.get(rid)
            if st is None:
                raise KeyError(f"unknown replica {rid!r}")
            router._http_post_json(st.endpoint, "/admin/quit", {})
            return {}

        prov = CallbackProvisioner(spawn=_no_spawn, stop=_no_spawn,
                                   restart=_quit_restart)
    return Autopilot(router, prov, policy=policy,
                     interval=args.autopilot_interval,
                     drain_timeout=args.drain_timeout)


def _router_teardown(router, registration, httpd,
                     autopilot=None) -> None:
    """The SIGTERM contract, in this order (tests/test_cli.py pins
    it): AUTOPILOT FIRST — stop the control loop so no scale/deploy
    decision races the teardown; DRAIN — stop admitting, let
    in-flight requests settle on their replicas; LEAVE — drop the
    router's membership lease so clients resolving through the
    directory stop finding it; CLOSE — only then stop answering the
    socket. A client mid-retry never sees a live directory entry
    pointing at a dead port."""
    if autopilot is not None:
        autopilot.stop()
    router.shutdown(drain=True)
    if registration is not None:
        registration.stop(leave=True)
    httpd.shutdown()
    httpd.server_close()


def _cmd_router(args) -> int:
    """Run the serving-fleet router daemon (docs/robustness.md
    "Serving fleet"): front N `paddle_tpu serve --coordinator`
    replicas with aggregate-KV admission, prefix-affinity routing,
    drain/deploy and exactly-once mid-stream failover."""
    import signal
    import threading

    from paddle_tpu.fleet import Router, build_router_http_server
    from paddle_tpu.fleet.registry import Registration
    from paddle_tpu.trainer.coordinator import connect

    router, httpd, coord, autopilot = _build_router(
        args, Router, build_router_http_server, connect)
    if autopilot is not None:
        autopilot.start()
    endpoint = f"http://{args.host}:{httpd.server_address[1]}"
    registration = Registration(
        coord, "fleet/router",
        {"role": "fleet_router", "endpoint": endpoint},
        heartbeat_s=args.heartbeat).join()

    stop = []

    def _on_stop_signal(*a):
        from paddle_tpu.obs.flight import FLIGHT
        FLIGHT.maybe_autodump("sigterm")
        stop.append(1)

    signal.signal(signal.SIGTERM, _on_stop_signal)
    signal.signal(signal.SIGINT, _on_stop_signal)
    t = threading.Thread(target=httpd.serve_forever, daemon=True,
                         name="pt-fleet-http")
    t.start()
    print(json.dumps({"job": "router", "status": "serving",
                      "host": args.host,
                      "port": httpd.server_address[1],
                      "affinity": args.affinity,
                      "autopilot": autopilot is not None,
                      "replicas": len(router.balancer.replicas())}),
          flush=True)
    while not stop:
        time.sleep(0.2)
    _router_teardown(router, registration, httpd,
                     autopilot=autopilot)
    print(json.dumps({"job": "router", "status": "stopped",
                      "stats": router.stats()}))
    return 0


def _build_soak(args, SoakConfig, SoakRunner):
    """soak-flag wiring, split from the signal loop so tests can
    assert the flags reach SoakConfig (and the runner) without
    building a live topology (tests/test_cli.py injects fakes)."""
    from paddle_tpu.loadgen import SoakSLO
    cfg = SoakConfig(seed=args.seed, duration_s=args.duration,
                     workload=args.workload, families=args.faults,
                     chat_rate=args.chat_rate, ctr_rate=args.ctr_rate,
                     arrival=args.arrival, journal=args.event_log,
                     slo=SoakSLO(ttft_p99_ms=args.slo_ttft_ms,
                                 token_p99_ms=args.slo_token_ms))
    return SoakRunner(cfg)


def _cmd_soak(args) -> int:
    """Run one seeded soak (docs/robustness.md 'The million-user
    soak'): open-loop CTR + chat load over the in-process serving
    estate, the seeded fault schedule injected mid-run, and the
    verdict report printed as JSON. Exit 0 iff the verdict is OK.

    SIGTERM/SIGINT stop offering load and unwind through the pinned
    teardown order (generators -> fleet -> coordinator); the partial
    run still produces a report from whatever the journal holds."""
    import signal

    from paddle_tpu.loadgen import SoakConfig, SoakRunner

    runner = _build_soak(args, SoakConfig, SoakRunner)

    def _on_stop_signal(*a):
        runner.stop()

    signal.signal(signal.SIGTERM, _on_stop_signal)
    signal.signal(signal.SIGINT, _on_stop_signal)
    print(json.dumps({"job": "soak", "status": "running",
                      "seed": args.seed, "duration_s": args.duration,
                      "workload": args.workload,
                      "faults": args.faults}), flush=True)
    report = runner.run()
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    print(json.dumps({
        "job": "soak", "status": "done", "ok": report["ok"],
        "checks": {k: c["ok"] for k, c in report["checks"].items()},
        "counts": report["counts"], "journal": report["journal"]}))
    return 0 if report["ok"] else 1


def _build_fleet_request(args):
    """fleet-verb wiring, split from the HTTP call so tests can
    assert the request shape without a live daemon
    (tests/test_cli.py): returns (method, url, json_body_or_None)."""
    base = args.router.rstrip("/")
    if args.action == "deploy":
        return "POST", f"{base}/admin/deploy", \
            {"force": bool(args.force)}
    if args.action == "scale":
        if args.replicas is None:
            raise SystemExit("fleet scale needs --replicas N")
        return "POST", f"{base}/admin/scale", \
            {"replicas": int(args.replicas)}
    return "GET", f"{base}/stats", None


def _cmd_fleet(args) -> int:
    """Operate a RUNNING `paddle_tpu router` daemon over its admin
    plane (docs/robustness.md "Fleet autopilot"): ``deploy`` runs the
    SLO-gated rolling restart (exit 1 when it pauses on a breach),
    ``scale`` resizes through the autopilot, ``status`` prints the
    fleet + autopilot snapshots."""
    import urllib.error
    import urllib.request

    def _call(method, url, body):
        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json"}
            if data else {})
        try:
            with urllib.request.urlopen(req,
                                        timeout=args.timeout) as resp:
                return resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    method, url, body = _build_fleet_request(args)
    code, payload = _call(method, url, body)
    out = {"job": "fleet", "action": args.action, "router": args.router,
           "http_status": code, "result": payload}
    if args.action == "status" and code == 200:
        ap_code, ap = _call("GET",
                            args.router.rstrip("/") + "/autopilot",
                            None)
        out["autopilot"] = ap if ap_code == 200 else None
    print(json.dumps(out))
    if code != 200:
        return 1
    if args.action == "deploy" and \
            payload.get("status") != "complete":
        return 1                       # paused rollout is not success
    return 0


def _cmd_lint(args) -> int:
    """ptlint — JAX-aware static analysis over the tree
    (docs/static_analysis.md): host syncs in hot paths, jit-in-loop
    recompilation, trace-time side effects, PRNG reuse, thread
    hygiene, silent f64 widening. Config in pyproject [tool.ptlint];
    the tier-1 gate tests/test_lint.py runs the same analysis."""
    from paddle_tpu.analysis.runner import main as lint_main
    argv = list(args.lint_args or [])
    if args.format:
        argv += ["--format", args.format]
    if args.write_baseline:
        argv.append("--write-baseline")
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.verbose:
        argv.append("--verbose")
    if getattr(args, "locks", None):
        argv += ["--locks", args.locks]
    if getattr(args, "contracts", None):
        argv += ["--contracts", args.contracts]
    return lint_main(argv)


def _cmd_diagram(args) -> int:
    from paddle_tpu.utils.diagram import make_diagram
    make_diagram(_topo_from_ns(_load_config(args.config)), args.out)
    print(json.dumps({"job": "diagram", "status": "ok", "out": args.out}))
    return 0


def _iter_journal_follow(path: str, domain=None, kind=None,
                         poll: float = 0.25, idle_timeout=None,
                         from_pos: int = 0, stop=None):
    """``tail -f`` over a journal JSONL file: yield each NEW
    schema-valid (filtered) record as it is appended. A torn trailing
    line stays buffered until its newline lands (the writer flushes
    whole lines, so this is just the race window). Ends when
    ``idle_timeout`` seconds pass with no new record (None: follow
    forever) or ``stop`` (a threading.Event) is set — the testable
    seam (tests/test_cli.py). Size-based rotation
    (EventJournal.configure(max_bytes=...)) is spanned losslessly:
    when the active file shrinks, the unread remainder of what is now
    ``path.1`` is drained first, then the fresh active file from 0."""
    from paddle_tpu.obs.events import validate
    pos = from_pos
    buf = ""
    last_new = time.monotonic()
    while True:
        try:
            size = os.path.getsize(path)
        except OSError:
            size = 0
        if size < pos:                  # truncated or rotated under us
            try:
                # rotation moved the active file to path.1 — drain the
                # records appended after our cursor before restarting
                with open(path + ".1", encoding="utf-8") as f:
                    f.seek(pos)
                    buf += f.read()
            except OSError:
                buf = ""                # plain truncation: drop the tail
            pos = 0
        if size > pos:
            with open(path, encoding="utf-8") as f:
                f.seek(pos)
                buf += f.read()
                pos = f.tell()
        if buf:
            lines = buf.split("\n")
            buf = lines.pop()           # possibly-torn tail
            for line in lines:
                if not line.strip():
                    continue
                try:
                    rec = validate(json.loads(line))
                except (json.JSONDecodeError, ValueError):
                    continue            # torn/corrupt mid-stream line
                last_new = time.monotonic()
                if domain is not None and rec["domain"] != domain:
                    continue
                if kind is not None and rec["kind"] != kind:
                    continue
                yield rec
        if stop is not None and stop.is_set():
            return
        if idle_timeout is not None and \
                time.monotonic() - last_new >= idle_timeout:
            return
        time.sleep(poll)


def _cmd_events(args) -> int:
    """`paddle_tpu events tail` — the incident-response verb: newest
    journal records (schema-validated, filtered) as JSON lines; with
    ``--follow`` keep streaming records as the run appends them
    (docs/observability.md)."""
    from paddle_tpu.obs.events import read_journal
    if not os.path.exists(args.log):
        raise SystemExit(f"no journal at {args.log!r}")
    recs = list(read_journal(args.log, strict=False,
                             domain=args.domain, kind=args.kind))
    for r in recs[-max(args.n, 0):]:
        print(json.dumps(r), flush=True)
    if not args.follow:
        return 0
    idle = args.exit_after_idle if args.exit_after_idle > 0 else None
    for r in _iter_journal_follow(
            args.log, domain=args.domain, kind=args.kind,
            idle_timeout=idle,
            from_pos=os.path.getsize(args.log)):
        print(json.dumps(r), flush=True)
    return 0


def _cmd_obs(args) -> int:
    """`paddle_tpu obs dump|selfcheck|catalog` — the flight-recorder
    verbs (docs/observability.md "Trace context & postmortems") plus
    the declared-contract dump (ptproto)."""
    from paddle_tpu.obs.flight import FLIGHT
    if args.action == "catalog":
        # the machine-readable contract: every legal journal
        # (domain, kind) + fields, metric family, protocol machine and
        # fault-family mapping — what ptlint R11-R13 and the runtime
        # witness both enforce
        from paddle_tpu.obs.catalog import catalog_as_dict
        print(json.dumps(catalog_as_dict(), indent=2, sort_keys=True))
        return 0
    if args.action == "dump":
        if args.url:
            # a RUNNING process's bundle over its /flight endpoint
            # (serving front or obs httpd)
            import urllib.request
            with urllib.request.urlopen(
                    args.url.rstrip("/") + "/flight", timeout=30) as r:
                bundle = json.loads(r.read())
            out = args.out or f"flight-remote-{os.getpid()}.json"
            with open(out, "w", encoding="utf-8") as f:
                json.dump(bundle, f)
            print(json.dumps({"job": "obs_dump", "status": "ok",
                              "source": args.url, "out": out,
                              "ring_records":
                                  len(bundle.get("ring", []))}))
            return 0
        path = FLIGHT.dump("cli", path=args.out)
        print(json.dumps({"job": "obs_dump", "status": "ok",
                          "out": path}))
        return 0
    # selfcheck: exercise every observability surface end-to-end —
    # the tier-1 smoke step (tests/test_cli.py)
    import tempfile

    from paddle_tpu.obs.events import EventJournal, read_journal
    from paddle_tpu.obs.metrics import REGISTRY
    from paddle_tpu.obs.trace import TRACER
    from paddle_tpu.utils.stats import global_counters
    checks = {}
    global_counters.bump("obs/selfcheck")
    text = REGISTRY.exposition()
    checks["metrics_scrape"] = \
        'paddle_tpu_counter_total{name="obs/selfcheck"} ' in text
    with tempfile.TemporaryDirectory(prefix="pt-obs-selfcheck-") as td:
        jpath = os.path.join(td, "journal.jsonl")
        j = EventJournal()
        j.configure(jpath)
        j.emit("obs", "selfcheck", probe=1)
        j.configure(None)
        recs = list(read_journal(jpath))
        checks["journal_roundtrip"] = (
            len(recs) == 1 and recs[0]["kind"] == "selfcheck"
            and "run_id" in recs[0] and "host" in recs[0])
        TRACER.start(capture_compiles=False)
        with TRACER.span("obs/selfcheck"):
            pass
        TRACER.stop()
        checks["trace_spans"] = any(
            s["name"] == "obs/selfcheck" for s in TRACER.spans())
        from paddle_tpu.obs.flight import BUNDLE_VERSION
        FLIGHT.record("mark", "obs/selfcheck")
        dpath = FLIGHT.dump("selfcheck",
                            path=os.path.join(td, "flight.json"))
        with open(dpath, encoding="utf-8") as f:
            bundle = json.load(f)
        checks["flight_dump"] = (
            bundle.get("v") == BUNDLE_VERSION
            and any(r.get("name") == "obs/selfcheck"
                    for r in bundle.get("ring", []))
            and "metrics" in bundle and "journal" in bundle)
    ok = all(checks.values())
    print(json.dumps({"job": "obs_selfcheck",
                      "status": "ok" if ok else "fail",
                      "checks": checks}))
    return 0 if ok else 1


def _cmd_trace(args) -> int:
    """`paddle_tpu trace merge` — fuse per-host journals + chrome
    traces into one timeline (paddle_tpu/obs/merge.py; the standalone
    twin is tools/trace_merge.py)."""
    from paddle_tpu.obs.merge import main as merge_main
    return merge_main(list(args.merge_args or []))


def _cmd_profile(args) -> int:
    """`paddle_tpu profile --config C --steps N` — the on-demand deep
    window (docs/observability.md "Profiling & SLOs"): build the
    trainer, turn the continuous profiler up to sample_every=1, arm a
    jax.profiler trace over N steps, drive them on synthetic data and
    print ONE JSON line: per-phase breakdown, MFU/roofline when the
    device and cost model resolve, and where the trace artifacts
    landed (the same dir a GET /profile?deep_steps=N caller would see
    in later snapshots/bundles)."""
    import paddle_tpu as paddle
    from paddle_tpu.obs.profile import PROFILER
    paddle.init(use_tpu=args.use_tpu, seed=args.seed,
                compute_dtype=args.dtype)
    ns = _load_config(args.config)
    trainer = _build_trainer(ns, args.init_model_path)
    batch = _synthetic_batch(trainer, args.batch_size, args.seq_len)

    def reader():
        while True:
            yield batch

    out = args.out or os.path.join(".", "profile_out")
    os.makedirs(out, exist_ok=True)
    PROFILER.enable(sample_every=1)
    try:
        # warmup outside the window so compile time doesn't pollute it
        trainer.train(reader, num_passes=1, event_handler=lambda e: None,
                      num_batches_per_pass=2)
        PROFILER.arm_window(args.steps, out_dir=out)
        trainer.train(reader, num_passes=1, event_handler=lambda e: None,
                      num_batches_per_pass=args.steps)
        trace_dir = PROFILER.finish_window()
        snap = PROFILER.snapshot()
        train = snap["kinds"].get("train", {})
        print(json.dumps({
            "job": "profile", "status": "ok", "steps": args.steps,
            "step_ms_median": train.get("step_ms_median"),
            "phases": train.get("phases"),
            "cost": snap.get("cost", {}).get("train"),
            "mfu": snap.get("mfu", {}).get("train"),
            "roofline_frac": snap.get("roofline_frac", {}).get("train"),
            "memory": snap.get("memory"),
            "trace_dir": trace_dir
            or snap["window"].get("last_trace_dir")}))
    finally:
        PROFILER.disable()
    return 0


def _wire_perf_obs(args) -> None:
    """--profile_every / --slo wiring shared by train and serve
    (docs/observability.md "Profiling & SLOs"): the continuous step
    profiler with its off-thread device-memory sampler, plus the SLO
    watchdog's declarative objectives. --slo alone implies profiling
    (the watchdog's step-time metrics come from the profiler)."""
    every = getattr(args, "profile_every", 0) or 0
    slo = getattr(args, "slo", None)
    if not every and not slo:
        return
    from paddle_tpu.obs.profile import PROFILER
    from paddle_tpu.obs.slo import WATCHDOG, parse_objective
    if slo:
        WATCHDOG.configure(
            objectives=[parse_objective(s) for s in slo])
    PROFILER.enable(sample_every=every or 8, memory_interval=0.5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="paddle_tpu",
        description="TPU-native trainer CLI (paddle train parity)")
    sub = ap.add_subparsers(dest="command", required=True)
    tr = sub.add_parser("train", help="train / time / test / checkgrad / "
                        "dump_config / profile")
    tr.add_argument("--config", required=True,
                    help=".py config script or serialized topology .json")
    tr.add_argument("--job", default="train",
                    choices=["train", "time", "test", "checkgrad",
                             "dump_config", "profile"])
    tr.add_argument("--checkgrad_eps", type=float, default=1e-3,
                    help="--job=checkgrad finite-difference step")
    tr.add_argument("--use_tpu", action="store_true", default=None)
    tr.add_argument("--trainer_count", type=int, default=1)
    tr.add_argument("--num_passes", type=int, default=None)
    tr.add_argument("--batch_size", type=int, default=128,
                    help="--job=time synthetic batch size")
    tr.add_argument("--seq_len", type=int, default=16,
                    help="synthetic sequence length for --job=time "
                         "(benchmark/README.md uses 100 for IMDB LSTM)")
    tr.add_argument("--iters", type=int, default=20,
                    help="--job=time timed steps")
    tr.add_argument("--save_dir", default=None)
    tr.add_argument("--checkpoint_dir", default=None,
                    help="full-state checkpoint dir (params + optimizer "
                         "slots + counters, md5-verified; "
                         "docs/robustness.md)")
    tr.add_argument("--checkpoint_period", type=int, default=0,
                    help="checkpoint every N steps (0: pass ends only)")
    tr.add_argument("--auto_resume", action="store_true",
                    help="resume from the newest intact checkpoint in "
                         "--checkpoint_dir: a killed run relaunched with "
                         "the same flags continues where it died")
    tr.add_argument("--fault_max_bad_steps", type=int, default=0,
                    help="enable the guarded train step: skip non-finite "
                         "updates, roll back after N consecutive bad "
                         "steps (0 disables)")
    tr.add_argument("--data_prefetch", type=int, default=0,
                    help="supervise the train reader with an N-batch "
                         "bounded prefetch pipeline (0 disables; "
                         "docs/robustness.md 'Data pipeline')")
    tr.add_argument("--data_sample_timeout", type=float, default=0,
                    help="hung-source watchdog: warn + count when the "
                         "reader produces nothing for N seconds "
                         "(0 disables)")
    tr.add_argument("--data_max_bad", type=int, default=0,
                    help="error budget: tolerate N quarantined bad "
                         "batches before emitting a data FaultEvent")
    tr.add_argument("--microbatch", default=None,
                    help="adaptive microbatching (docs/robustness.md "
                         "'Memory pressure'): 'auto' starts full-batch "
                         "and bisects into gradient-accumulated "
                         "microbatches when a step hits XLA "
                         "RESOURCE_EXHAUSTED (numerically equivalent, "
                         "no samples lost); an integer fixes the "
                         "starting microbatch rows")
    tr.add_argument("--oom_probe", action="store_true",
                    help="with --microbatch: binary-search the largest "
                         "safe microbatch on the first batch (against "
                         "state copies) before training, instead of "
                         "discovering it by failing mid-pass")
    tr.add_argument("--data_on_bad", default="log",
                    choices=["log", "raise"],
                    help="past --data_max_bad: keep skipping (log) or "
                         "abort the run (raise)")
    tr.add_argument("--init_model_path", default=None,
                    help="params.tar to start from")
    tr.add_argument("--log_period", type=int, default=100)
    tr.add_argument("--metrics_port", type=int, default=None,
                    help="expose GET /metrics (Prometheus) + /events "
                         "on this port for the whole run so training "
                         "fleets are scrapeable (0 picks a free port, "
                         "printed as JSON; omit to disable — "
                         "docs/observability.md)")
    tr.add_argument("--event_log", default=None,
                    help="append the structured event journal (faults, "
                         "OOMs, data faults, checkpoints — schema v1 "
                         "JSONL) to this file; inspect with "
                         "`paddle_tpu events tail --log FILE`")
    tr.add_argument("--run_id", default=None,
                    help="correlation id stamped on every journal "
                         "record/span this run emits (default: "
                         "generated; pass the SAME id to every worker "
                         "of a multi-host job so `paddle_tpu trace "
                         "merge` groups them — docs/observability.md)")
    tr.add_argument("--flight_dir", default=None,
                    help="arm flight-recorder auto-dump: postmortem "
                         "bundles (recent spans/events, metrics, "
                         "journal tail, live state) land here on "
                         "fault streaks, OOM and fatal exceptions; "
                         "`paddle_tpu obs dump` fetches one on demand")
    tr.add_argument("--profile_dir", default=None,
                    help="--job=profile trace output dir "
                         "(default ./profile_out)")
    tr.add_argument("--profile_every", type=int, default=0,
                    help="continuous step profiler: sample the "
                         "per-phase breakdown every N steps and export "
                         "live MFU/roofline + device-memory gauges "
                         "(obs/profile.py; 0 disables — "
                         "docs/observability.md 'Profiling & SLOs')")
    tr.add_argument("--slo", action="append", default=None,
                    metavar="METRIC<=TARGET[@WINDOW]",
                    help="declarative SLO objective for the watchdog, "
                         "repeatable (e.g. step_time_p99_ms<=250@64, "
                         "tokens_per_s>=1000); breaches journal under "
                         "the slo domain and auto-dump flight bundles. "
                         "Implies --profile_every 8 when that flag is "
                         "absent")
    tr.add_argument("--event_log_max_bytes", type=int, default=0,
                    help="rotate the --event_log file when it reaches "
                         "N bytes (journal.jsonl.1 ... .K; 0: never). "
                         "`events tail --follow` spans rotations")
    tr.add_argument("--event_log_keep", type=int, default=3,
                    help="rotated journal segments to keep (default 3)")
    tr.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    tr.add_argument("--seed", type=int, default=0)
    tr.add_argument("--compile_cache", default=None,
                    help="persistent XLA compile-cache dir: a "
                         "relaunched run (auto_resume, elastic "
                         "replacement) skips recompiling unchanged "
                         "steps ('0'/'off' disables; default: "
                         "$PADDLE_TPU_COMPILE_CACHE, else cold — "
                         "docs/robustness.md 'Warm start')")
    mg = sub.add_parser("merge", help="bundle topology + params into one "
                        "deployable artifact (MergeModel parity)")
    mg.add_argument("--config", required=True,
                    help=".py config defining `output`")
    mg.add_argument("--init_model_path", required=True,
                    help="params.tar (e.g. a save_pass checkpoint)")
    mg.add_argument("--out", required=True, help="output .tar path")

    inf = sub.add_parser("infer", help="forward a merged artifact")
    inf.add_argument("--model", required=True,
                     help="merged .tar from `paddle_tpu merge`")
    inf.add_argument("--config", default=None,
                     help="optional .py config defining `infer_reader`")
    inf.add_argument("--batch_size", type=int, default=8)
    inf.add_argument("--seq_len", type=int, default=16,
                     help="synthetic sequence length (no --config)")

    sv = sub.add_parser("serve", help="serve a merged artifact over HTTP "
                        "with admission control (docs/robustness.md)")
    sv.add_argument("--model", default=None,
                    help="merged .tar from `paddle_tpu merge` "
                         "(optional when --decode_config makes this a "
                         "generate-only fleet replica)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (printed as JSON)")
    sv.add_argument("--workers", type=int, default=2,
                    help="forward worker threads")
    sv.add_argument("--max_queue", type=int, default=64,
                    help="bounded request queue; a full queue rejects "
                         "with retry-after instead of buffering")
    sv.add_argument("--deadline_ms", type=float, default=0,
                    help="default per-request deadline (0: none)")
    sv.add_argument("--max_batch_memory", type=int, default=0,
                    help="admission budget in bytes for one request's "
                         "estimated device footprint (0: none). "
                         "Independently, a forward that hits XLA "
                         "RESOURCE_EXHAUSTED sheds with retry-after "
                         "and halves the adaptive max-batch-rows "
                         "limit (docs/robustness.md 'Memory pressure')")
    sv.add_argument("--breaker_window", type=int, default=64,
                    help="circuit-breaker sliding window size")
    sv.add_argument("--breaker_threshold", type=float, default=0.5,
                    help="failure fraction that opens the breaker")
    sv.add_argument("--breaker_cooldown", type=float, default=2.0,
                    help="seconds open before half-open probes")
    sv.add_argument("--decode_config", default=None,
                    help=".py script defining `decoder` (a "
                         "models.TransformerDecoder): attaches the "
                         "continuous-batching decode engine and the "
                         "POST /generate route")
    sv.add_argument("--draft_config", default=None,
                    help=".py script defining the DRAFT `decoder` for "
                         "speculative decoding (requires "
                         "--decode_config and --spec_k >= 1)")
    sv.add_argument("--spec_k", type=int, default=0,
                    help="draft tokens proposed per decode step "
                         "(greedy verify; 0 disables speculation)")
    sv.add_argument("--prefix_cache", choices=["on", "off"],
                    default="on",
                    help="shared-prefix KV page reuse across requests "
                         "(docs/perf.md 'Prefix reuse')")
    sv.add_argument("--gen_slots", type=int, default=4,
                    help="decode engine slot count")
    sv.add_argument("--gen_page_size", type=int, default=16,
                    help="KV page size in tokens")
    sv.add_argument("--kv_quant", choices=["none", "int8"],
                    default="none",
                    help="KV page dtype: int8 stores quantized pages "
                         "with per-row scales (~2.7x the tokens per "
                         "HBM byte; docs/robustness.md 'Two-tier KV "
                         "cache')")
    sv.add_argument("--kv_spill_pages", type=int, default=0,
                    help="host-RAM spill store capacity in pages: "
                         "cold trie pages spill there instead of "
                         "being freed and restore on the next prefix "
                         "match (0 disables the second tier; needs "
                         "--prefix_cache on)")
    sv.add_argument("--event_log", default=None,
                    help="append the structured event journal (sheds, "
                         "breaker flips, engine preemptions) to this "
                         "JSONL file; the ring is always served on "
                         "GET /events")
    sv.add_argument("--run_id", default=None,
                    help="correlation id stamped on every journal "
                         "record/span (default: generated)")
    sv.add_argument("--flight_dir", default=None,
                    help="arm flight-recorder auto-dump: postmortem "
                         "bundles land here on breaker-open, engine "
                         "step failures, SIGTERM and fatal "
                         "exceptions; GET /flight serves one on "
                         "demand")
    sv.add_argument("--profile_every", type=int, default=0,
                    help="continuous decode-step profiler: per-phase "
                         "breakdown + device-memory/KV-pool gauges, "
                         "served on GET /profile (0 disables)")
    sv.add_argument("--slo", action="append", default=None,
                    metavar="METRIC<=TARGET[@WINDOW]",
                    help="declarative SLO objective, repeatable (e.g. "
                         "decode_step_time_p99_ms<=50, "
                         "shed_rate<=0.05, tokens_per_s>=500); "
                         "breaches journal under the slo domain and "
                         "auto-dump flight bundles. Implies "
                         "--profile_every 8 when that flag is absent")
    sv.add_argument("--event_log_max_bytes", type=int, default=0,
                    help="rotate the --event_log file at N bytes "
                         "(0: never)")
    sv.add_argument("--event_log_keep", type=int, default=3,
                    help="rotated journal segments to keep (default 3)")
    sv.add_argument("--coordinator", default=None,
                    help="HOST:PORT of a `paddle_tpu coordinator` "
                         "daemon — join the membership plane as "
                         "serve/<replica_id> publishing this HTTP "
                         "endpoint, so a `paddle_tpu router` "
                         "discovers and fails over this replica "
                         "(docs/robustness.md 'Serving fleet')")
    sv.add_argument("--replica_id", default=None,
                    help="fleet replica id (default: host-port)")
    sv.add_argument("--heartbeat", type=float, default=1.0,
                    help="membership lease heartbeat seconds")
    sv.add_argument("--compile_cache", default=None,
                    help="persistent XLA compile-cache dir ('0'/'off' "
                         "disables; default: $PADDLE_TPU_COMPILE_CACHE, "
                         "else cold)")
    sv.add_argument("--artifacts", default=None,
                    help="AOT executable artifact store dir "
                         "(docs/robustness.md 'Warm start & artifact "
                         "integrity'): the decode engine loads "
                         "fingerprint-verified compiled executables "
                         "from here at startup — a respawned replica "
                         "serves with ZERO XLA compiles — and "
                         "backfills it after a cold build (default: "
                         "$PADDLE_TPU_ARTIFACTS, else none)")

    rt = sub.add_parser("router", help="run the serving-fleet router "
                        "daemon: KV-aware, prefix-affine dispatch over "
                        "N serve replicas with mid-stream failover "
                        "(docs/robustness.md 'Serving fleet')")
    rt.add_argument("--coordinator", required=True,
                    help="HOST:PORT of the `paddle_tpu coordinator` "
                         "whose membership plane the replicas join")
    rt.add_argument("--host", default="127.0.0.1")
    rt.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (printed as JSON)")
    rt.add_argument("--affinity", choices=["prefix", "load"],
                    default="prefix",
                    help="placement policy: 'prefix' steers "
                         "shared-prefix traffic to the replica whose "
                         "KV trie holds those pages; 'load' is pure "
                         "least-loaded-by-KV-headroom")
    rt.add_argument("--drain_timeout", type=float, default=10.0,
                    help="seconds to wait for in-flight settles on "
                         "POST /admin/drain and SIGTERM")
    rt.add_argument("--page_size", type=int, default=16,
                    help="KV page size in tokens — must match the "
                         "replicas' --gen_page_size (the affinity "
                         "index mirrors their prefix-trie keying)")
    rt.add_argument("--scrape_interval", type=float, default=0.5,
                    help="seconds between KV-gauge scrapes of each "
                         "replica's /metrics")
    rt.add_argument("--queue_timeout", type=float, default=5.0,
                    help="how long a request may queue for fleet KV "
                         "headroom before a typed 429")
    rt.add_argument("--heartbeat", type=float, default=1.0,
                    help="the router's own membership lease heartbeat")
    rt.add_argument("--event_log", default=None,
                    help="append the fleet journal (route/failover/"
                         "drain/rejoin records) to this JSONL file")
    rt.add_argument("--run_id", default=None,
                    help="correlation id stamped on every journal "
                         "record/span (default: generated)")
    rt.add_argument("--flight_dir", default=None,
                    help="arm flight-recorder auto-dump (SIGTERM and "
                         "fatal exceptions)")
    rt.add_argument("--event_log_max_bytes", type=int, default=0,
                    help="rotate the --event_log file at N bytes "
                         "(0: never)")
    rt.add_argument("--event_log_keep", type=int, default=3,
                    help="rotated journal segments to keep (default 3)")
    rt.add_argument("--autopilot", action="store_true",
                    help="run the fleet autopilot control loop "
                         "(autoscaler + SLO-gated deploys — "
                         "docs/robustness.md 'Fleet autopilot'); "
                         "implied by --spawn_cmd")
    rt.add_argument("--spawn_cmd", default=None,
                    help="shell command template spawning ONE replica "
                         "process ({replica_id} substituted; the "
                         "process must print the serve daemon's JSON "
                         "status line) — arms scale-up/down; without "
                         "it the autopilot can deploy (replicas quit, "
                         "supervisors respawn) but not spawn")
    rt.add_argument("--kv_quant", choices=["none", "int8"],
                    default="none",
                    help="fleet KV mode, appended to --spawn_cmd so "
                         "autoscaled replicas boot in the same "
                         "two-tier configuration as the hand-started "
                         "ones (affinity keys and restore paths only "
                         "line up fleet-wide when every replica "
                         "agrees)")
    rt.add_argument("--kv_spill_pages", type=int, default=0,
                    help="per-replica host spill capacity, appended "
                         "to --spawn_cmd replicas (0: omit)")
    rt.add_argument("--min_replicas", type=int, default=1,
                    help="autoscaler floor (scale-down stops here)")
    rt.add_argument("--max_replicas", type=int, default=8,
                    help="autoscaler ceiling (scale-up stops here)")
    rt.add_argument("--autopilot_interval", type=float, default=1.0,
                    help="seconds between autopilot control ticks")
    rt.add_argument("--compile_cache", default=None,
                    help="persistent XLA compile-cache dir, forwarded "
                         "to --spawn_cmd replicas so autoscale-up "
                         "cold starts stay bounded ('0'/'off' "
                         "disables; default: "
                         "$PADDLE_TPU_COMPILE_CACHE)")

    fl = sub.add_parser("fleet", help="operate a running "
                        "`paddle_tpu router` daemon: SLO-gated "
                        "rolling deploy, operator scaling, status "
                        "(docs/robustness.md 'Fleet autopilot')")
    fl.add_argument("action", choices=["deploy", "scale", "status"],
                    help="deploy: drain->restart->rejoin each replica "
                         "one at a time, pausing on SLO breaches; "
                         "scale: resize to --replicas through the "
                         "autopilot; status: fleet + autopilot "
                         "snapshots as JSON")
    fl.add_argument("--router", required=True,
                    help="base URL of the router daemon "
                         "(http://HOST:PORT)")
    fl.add_argument("--replicas", type=int, default=None,
                    help="scale: target replica count (clamped to "
                         "the daemon's --min/--max_replicas)")
    fl.add_argument("--force", action="store_true",
                    help="deploy: keep rolling through SLO breaches "
                         "(the journal still records them)")
    fl.add_argument("--timeout", type=float, default=600.0,
                    help="HTTP timeout for the admin call (a deploy "
                         "waits for every replica to cycle)")

    arts = sub.add_parser("artifacts", help="operate the warm-start "
                          "artifact store: build AOT decode "
                          "executables, verify frame integrity, list "
                          "(docs/robustness.md 'Warm start & "
                          "artifact integrity')")
    arts.add_argument("action", choices=["build", "verify", "ls"],
                      help="build: compile + serialize the decode "
                           "executables for a --decode_config into "
                           "--dir, so replica cold starts become "
                           "zero-compile; verify: re-read every frame "
                           "(nonzero exit + artifacts/verify_failed "
                           "journal records on any corrupt/torn "
                           "file); ls: one JSON row per artifact "
                           "with age/size/fingerprint")
    arts.add_argument("--dir", default=None,
                      help="artifact store directory (default: "
                           "$PADDLE_TPU_ARTIFACTS)")
    arts.add_argument("--decode_config", default=None,
                      help="build: .py script defining `decoder` — "
                           "the SAME script (and shape flags) the "
                           "serve replicas run with, or the "
                           "fingerprints won't match")
    arts.add_argument("--draft_config", default=None,
                      help="build: draft decoder script for "
                           "speculative fleets")
    arts.add_argument("--spec_k", type=int, default=0)
    arts.add_argument("--gen_slots", type=int, default=4)
    arts.add_argument("--gen_page_size", type=int, default=16)
    arts.add_argument("--prefix_cache", choices=["on", "off"],
                      default="on")
    arts.add_argument("--event_log", default=None,
                      help="append the artifacts journal records to "
                           "this JSONL file")

    sk = sub.add_parser("soak", help="run the million-user soak: "
                        "open-loop CTR + chat load over an in-process "
                        "fleet with seeded multi-family fault "
                        "injection and an exactly-once settle audit "
                        "(docs/robustness.md 'The million-user soak')")
    sk.add_argument("--seed", type=int, default=7,
                    help="the ONE seed: workloads, arrivals and the "
                         "fault schedule are all pure functions of it "
                         "(same seed, same soak)")
    sk.add_argument("--duration", type=float, default=8.0,
                    help="soak duration in seconds (the fault windows "
                         "scale with it)")
    sk.add_argument("--workload", choices=["mixed", "chat", "ctr"],
                    default="mixed",
                    help="mixed runs both loops; ctr implies the "
                         "online-training freshness loop")
    sk.add_argument("--faults", default="pokq",
                    help="fault families to compose, as letters from "
                         "the docs/robustness.md catalogue: p=replica "
                         "kill mid-stream, o=embedding shard kill in "
                         "the commit window, k=lease lapse, "
                         "q=coordinator outage ('' = no faults)")
    sk.add_argument("--chat_rate", type=float, default=4.0,
                    help="mean chat req/s offered (open loop)")
    sk.add_argument("--ctr_rate", type=float, default=4.0,
                    help="mean CTR impressions/s offered (open loop)")
    sk.add_argument("--arrival", default="diurnal",
                    choices=["constant", "ramp", "diurnal"],
                    help="arrival shape (mean stays at the rate flags)")
    sk.add_argument("--event_log", default=None,
                    help="soak journal JSONL path (default: fresh "
                         "temp file, printed in the report)")
    sk.add_argument("--report", default=None,
                    help="also write the full verdict report JSON "
                         "to this path")
    sk.add_argument("--slo_ttft_ms", type=float, default=8000.0,
                    help="p99 time-to-first-token bound (ms)")
    sk.add_argument("--slo_token_ms", type=float, default=4000.0,
                    help="p99 inter-token latency bound (ms)")
    sk.add_argument("--compile_cache", default=None,
                    help="persistent XLA compile-cache dir for the "
                         "in-process fleet ('0'/'off' disables; "
                         "default: $PADDLE_TPU_COMPILE_CACHE)")

    pf = sub.add_parser("profile", help="on-demand deep profile window: "
                        "N traced steps + per-phase/MFU summary "
                        "(docs/observability.md 'Profiling & SLOs')")
    pf.add_argument("--config", required=True,
                    help=".py config script or serialized topology .json")
    pf.add_argument("--steps", type=int, default=10,
                    help="steps inside the jax.profiler trace window")
    pf.add_argument("--batch_size", type=int, default=128)
    pf.add_argument("--seq_len", type=int, default=16)
    pf.add_argument("--init_model_path", default=None,
                    help="params.tar to start from")
    pf.add_argument("--out", default=None,
                    help="trace artifact dir (default ./profile_out)")
    pf.add_argument("--use_tpu", action="store_true", default=None)
    pf.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    pf.add_argument("--seed", type=int, default=0)

    sub.add_parser("version", help="print version (paddle version parity)")

    evp = sub.add_parser("events", help="inspect a structured event "
                         "journal (docs/observability.md)")
    evp.add_argument("action", choices=["tail"],
                     help="tail: print the newest records as JSON lines")
    evp.add_argument("--log", required=True,
                     help="journal JSONL file (train/serve --event_log)")
    evp.add_argument("-n", type=int, default=20, dest="n",
                     help="how many records (newest last)")
    evp.add_argument("--domain", default=None,
                     help="filter: trainer|data|serving|engine|"
                          "checkpoint|slo|profile")
    evp.add_argument("--kind", default=None,
                     help="filter: oom, quarantine, shed, preemption, "
                          "...")
    evp.add_argument("--follow", action="store_true",
                     help="after printing the tail, keep streaming "
                          "records as the run appends them "
                          "(tail -f for the journal)")
    evp.add_argument("--exit-after-idle", type=float, default=0,
                     dest="exit_after_idle",
                     help="with --follow: exit after N seconds with "
                          "no new record (0: follow forever) — for "
                          "scripted incident capture")

    ob = sub.add_parser("obs", help="flight-recorder verbs: postmortem "
                        "dump + observability selfcheck "
                        "(docs/observability.md)")
    ob.add_argument("action", choices=["dump", "selfcheck", "catalog"],
                    help="dump: write a postmortem bundle (this "
                         "process, or --url for a running one); "
                         "selfcheck: exercise metrics/journal/trace/"
                         "recorder end-to-end; catalog: print the "
                         "declared journal/metric/protocol contracts "
                         "as JSON")
    ob.add_argument("--url", default=None,
                    help="dump: base URL of a running process's obs "
                         "endpoint (serving front or train "
                         "--metrics_port) — fetches GET /flight")
    ob.add_argument("--out", default=None,
                    help="dump: output path (default: the configured "
                         "dump dir or the system temp dir)")

    trc = sub.add_parser("trace", help="cross-process trace tooling "
                         "(docs/observability.md)")
    trc.add_argument("action", choices=["merge"],
                     help="merge: fuse N per-host journals + chrome "
                          "traces into one timeline")
    trc.add_argument("merge_args", nargs=argparse.REMAINDER,
                     help="trace_merge flags: --journal FILES... "
                          "--trace FILES... --out-journal P "
                          "--out-trace P --offset HOST=SECONDS")

    ln = sub.add_parser("lint", help="JAX-aware static analysis "
                        "(ptlint — docs/static_analysis.md)")
    ln.add_argument("lint_args", nargs="*",
                    help="paths to lint (default: [tool.ptlint] paths)")
    ln.add_argument("--format", default=None,
                    choices=["text", "github", "json"],
                    help="github = GitHub Actions annotations for CI")
    ln.add_argument("--write-baseline", action="store_true",
                    help="regenerate the grandfathered-findings file")
    ln.add_argument("--no-baseline", action="store_true",
                    help="report baselined findings too")
    ln.add_argument("-v", "--verbose", action="store_true",
                    help="also list suppressed/baselined findings")
    ln.add_argument("--locks", nargs="?", const="text",
                    choices=["text", "dot"],
                    help="print the global lock-acquisition graph "
                         "discovered by R8 (text or DOT) and exit")
    ln.add_argument("--contracts", nargs="?", const="text",
                    choices=["text", "github", "json"],
                    help="run ONLY the journal/metric/protocol "
                         "contract rules R11-R13 (stale catalog "
                         "entries included) and exit")

    co = sub.add_parser("coordinator", help="run the elastic-training "
                        "coordinator daemon (go/cmd/master parity)")
    co.add_argument("--data", nargs="+", required=True,
                    help="RecordIO file paths or globs to partition")
    co.add_argument("--chunks_per_task", type=int, default=1)
    co.add_argument("--host", default="127.0.0.1")
    co.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (printed as JSON)")
    co.add_argument("--task_timeout", type=float, default=60.0)
    co.add_argument("--failure_max", type=int, default=3)
    co.add_argument("--worker_lease", type=float, default=None,
                    help="elastic membership lease seconds (expiry = "
                         "implicit leave + reshard; default: "
                         "--task_timeout)")
    co.add_argument("--snapshot", default=None,
                    help="dir for crash-recovery snapshots (FileStore)")
    co.add_argument("--snapshot_rpc", default=None,
                    help="HOST:PORT of a KVStoreServer — snapshot over "
                         "RPC instead of a shared filesystem "
                         "(RpcStore; mutually exclusive with "
                         "--snapshot)")

    ps = sub.add_parser("pserver", help="run one embedding shard daemon "
                        "(the 2017 `paddle pserver` reborn — "
                        "docs/robustness.md 'Sharded embedding service')")
    ps.add_argument("--shard_id", type=int, required=True,
                    help="this shard's index in [0, --shards)")
    ps.add_argument("--shards", type=int, required=True,
                    help="total shard count (the hash-partition modulus "
                         "— every pserver of one table must agree)")
    ps.add_argument("--dim", type=int, default=64,
                    help="embedding row width")
    ps.add_argument("--host", default="127.0.0.1")
    ps.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (printed as JSON)")
    ps.add_argument("--coordinator", default=None,
                    help="HOST:PORT of a `paddle_tpu coordinator` daemon "
                         "— register on the membership plane so clients "
                         "resolve endpoints (and fail over) through the "
                         "directory")
    ps.add_argument("--snapshot_dir", default=None,
                    help="dir for WAL + snapshots (FileStore): a "
                         "replacement started with the same flags "
                         "restores this shard's key range digest-stable")
    ps.add_argument("--heartbeat", type=float, default=1.0,
                    help="membership lease heartbeat seconds")
    ps.add_argument("--seed", type=int, default=0,
                    help="row-init seed (every pserver of one table "
                         "must agree)")

    dg = sub.add_parser("diagram", help="emit a Graphviz .dot of the model "
                        "(python/paddle/utils/make_model_diagram.py parity)")
    dg.add_argument("--config", required=True,
                    help=".py config script or serialized topology .json")
    dg.add_argument("--out", required=True, help="output .dot path")
    args = ap.parse_args(argv)

    if args.command in ("train", "serve", "router", "soak"):
        # warm-start plane, one seam for every long-lived verb
        # (docs/robustness.md "Warm start & artifact integrity"):
        # --compile_cache wins, else $PADDLE_TPU_COMPILE_CACHE, else
        # cold. Exported so child processes (--spawn_cmd replicas,
        # subprocess provisioners) inherit the same warm plane.
        from paddle_tpu.artifacts import cache as _compile_cache
        if args.compile_cache is not None:
            d = _compile_cache.enable(args.compile_cache)
            os.environ[_compile_cache.ENV_VAR] = d if d else "0"
        else:
            _compile_cache.ensure_default()

    if args.command == "artifacts":
        return _cmd_artifacts(args)
    if args.command == "lint":
        return _cmd_lint(args)
    if args.command == "merge":
        return _cmd_merge(args)
    if args.command == "infer":
        return _cmd_infer(args)
    if args.command == "diagram":
        return _cmd_diagram(args)
    if args.command == "events":
        return _cmd_events(args)
    if args.command == "obs":
        return _cmd_obs(args)
    if args.command == "trace":
        return _cmd_trace(args)
    if args.command == "coordinator":
        return _cmd_coordinator(args)
    if args.command == "pserver":
        return _cmd_pserver(args)
    if args.command == "fleet":
        return _cmd_fleet(args)
    if args.command == "soak":
        return _cmd_soak(args)
    if args.command == "router":
        from paddle_tpu.obs import context as obs_context
        from paddle_tpu.obs.events import JOURNAL
        from paddle_tpu.obs.flight import FLIGHT, install_excepthook
        if args.run_id:
            obs_context.set_run_id(args.run_id)
        if args.event_log:
            JOURNAL.configure(args.event_log,
                              max_bytes=args.event_log_max_bytes or None,
                              keep=args.event_log_keep)
        if args.flight_dir:
            FLIGHT.configure(dump_dir=args.flight_dir)
        install_excepthook()
        return _cmd_router(args)
    if args.command == "profile":
        return _cmd_profile(args)
    if args.command == "serve":
        from paddle_tpu.obs import context as obs_context
        from paddle_tpu.obs.events import JOURNAL
        from paddle_tpu.obs.flight import FLIGHT, install_excepthook
        if args.run_id:
            obs_context.set_run_id(args.run_id)
        if args.event_log:
            JOURNAL.configure(args.event_log,
                              max_bytes=args.event_log_max_bytes or None,
                              keep=args.event_log_keep)
        if args.flight_dir:
            FLIGHT.configure(dump_dir=args.flight_dir)
        install_excepthook()
        _wire_perf_obs(args)
        if args.artifacts:
            from paddle_tpu.artifacts import configure
            from paddle_tpu.artifacts.runtime import ENV_STORE
            configure(args.artifacts)
            os.environ[ENV_STORE] = args.artifacts
        return _cmd_serve(args)
    if args.command == "version":
        import paddle_tpu
        print(json.dumps({"version": paddle_tpu.__version__,
                          "framework": "paddle_tpu"}))
        return 0

    import paddle_tpu as paddle
    if args.job == "dump_config":
        # dump_config.py/show_pb.py parity: print the normalized topology
        # (the JSON twin of the protobuf text dump) without training
        print(_topo_from_ns(_load_config(args.config)).serialize())
        return 0
    paddle.init(use_tpu=args.use_tpu, trainer_count=args.trainer_count,
                seed=args.seed, compute_dtype=args.dtype,
                log_period=args.log_period)
    # observability wiring (docs/observability.md): the event journal's
    # file sink, the flight recorder and the standalone /metrics +
    # /events endpoint cover the WHOLE run, whichever --job it is
    from paddle_tpu.obs import context as obs_context
    from paddle_tpu.obs.events import JOURNAL
    from paddle_tpu.obs.flight import FLIGHT, install_excepthook
    if args.run_id:
        obs_context.set_run_id(args.run_id)
    if args.event_log:
        JOURNAL.configure(args.event_log,
                          max_bytes=args.event_log_max_bytes or None,
                          keep=args.event_log_keep)
    if args.flight_dir:
        FLIGHT.configure(dump_dir=args.flight_dir)
    install_excepthook()
    _wire_perf_obs(args)
    obs_httpd = None
    if args.metrics_port is not None:
        from paddle_tpu.obs.httpd import start_obs_server
        obs_httpd = start_obs_server(port=args.metrics_port)
        print(json.dumps({"job": "obs", "status": "serving",
                          "metrics_port": obs_httpd.server_address[1]}),
              flush=True)
    JOURNAL.emit("trainer", "run_start", job=args.job,
                 config=args.config)
    try:
        ns = _load_config(args.config)
        trainer = _build_trainer(ns, args.init_model_path)
        if args.job == "time":
            return _job_time(trainer, args.batch_size, args.iters,
                             args.seq_len)
        if args.job == "test":
            return _job_test(trainer, ns)
        if args.job == "checkgrad":
            return _job_checkgrad(trainer, ns, args)
        if args.job == "profile":
            return _job_profile(trainer, args)
        return _job_train(trainer, ns, args)
    finally:
        JOURNAL.emit("trainer", "run_end", job=args.job)
        if args.profile_every or args.slo:
            from paddle_tpu.obs.profile import PROFILER
            PROFILER.disable()      # joins the pt-obs-profiler thread
        if obs_httpd is not None:
            obs_httpd.shutdown()


if __name__ == "__main__":
    sys.exit(main())
