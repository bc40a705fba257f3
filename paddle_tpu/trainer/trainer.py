"""SGD trainer — the python train loop with a fused, jitted train step.

Reference: python/paddle/v2/trainer.py SGD (:24, train :116-184): reader ->
DataFeeder -> gm.forwardBackward -> per-param updater.update -> events.
The per-batch Python loop survives (the v2 API contract), but everything
from forward through optimizer update is ONE jitted XLA program per feed
shape — forward, jax.grad backward, and the whole optimizer fuse into a
single device step (replacing TrainerInternal::trainOneBatch's pipelined
updateCallback with something strictly better on TPU).

Data-parallel runs shard the same step over the mesh via
paddle_tpu.parallel (trainer_count>1 — MultiGradientMachine parity).
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.config import global_config
from paddle_tpu.core.registry import LayerOutput
from paddle_tpu.core.topology import Topology
from paddle_tpu.obs import context as obs_context
from paddle_tpu.obs import events as obs_events
from paddle_tpu.obs.profile import PROFILER
from paddle_tpu.trainer import event as evt
from paddle_tpu.trainer.parameters import Parameters
from paddle_tpu.utils.stats import global_counters, stat_timer, watch_gc


class SGD:
    """v2-compatible trainer.

    cost: cost LayerOutput (or list); parameters: Parameters;
    update_equation: an Optimizer; extra_layers: metric nodes evaluated and
    reported in events (e.g. layer.classification_error(...)).
    """

    def __init__(self, cost, parameters: Parameters, update_equation,
                 extra_layers: Optional[Sequence[LayerOutput]] = None,
                 is_local: bool = True, mesh=None, evaluators=None,
                 pipeline_stages=None, pipeline_remat: bool = False,
                 pipeline_schedule: str = "gpipe",
                 pipeline_microbatches: Optional[int] = None,
                 **kwargs):
        costs = cost if isinstance(cost, (list, tuple)) else [cost]
        self.costs = list(costs)
        self.extra_layers = list(extra_layers or [])
        # Evaluator framework (gserver/evaluators parity): their input
        # layers become extra topology outputs; per-batch values feed the
        # host-side streaming accumulators (see paddle_tpu/evaluator).
        self.evaluators = list(evaluators or [])
        # gradient-printer evaluators need d(cost)/d(activation) of their
        # input layers: the train step adds a zero tap on those outputs
        # and differentiates w.r.t. it alongside the params (one backward)
        self._grad_tap_names = sorted({
            li.name for ev in self.evaluators
            if getattr(ev, "wants_gradient", False) for li in ev.inputs})
        eval_inputs: List[LayerOutput] = []
        seen = {c.name for c in self.costs} | \
            {e.name for e in self.extra_layers}
        for ev in self.evaluators:
            for li in ev.inputs:
                if li.name not in seen and hasattr(li, "parents"):
                    # real graph nodes become extra outputs; name-only
                    # references (data/feed layers) resolve from the feed
                    seen.add(li.name)
                    eval_inputs.append(li)
        self._eval_out_names = sorted({li.name for ev in self.evaluators
                                       for li in ev.inputs})
        self.topology = Topology(
            self.costs, extra_outputs=self.extra_layers + eval_inputs)
        # validate evaluator inputs NOW: every name must be a graph node
        # or a data (feed) layer of this topology — a typo'd name used to
        # surface only at step time as a KeyError deep in the jit
        feed_names = {name for name, _ in self.topology.data_type()}
        known = set(self.topology.by_name) | feed_names
        for ev in self.evaluators:
            for li in ev.inputs:
                if li.name not in known:
                    raise ValueError(
                        f"evaluator {ev.name!r} input {li.name!r} is "
                        "neither a layer in this topology nor one of its "
                        f"data layers {sorted(feed_names)}")
        self.parameters = parameters
        # ensure state entries exist (parameters.create fills them, but a
        # Parameters loaded from tar may lack new state keys)
        for name, spec in self.topology.state_specs.items():
            if name not in parameters.state:
                parameters.state[name] = jnp.full(
                    tuple(spec.shape), spec.init_value, spec.dtype)
        # likewise params: evaluator inputs may pull in layers (and their
        # params) that the cost-only topology the user created params from
        # never reached
        missing = [n for n in self.topology.param_specs
                   if n not in parameters.raw]
        if missing:
            fresh = self.topology.init_params(
                jax.random.PRNGKey(global_config().seed), only=missing)
            parameters.raw.update(fresh)
        # a loaded table can carry a bias for a layer this topology builds
        # bias-FREE (e.g. a pre-round-4 transformer_lm head). Training
        # would silently ignore it while raw-table consumers
        # (models/block.py DefaultBlock.logits) still apply it — numerics
        # diverge with no error. Surface it. (Params for layers absent from the
        # topology entirely stay silent: that's the normal transfer-
        # learning shape, e.g. an MLM head alongside a classifier.)
        stale_bias = [
            n for n in parameters.raw
            if n.endswith(".wbias") and n not in self.topology.param_specs
            and n[:-len("wbias")] + "w0" in self.topology.param_specs]
        if stale_bias:
            import warnings
            warnings.warn(
                f"parameter table carries bias entries {stale_bias} for "
                "layers this topology builds WITHOUT bias: training "
                "ignores them, but inference paths reading the raw table "
                "may still apply them. Re-save the checkpoint (or delete "
                "the entries) to keep train and decode numerics aligned.",
                stacklevel=2)
        self.optimizer = update_equation.bind(
            self.topology.param_specs,
            sparse_params=self.topology.sparse_tables().keys())
        self.opt_state = self.optimizer.init_state(parameters.raw)
        self._rng = jax.random.PRNGKey(global_config().seed)
        self._step_count = 0
        # position counters for auto-resume: completed passes, and
        # completed batches within the current pass (both checkpointed, so
        # a relaunched run re-enters the pass it died in)
        self._pass_count = 0
        self._batch_in_pass = 0
        # checkpointable-reader plumbing (reader/pipeline.py): when the
        # train reader exposes state_for()/set_state(), mid-pass
        # checkpoints carry the reader position and auto-resume SEEKS
        # instead of re-reading the consumed prefix
        self._reader_batches = None
        self._reader_batch_base = 0
        self._reader_state = None
        if mesh is None:
            mesh = self._default_mesh()
        self.mesh = mesh
        # explicit stage map for pipeline parallelism over the mesh `pp`
        # axis (ParallelNeuralNetwork deviceId-pinning parity):
        # [[stage0 layer names], [stage1 ...], ...]
        self.pipeline_stages = pipeline_stages
        # jax.checkpoint each pipeline stage: backward holds only stage
        # boundaries and recomputes interiors (FLOPs-for-memory trade)
        self.pipeline_remat = pipeline_remat
        # "gpipe" (jax.grad-reversed scan) or "1f1b" (hand-scheduled
        # one-forward-one-backward: O(stages) activation memory instead
        # of O(microbatches + stages) — see parallel/pipeline.py)
        assert pipeline_schedule in ("gpipe", "1f1b"), pipeline_schedule
        self.pipeline_schedule = pipeline_schedule
        self.pipeline_microbatches = pipeline_microbatches
        self._train_step = self._build_train_step()
        # guarded variant (train(fault_policy=...)) compiled on first use
        self._train_step_guarded = None
        self._fault_policy = None
        self._bad_streak = None
        # gradient-accumulation steps compiled on demand, cached per
        # (accum_steps, guarded) — the memory executor and the warmup
        # probe share this cache (trainer/memory.py)
        self._accum_steps = {}
        self._memory_exec = None
        self._restored_memory_plan = None
        # fault-injection seam (testing/faults.py oom_at /
        # memory_pressure): called as (accum_steps, microbatch_rows)
        # immediately before each jitted step the memory executor or
        # probe dispatches; may raise RESOURCE_EXHAUSTED
        self._step_interceptor = None
        # continuous-profiler seam (obs/profile.py): the latest step's
        # concrete args, stored only while the profiler is enabled so
        # its lazy cost source can AOT-compile the live executable
        self._profile_feed = None
        self._profile_cost_armed = False
        self._test_step = self._build_test_step()

    # ------------------------------------------------------------------
    def refresh_update_hooks(self):
        """Recompute parameter-hook state (pruning masks) from the current
        parameter values — call after loading weights into an
        already-constructed trainer (ParameterUpdaterHook init-after-load
        parity)."""
        self.opt_state = self.optimizer.refresh_hooks(
            self.parameters.raw, self.opt_state)

    @staticmethod
    def _default_mesh():
        """trainer_count > 1 without an explicit mesh = transparent data
        parallelism, the v2 contract where trainer_count>1 selected
        MultiGradientMachine (GradientMachine.cpp:29). trainer_count=0
        means "all local devices" (Flags.cpp:23 semantics)."""
        tc = global_config().trainer_count
        if tc <= 1:
            return None
        n_dev = len(jax.devices())
        if tc > n_dev:
            raise RuntimeError(
                f"trainer_count={tc} requested but only {n_dev} "
                "device(s) are visible")
        from paddle_tpu.parallel.mesh import data_parallel_mesh
        return data_parallel_mesh(tc)

    @staticmethod
    def _masked_cost(v, row0, n_real):
        """Per-row cost reduction shared by the full-batch loss and the
        1F1B per-microbatch objective: sum the cost rows whose GLOBAL
        row index (row0 + local) is < n_real, divided by n_real — so
        the microbatch contributions sum to exactly the full-batch
        value."""
        v = v.reshape(v.shape[0], -1).sum(axis=-1) if v.ndim > 1 else v
        mask = ((row0 + jnp.arange(v.shape[0])) < n_real).astype(v.dtype)
        return jnp.sum(v * mask) / jnp.maximum(n_real.astype(v.dtype), 1.0)

    def _loss_and_metrics(self, params, state, feed, rng, n_real, mode,
                          sparse_sub=None, injected=None, skip=(),
                          taps=None):
        outs, new_state = self.topology.forward(
            params, state, feed, mode=mode, rng=rng, sparse_sub=sparse_sub,
            injected=injected, skip=skip, mesh=self.mesh, n_real=n_real,
            taps=taps)
        total = 0.0
        metrics = {}
        for c in self.costs:
            cost_val = self._masked_cost(outs[c.name], 0, n_real)
            total = total + cost_val
            metrics[c.name] = cost_val
        for e in self.extra_layers:
            v = outs[e.name]
            from paddle_tpu.core.sequence import SequenceBatch
            if isinstance(v, SequenceBatch):
                m = v.mask()
                data = v.data.reshape(v.data.shape[0], v.data.shape[1], -1)
                metrics[e.name] = jnp.sum(data.mean(-1) * m) / jnp.maximum(
                    jnp.sum(m), 1.0)
            else:
                v = v.reshape(v.shape[0], -1).mean(axis=-1)
                row_mask = (jnp.arange(v.shape[0]) < n_real).astype(v.dtype)
                metrics[e.name] = jnp.sum(v * row_mask) / jnp.maximum(
                    n_real.astype(v.dtype), 1.0)
        # evaluator inputs: graph outputs, or raw feed entries (labels)
        eval_outs = {n: (outs[n] if n in outs else feed[n])
                     for n in self._eval_out_names}
        return total, (metrics, new_state, eval_outs)

    def _guard_step(self, step_fn):
        """Fold the FaultPolicy finiteness guard into a train step — ON
        DEVICE, no host sync (trainer/fault.py). The guard checks the
        cost and every post-update float leaf (params, optimizer slots,
        layer state): a non-finite gradient necessarily produces a
        non-finite update under every optimizer here, and checking the
        results also catches slot overflow from huge-but-finite grads
        (g^2 -> inf in Adam's v) that a grads-only check would let
        poison the state. On a bad step the update is selected away with
        jnp.where — params/slots/state stay bit-identical to skipping
        the batch — the step's metric contributions are zeroed (pass
        averages stay finite; `fault_ok` records 1/0), and a device-side
        consecutive-bad-step counter rides along for the host to sample
        on the policy's check_period."""
        def gstep(params, opt_state, state, feed, rng, n_real, bad_streak):
            (new_params, new_opt_state, new_state, loss, metrics,
             eval_outs) = step_fn(params, opt_state, state, feed, rng,
                                  n_real)
            ok = jnp.isfinite(loss)
            for leaf in jax.tree_util.tree_leaves(
                    (new_params, new_opt_state, new_state)):
                if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
                    ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))

            def sel(n, o):
                return jnp.where(ok, n, o)

            new_params = jax.tree_util.tree_map(sel, new_params, params)
            new_opt_state = jax.tree_util.tree_map(sel, new_opt_state,
                                                   opt_state)
            new_state = jax.tree_util.tree_map(sel, new_state, state)
            metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                       for k, v in metrics.items()}
            metrics["fault_ok"] = ok.astype(jnp.float32)
            # [current streak, peak since the host last looked]: the peak
            # is sticky so a K-streak that ends between host checks is
            # still detected at the next check
            cur = jnp.where(ok, jnp.zeros((), bad_streak.dtype),
                            bad_streak[0] + 1)
            high = jnp.maximum(bad_streak[1], cur)
            bad_streak = jnp.stack([cur, high])
            return (new_params, new_opt_state, new_state, loss, metrics,
                    eval_outs, bad_streak)
        return gstep

    def _build_train_step(self, guarded: bool = False):
        # Row-sparse tables (ParamAttr(sparse=True) embeddings fed by data
        # layers): prefetch their touched rows, differentiate w.r.t. the
        # row block only, scatter-update rows + slots. The dense
        # [vocab, emb] gradient never materializes (SparseRowMatrix /
        # prefetch parity, MultiGradientMachine.h:99-166).
        sparse_map = self.topology.sparse_tables()

        from paddle_tpu.parallel.mesh import PP_AXIS
        if self.mesh is not None and PP_AXIS in self.mesh.shape and \
                self.mesh.shape[PP_AXIS] > 1:
            if self._grad_tap_names:
                raise NotImplementedError(
                    "gradient_printer is not supported with a pipelined "
                    "train step; use it on the plain path")
            return self._build_pipelined_train_step(guarded=guarded)
        if sparse_map and self._grad_tap_names:
            raise NotImplementedError(
                "gradient_printer is not supported together with "
                "row-sparse embedding tables")

        def step(params, opt_state, state, feed, rng, n_real):
            if sparse_map:
                from paddle_tpu.core.sequence import SequenceBatch
                from paddle_tpu.ops import embedding as emb_ops
                next_step = opt_state["step"] + 1
                uids_map, rows0, slot_rows_map = {}, {}, {}
                for pname, src in sparse_map.items():
                    v = feed[src]
                    ids = v.data if isinstance(v, SequenceBatch) else v
                    vocab = params[pname].shape[0]
                    uids = emb_ops.touched_ids(ids, vocab)
                    # prefetch WITH optimizer catch-up so the forward sees
                    # the values a dense run would hold at this step
                    p_rows, s_rows = self.optimizer.sparse_prefetch(
                        pname, params[pname], opt_state["slots"][pname],
                        uids, next_step)
                    uids_map[pname] = uids
                    rows0[pname] = p_rows
                    slot_rows_map[pname] = s_rows
                dense = {k: v for k, v in params.items()
                         if k not in sparse_map}

                def loss_fn(dp, rows):
                    full = dict(dp)
                    for k in sparse_map:
                        full[k] = params[k]
                    sub = {k: (uids_map[k], rows[k]) for k in rows}
                    return self._loss_and_metrics(full, state, feed, rng,
                                                  n_real, "train",
                                                  sparse_sub=sub)

                grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1),
                                             has_aux=True)
                with jax.named_scope("loss_and_grad"):
                    ((loss, (metrics, new_state, eval_outs)),
                     (g_dense, g_rows)) = grad_fn(dense, rows0)
                sparse_rows = {k: (uids_map[k], g_rows[k], rows0[k],
                                   slot_rows_map[k]) for k in g_rows}
                with jax.named_scope("optimizer"):
                    new_params, new_opt_state = self.optimizer.update(
                        params, g_dense, opt_state,
                        n_real.astype(jnp.float32),
                        sparse_rows=sparse_rows)
                return (new_params, new_opt_state, new_state, loss, metrics,
                        eval_outs)
            if self._grad_tap_names:
                # activation gradients for gradient_printer evaluators:
                # tap each target layer's output with zeros and take the
                # cotangent w.r.t. the tap in the SAME backward pass
                from paddle_tpu.core.sequence import SequenceBatch

                def _tap_zero(o):
                    s = o.data if isinstance(o, SequenceBatch) else o
                    return jnp.zeros(s.shape, s.dtype)

                tap_structs = jax.eval_shape(
                    lambda p: self.topology.forward(
                        p, state, feed, mode="train", rng=rng,
                        mesh=self.mesh, n_real=n_real,
                        output_names=self._grad_tap_names)[0], params)
                taps0 = {n: _tap_zero(o) for n, o in tap_structs.items()}
                grad_fn = jax.value_and_grad(
                    lambda p, t: self._loss_and_metrics(
                        p, state, feed, rng, n_real, "train", taps=t),
                    argnums=(0, 1), has_aux=True)
                with jax.named_scope("loss_and_grad"):
                    ((loss, (metrics, new_state, eval_outs)),
                     (grads, tap_grads)) = grad_fn(params, taps0)
                eval_outs = dict(eval_outs)
                for n, g in tap_grads.items():
                    eval_outs["__grad__" + n] = g
            else:
                grad_fn = jax.value_and_grad(
                    lambda p: self._loss_and_metrics(p, state, feed, rng,
                                                     n_real, "train"),
                    has_aux=True)
                # the scopes name the step's two regions in a device
                # trace (PERF.md section 3)
                with jax.named_scope("loss_and_grad"):
                    ((loss, (metrics, new_state, eval_outs)),
                     grads) = grad_fn(params)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = self.optimizer.update(
                    params, grads, opt_state, n_real.astype(jnp.float32))
            return (new_params, new_opt_state, new_state, loss, metrics,
                    eval_outs)

        return self._finalize_step(step, guarded)

    def _finalize_step(self, step, guarded: bool):
        """Shared tail of the plain and accumulation step builders:
        fold in the fault guard, then mesh-shard or plain-jit."""
        if guarded:
            step = self._guard_step(step)
        if self.mesh is not None:
            from paddle_tpu.parallel import tensor_parallel as tp
            from paddle_tpu.parallel.data_parallel import shard_train_step
            from paddle_tpu.parallel.mesh import EP_AXIS, MP_AXIS
            p_sh = o_sh = None
            if any(ax in self.mesh.shape and self.mesh.shape[ax] > 1
                   for ax in (MP_AXIS, EP_AXIS)):
                # shard over the LIVE param dict (may hold extra entries,
                # e.g. a tar checkpoint from an older topology)
                from jax.sharding import NamedSharding
                p_sh = {
                    name: NamedSharding(
                        self.mesh,
                        tp.spec_for(name, tuple(arr.shape), self.mesh))
                    for name, arr in self.parameters.raw.items()}
                o_sh = tp.opt_state_shardings(self.opt_state, p_sh,
                                              self.mesh)
            return shard_train_step(step, self.mesh, p_sh, o_sh,
                                    n_extra=1 if guarded else 0)
        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _build_accum_train_step(self, k: int, guarded: bool = False):
        """Gradient-accumulation step for the memory executor
        (trainer/memory.py — docs/robustness.md "Memory pressure"): the
        batch is split into ``k`` microbatches scanned ON DEVICE, the
        per-microbatch gradients SUM into the full-batch gradient, and
        the optimizer applies ONE update.

        Equivalence: each microbatch objective is the masked cost over
        its GLOBAL rows divided by ``n_real`` (the same ``_masked_cost``
        the 1F1B schedule uses), so the k partial losses — and their
        gradients — add up to exactly the full-batch value: summing the
        grads IS the mean-of-per-sample-grads the full step computes.
        tests/test_oom.py pins loss and params at k=1,2,4 to f32
        tolerance. The loop is a ``lax.scan``: ONE compile per k, never
        one per microbatch (``@pytest.mark.recompile_budget``).

        Peak live activation memory drops from O(batch) to O(batch/k)
        plus one grads-sized accumulator. Stateful layers see
        microbatch statistics and dropout draws per-microbatch masks
        (``fold_in(rng, j)``) — the standard grad-accumulation trade,
        documented in docs/robustness.md."""
        assert k >= 2, k
        if self.topology.sparse_tables():
            raise NotImplementedError(
                "microbatch accumulation does not compose with "
                "row-sparse embedding tables yet")
        if self._grad_tap_names or self.evaluators:
            raise NotImplementedError(
                "microbatch accumulation does not support "
                "gradient-printer or host evaluators")
        from paddle_tpu.parallel.mesh import PP_AXIS
        if self.mesh is not None and PP_AXIS in self.mesh.shape and \
                self.mesh.shape[PP_AXIS] > 1:
            raise NotImplementedError(
                "pipelined meshes microbatch through "
                "pipeline_microbatches, not the memory executor")
        metric_names = [c.name for c in self.costs] + \
            [e.name for e in self.extra_layers]

        def mb_loss(params, state, feed_j, rng_j, row0, n_real):
            from paddle_tpu.core.sequence import SequenceBatch
            mb_rows = jax.tree_util.tree_leaves(feed_j)[0].shape[0]
            # rows are contiguous: local row i is global row row0+i, so
            # the local real-row count keeps n_real-consuming layers
            # (MoE row masking) exact under the split
            n_local = jnp.clip(n_real - row0, 0, mb_rows)
            outs, new_state = self.topology.forward(
                params, state, feed_j, mode="train", rng=rng_j,
                mesh=self.mesh, n_real=n_local)
            total = 0.0
            metrics = {}
            for c in self.costs:
                v = self._masked_cost(outs[c.name], row0, n_real)
                total = total + v
                metrics[c.name] = v
            for e in self.extra_layers:
                v = outs[e.name]
                if isinstance(v, SequenceBatch):
                    raise NotImplementedError(
                        f"sequence-output extra layer {e.name!r} is not "
                        "supported under microbatch accumulation")
                v = v.reshape(v.shape[0], -1).mean(axis=-1)
                mask = ((row0 + jnp.arange(v.shape[0])) <
                        n_real).astype(v.dtype)
                metrics[e.name] = jnp.sum(v * mask) / jnp.maximum(
                    n_real.astype(v.dtype), 1.0)
            return total, (metrics, new_state)

        grad_fn = jax.value_and_grad(mb_loss, has_aux=True)

        def step(params, opt_state, state, feed, rng, n_real):
            b = jax.tree_util.tree_leaves(feed)[0].shape[0]
            assert b % k == 0, (b, k)   # the executor pads to a multiple
            mb = b // k
            feed_m = jax.tree_util.tree_map(
                lambda a: a.reshape((k, mb) + a.shape[1:]), feed)
            if self.mesh is not None:
                from paddle_tpu.parallel.data_parallel import \
                    shard_microbatched_feed
                feed_m = shard_microbatched_feed(feed_m, self.mesh)
            g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
            m0 = {name: jnp.zeros((), jnp.float32)
                  for name in metric_names}

            def body(carry, xs):
                g_acc, loss_acc, m_acc, st = carry
                feed_j, j = xs
                row0 = j * mb
                (loss_j, (metrics_j, new_st)), g_j = grad_fn(
                    params, st, feed_j, jax.random.fold_in(rng, j),
                    row0, n_real)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g_j)
                m_acc = {name: m_acc[name] +
                         metrics_j[name].astype(jnp.float32)
                         for name in m_acc}
                return (g_acc, loss_acc + loss_j.astype(jnp.float32),
                        m_acc, new_st), None

            with jax.named_scope("loss_and_grad"):
                (grads, loss, metrics, new_state), _ = jax.lax.scan(
                    body, (g0, jnp.zeros((), jnp.float32), m0, state),
                    (feed_m, jnp.arange(k)))
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = self.optimizer.update(
                    params, grads, opt_state, n_real.astype(jnp.float32))
            return (new_params, new_opt_state, new_state, loss, metrics,
                    {})
        return self._finalize_step(step, guarded)

    def _get_memory_step(self, k: int, guarded: bool):
        """Compiled step for ``k`` accumulation steps (k==1: the plain
        or guarded full-batch step), cached per (k, guarded). The
        memory executor and the warmup probe share this cache, so a
        probed plan's first real step pays no extra compile."""
        if k <= 1:
            if guarded:
                if self._train_step_guarded is None:
                    self._train_step_guarded = self._build_train_step(
                        guarded=True)
                return self._train_step_guarded
            return self._train_step
        key = (int(k), bool(guarded))
        fn = self._accum_steps.get(key)
        if fn is None:
            fn = self._build_accum_train_step(k, guarded=guarded)
            self._accum_steps[key] = fn
        return fn

    def _build_pipelined_train_step(self, guarded: bool = False):
        """Train step with the model body GPipe-pipelined over the mesh
        `pp` axis (ParallelNeuralNetwork parity — see
        parallel/pipeline.py). The tail (costs, metrics) runs replicated
        on the boundary activation."""
        from paddle_tpu.parallel.data_parallel import shard_train_step
        from paddle_tpu.parallel.pipeline import pipeline, topology_stages
        assert self.pipeline_stages, \
            "a pp mesh needs SGD(..., pipeline_stages=[[layer names]...])"
        mesh = self.mesh
        from paddle_tpu.parallel.mesh import PP_AXIS
        assert len(self.pipeline_stages) == mesh.shape[PP_AXIS], \
            "pipeline_stages must have one entry per pp rank"
        (stage_fn, stack_params, body_names, x_src,
         body_end) = topology_stages(self.topology, self.pipeline_stages)

        prologue_skip = self._pipeline_prologue_skip(x_src)

        if self.pipeline_schedule == "1f1b":
            return self._build_1f1b_train_step(
                stage_fn, stack_params, body_names, x_src, body_end,
                prologue_skip, guarded=guarded)

        def step(params, opt_state, state, feed, rng, n_real):
            def loss_fn(p):
                if prologue_skip is None:
                    xv = feed[x_src]
                else:
                    # boundary computed by earlier layers (embeddings):
                    # run just its ancestor slice; jax.grad flows the
                    # pipeline's dx back through it automatically
                    xv = self._prologue_forward(p, state, feed, rng,
                                                n_real, x_src,
                                                prologue_skip)
                y = pipeline(stage_fn, stack_params(p), xv, mesh,
                             remat=self.pipeline_remat,
                             num_microbatches=self.pipeline_microbatches)
                return self._loss_and_metrics(
                    p, state, feed, rng, n_real, "train",
                    injected={body_end: y}, skip=body_names)

            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (loss, (metrics, new_state, eval_outs)), grads = grad_fn(params)
            new_params, new_opt_state = self.optimizer.update(
                params, grads, opt_state, n_real.astype(jnp.float32))
            return (new_params, new_opt_state, new_state, loss, metrics,
                    eval_outs)

        if guarded:
            step = self._guard_step(step)
        return shard_train_step(step, mesh, n_extra=1 if guarded else 0)

    def _prologue_forward(self, params, state, feed, rng, n_real, x_src,
                          prologue_skip):
        """The boundary's ancestor slice (embeddings etc.) — ONE shared
        implementation so the GPipe and 1F1B schedules cannot drift."""
        pouts, _ = self.topology.forward(
            params, state, feed, mode="train", rng=rng,
            output_names=[x_src], skip=prologue_skip, mesh=self.mesh,
            n_real=n_real)
        return pouts[x_src]

    def _pipeline_prologue_skip(self, x_src):
        """None when the pipeline boundary is a data layer (fed
        directly); otherwise the layer names to SKIP so a forward
        computes exactly the boundary's ancestor slice."""
        if self.topology.by_name[x_src].type == "data":
            return None
        anc = set()
        stack = [self.topology.by_name[x_src]]
        while stack:
            l = stack.pop()
            if l.name in anc:
                continue
            anc.add(l.name)
            stack.extend(l.parents)
        return [l.name for l in self.topology.layers if l.name not in anc]

    def _build_1f1b_train_step(self, stage_fn, stack_params, body_names,
                               x_src, body_end, prologue_skip=None,
                               guarded: bool = False):
        """Hand-scheduled 1F1B: gradients come out of the schedule
        itself (parallel/pipeline.pipeline_1f1b), not an outer
        jax.grad; a cheap replicated tail pass afterwards produces the
        reported loss / metrics / eval outputs / state update with math
        identical to the GPipe path. Caveat (documented in
        docs/parallelism.md): dropout in the TAIL would draw different
        masks in the gradient pass (per-microbatch folded rng) than in
        the metrics pass — keep dropout out of pipelined models' tails
        (stages already reject it)."""
        from paddle_tpu.parallel.data_parallel import shard_train_step
        from paddle_tpu.parallel.pipeline import pipeline_1f1b
        mesh = self.mesh
        # the gradient pass folds the rng per microbatch while the
        # metrics pass uses the unfolded rng — an rng-consuming tail
        # layer would make the reported loss diverge from the trained
        # objective, so reject it at build time (stages already do)
        for lname, l in self.topology.by_name.items():
            if lname not in body_names and l.type == "dropout":
                raise AssertionError(
                    f"dropout layer {lname!r} in the tail is unsupported "
                    "with pipeline_schedule='1f1b' (per-microbatch rng "
                    "would diverge from the metrics pass)")

        def step(params, opt_state, state, feed, rng, n_real):
            if prologue_skip is None:
                x = feed[x_src]
                pvjp = None
            else:
                def prologue(p):
                    return self._prologue_forward(p, state, feed, rng,
                                                  n_real, x_src,
                                                  prologue_skip)

                # ONE differentiated trace: float leaves are the vjp'd
                # output, integer leaves ride out as aux (the dyn/static
                # predicate and interleave are pipeline.py's — the
                # prologue cotangent ordering and the schedule's dx
                # ordering share one definition)
                from paddle_tpu.parallel.pipeline import (
                    interleave_leaves, is_dynamic_leaf)
                shape = jax.eval_shape(prologue, params)
                leaves_s, treedef = jax.tree_util.tree_flatten(shape)
                is_dyn = [is_dynamic_leaf(s) for s in leaves_s]

                def prologue_split(p):
                    lv = jax.tree_util.tree_leaves(prologue(p))
                    return ([a for a, d in zip(lv, is_dyn) if d],
                            [a for a, d in zip(lv, is_dyn) if not d])

                x_dyn, pvjp, x_static = jax.vjp(prologue_split, params,
                                                has_aux=True)
                x = jax.tree_util.tree_unflatten(
                    treedef, interleave_leaves(list(x_dyn), list(x_static),
                                               is_dyn))
            from paddle_tpu.parallel.mesh import PP_AXIS
            m = self.pipeline_microbatches or mesh.shape[PP_AXIS]
            b = jax.tree_util.tree_leaves(x)[0].shape[0]
            assert b % m == 0, f"microbatches {m} must divide batch {b}"
            mb = b // m
            feed_m = jax.tree_util.tree_map(
                lambda a: a.reshape((m, mb) + a.shape[1:]), feed)

            # the tail differentiates ONLY the non-stage params: vjp'ing
            # the full dict would make the scan carry (and psum) a
            # zero-gradient copy of every body parameter per tick,
            # eroding the O(stages) memory win
            stage_names_set = stack_params.param_names
            tail_p0 = {k: v for k, v in params.items()
                       if k not in stage_names_set}
            stage_part = {k: v for k, v in params.items()
                          if k in stage_names_set}

            def tail_cost(p, y_mb, j, fm):
                feed_j = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, j, 0, keepdims=False), fm)
                # stage params are never read (body layers are skipped);
                # merge them back un-differentiated for the full dict
                outs, _ = self.topology.forward(
                    {**stage_part, **p}, state, feed_j, mode="train",
                    rng=jax.random.fold_in(rng, j),
                    injected={body_end: y_mb}, skip=body_names,
                    mesh=None,  # runs INSIDE shard_map — no constraints
                    n_real=n_real)
                total = 0.0
                for c in self.costs:
                    total = total + self._masked_cost(outs[c.name],
                                                      j * mb, n_real)
                return total

            def tail_vjp(y_mb, j, p, fm):
                loss_j, vjp = jax.vjp(
                    lambda p_, y_: tail_cost(p_, y_, j, fm), p, y_mb)
                dtail, dy = vjp(jnp.float32(1.0))
                return loss_j, dy, dtail

            loss_sum, y, g_stacked, dtail, dx = pipeline_1f1b(
                stage_fn, stack_params(params), x, tail_vjp, mesh,
                num_microbatches=m, tail_args=(tail_p0, feed_m))
            grads = dict(dtail)
            if pvjp is not None:
                # route the pipeline's input cotangent back through the
                # prologue (embedding grads)
                (dp_pro,) = pvjp(dx)
                grads = {k: grads[k] + dp_pro[k] if k in grads
                         else dp_pro[k] for k in dp_pro}
            grads.update(stack_params.unstack(g_stacked))
            # replicated tail pass for metrics/state; the scheduled
            # loss_sum must equal its loss — the drift is EMITTED as a
            # metric so an inconsistency between the trained objective
            # and the reported loss is visible, not silent
            loss, (metrics, new_state, eval_outs) = self._loss_and_metrics(
                params, state, feed, rng, n_real, "train",
                injected={body_end: y}, skip=body_names)
            metrics["pipeline_loss_drift"] = loss_sum - loss
            new_params, new_opt_state = self.optimizer.update(
                params, grads, opt_state, n_real.astype(jnp.float32))
            return (new_params, new_opt_state, new_state, loss, metrics,
                    eval_outs)

        if guarded:
            step = self._guard_step(step)
        return shard_train_step(step, mesh, n_extra=1 if guarded else 0)

    def _build_test_step(self):
        def step(params, state, feed, n_real):
            loss, (metrics, _, eval_outs) = self._loss_and_metrics(
                params, state, feed, jax.random.PRNGKey(0), n_real, "test")
            return loss, metrics, eval_outs
        return jax.jit(step)

    # ------------------------------------------------------------------
    def train(self, reader=None, num_passes: int = 1,
              event_handler: Optional[Callable] = None, feeding=None,
              num_batches_per_pass: Optional[int] = None,
              coordinator=None, chunk_reader=None, batch_size: int = 0,
              checkpoint_manager=None, checkpoint_period: int = 0,
              checkpoint_dir: Optional[str] = None,
              auto_resume: bool = False, fault_policy=None,
              idle_timeout: float = 600.0, microbatch=None,
              oom_probe: bool = False,
              worker_id: Optional[str] = None, on_reshape=None):
        """reader: callable yielding BATCHES (lists of sample tuples), i.e.
        the output of paddle_tpu.reader.batch(...).

        Elastic mode (the Go-master cloud-training path, go/master/
        service.go + NewRemoteParameterUpdater): pass `coordinator` (a
        Coordinator or a connect() RPC proxy) + `chunk_reader` instead of
        `reader` — data then flows through coordinator-dispatched tasks
        (lease-requeued if this trainer dies), `num_passes` counts
        coordinator epochs, and with `checkpoint_manager` the trainer
        auto-restores the newest full-state checkpoint on entry and saves
        every `checkpoint_period` batches + each pass end, so a SIGKILLed
        trainer resumes within the pass it died in.

        checkpoint_dir: shorthand for checkpoint_manager=
        CheckpointManager(checkpoint_dir) (docs/robustness.md).

        auto_resume: restore the newest intact checkpoint before the
        first pass and continue FROM it — pass counter, position within
        the interrupted pass, optimizer slots, and RNG state all resume,
        so a kill -9'd run relaunched with the same flags replays the
        uninterrupted run exactly (deterministic readers; num_passes is
        then the run TOTAL, not additional passes). No-op when no
        checkpoint exists yet. A CHECKPOINTABLE reader (reader.batch
        over a CheckpointableReader — reader/pipeline.py) resumes by
        seeking the source to the saved (epoch, shard, chunk, offset)
        instead of re-reading the consumed prefix: each remaining
        record is consumed exactly once, none re-read or dropped.

        fault_policy: a trainer.fault.FaultPolicy — check every step's
        numerics on device, skip non-finite updates, and roll back to
        the newest checkpoint after K consecutive bad steps, emitting
        event.FaultEvent (docs/robustness.md).

        microbatch: "auto" or an int — adaptive microbatching
        (trainer/memory.py, docs/robustness.md "Memory pressure"): a
        step that raises XLA RESOURCE_EXHAUSTED is bisected into
        microbatches with on-device gradient accumulation (numerically
        equivalent to the full-batch step) and re-run — no samples
        lost, an event.OOMEvent per adaptation. An int fixes the
        starting microbatch rows; "auto" starts full-batch. The
        discovered plan rides in checkpoint meta, so auto_resume
        restarts at the known-safe microbatch without re-probing.

        oom_probe: with microbatch="auto", binary-search the largest
        safe microbatch on the first batch (against COPIES of the
        state) before stepping, instead of discovering it by failing
        mid-pass.

        worker_id: elastic-membership identity (coordinator mode,
        docs/robustness.md "Elastic training"). The trainer join()s the
        coordinator before its first task — adopting the fleet's
        published MemoryPlan (provenance="adopted") when it has no
        better one, so a replacement host never re-discovers the safe
        microbatch by OOMing — and leave()s gracefully at the end, so
        its in-flight tasks requeue with their reader position instead
        of burning a lease timeout. Each pulled grant carries the
        membership generation; when it changes mid-pass the trainer
        journals a ``trainer/reshape`` event and calls
        ``on_reshape(generation)`` if given (the hook may rebalance
        async-SGD islands — parallel/async_sgd.py)."""
        from paddle_tpu.trainer.data_feeder import DataFeeder
        if event_handler is None:
            event_handler = _default_event_handler
        # one run_id for the whole run (generated here if the CLI set
        # none): every span/journal record the run emits carries it
        obs_context.ensure_run_id()
        watch_gc()
        # warm start: a relaunched (auto_resume / elastic-replacement)
        # trainer re-pays the step compile unless the operator pointed
        # PADDLE_TPU_COMPILE_CACHE at a persistent cache — opt-in, so
        # chaos tests that time cold starts stay cold
        from paddle_tpu.artifacts import cache as _compile_cache
        _compile_cache.ensure_default()
        feeder = DataFeeder(self.topology.data_type(), feeding)
        if checkpoint_manager is None and checkpoint_dir:
            from paddle_tpu.trainer.checkpoint import CheckpointManager
            checkpoint_manager = CheckpointManager(checkpoint_dir)

        self._fault_policy = fault_policy
        if fault_policy is not None:
            if self._train_step_guarded is None:
                self._train_step_guarded = self._build_train_step(
                    guarded=True)
            if self._bad_streak is None:
                self._bad_streak = jnp.zeros((2,), jnp.int32)
            self._fault_steps_since_check = 0

        self._memory_exec = None
        if microbatch is not None:
            from paddle_tpu.trainer.memory import (AdaptiveMicrobatcher,
                                                   MemoryPlan)
            if self.evaluators:
                raise NotImplementedError(
                    "microbatch= does not compose with host evaluators "
                    "yet — drop the evaluators or the microbatching")
            if microbatch == "auto":
                plan = MemoryPlan()
            else:
                mb = int(microbatch)
                if mb < 1:
                    raise ValueError(
                        "microbatch must be >= 1 or 'auto'")
                plan = MemoryPlan(microbatch=mb, provenance="configured")
            self._memory_exec = AdaptiveMicrobatcher(self, plan,
                                                     probe=oom_probe)
        elif oom_probe:
            raise ValueError(
                "oom_probe=True needs microbatch='auto' or an int")

        if coordinator is not None:
            import xmlrpc.client as _xc

            from paddle_tpu.reader import batch as batch_reader
            from paddle_tpu.trainer.coordinator import (RetryPolicy,
                                                        call_with_retry,
                                                        coordinator_epoch,
                                                        task_reader)
            assert chunk_reader is not None, \
                "coordinator mode needs chunk_reader(chunk) -> records"
            # every coordinator RPC (here and inside task_reader) retries
            # with backoff — a coordinator restarting while trainers come
            # up delays them instead of killing them
            retry = RetryPolicy()
            joined = False
            join_plan_meta = None
            if worker_id is not None:
                try:
                    resp = call_with_retry(coordinator.join, worker_id,
                                           policy=retry)
                    joined = True
                    join_plan_meta = (resp or {}).get("memory_plan")
                except _xc.Fault:
                    # pre-elastic server: train as an anonymous worker
                    import warnings
                    warnings.warn(
                        "coordinator has no join() RPC — running "
                        "without elastic membership (upgrade the "
                        "coordinator for scale-out/in)")

            def _on_gen_change(gen):
                # a grant revealed a new membership generation: the
                # fleet resharded under us. Journal it (run_id/host
                # stamped) and let the caller rebalance.
                from paddle_tpu.obs.events import emit as _emit
                _emit("trainer", "reshape", generation=int(gen),
                      worker_id=worker_id)
                if on_reshape is not None:
                    on_reshape(gen)

            rdr = task_reader(coordinator, chunk_reader,
                              idle_timeout=idle_timeout, retry=retry,
                              worker_id=worker_id if joined else None,
                              on_generation_change=_on_gen_change)
            if batch_size:
                rdr = batch_reader(rdr, batch_size)
            if checkpoint_manager is not None and \
                    self.restore_checkpoint(checkpoint_manager):
                self._adopt_restored_plan()
            self._adopt_fleet_plan(join_plan_meta)

            def _publish_plan():
                # share the discovered/known-safe plan with the fleet:
                # the NEXT joiner adopts it from its join() response
                # instead of re-probing (or re-OOMing) on its own
                if not joined or self._memory_exec is None:
                    return
                pm = self._memory_exec.plan.to_meta()
                if pm is None:
                    return
                try:
                    call_with_retry(coordinator.put_memory_plan, pm,
                                    policy=retry)
                except (_xc.Fault, TimeoutError):
                    pass         # pre-elastic server / coordinator gone

            _publish_plan()
            try:
                while coordinator_epoch(coordinator,
                                        retry=retry) < num_passes:
                    pass_id = coordinator_epoch(coordinator, retry=retry)
                    self._run_pass(pass_id, rdr, feeder, event_handler,
                                   num_batches_per_pass, checkpoint_manager,
                                   checkpoint_period)
                    if checkpoint_manager is not None:
                        self.save_checkpoint(checkpoint_manager)
                    _publish_plan()
                    if coordinator_epoch(coordinator, retry=retry) == \
                            pass_id:
                        # the reader gave up without the epoch turning
                        # (every task dropped, or idle_timeout hit) —
                        # surfaced by task_reader's warning; don't spin
                        import warnings
                        warnings.warn(
                            f"elastic training stopped at epoch {pass_id} "
                            f"of {num_passes}: the pass never completed")
                        break
            finally:
                if joined:
                    # graceful scale-in: hand leased tasks back (with
                    # their reader position) instead of burning a lease
                    # timeout on the survivors
                    try:
                        call_with_retry(coordinator.leave, worker_id,
                                        policy=retry)
                    except (_xc.Fault, TimeoutError):
                        pass     # coordinator gone: leases expire
                # saves run off the step path (async writer); never leave
                # train() — even via an exception — with a checkpoint
                # still in flight (and surface any background write error)
                if checkpoint_manager is not None:
                    checkpoint_manager.wait()
            return

        # a checkpointable reader (reader.batch over a
        # CheckpointableReader / ordered SupervisedReader) carries its
        # position through checkpoints: resume SEEKS the source instead
        # of re-reading and discarding the consumed prefix
        ckptable = hasattr(reader, "state_for") and \
            hasattr(reader, "set_state")
        self._reader_batches = reader if ckptable else None

        start_pass, skip_batches, seek_batches = 0, 0, 0
        if auto_resume and checkpoint_manager is not None and \
                self.restore_checkpoint(checkpoint_manager):
            # replay position: skip the passes (and the leading batches
            # of the interrupted pass) the checkpoint already covers.
            # RNG splits for skipped batches already happened before the
            # save, so skipped batches must not re-split (_run_pass).
            self._adopt_restored_plan()
            start_pass = self._pass_count
            skip_batches = self._batch_in_pass
            if ckptable and skip_batches and self._reader_state:
                # mid-pass reader state: position the source exactly
                # after the last checkpointed batch — each remaining
                # record is then consumed exactly once, nothing re-read
                reader.set_state(self._reader_state)
                seek_batches, skip_batches = skip_batches, 0
        try:
            for pass_id in range(start_pass, num_passes):
                self._run_pass(pass_id, reader, feeder, event_handler,
                               num_batches_per_pass, checkpoint_manager,
                               checkpoint_period,
                               skip_batches=skip_batches
                               if pass_id == start_pass else 0,
                               batch_offset=seek_batches
                               if pass_id == start_pass else 0)
                if checkpoint_manager is not None:
                    self.save_checkpoint(checkpoint_manager)
        finally:
            self._reader_batches = None
            if checkpoint_manager is not None:
                checkpoint_manager.wait()

    def _own_params(self):
        """This topology's parameter subset. Parameters may be SHARED
        across trainers (GAN-style alternating optimization: two SGDs,
        one Parameters object); the jitted step and the optimizer must
        only see/update the params this trainer's graph owns."""
        raw = self.parameters.raw
        return {k: raw[k] for k in self.topology.param_specs}

    def _merge_params(self, new_params):
        merged = dict(self.parameters.raw)
        merged.update(new_params)
        self.parameters.replace(merged)

    def train_batch(self, data_batch, feeding=None):
        """Run ONE optimizer step on a batch (list of sample tuples) and
        return (cost, metrics).

        The step-level API alternating-optimization setups need (the v1
        GAN demo drove GradientMachine.forwardBackward per network;
        here two SGD instances sharing one Parameters object call
        train_batch in turn — see demo/gan)."""
        from paddle_tpu.trainer.data_feeder import DataFeeder
        feeder = DataFeeder(self.topology.data_type(), feeding)
        feed = feeder(data_batch)
        obs_context.set_step(self._step_count)
        n_real = jnp.asarray(feed.pop("__batch_size__"), jnp.int32)
        self._rng, sub = jax.random.split(self._rng)
        (new_params, self.opt_state, new_state, loss, metrics,
         eval_outs) = self._train_step(
            self._own_params(), self.opt_state, self.parameters.state,
            feed, sub, n_real)
        self._merge_params(new_params)
        self.parameters.state = new_state
        self._step_count += 1
        global_counters.bump("trainer/steps")
        if PROFILER.enabled:
            self._profile_feed = (feed, sub, n_real)
            self._arm_profile_cost()
            PROFILER.on_step("train")
        loss_np, metrics_np, _ = self._fetch_host(loss, metrics)
        return loss_np, metrics_np

    def _arm_profile_cost(self) -> None:
        """(Re-)register the continuous profiler's lazy FLOPs+bytes
        source: a weakref closure that AOT-compiles the plain train
        step with the trainer's CURRENT args (obs/profile.py invokes
        it at most once per enable, off a sampled step — never per
        step). Microbatched runs approximate with the un-accumulated
        executable."""
        if self._profile_cost_armed:
            return
        self._profile_cost_armed = True
        import weakref
        ref = weakref.ref(self)

        def _cost():
            tr = ref()
            if tr is None or tr._profile_feed is None:
                return None, None
            from paddle_tpu.obs.profile import cost_of
            feed, sub, n_real = tr._profile_feed
            return cost_of(tr._train_step, tr._own_params(),
                           tr.opt_state, tr.parameters.state,
                           feed, sub, n_real)

        PROFILER.set_cost_source("train", _cost)

    @staticmethod
    def _fetch_host(loss, metrics, eval_outs=None):
        """ONE device->host transfer for a step's scalars + evaluator
        outputs. Keep every per-step read inside this call: a separate
        float(x)/int(x) on a device array costs a full round-trip
        (docs/perf.md 'One host sync per step').
        The scope is the continuous profiler's 'settle' phase — time
        spent waiting for the device to drain into host floats."""
        with stat_timer("train/settle"):
            loss_np, metrics_host, eval_host = jax.device_get(
                (loss, metrics, {} if eval_outs is None else eval_outs))
        return (float(loss_np),
                {k: float(v) for k, v in metrics_host.items()},
                eval_host)

    @staticmethod
    def _prefetched(reader, feeder, depth: int = 2):
        """Run feed CONVERSION (python->padded arrays->device transfer) in
        a background thread, `depth` batches ahead — the DoubleBuffer
        discipline (DataProvider.h:249) applied to the feeder itself. On
        slow-memory hosts the numpy pack of an image batch costs as much
        as the device step; overlapping the two restores device-bound
        throughput. Order and semantics are unchanged.

        Lifecycle contract (reader/pipeline.py convention): the fill
        thread is named ``pt-data-feed`` and exits on a stop event when
        the consumer abandons the generator (an early ``break`` out of
        the pass, num_batches_per_pass) instead of wedging forever on a
        full queue — the conftest thread-leak fixture enforces it."""
        import queue
        import threading
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        stop = threading.Event()
        DONE = object()

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def work():
            try:
                for item in reader():
                    # feed conversion/packing is the host half of the
                    # h2d phase (the device copy itself rides the next
                    # dispatch) — timed for the profiler's breakdown
                    with stat_timer("train/h2d"):
                        converted = feeder(item)
                    if not put((None, converted)):
                        return
                put((None, DONE))
            except BaseException as e:      # surfaced in the main thread
                put((e, None))

        t = threading.Thread(target=work, daemon=True,
                             name="pt-data-feed")
        t.start()
        try:
            while True:
                # the wait for a converted batch IS the pipeline-bound
                # signal: its timer/span (obs/trace.py) shows a
                # data-starved step loop at a glance
                with stat_timer("train/data_wait"):
                    err, feed = q.get()
                if err is not None:
                    raise err
                if feed is DONE:
                    return
                yield feed
        finally:
            stop.set()

    @staticmethod
    def _kahan_add(acc, v):
        """One compensated-summation step on device: (sum, comp) + v.
        Eager jnp ops — XLA never sees the expression, so the
        compensation term cannot be algebraically simplified away."""
        s, c = acc
        y = v - c
        t = s + y
        return t, (t - s) - y

    def _check_faults(self, policy, pass_id, batch_id, event_handler,
                      checkpoint_manager):
        """Host side of the guarded step: sample the device-side
        [current, peak-since-last-check] bad-step counter every
        check_period steps (the only host sync the fault path adds), and
        roll back + emit FaultEvent when the peak reached the policy
        limit. The peak is sticky on device, so a K-streak that ends
        between checks is still seen."""
        self._fault_steps_since_check += 1
        if self._fault_steps_since_check < policy.effective_check_period:
            return
        self._fault_steps_since_check = 0
        cur, high = (int(v) for v in jax.device_get(self._bad_streak))
        if high >= policy.max_bad_steps:
            restored = None
            if policy.rollback and checkpoint_manager is not None and \
                    self.restore_checkpoint(checkpoint_manager):
                restored = self._step_count
            self._bad_streak = jnp.zeros((2,), jnp.int32)
            ev = evt.FaultEvent(pass_id, batch_id, "rollback", high,
                                restored)
            global_counters.bump("trainer/fault_events")
            obs_events.emit_event(ev)   # journaled BEFORE the handler:
            # a handler that raises to abort still leaves the record
            event_handler(ev)
        elif high > 0:
            # streak live or recently ended, below the rollback limit:
            # surface it, and lower the peak to the live value so an
            # ended streak is reported once
            self._bad_streak = jnp.asarray([cur, cur], jnp.int32)
            ev = evt.FaultEvent(pass_id, batch_id, "nonfinite", high,
                                None)
            global_counters.bump("trainer/fault_events")
            obs_events.emit_event(ev)
            event_handler(ev)

    def _run_pass(self, pass_id, reader, feeder, event_handler,
                  num_batches_per_pass, checkpoint_manager=None,
                  checkpoint_period: int = 0, skip_batches: int = 0,
                  batch_offset: int = 0):
        """batch_offset: reader-state resume — the source was SEEKED
        past the first `batch_offset` batches (nothing to re-read), so
        batch numbering continues from there while the reader yields
        only the remainder. skip_batches is the legacy replay path for
        non-checkpointable readers: consume-and-discard."""
        event_handler(evt.BeginPass(pass_id))
        pass_metrics: Dict[str, float] = {}
        metrics_dev = None      # lazy path: on-device (sum, comp) pairs
        n_batches = 0
        policy = self._fault_policy
        for ev in self.evaluators:
            ev.start()
        # With host-side evaluators attached, their streaming update needs
        # eval_outs on the host EVERY step. Without them, nothing in the
        # loop needs per-step host data, so events go out lazy and the
        # dispatch queue runs ahead of the device (the JAX async idiom) —
        # a handler reading e.cost still syncs, on ITS schedule.
        lazy = not self.evaluators
        # lazy per-pass sums accumulate compensated (Kahan) — or in real
        # float64 when x64 is on — so long-pass averages match the eager
        # path's host-float64 accumulation instead of drifting in
        # sequential f32 (docs/perf.md 'Lazy pass metrics').
        acc_dt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        self._batch_in_pass = skip_batches or batch_offset
        self._reader_batch_base = batch_offset
        for idx, feed in enumerate(self._prefetched(reader, feeder)):
            batch_id = idx + batch_offset
            if num_batches_per_pass is not None and \
                    batch_id >= num_batches_per_pass:
                break
            if batch_id < skip_batches:
                # auto-resume replay: the checkpoint already covers this
                # batch — and its RNG split happened before the save, so
                # the batch is consumed without stepping or re-splitting
                continue
            # stamp the global step on the trace context: every span /
            # journal record this iteration produces (train_step,
            # nonfinite/rollback/oom, checkpoint writes) is then
            # attributable to run_id + step (docs/observability.md)
            obs_context.set_step(self._step_count)
            # the caller's code (and, in a benchmark, its wait for the
            # step before): with data_wait, train_step and settle, the
            # fourth named span of an iteration's wall time
            with stat_timer("train/event"):
                event_handler(evt.BeginIteration(pass_id, batch_id))
            n_real_host = int(feed.pop("__batch_size__"))
            n_real = jnp.asarray(n_real_host, jnp.int32)
            self._rng, sub = jax.random.split(self._rng)
            with stat_timer("train_step"):
                if self._memory_exec is not None:
                    # adaptive microbatching (trainer/memory.py): OOM'd
                    # steps bisect + re-run instead of killing the pass
                    out = self._memory_exec.run(
                        feed, sub, n_real, guarded=policy is not None,
                        bad_streak=self._bad_streak,
                        ctx=(pass_id, batch_id, event_handler))
                    if policy is not None:
                        (new_params, self.opt_state, new_state, loss,
                         metrics, eval_outs, self._bad_streak) = out
                    else:
                        (new_params, self.opt_state, new_state, loss,
                         metrics, eval_outs) = out
                elif policy is not None:
                    (new_params, self.opt_state, new_state, loss,
                     metrics, eval_outs,
                     self._bad_streak) = self._train_step_guarded(
                        self._own_params(), self.opt_state,
                        self.parameters.state, feed, sub, n_real,
                        self._bad_streak)
                else:
                    (new_params, self.opt_state, new_state, loss,
                     metrics, eval_outs) = self._train_step(
                        self._own_params(), self.opt_state,
                        self.parameters.state, feed, sub, n_real)
            self._merge_params(new_params)
            self.parameters.state = new_state
            self._step_count += 1
            global_counters.bump("trainer/steps")
            if PROFILER.enabled:
                self._profile_feed = (feed, sub, n_real)
                self._arm_profile_cost()
                PROFILER.on_step("train")
            self._batch_in_pass = batch_id + 1
            n_batches += 1
            if lazy:
                # running on-device sums: O(1) live buffers, still async
                if metrics_dev is None:
                    metrics_dev = {
                        k: (v.astype(acc_dt), jnp.zeros((), acc_dt))
                        for k, v in metrics.items()}
                else:
                    metrics_dev = {
                        k: self._kahan_add(metrics_dev[k], v.astype(acc_dt))
                        for k, v in metrics.items()}
                fetch_host = self._fetch_host   # plain function — the
                # event closure must not pin the trainer alive
                with stat_timer("train/event"):
                    event_handler(evt.LazyEndIteration(
                        pass_id, batch_id,
                        lambda loss=loss, metrics=metrics, fh=fetch_host:
                            fh(loss, metrics)[:2]))
            else:
                loss_np, metrics_np, eval_host = self._fetch_host(
                    loss, metrics, eval_outs)
                for k, v in metrics_np.items():
                    pass_metrics[k] = pass_metrics.get(k, 0.0) + v
                metrics_np.update(
                    self._feed_evaluators(eval_host, n_real_host))
                with stat_timer("train/event"):
                    event_handler(evt.EndIteration(pass_id, batch_id,
                                                   loss_np, metrics_np))
            if policy is not None:
                self._check_faults(policy, pass_id, batch_id,
                                   event_handler, checkpoint_manager)
            if checkpoint_manager is not None and checkpoint_period and \
                    self._step_count % checkpoint_period == 0:
                self.save_checkpoint(checkpoint_manager)
        if metrics_dev is not None:
            # one transfer fetches the whole pass's sums
            for k, (s, c) in jax.device_get(metrics_dev).items():
                pass_metrics[k] = pass_metrics.get(k, 0.0) + float(s) + \
                    float(c)
        # guarded runs: skipped steps contributed zeros — average over
        # the GOOD steps so one bad batch doesn't dilute the pass metrics
        denom = float(max(n_batches, 1))
        if policy is not None and "fault_ok" in pass_metrics:
            good = pass_metrics.pop("fault_ok")
            avg = {k: v / max(good, 1.0) for k, v in pass_metrics.items()}
            avg["fault_ok"] = good / denom
        else:
            avg = {k: v / denom for k, v in pass_metrics.items()}
        for ev in self.evaluators:
            avg.update(ev.result())
        self._pass_count = pass_id + 1
        self._batch_in_pass = 0
        event_handler(evt.EndPass(pass_id, avg, self.parameters))

    def test(self, reader, feeding=None) -> evt.TestResult:
        from paddle_tpu.trainer.data_feeder import DataFeeder
        feeder = DataFeeder(self.topology.data_type(), feeding)
        totals: Dict[str, float] = {}
        total_loss, n = 0.0, 0
        params = self.optimizer.test_params(self._own_params(),
                                            self.opt_state)
        # test() may run mid-pass (from an EndIteration handler): save the
        # evaluators' training accumulators and restore them afterwards so
        # the train pass's metrics aren't corrupted by the test sweep.
        import copy
        saved = [{k: copy.deepcopy(v) for k, v in ev.__dict__.items()
                  if k != "inputs"} for ev in self.evaluators]
        for ev in self.evaluators:
            ev.start()
        for feed in self._prefetched(reader, feeder):
            n_real_host = int(feed.pop("__batch_size__"))
            n_real = jnp.asarray(n_real_host, jnp.int32)
            loss, metrics, eval_outs = self._test_step(
                params, self.parameters.state, feed, n_real)
            loss_np, metrics_np, eval_host = self._fetch_host(
                loss, metrics, eval_outs)
            total_loss += loss_np
            for k, v in metrics_np.items():
                totals[k] = totals.get(k, 0.0) + v
            self._feed_evaluators(eval_host, n_real_host)
            n += 1
        n = max(n, 1)
        avg = {k: v / n for k, v in totals.items()}
        for ev, st in zip(self.evaluators, saved):
            avg.update(ev.result())
            ev.__dict__.update(st)           # resume training accumulators
        return evt.TestResult(total_loss / n, avg)

    def _feed_evaluators(self, eval_outs, n_real: int) -> Dict[str, float]:
        """Push fetched batch outputs through the host evaluators; returns
        their running pass-so-far results (printed per log_period, the
        reference's per-batch evaluator lines)."""
        if not self.evaluators:
            return {}
        from paddle_tpu.evaluator import _to_np
        host = {k: _to_np(v) for k, v in eval_outs.items()}
        results: Dict[str, float] = {}
        for ev in self.evaluators:
            if getattr(ev, "wants_gradient", False):
                keys = ["__grad__" + li.name for li in ev.inputs]
                if any(k not in host for k in keys):
                    continue    # no backward ran (test sweep) — skip
                ev.eval_batch([host[k] for k in keys], n_real)
            else:
                ev.eval_batch([host[li.name] for li in ev.inputs], n_real)
            if not getattr(ev, "expensive_result", False):
                results.update(ev.result())   # running pass-so-far display
        return results

    # ------------------------------------------------------------------
    def save_checkpoint(self, manager, meta: Optional[Dict] = None) -> str:
        """Full-state checkpoint (params + optimizer slots + layer state +
        step counters) via a CheckpointManager — the Go-pserver
        checkpoint-with-optimizer-state capability (go/pserver/
        service.go:272, paddle/optimizer/serialization.h)."""
        import numpy as _np
        m = {"step_count": self._step_count,
             "pass_count": self._pass_count,
             "batch_in_pass": self._batch_in_pass,
             "rng": _np.asarray(jax.random.key_data(self._rng)).tolist()}
        # mid-pass position of a checkpointable reader: the source state
        # after the last completed batch, so auto-resume seeks instead
        # of replaying (reader/pipeline.py; pass-end saves carry none —
        # the next pass starts fresh)
        if self._reader_batches is not None and self._batch_in_pass > 0:
            rs = self._reader_batches.state_for(
                self._batch_in_pass - 1 - self._reader_batch_base)
            if rs is not None:
                m["reader_state"] = rs
        # the discovered memory plan (trainer/memory.py): auto-resume
        # restarts at the known-safe microbatch instead of re-probing
        if self._memory_exec is not None:
            pm = self._memory_exec.plan.to_meta()
            if pm is not None:
                m["memory_plan"] = pm
        m.update(meta or {})
        return manager.save(self._step_count, self.parameters.raw,
                            self.opt_state, self.parameters.state, m)

    def restore_checkpoint(self, manager, step: Optional[int] = None) -> bool:
        """Resume params/optimizer/state from the newest intact checkpoint
        (LoadCheckpoint parity). Returns False if none exists."""
        res = manager.restore(step)
        if res is None:
            return False
        _, tree = res
        self.parameters.replace(tree["params"])
        self.parameters.state = tree["state"]
        self.opt_state = tree["opt_state"]
        self._step_count = int(tree["meta"].get("step_count", 0))
        self._pass_count = int(tree["meta"].get("pass_count", 0))
        self._batch_in_pass = int(tree["meta"].get("batch_in_pass", 0))
        self._reader_state = tree["meta"].get("reader_state")
        self._restored_memory_plan = tree["meta"].get("memory_plan")
        if "rng" in tree["meta"]:
            # Restore raw uint32 bits to keep the legacy key flavor the
            # rest of the trainer uses — wrap_key_data would produce a
            # typed key with a different aval and force a jit retrace.
            self._rng = jnp.asarray(tree["meta"]["rng"], jnp.uint32)
        return True

    def _adopt_restored_plan(self):
        """Auto-resume with microbatching active: restart at the
        checkpoint's known-safe MemoryPlan instead of re-probing or
        re-discovering it by OOM (docs/robustness.md 'Memory
        pressure')."""
        if self._memory_exec is None or not self._restored_memory_plan:
            return
        from paddle_tpu.trainer.memory import MemoryPlan
        plan = MemoryPlan.from_meta(self._restored_memory_plan,
                                    provenance="resumed")
        if plan is not None:
            self._memory_exec.adopt(plan)

    def _adopt_fleet_plan(self, meta):
        """Elastic join: adopt the fleet's published MemoryPlan
        (coordinator.join() response) when this trainer has no better
        one of its own — a replacement host starts at the known-safe
        microbatch (provenance="adopted") instead of re-probing or
        re-discovering it by OOM. A restored/configured/probed plan
        always wins (same precedence as maybe_probe)."""
        if self._memory_exec is None or not meta:
            return
        if self._memory_exec.plan.provenance != "full":
            return               # it already knows better
        from paddle_tpu.trainer.memory import MemoryPlan
        plan = MemoryPlan.from_meta(meta, provenance="adopted")
        if plan is None:
            return
        self._memory_exec.adopt(plan)
        from paddle_tpu.obs.events import emit as _emit
        _emit("trainer", "plan_adopted", provenance="adopted",
              microbatch=plan.microbatch, accum_steps=plan.accum_steps)

    def save_parameter_to_tar(self, f):
        self.parameters.to_tar(f)

    def save_pass(self, output_dir: str, pass_id: int):
        """ParamUtil parity: output/pass-%05d/params.tar
        (paddle/trainer/ParamUtil.h:89)."""
        d = os.path.join(output_dir, f"pass-{pass_id:05d}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "params.tar"), "wb") as f:
            self.parameters.to_tar(f)


def _default_event_handler(e):
    cfg = global_config()
    if isinstance(e, evt.EndIteration):
        if e.batch_id % max(cfg.log_period, 1) == 0:
            print(f"Pass {e.pass_id}, Batch {e.batch_id}, "
                  f"Cost {e.cost:.6f}, {e.evaluator}")
    elif isinstance(e, evt.EndPass):
        print(f"Pass {e.pass_id} done. {e.evaluator}")
    elif isinstance(e, evt.FaultEvent):
        print(f"FAULT {e!r}", file=sys.stderr)
