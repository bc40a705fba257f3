"""Parallelism tests on the virtual 8-device CPU mesh (the 'CPU build as
fake device' discipline — mirrors MultiGradientMachine multi-thread tests
and test_CompareTwoNets: sharded training must match single-device)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.parallel import create_mesh, DP_AXIS, MP_AXIS
from paddle_tpu.parallel import tensor_parallel as tp


def _net(seed=0):
    img = paddle.layer.data("x", paddle.data_type.dense_vector(32))
    h = paddle.layer.fc(img, size=64, act=paddle.activation.Relu(),
                        name="h")
    out = paddle.layer.fc(h, size=8, act=paddle.activation.Softmax(),
                          name="out")
    lbl = paddle.layer.data("y", paddle.data_type.integer_value(8))
    cost = paddle.layer.classification_cost(out, lbl, name="cost")
    return cost


def _reader(n=64, dim=32, k=8, seed=3):
    rng = np.random.RandomState(seed)
    feats = rng.randn(n, dim).astype("float32")
    labels = rng.randint(0, k, n)

    def reader():
        yield [(feats[i], int(labels[i])) for i in range(n)]
    return reader


def _run(mesh, passes=3, trainer_count=1):
    from paddle_tpu.core import registry
    registry.reset_name_counters()
    paddle.init(use_tpu=False, seed=0, trainer_count=trainer_count)
    cost = _net()
    params = paddle.create_parameters(paddle.Topology(cost))
    tr = paddle.SGD(cost=cost, parameters=params,
                    update_equation=paddle.optimizer.Momentum(
                        learning_rate=0.1, momentum=0.9),
                    mesh=mesh)
    costs = []
    tr.train(_reader(), num_passes=passes,
             event_handler=lambda e: costs.append(e.cost)
             if isinstance(e, paddle.event.EndIteration) else None)
    return costs


class TestDataParallel:
    def test_dp_matches_single_device(self):
        single = _run(None)
        mesh = create_mesh([(DP_AXIS, 8)])
        dp = _run(mesh)
        np.testing.assert_allclose(single, dp, rtol=2e-4, atol=2e-5)

    def test_dp_mp_matches_single_device(self):
        single = _run(None)
        mesh = create_mesh([(DP_AXIS, 4), (MP_AXIS, 2)])
        both = _run(mesh)
        np.testing.assert_allclose(single, both, rtol=2e-4, atol=2e-5)


class TestShardingRules:
    def test_embedding_rows_sharded_fc_cols_sharded(self):
        mesh = create_mesh([(DP_AXIS, 4), (MP_AXIS, 2)])
        from jax.sharding import PartitionSpec as P
        assert tp.spec_for("_emb0.w0", (100, 64), mesh) == P(MP_AXIS, None)
        assert tp.spec_for("_fc1.w0", (64, 64), mesh) == P(None, MP_AXIS)
        assert tp.spec_for("_fc1.wbias", (64,), mesh) == P()
        # non-divisible dims fall back to replication
        assert tp.spec_for("_fc2.w0", (64, 63), mesh) == P()

    def test_param_placement(self):
        mesh = create_mesh([(DP_AXIS, 4), (MP_AXIS, 2)])
        from paddle_tpu.core import registry
        registry.reset_name_counters()
        cost = _net()
        topo = paddle.Topology(cost)
        shardings = tp.param_shardings(topo.param_specs, mesh)
        params = tp.shard_params(topo.init_params(), mesh, shardings)
        w = params["_h.w0"]   # (32, 64) -> cols over mp
        assert w.sharding.spec == shardings["_h.w0"].spec
        assert len(w.devices()) == 8


class TestGraftEntry:
    def test_dryrun_multichip(self):
        import sys
        sys.path.insert(0, "/root/repo")
        import __graft_entry__ as g
        g.dryrun_multichip(8)


class TestTrainerCountMesh:
    def test_trainer_count_builds_dp_mesh(self):
        """paddle.init(trainer_count=4) + plain SGD must shard over 4
        devices with no explicit mesh= (GradientMachine.cpp:29 —
        trainer_count>1 transparently selected MultiGradientMachine)."""
        from paddle_tpu.core import registry
        registry.reset_name_counters()
        paddle.init(use_tpu=False, seed=0, trainer_count=4)
        try:
            cost = _net()
            params = paddle.create_parameters(paddle.Topology(cost))
            tr = paddle.SGD(cost=cost, parameters=params,
                            update_equation=paddle.optimizer.Momentum(
                                learning_rate=0.1, momentum=0.9))
            assert tr.mesh is not None
            assert dict(tr.mesh.shape)[DP_AXIS] == 4
            costs = []
            tr.train(_reader(), num_passes=2,
                     event_handler=lambda e: costs.append(e.cost)
                     if isinstance(e, paddle.event.EndIteration) else None)
            assert costs and np.isfinite(costs).all()
        finally:
            paddle.init(use_tpu=False, seed=0, trainer_count=1)

    def test_trainer_count_above_devices_raises(self):
        """More trainers than devices is an error, not a quiet run on
        fewer: no training believes it spans chips it does not have."""
        from paddle_tpu.core import registry
        registry.reset_name_counters()
        paddle.init(use_tpu=False, seed=0,
                    trainer_count=len(jax.devices()) + 1)
        try:
            cost = _net()
            params = paddle.create_parameters(paddle.Topology(cost))
            with pytest.raises(RuntimeError, match="trainer_count"):
                paddle.SGD(cost=cost, parameters=params,
                           update_equation=paddle.optimizer.Momentum(
                               learning_rate=0.1))
        finally:
            paddle.init(use_tpu=False, seed=0, trainer_count=1)

    def test_init_use_tpu_without_a_tpu_raises(self):
        with pytest.raises(RuntimeError, match="use_tpu=True"):
            paddle.init(use_tpu=True)
        with pytest.raises(RuntimeError, match="use_tpu=True"):
            paddle.init(use_gpu=True)

    def test_trainer_count_numerics_match_explicit_mesh(self):
        explicit = _run(create_mesh([(DP_AXIS, 4)]))
        try:
            implicit = _run(None, trainer_count=4)
        finally:
            paddle.init(use_tpu=False, seed=0, trainer_count=1)
        np.testing.assert_allclose(implicit, explicit, rtol=1e-5)


class TestThreeAxisMesh:
    def test_dp_mp_sp_transformer_matches_single_device(self):
        """Composability: tensor-parallel fc columns + ring attention over
        sp + data parallelism in ONE mesh (dp2 x mp2 x sp2 = 8 devices)
        must reproduce single-device numerics exactly."""
        from paddle_tpu import models
        from paddle_tpu.core import registry

        def run(mesh):
            paddle.init(use_tpu=False, seed=0)
            registry.reset_name_counters()
            spec = models.transformer_lm(vocab_size=64, d_model=32,
                                         n_heads=4, n_layers=2, d_ff=64,
                                         max_len=32)
            params = paddle.create_parameters(
                paddle.Topology(spec.cost, extra_outputs=[spec.output]))
            tr = paddle.SGD(cost=spec.cost, parameters=params,
                            extra_layers=[spec.output],
                            update_equation=paddle.optimizer.Adam(
                                learning_rate=1e-3),
                            mesh=mesh)
            rng = np.random.RandomState(0)
            b, T = 4, 16
            ids = rng.randint(0, 64, (b, T + 1)).astype("int32")
            batch = [(ids[i, :T], np.arange(T, dtype="int32"), ids[i, 1:])
                     for i in range(b)]
            return [float(tr.train_batch(batch)[0]) for _ in range(3)]

        single = run(None)
        meshed = run(create_mesh([("dp", 2), ("mp", 2), ("sp", 2)]))
        np.testing.assert_allclose(single, meshed, rtol=2e-4)


class TestIslandReconcileGuard:
    """AsyncSGDIsland.reconcile under a poisoned island: the isfinite
    guard (the PR 1 discipline applied to reconcile) must drop the
    NaN/Inf island's tree from the average — counted in utils/stats —
    and heal the poisoned island with the healthy average instead of
    letting one bad island contaminate every peer."""

    def _island(self, seed=0):
        from paddle_tpu.core import registry
        registry.reset_name_counters()
        paddle.init(use_tpu=False, seed=seed)
        cost = _net()
        params = paddle.create_parameters(paddle.Topology(cost))
        tr = paddle.SGD(cost=cost, parameters=params,
                        update_equation=paddle.optimizer.Momentum(
                            learning_rate=0.1))
        return tr

    def test_poisoned_island_dropped_and_healed(self):
        from paddle_tpu.parallel.async_sgd import AsyncSGDIsland
        from paddle_tpu.utils.stats import global_counters

        t1, t2, t3 = (self._island(s) for s in (0, 1, 2))
        healthy = {k: np.asarray(v)
                   for k, v in t2.parameters.raw.items()}
        healthy3 = {k: np.asarray(v)
                    for k, v in t3.parameters.raw.items()}
        # island 1 went NaN (a poisoned batch that slipped the guard)
        k0 = sorted(t1.parameters.raw)[0]
        bad = dict(t1.parameters.raw)
        bad[k0] = jnp.full_like(bad[k0], jnp.nan)
        t1.parameters.replace(bad)

        island = AsyncSGDIsland(
            t1, sync_period=1,
            sync_group=[t1.parameters, t2.parameters, t3.parameters])
        before = global_counters.value("parallel/poisoned_islands")
        with pytest.warns(UserWarning, match="non-finite"):
            island.reconcile()
        assert global_counters.value(
            "parallel/poisoned_islands") == before + 1

        expect = {k: (healthy[k] + healthy3[k]) / 2.0 for k in healthy}
        for tr in (t1, t2, t3):
            for k in expect:
                got = np.asarray(tr.parameters.raw[k])
                assert np.isfinite(got).all()
                np.testing.assert_allclose(got, expect[k], rtol=1e-6,
                                           atol=1e-7)

    def test_all_poisoned_skips_reconcile(self):
        from paddle_tpu.parallel.async_sgd import AsyncSGDIsland

        t1, t2 = (self._island(s) for s in (0, 1))
        for tr in (t1, t2):
            bad = {k: jnp.full_like(v, jnp.inf)
                   for k, v in tr.parameters.raw.items()}
            tr.parameters.replace(bad)
        island = AsyncSGDIsland(t1, sync_period=1,
                                sync_group=[t1.parameters, t2.parameters])
        with pytest.warns(UserWarning, match="every island"):
            island.reconcile()          # no crash, params untouched
        assert not np.isfinite(
            np.asarray(t1.parameters.raw[sorted(t1.parameters.raw)[0]])
        ).any()

    def test_healthy_islands_unchanged_semantics(self):
        # no poison: reconcile is the plain average (regression guard
        # for the guarded path)
        from paddle_tpu.parallel.async_sgd import AsyncSGDIsland

        t1, t2 = (self._island(s) for s in (0, 1))
        raws = [{k: np.asarray(v) for k, v in t.parameters.raw.items()}
                for t in (t1, t2)]
        island = AsyncSGDIsland(t1, sync_period=1,
                                sync_group=[t1.parameters, t2.parameters])
        island.reconcile()
        for k in raws[0]:
            expect = (raws[0][k] + raws[1][k]) / 2.0
            np.testing.assert_allclose(np.asarray(t1.parameters.raw[k]),
                                       expect, rtol=1e-6, atol=1e-7)
