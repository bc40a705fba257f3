"""dot_product_attention layer: plain vs ring (sp mesh) equivalence —
the VERDICT criterion that ring attention is usable FROM A LAYER with the
switch being purely a mesh decision."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import registry
from paddle_tpu.parallel import create_mesh
from paddle_tpu.parallel.mesh import SP_AXIS

T = 8


def _model(causal):
    registry.reset_name_counters()
    ids = paddle.layer.data(
        "ids", paddle.data_type.integer_value_sequence(50))
    lbl = paddle.layer.data("y", paddle.data_type.integer_value(2))
    emb = paddle.layer.embedding(ids, size=32, name="att_emb")
    att = paddle.layer.dot_product_attention(emb, num_heads=4,
                                             causal=causal, name="att")
    pooled = paddle.layer.pooling(
        att, pooling_type=paddle.pooling.Avg(), name="att_pool")
    out = paddle.layer.fc(pooled, size=2, act=paddle.activation.Softmax(),
                          name="att_out")
    cost = paddle.layer.classification_cost(out, lbl, name="att_cost")
    return cost


def _reader(n=2, b=8):
    rng = np.random.RandomState(0)
    batches = [[([int(v) for v in rng.randint(0, 50, T)],
                 int(rng.randint(2))) for _ in range(b)]
               for _ in range(n)]

    def reader():
        yield from batches
    return reader


def _train(mesh, causal):
    paddle.init(seed=0)
    cost = _model(causal)
    params = paddle.create_parameters(paddle.Topology(cost))
    tr = paddle.SGD(cost=cost, parameters=params,
                    update_equation=paddle.optimizer.Adam(
                        learning_rate=1e-2), mesh=mesh)
    losses = []
    tr.train(_reader(), num_passes=2,
             event_handler=lambda e: losses.append(e.cost)
             if isinstance(e, paddle.event.EndIteration) else None)
    return tr, losses


class TestAttentionLayer:
    @pytest.mark.parametrize("causal", [False, True])
    def test_sp2_matches_plain(self, causal):
        mesh = create_mesh([(SP_AXIS, 2)])
        tr_sp, losses_sp = _train(mesh, causal)
        tr_ref, losses_ref = _train(None, causal)
        np.testing.assert_allclose(losses_sp, losses_ref,
                                   rtol=1e-4, atol=1e-5)
        for k in tr_ref.parameters.raw:
            np.testing.assert_allclose(
                np.asarray(tr_sp.parameters.raw[k]),
                np.asarray(tr_ref.parameters.raw[k]),
                rtol=1e-3, atol=1e-5, err_msg=k)

    def test_ragged_masking(self):
        # padded positions must not contribute: two batches identical
        # except for values past the valid length give identical outputs
        paddle.init(seed=0)
        cost = _model(False)
        topo = paddle.Topology(cost)
        params = paddle.create_parameters(topo)
        from paddle_tpu.core.sequence import SequenceBatch
        import jax.numpy as jnp
        ids1 = np.zeros((2, T), np.int32)
        ids1[:, :4] = 7
        ids2 = ids1.copy()
        ids2[:, 4:] = 23                          # garbage past length 4
        lengths = np.array([4, 4], np.int32)
        outs = []
        for ids in (ids1, ids2):
            feed = {"ids": SequenceBatch(jnp.asarray(ids),
                                         jnp.asarray(lengths)),
                    "y": jnp.zeros((2,), jnp.int32)}
            o, _ = topo.forward(params.raw, {}, feed, mode="test",
                                output_names=["att_pool"])
            outs.append(np.asarray(o["att_pool"]))
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


class TestFlashOnMesh:
    """The flash kernel inside a sharded step (layers/attention_layers
    flash_on_mesh): each device runs it on its own batch rows and
    heads. Interpreted here on virtual devices; that the same call
    lowers for four real chips is tests/test_chip_compile.py's."""

    @pytest.mark.parametrize("axes", [[("dp", 4)], [("dp", 2), ("mp", 2)],
                                      [("mp", 4)]],
                             ids=["dp4", "dp2-mp2", "mp4"])
    def test_matches_unsharded_kernel(self, axes):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.layers.attention_layers import flash_on_mesh
        from paddle_tpu.ops.pallas_attention import flash_attention
        rng = np.random.RandomState(0)
        b, t, h, d = 4, 16, 4, 8
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
                   for _ in range(3))
        lens = jnp.asarray([16, 9, 12, 3], jnp.int32)
        want = flash_attention(q, k, v, kv_lens=lens, causal=True,
                               interpret=True)
        got = jax.jit(lambda *a: flash_on_mesh(
            *a, create_mesh(axes), causal=True, interpret=True))(
                q, k, v, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
