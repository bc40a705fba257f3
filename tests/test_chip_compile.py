"""The main path's kernels, compiled for the real chip without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for
a device that is described, not attached (topology ``v5e:2x2``). Nothing
runs, so these say nothing about results or times — they say that what
interpret mode accepts also LOWERS: block shapes that tile, kernels
inside VMEM/SMEM, shape casts Mosaic implements. Every shape
``paged_kernel_supported`` accepts on the serving path is held to that
here; a shape it rejects must say so and compile through the gather
path instead.

The topology is described inside a fixture (only one process at a time
may load the TPU's library, and every xdist worker imports this file),
and everything built from it is built in a fixture or a test. All the
cases stay in this one file. The persistent compile cache is off around
them: an entry written for a described device cannot be read back.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def cache_off():
    """No persistent compile cache around a compile for a described
    device: such an entry is written but cannot be read back."""
    from paddle_tpu.artifacts import cache
    with cache.disabled():
        yield


@pytest.fixture
def chip_executable(one_chip, cache_off):
    """compile(fn, *shape_structs, donate=()) -> ``fn`` compiled for one
    described v5e chip. Raises what the chip's compiler raises."""
    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def compile_(fn, *args, donate=()):
        return jax.jit(fn, donate_argnums=donate).lower(
            *jax.tree_util.tree_map(on_chip, args)).compile()

    return compile_


@pytest.fixture
def chip_compile(chip_executable):
    """compile(fn, *shape_structs) -> the HLO text of that executable."""
    return lambda fn, *args: chip_executable(fn, *args).as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------ trainer
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_t1024(chip_compile, grad):
    """The LM train step's attention at the benchmark's width."""
    from paddle_tpu.ops.pallas_attention import flash_attention
    x = _sds((8, 1024, 8, 64), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    assert "tpu_custom_call" in chip_compile(fn, x, x, x)


def test_flash_on_a_four_chip_mesh(topo, cache_off):
    """Data-parallel training: the compiler refuses to partition a
    Mosaic kernel by itself, so the attention layer hands each device
    its own batch rows (flash_on_mesh). Forward and gradient, batch
    split over the four described chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.layers.attention_layers import flash_on_mesh
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    x = jax.ShapeDtypeStruct((8, 1024, 8, 64), jnp.bfloat16, sharding=rows)
    lens = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rows)

    def loss(q, k, v, lens):
        out = flash_on_mesh(q, k, v, lens, mesh, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, lens).compile().as_text()
    assert "tpu_custom_call" in hlo and "all-gather" not in hlo


@pytest.fixture
def bf16_compute():
    from paddle_tpu.config import global_config
    prev = global_config().compute_dtype
    global_config().compute_dtype = "bfloat16"
    yield
    global_config().compute_dtype = prev


@pytest.mark.parametrize("cell,grad", [("lstm", False), ("lstm", True),
                                       ("gru", False)],
                         ids=["lstm-fwd", "lstm-grad", "gru-fwd"])
def test_fused_rnn_h1280(chip_compile, bf16_compute, cell, grad):
    """The fused recurrent kernels at the lstm_bs128_h1280 row's shape."""
    from paddle_tpu.ops import pallas_rnn
    b, T, h = 128, 32, 1280
    gates = 4 if cell == "lstm" else 3
    seq = pallas_rnn.lstm_sequence if cell == "lstm" else \
        pallas_rnn.gru_sequence
    extra = (None,) if cell == "lstm" else ()      # lstm: no peephole

    def fwd(x, lens, w, bias):
        return seq(x, lens, w, bias, *extra)[0]

    def loss(x, lens, w, bias):
        return jnp.sum(fwd(x, lens, w, bias) ** 2)

    fn = jax.grad(loss, argnums=(0, 2, 3)) if grad else fwd
    hlo = chip_compile(fn, _sds((b, T, gates * h), jnp.float32),
                       _sds((b,), jnp.int32),
                       _sds((h, gates * h), jnp.float32),
                       _sds((gates * h,), jnp.float32))
    assert "tpu_custom_call" in hlo


# ------------------------------------------------------------- serving
def _paged_structs(S, W, h, g, dh, ps, P, quant, dtype):
    n_pages = S * P + 1
    q = _sds((S, W, h, dh), dtype)
    # the pools' stored layout: the kv heads side by side on the lanes
    pages = _sds((n_pages, ps, g * dh), jnp.int8 if quant else dtype)
    scales = _sds((n_pages, ps, g), jnp.float32) if quant else None
    return (q, pages, scales, _sds((S, P), jnp.int32),
            _sds((S, W), jnp.int32))


def _paged_fn(use_kernel, quant):
    from paddle_tpu.ops.pallas_decode import paged_window_attention

    def float_fn(q, k, v, tables, lens):
        return paged_window_attention(q, k, v, tables, lens,
                                      use_kernel=use_kernel)

    def int8_fn(q, k, v, tables, lens, ks, vs):
        return paged_window_attention(q, k, v, tables, lens,
                                      use_kernel=use_kernel,
                                      k_scales=ks, v_scales=vs)

    return int8_fn if quant else float_fn


@pytest.mark.parametrize("W", [1, 3], ids=["W1", "W3"])
@pytest.mark.parametrize("g", [8, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_kernel_lowers(chip_compile, quant, g, W):
    """What the gate accepts lowers: h == g and grouped-query, the
    one-token step and the speculative verify window, float and int8
    pools, at the benchmark decoder's widths."""
    from paddle_tpu.ops.pallas_decode import paged_kernel_supported
    q, pages, scales, tables, lens = _paged_structs(
        8, W, 8, g, 64, 16, 34, quant, jnp.bfloat16)
    assert paged_kernel_supported(q, pages, scales, pages_per_slot=34)
    args = (q, pages, pages, tables, lens) + \
        ((scales, scales) if quant else ())
    assert "tpu_custom_call" in chip_compile(_paged_fn(True, quant), *args)


@pytest.mark.parametrize("S,W,h,g,dh,P,quant", [
    (32, 1, 32, 32, 64, 128, False),
    (32, 1, 32, 8, 128, 128, False),
    (32, 1, 32, 32, 64, 128, True),
    (32, 4, 32, 32, 64, 128, False),
], ids=["opt13b-bf16", "gqa-dh128", "opt13b-int8", "opt13b-W4"])
def test_paged_kernel_lowers_at_the_benchmark_shape(chip_compile, S, W, h,
                                                    g, dh, P, quant):
    """The benchmark's own call (32 slots, a table of 128 pages of 16,
    32 heads of 64, bf16, the pool with its layer axis) and its
    neighbours: grouped-query heads of 128, the int8 layout, a verify
    window. One Mosaic call, and no copy of a pool beside it."""
    from paddle_tpu.ops.pallas_decode import (paged_kernel_supported,
                                              paged_window_attention)
    q, pages, scales, tables, lens = _paged_structs(
        S, W, h, g, dh, 16, P, quant, jnp.bfloat16)
    assert paged_kernel_supported(q, pages, scales, pages_per_slot=P)
    pool = _sds((2,) + pages.shape, pages.dtype)
    sc = _sds((2,) + scales.shape, scales.dtype) if quant else None

    def fn(q, k, v, tables, lens, *s):
        kw = dict(k_scales=s[0], v_scales=s[1]) if s else {}
        return paged_window_attention(q, k, v, tables, lens, layer=1,
                                      use_kernel=True, **kw)

    hlo = chip_compile(fn, q, pool, pool, tables, lens,
                       *((sc, sc) if quant else ()))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    strays = [line.strip()[:160]
              for op, line in _pool_sized_ops(hlo, pool.shape)
              if op != "parameter"]
    assert not strays, strays


def test_gate_rejects_rows_that_are_not_whole_lane_tiles(chip_compile):
    """One kv head of 64 (MQA) is half a lane tile: the chip's compiler
    does not slice such a pool in HBM ("Slice shape along dimension 2
    must be aligned to tiling (128), but is 64"), so a page cannot be
    copied by itself; the gate answers False and the gather path serves
    the shape."""
    from paddle_tpu.ops.pallas_decode import paged_kernel_supported
    q, pages, _, tables, lens = _paged_structs(
        8, 1, 8, 1, 64, 16, 34, False, jnp.bfloat16)
    assert not paged_kernel_supported(q, pages, pages_per_slot=34)
    with pytest.raises(Exception, match="aligned to tiling"):
        chip_compile(_paged_fn(True, False), q, pages, pages, tables, lens)
    hlo = chip_compile(_paged_fn(False, False), q, pages, pages, tables,
                       lens)
    assert "tpu_custom_call" not in hlo


def test_gate_rejects_what_vmem_cannot_hold(chip_compile):
    """256-row f32 pages of 32 x 128 heads: the chip's compiler refuses
    the kernel ("Ran out of memory in memory space vmem"), so the gate
    answers False and the gather path serves the shape."""
    from paddle_tpu.ops.pallas_decode import paged_kernel_supported
    q, pages, _, tables, lens = _paged_structs(
        4, 1, 32, 32, 128, 256, 8, False, jnp.float32)
    assert not paged_kernel_supported(q, pages, pages_per_slot=8)
    hlo = chip_compile(_paged_fn(False, False), q, pages, pages, tables,
                       lens)
    assert "tpu_custom_call" not in hlo


def test_paged_decoder_step_at_benchmark_width(chip_compile, monkeypatch):
    """The whole PagedDecoder step of the d512/L6/h8/vocab-32000 bf16
    decoder (8 slots, page 16, 34 pages per slot — chip_smoke.py's
    engine) on the path ``attention="auto"`` picks on the chip."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core import registry

    registry.reset_name_counters()
    paddle.init(use_tpu=False, seed=0)
    spec = models.transformer_lm(vocab_size=32000, d_model=512, n_heads=8,
                                 n_layers=6, d_ff=2048, max_len=1024,
                                 tie_embeddings=True)
    shapes = jax.eval_shape(paddle.Topology(spec.cost).init_params,
                            jax.random.PRNGKey(0))
    params = {k: np.zeros(v.shape, jnp.bfloat16) for k, v in shapes.items()}
    dec = models.TransformerDecoder(params, n_layers=6, n_heads=8)
    # the decoder asks JAX for its backend and here sees the CPU: steer
    # it, in the test, onto the branch it takes on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged = dec.paged(num_slots=8, page_size=16, num_pages=8 * 34 + 1,
                      max_pages_per_slot=34, warm_start=False)
    assert paged.use_kernel and not paged.kernel_interpret
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    sw = _sds((8, 1), jnp.int32)
    hlo = chip_compile(paged._step_impl, paged.dense.p, k_pool, v_pool,
                       sw, sw, _sds((8, 34), jnp.int32),
                       _sds((8, 1), jnp.bool_), _sds((2,), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.fixture(scope="module")
def opt13b_decoder():
    """OPT-1.3B's served decoder (24 layers, 32 heads of 64, vocab 50272,
    bf16) over zero weights: only its shapes are compiled."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.core import registry

    registry.reset_name_counters()
    paddle.init(use_tpu=False, seed=0)
    spec = models.transformer_lm(vocab_size=50272, d_model=2048, n_heads=32,
                                 n_layers=24, d_ff=8192, max_len=2048,
                                 tie_embeddings=True)
    shapes = jax.eval_shape(paddle.Topology(spec.cost).init_params,
                            jax.random.PRNGKey(0))
    params = {k: np.zeros(v.shape, jnp.bfloat16) for k, v in shapes.items()}
    return models.TransformerDecoder(params, n_layers=24, n_heads=32)


def _pool_sized_ops(hlo, pool_shape):
    """[(opcode, line)] of every instruction whose result is a whole
    pool or one layer of it."""
    import re
    n_layers, rest = pool_shape[0], ",".join(map(str, pool_shape[1:]))
    sized = re.compile(
        r"= \w+\[(?:(?:1|%d),)?%s\]\S* ([\w\-]+)\(" % (n_layers, rest))
    return [(m.group(1), line) for line in hlo.splitlines()
            for m in [sized.search(line)] if m]


def _lane_args(paged, plain_args):
    """The lane program's arguments: the plain program's with the
    ``lanes`` [Sp, 3 + C] before the key."""
    *args, key = plain_args
    lanes, width = paged.lanes
    return (*args, _sds((lanes, 3 + width), jnp.int32), key)


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["bf16", "int8"])
def test_serving_lane_step_updates_the_pools_in_place(
        opt13b_decoder, chip_executable, monkeypatch, kv_quant):
    """The LANE program of the benchmark's serving step (32 slots beside
    one prefill lane of 64 tokens, the cache kind's own statement at
    OPT-1.3B's widths): both kernels of a layer lower, the slot group's
    [32, 1] queries and the lane's [1, 64]; the pools are still written
    in place by ONE scatter a pool and layer over the rows of both
    groups, with no copy or slice of a pool and temporaries far under
    the pools' bytes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged = opt13b_decoder.paged(num_slots=32, page_size=16, num_pages=896,
                                 max_pages_per_slot=128, warm_start=False,
                                 kv_quant=kv_quant)
    assert paged.use_kernel and not paged.kernel_interpret
    assert paged.lanes == (1, 64)
    assert paged.cache.kernel_supported((1, 64))
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    sw = _sds((32, 1), jnp.int32)
    args = (paged.dense.p, k_pool, v_pool, sw, sw, _sds((32, 128), jnp.int32),
            _sds((32, 1), jnp.bool_), _sds((2,), jnp.uint32))
    compiled = chip_executable(paged._step_impl_lanes,
                               *_lane_args(paged, args), donate=(1, 2))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * 24
    values = k_pool["q"] if kv_quant else k_pool
    in_place = ("parameter", "get-tuple-element", "tuple", "bitcast",
                "scatter")
    strays = [line.strip()[:160]
              for op, line in _pool_sized_ops(hlo, values.shape)
              if op not in in_place
              and not (op == "fusion" and "kv_write/scatter" in line)]
    assert not strays, strays
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == paged.pool_bytes()
    if kv_quant is None:
        assert mem.temp_size_in_bytes < paged.pool_bytes() // 8, mem
    else:
        # the int8 step's own temporaries are 565 MB with or without
        # lanes (the scales' pools are not yet scattered in place): the
        # lanes add their rows' worth, not a pool's
        plain = chip_executable(paged._step_impl, *args,
                                donate=(1, 2)).memory_analysis()
        assert mem.temp_size_in_bytes < plain.temp_size_in_bytes \
            + paged.pool_bytes() // 16, (mem, plain)
    # the head reads 32 + 1 rows, never the lane's 64
    assert "[96,50272]" not in hlo and "[1,96,50272]" not in hlo


@pytest.mark.parametrize("num_pages", [896, 1536],
                         ids=["pages896", "pages1536"])
def test_serving_step_updates_the_pools_in_place(
        opt13b_decoder, chip_executable, monkeypatch, num_pages):
    """The benchmark's serving step (32 slots, page 16, bf16, the pools
    donated as the engine donates them): the pools stay in the layout
    the kernel's blocks read and are written in place, so the compiled
    step holds no copy and no slice of a pool or of one layer of it, and
    its temporaries are far under the pools' bytes (they were 3 x the
    pools, and 1536 pages did not compile, while the pool was
    [L, N, page, g, dh])."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    paged = opt13b_decoder.paged(num_slots=32, page_size=16,
                                 num_pages=num_pages,
                                 max_pages_per_slot=128, warm_start=False)
    assert paged.use_kernel and not paged.kernel_interpret
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    sw = _sds((32, 1), jnp.int32)
    args = (paged.dense.p, k_pool, v_pool, sw, sw, _sds((32, 128), jnp.int32),
            _sds((32, 1), jnp.bool_), _sds((2,), jnp.uint32))
    compiled = chip_executable(paged._step_impl, *args, donate=(1, 2))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 24
    # what may have a pool's shape: the pools themselves and the 48
    # in-place row scatters of the kv_write scope
    in_place = ("parameter", "get-tuple-element", "tuple", "bitcast",
                "scatter")
    strays = [line.strip()[:160]
              for op, line in _pool_sized_ops(hlo, k_pool.shape)
              if op not in in_place
              and not (op == "fusion" and "kv_write/scatter" in line)]
    assert not strays, strays
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == paged.pool_bytes()
    assert mem.temp_size_in_bytes < paged.pool_bytes() // 8, mem
    if num_pages != 896:
        return
    # the engine's three small page programs (copy-on-write, spill,
    # restore) move one page and keep no copy of a pool either
    page = _sds((), jnp.int32)
    k_page, v_page = jax.eval_shape(paged._read_page_impl, k_pool, v_pool,
                                    page)
    for fn, donate, xs in (
            (paged._copy_page_impl, (0, 1), (k_pool, v_pool, page, page)),
            (paged._read_page_impl, (), (k_pool, v_pool, page)),
            (paged._write_page_impl, (0, 1),
             (k_pool, v_pool, k_page, v_page, page))):
        mem = chip_executable(fn, *xs, donate=donate).memory_analysis()
        assert mem.temp_size_in_bytes < k_page.size * 2 * 4, (fn, mem)
        assert mem.alias_size_in_bytes == (
            paged.pool_bytes() if donate else 0), (fn, mem)


# ------------------------------------------------------- latent (MLA) step
def _kimi_k2_decoder(n_layers=3):
    """Kimi-K2's served decoder at the published widths over zero
    weights (only shapes are compiled): 1 dense + ``n_layers - 1`` expert
    layers holding 12 of 384 experts, an eighth of the vocabulary, bf16."""
    from benchmarks.lib import manifest
    from paddle_tpu import models
    cell = manifest.cell(manifest.load_manifest(), "kimik2_agent_2k")
    cfg = dict(cell["config"], num_hidden_layers=n_layers)
    shapes = cell["reference"].leaf_shapes(cfg)
    params = {cell["model"].program_name(k):
              jax.ShapeDtypeStruct(v, jnp.bfloat16)
              for k, v in shapes.items()}
    dec = models.TransformerDecoder(
        {}, n_layers=n_layers, n_heads=64, name=cell["model"].NAME,
        block=cell["model"].block_of(cfg, 4096))
    dec.p = params
    return dec


@pytest.mark.parametrize("S,W,H,ps", [
    (8, 1, 64, 16), (8, 2, 64, 16), (8, 1, 64, 32), (8, 2, 64, 32),
    (64, 1, 64, 32), (8, 4, 64, 32), (128, 1, 32, 32), (16, 8, 32, 32),
], ids=["page16-W1", "page16-W2", "page32-W1", "page32-W2",
        "kimik2-slots", "kimik2-lanes", "kimilinear-slots",
        "kimilinear-lanes"])
def test_latent_kernel_lowers(chip_compile, S, W, H, ps):
    """What ``latent_kernel_supported`` accepts lowers: heads against one
    [c_kv 512 | k_rope 64 | 64 zero lanes] row a token, bf16, the pool
    whole in HBM and the layer an operand; at 8 slots, and at both
    deployments' groups (Kimi-K2: 64 slots of 64 heads, 8 lanes of 4
    tokens; Kimi-Linear: 128 slots of 32 heads, 16 lanes of 8)."""
    from paddle_tpu.ops import pallas_decode as pd
    P, N = 4096 // ps, 64
    assert pd.latent_kernel_supported(S, W * H, 640, 512, ps, P,
                                      jnp.bfloat16)
    assert not pd.latent_kernel_supported(S, W * H, 576, 512, ps, P,
                                          jnp.bfloat16)
    # a lane twice as wide is what the gate turns away at both widths
    assert pd.latent_kernel_supported(S, 256, 640, 512, ps, P, jnp.bfloat16)
    assert not pd.latent_kernel_supported(S, 512, 640, 512, ps, P,
                                          jnp.bfloat16)

    def fn(ql, qr, pool, tables, lens):
        return pd.paged_latent_attention(ql, qr, pool, tables, lens,
                                         layer=1, scale=0.1, use_kernel=True)

    hlo = chip_compile(
        fn, _sds((S, W, H, 512), jnp.float32),
        _sds((S, W, H, 64), jnp.float32),
        _sds((2, N, ps, 640), jnp.bfloat16), _sds((S, P), jnp.int32),
        _sds((S, W), jnp.int32))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


def test_latent_step_updates_the_pool_in_place(chip_executable,
                                               monkeypatch):
    """``kimik2_agent_2k``'s serving step (64 slots, 8,192 pages of 32,
    bf16, the latent pool donated; three of its six layers, which is
    every kind of layer): the pool stays in the layout the kernel's
    blocks read and is written in place: no copy and no slice of it or
    of one layer of it, all its bytes aliased, temporaries far under
    them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dec = _kimi_k2_decoder()
    paged = dec.paged(num_slots=64, page_size=32, num_pages=8192,
                      max_pages_per_slot=128, warm_start=False)
    assert paged.use_kernel and not paged.kernel_interpret
    pool, none = jax.eval_shape(paged.init_pools)
    assert pool.shape == (3, 8192, 32, 640) and none == {}
    sw = _sds((64, 1), jnp.int32)
    args = (dec.p, pool, none, sw, sw, _sds((64, 128), jnp.int32),
            _sds((64, 1), jnp.bool_), _sds((2,), jnp.uint32))
    compiled = chip_executable(paged._step_impl, *args, donate=(1, 2))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    in_place = ("parameter", "get-tuple-element", "tuple", "bitcast",
                "scatter")
    strays = [line.strip()[:160]
              for op, line in _pool_sized_ops(hlo, pool.shape)
              if op not in in_place
              and not (op == "fusion"
                       and "latent_kv_write/scatter" in line)]
    assert not strays, strays
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == paged.pool_bytes()
    assert mem.temp_size_in_bytes < paged.pool_bytes() // 8, mem


def test_latent_lane_step_updates_the_pool_in_place(chip_executable,
                                                    monkeypatch):
    """The LANE program of ``kimik2_agent_2k``'s step: 64 slots beside 8
    lanes of 4 tokens (what the latent kernel's gate takes at 64 heads),
    two kernel calls a layer, the pool written in place by one scatter a
    layer over the rows of both groups."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dec = _kimi_k2_decoder()
    paged = dec.paged(num_slots=64, page_size=32, num_pages=8192,
                      max_pages_per_slot=128, warm_start=False)
    assert paged.use_kernel and not paged.kernel_interpret
    assert paged.lanes == (8, 4)
    pool, none = jax.eval_shape(paged.init_pools)
    sw = _sds((64, 1), jnp.int32)
    args = (dec.p, pool, none, sw, sw, _sds((64, 128), jnp.int32),
            _sds((64, 1), jnp.bool_), _sds((2,), jnp.uint32))
    compiled = chip_executable(paged._step_impl_lanes,
                               *_lane_args(paged, args), donate=(1, 2))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * 3
    in_place = ("parameter", "get-tuple-element", "tuple", "bitcast",
                "scatter")
    strays = [line.strip()[:160]
              for op, line in _pool_sized_ops(hlo, pool.shape)
              if op not in in_place
              and not (op == "fusion"
                       and "latent_kv_write/scatter" in line)]
    assert not strays, strays
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == paged.pool_bytes()
    assert mem.temp_size_in_bytes < paged.pool_bytes() // 8, mem


# ------------------------------------------- recurrent state beside the pages
@pytest.mark.parametrize("B,C", [(128, 1), (4, 8)], ids=["slots", "lanes"])
def test_state_kernel_lowers(chip_executable, B, C):
    """``kda_state_update`` at the published widths (32 heads of
    [128, 128] float32) over a pool of 322 rows: the 128 slots' one token,
    and four lanes of 8. The pool is written in place: all its bytes
    aliased, no temporary of its size."""
    from paddle_tpu.ops import pallas_kda as kk
    L, R, H, d = 6, 322, 32, 128
    assert kk.state_kernel_supported(H, d, d)
    assert not kk.state_kernel_supported(H, 64, 64)

    def fn(pool, q, k, v, g, beta, rows, first, fed):
        return kk.kda_state_update(pool, q, k, v, g, beta, rows, first, fed,
                                   layer=3, junk_row=R - 1, use_kernel=True)

    x = _sds((B, C, H, d), jnp.float32)
    compiled = chip_executable(
        fn, _sds((L, R, H, d, d), jnp.float32), x, x, x, x,
        _sds((B, C, H), jnp.float32), _sds((B,), jnp.int32),
        _sds((B,), jnp.bool_), _sds((B,), jnp.int32), donate=(0,))
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    pool_bytes = L * R * H * d * d * 4
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100, mem


@pytest.fixture(scope="module")
def kimi_linear_paged():
    """``kimilinear_agent_2k``'s paged decoder at the published widths
    over zero weights (only shapes are compiled): its 8 layers, 128 slots,
    12,288 pages of 32, 192 snapshot rows."""
    from benchmarks.lib import manifest
    from paddle_tpu import models
    cell = manifest.cell(manifest.load_manifest(), "kimilinear_agent_2k")
    cfg, dep = cell["config"], cell["config"]["deployment"]
    params = {cell["model"].program_name(k, cfg):
              jax.ShapeDtypeStruct(v, jnp.bfloat16)
              for k, v in cell["reference"].leaf_shapes(cfg).items()}
    dec = models.TransformerDecoder(
        {}, n_layers=8, n_heads=32, name=cell["model"].NAME,
        block=cell["model"].block_of(cfg, dep["max_seq_len"]))
    dec.p = params
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        paged = dec.paged(num_slots=dep["num_slots"],
                          page_size=dep["page_size"],
                          num_pages=dep["num_pages"], max_pages_per_slot=128,
                          state_snapshots=dep["state_snapshots"],
                          warm_start=False)
    finally:
        jax.default_backend = backend
    return dec, paged


@pytest.mark.parametrize("program", ["plain", "lanes", "copy_state"])
def test_state_step_updates_both_pools_in_place(chip_executable,
                                                kimi_linear_paged, program):
    """The serving step of the cell, its lane program and the row copy of
    a snapshot: the latent page pool over the 2 MLA layers alone and the
    float32 state pool over the 6 KDA layers (5.3 GB together) are donated
    and every byte of them aliased; temporaries stay under an eighth; a
    KDA layer is two kernel calls a group of rows (its convolution with
    the tails, its state), an MLA layer one."""
    dec, paged = kimi_linear_paged
    assert paged.use_kernel and not paged.kernel_interpret
    assert paged.cache.state_kernel and paged.lanes == (16, 8)
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    assert k_pool.shape == (2, 12288, 32, 640)
    assert v_pool["S"].shape == (6, 128 + 192 + 2, 32, 128, 128)
    assert v_pool["conv"].shape == (6, 322, 9 * 32, 128)
    if program == "copy_state":
        row = _sds((), jnp.int32)
        compiled = chip_executable(paged._copy_state_impl, k_pool, v_pool,
                                   row, row, donate=(0, 1))
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == paged.pool_bytes()
        assert mem.temp_size_in_bytes < 32 * 1024 * 1024, mem
        return
    sw = _sds((128, 1), jnp.int32)
    args = (dec.p, k_pool, v_pool, sw, sw, _sds((128, 128), jnp.int32),
            _sds((128, 1), jnp.bool_), _sds((2,), jnp.uint32))
    if program == "lanes":
        compiled = chip_executable(paged._step_impl_lanes,
                                   *_lane_args(paged, args), donate=(1, 2))
    else:
        compiled = chip_executable(paged._step_impl, *args, donate=(1, 2))
    groups = 2 if program == "lanes" else 1
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == groups * (2 * 6 + 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == paged.pool_bytes()
    assert mem.temp_size_in_bytes < paged.pool_bytes() // 8, mem
    # neither pool of states is copied, sliced or re-laid out whole
    hlo = compiled.as_text()
    for shape in (v_pool["S"].shape, v_pool["conv"].shape):
        strays = [line.strip()[:160]
                  for op, line in _pool_sized_ops(hlo, shape)
                  if op not in ("parameter", "get-tuple-element", "tuple",
                                "bitcast", "custom-call")]
        assert not strays, strays


# --------------------------------- short-convolution tails beside per-head pages
@pytest.mark.parametrize("S,W", [(32, 1), (16, 16)], ids=["slots", "lanes"])
def test_paged_kernel_lowers_at_gqa_over_long_tables(chip_compile, S, W):
    """``lfm2_doc_8k``'s own calls: 32 query heads on 8 kv heads of 64
    (two kv heads x four query heads a lane chunk), a float32 query over
    bfloat16 pages (two terms), a table of 288 pages of 32, the pool with
    its layer axis; the slots' one token and 16 lanes of 16. One Mosaic
    call, and no copy of a pool beside it."""
    from paddle_tpu.ops.pallas_decode import (paged_kernel_supported,
                                              paged_window_attention)
    q = _sds((S, W, 32, 64), jnp.float32)
    pool = _sds((10, 9216, 32, 512), jnp.bfloat16)
    assert paged_kernel_supported(q, pool, None, pages_per_slot=288)

    def fn(q, k, v, tables, lens):
        return paged_window_attention(q, k, v, tables, lens, layer=7,
                                      use_kernel=True)

    hlo = chip_compile(fn, q, pool, pool, _sds((S, 288), jnp.int32),
                       _sds((S, W), jnp.int32))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    strays = [line.strip()[:160]
              for op, line in _pool_sized_ops(hlo, pool.shape)
              if op != "parameter"]
    assert not strays, strays


@pytest.mark.parametrize("taps,silu,ch", [(3, False, 2048), (4, True, 12288)],
                         ids=["lfm2", "kimi-linear"])
@pytest.mark.parametrize("B,C", [(32, 1), (16, 16)], ids=["slots", "lanes"])
def test_short_conv_kernel_lowers(chip_executable, B, C, taps, silu, ch):
    """``short_conv`` at both families' widths (LFM2: 2,048 channels, 3
    taps, no activation; Kimi-Linear: 12,288 channels, 4 taps, SiLU) over
    a pool of 98 rows: the tails are written in place, all their bytes
    aliased, no temporary of their size."""
    from paddle_tpu.ops import pallas_kda as kk
    L, R = 6, 98

    def fn(tails, x, w, rows, first, fed):
        return kk.short_conv(tails, x, w, rows, first, fed, layer=3,
                             junk_row=R - 1, use_kernel=True, silu=silu)

    compiled = chip_executable(
        fn, _sds((L, R, (taps - 1) * ch // 128, 128), jnp.float32),
        _sds((B, C, ch), jnp.float32), _sds((taps, ch), jnp.float32),
        _sds((B,), jnp.int32), _sds((B,), jnp.bool_), _sds((B,), jnp.int32),
        donate=(0,))
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    mem = compiled.memory_analysis()
    pool_bytes = L * R * (taps - 1) * ch * 4
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < max(pool_bytes // 10,
                                        4 * B * C * ch * 4), mem


@pytest.fixture(scope="module")
def lfm2_paged():
    """``lfm2_doc_8k``'s paged decoder at the published widths over zero
    weights (only shapes are compiled), its first two periods (8 of the 40
    layers: the program is the same a period, and 40 compile in 40 s):
    32 slots, 9,216 pages of 32."""
    from benchmarks.lib import manifest
    from paddle_tpu import models
    cell = manifest.cell(manifest.load_manifest(), "lfm2_doc_8k")
    cfg = dict(cell["config"], num_hidden_layers=8,
               layer_types=cell["config"]["layer_types"][:8])
    dep = cfg["deployment"]
    params = {cell["model"].program_name(k):
              jax.ShapeDtypeStruct(v, jnp.bfloat16)
              for k, v in cell["reference"].leaf_shapes(cfg).items()}
    dec = models.TransformerDecoder(
        {}, n_layers=8, n_heads=32, name=cell["model"].NAME,
        block=cell["model"].block_of(cfg, dep["max_seq_len"]))
    dec.p = params
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        paged = dec.paged(num_slots=dep["num_slots"],
                          page_size=dep["page_size"],
                          num_pages=dep["num_pages"], max_pages_per_slot=288,
                          state_snapshots=dep["state_snapshots"],
                          warm_start=False)
    finally:
        jax.default_backend = backend
    return dec, paged


@pytest.mark.parametrize("program", ["plain", "lanes", "copy_state",
                                     "copy_page"])
def test_conv_step_updates_pages_and_tails_in_place(chip_executable,
                                                    lfm2_paged, program):
    """The serving step of the cell, its lane program, the row copy of a
    snapshot and the page copy of a partial match: K and V pages over the
    attention layers alone and the float32 tails over the conv layers are
    donated and every byte of them aliased; a conv layer is one kernel
    call a group of rows, an attention layer one; no pool is copied,
    sliced or re-laid out whole."""
    from paddle_tpu.models.block import StatePerHeadCache
    dec, paged = lfm2_paged
    assert paged.use_kernel and not paged.kernel_interpret
    assert paged.cache.state_kernel
    assert paged.lanes == (StatePerHeadCache.LANE_TOKENS // 16, 16)
    pages, state = jax.eval_shape(paged.init_pools)
    assert pages["k"].shape == pages["v"].shape == (2, 9216, 32, 512)
    assert state["conv"].shape == (6, 32 + 64 + 2, 2 * 16, 128)
    if program.startswith("copy"):
        n = _sds((), jnp.int32)
        impl = paged._copy_state_impl if program == "copy_state" \
            else paged._copy_page_impl
        compiled = chip_executable(impl, pages, state, n, n, donate=(0, 1))
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == paged.pool_bytes()
        assert mem.temp_size_in_bytes < 32 * 1024 * 1024, mem
        return
    sw = _sds((32, 1), jnp.int32)
    args = (dec.p, pages, state, sw, sw, _sds((32, 288), jnp.int32),
            _sds((32, 1), jnp.bool_), _sds((2,), jnp.uint32))
    if program == "lanes":
        compiled = chip_executable(paged._step_impl_lanes,
                                   *_lane_args(paged, args), donate=(1, 2))
    else:
        compiled = chip_executable(paged._step_impl, *args, donate=(1, 2))
    groups = 2 if program == "lanes" else 1
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == groups * 8
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == paged.pool_bytes()
    assert mem.temp_size_in_bytes < paged.pool_bytes() // 8, mem
    # what may have a pool's shape: the pools themselves, the in-place
    # row scatters of the kv_write scope, and the kernel that writes
    # tails; of the tails pool also the compiler's own moves of it into
    # its fast memory space and back (this fixture's 9.6 MB it moves once;
    # the cell's 48 MB over 40 layers it leaves where they are at 128
    # lane tokens and moved 28 times at 256: PERF.md section 7, "Open
    # after PR 41"), never a re-layout
    own = ("parameter", "get-tuple-element", "tuple", "bitcast",
           "custom-call", "scatter")
    moves = ("copy-start", "copy-done", "slice-start", "slice-done")
    for shape, allowed in ((pages["k"].shape, own),
                           (state["conv"].shape, own + moves)):
        strays = [line.strip()[:160]
                  for op, line in _pool_sized_ops(hlo, shape)
                  if op not in allowed
                  and not (op == "fusion" and "kv_write/scatter" in line)]
        assert not strays, strays
