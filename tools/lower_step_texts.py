"""Lower the decoders' step programs of a tree and hash their texts.

    python tools/lower_step_texts.py <tree root> <out dir>

The check of a refactor that must not move a program (PR 35, PR 37): run
it on an unpacked parent (``git archive``) and on the change, and compare
the two ``hashes.json``. Nothing runs and no chip is needed: the
deployment-shape steps are lowered for a described v5e (the kernel path),
the rest on the CPU. ``Lowered.as_text()`` carries no source locations,
but a Mosaic kernel travels inside it as serialized MLIR that does (file
paths with the checkout's root, line numbers), so each kernel body is
replaced by its text without debug info before hashing. Lowered here:
``PagedDecoder._step`` of opt-1.3b at the benchmark's deployment shape, fp
and int8 pages, and its three page programs; Kimi-K2's step at its
deployment shape and at ``tiny()`` (gather and the interpreted kernel, W 1
and 2), its ``generate`` and page read; at toy size the default block
(MHA; GQA tied; capacity-routed experts drop-free and at a factor): the
paged step (gather and kernel, fp and int8, W 1 and 3, sampled),
``DraftDecoder._step``, ``generate`` greedy and sampled, both beams.
Where the tree's ``PagedDecoder`` has prefill lanes (PR 38), the LANE
program of each deployment-shape and tiny step is lowered beside the plain
one, as ``<name>_lanes``: a tree without them lowers the plain ones alone,
so the plain hashes of a parent and a change still compare. Where the tree
has them, the blocks with a state a slot (Kimi-Linear, LFM2) are lowered
the same way, at their cells' deployment shapes and at ``tiny()``; a tree
whose program cannot serve one skips it.
"""
import base64
import hashlib
import json
import os
import re
import sys

root, out = os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2])
os.chdir(root)
sys.path.insert(0, root)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.makedirs(out, exist_ok=True)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.artifacts import cache  # noqa: E402
from paddle_tpu.core import registry  # noqa: E402
from paddle_tpu.models.decode import DraftDecoder  # noqa: E402

assert os.path.abspath(paddle.__file__).startswith(root), paddle.__file__
hashes = {}


def _no_locations(match):
    """A Mosaic kernel travels as serialized MLIR that carries source
    locations (file paths with the checkout's root, line numbers): put its
    text without debug info in its place."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir
    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        asm = ir.Module.parse(base64.b64decode(match.group(1))) \
            .operation.get_asm(enable_debug_info=False)
    return "\\22body\\22: \\22" + asm.replace("\n", "\\n") + "\\22"


def record(name, text):
    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                  _no_locations, text)
    with open(os.path.join(out, name + ".txt"), "w") as f:
        f.write(text)
    hashes[name] = hashlib.sha256(text.encode()).hexdigest()
    print(name, len(text), hashes[name], flush=True)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


from jax.experimental import topologies  # noqa: E402
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one_chip = SingleDeviceSharding(topo.devices[0])


def on_chip(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)


def lower_chip(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(
        *jax.tree_util.tree_map(on_chip, args)).as_text()


def step_args(paged, S, P, W=1):
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    sw = sds((S, W), jnp.int32)
    return (paged.dense.p, k_pool, v_pool, sw, sw, sds((S, P), jnp.int32),
            sds((S, W), jnp.bool_), sds((2,), jnp.uint32))


def lane_args(paged, S, P, W=1):
    """``step_args`` with the lane program's ``lanes`` before the key, or
    None for a tree (or a cache kind) without prefill lanes."""
    lanes, width = getattr(paged, "lanes", (0, 0))
    if not lanes:
        return None
    *args, key = step_args(paged, S, P, W)
    return (*args, sds((lanes, 3 + width), jnp.int32), key)


def lm(**cfg):
    registry.reset_name_counters()
    paddle.init(use_tpu=False, seed=0)
    spec = models.transformer_lm(**cfg)
    return spec


real_backend = jax.default_backend

with cache.disabled():
    # ---------------- (a), (b): opt-1.3b at the deployment's shape, kernel
    spec = lm(vocab_size=50272, d_model=2048, n_heads=32, n_layers=24,
              d_ff=8192, max_len=2048, tie_embeddings=True)
    shapes = jax.eval_shape(paddle.Topology(spec.cost).init_params,
                            jax.random.PRNGKey(0))
    params = {k: np.zeros(v.shape, jnp.bfloat16) for k, v in shapes.items()}
    opt = models.TransformerDecoder(params, n_layers=24, n_heads=32)
    jax.default_backend = lambda: "tpu"
    for kvq in (None, "int8"):
        paged = opt.paged(num_slots=32, page_size=16, num_pages=896,
                          max_pages_per_slot=128, warm_start=False,
                          kv_quant=kvq)
        assert paged.use_kernel and not paged.kernel_interpret
        record(f"opt13b_step_{kvq or 'fp'}",
               lower_chip(paged._step_impl, *step_args(paged, 32, 128),
                          donate=(1, 2)))
        if lane_args(paged, 32, 128):
            record(f"opt13b_step_{kvq or 'fp'}_lanes",
                   lower_chip(paged._step_impl_lanes,
                              *lane_args(paged, 32, 128), donate=(1, 2)))
        if kvq is None:
            k_pool, v_pool = jax.eval_shape(paged.init_pools)
            page = sds((), jnp.int32)
            k_page, v_page = jax.eval_shape(paged._read_page_impl, k_pool,
                                            v_pool, page)
            record("opt13b_copy", lower_chip(
                paged._copy_page_impl, k_pool, v_pool, page, page,
                donate=(0, 1)))
            record("opt13b_read", lower_chip(
                paged._read_page_impl, k_pool, v_pool, page))
            record("opt13b_write", lower_chip(
                paged._write_page_impl, k_pool, v_pool, k_page, v_page,
                page, donate=(0, 1)))
    del opt, params

    # ---------------- (c): kimi-k2 at the deployment's shape, kernel
    from benchmarks.lib import manifest
    cell = manifest.cell(manifest.load_manifest(), "kimik2_agent_2k")
    cfg = dict(cell["config"])
    L = int(cfg["num_hidden_layers"])
    kp = {cell["model"].program_name(k): jax.ShapeDtypeStruct(v, jnp.bfloat16)
          for k, v in cell["reference"].leaf_shapes(cfg).items()}
    kimi = models.TransformerDecoder(
        {}, n_layers=L, n_heads=64, name=cell["model"].NAME,
        block=cell["model"].block_of(cfg, 4096))
    kimi.p = kp
    paged = kimi.paged(num_slots=64, page_size=32, num_pages=8192,
                       max_pages_per_slot=128, warm_start=False)
    assert paged.use_kernel and not paged.kernel_interpret
    record("kimik2_step_deploy",
           lower_chip(paged._step_impl, *step_args(paged, 64, 128),
                      donate=(1, 2)))
    if lane_args(paged, 64, 128):
        record("kimik2_step_deploy_lanes",
               lower_chip(paged._step_impl_lanes,
                          *lane_args(paged, 64, 128), donate=(1, 2)))
    jax.default_backend = real_backend

    # ---------------- (c): kimi-k2 tiny, on the CPU (gather, and the
    # kernel in interpret mode), W = 1 and 2, and its generate program
    MODEL, REF = cell["model"], cell["reference"]
    tcfg = MODEL.tiny()
    named = MODEL.make_weights(REF, 7, tcfg, jnp.float32)
    tiny = models.TransformerDecoder(
        named, n_layers=tcfg["num_hidden_layers"],
        n_heads=tcfg["num_attention_heads"], name=MODEL.NAME,
        block=MODEL.block_of(tcfg, 64))
    for att in ("gather", "kernel"):
        for W in (1, 2):
            paged = tiny.paged(num_slots=4, page_size=4, num_pages=80,
                               max_pages_per_slot=16, warm_start=False,
                               attention=att, window=W)
            record(f"kimik2_tiny_step_{att}_W{W}", paged._step.lower(
                *step_args(paged, 4, 16, W)).as_text())
            if lane_args(paged, 4, 16, W):
                record(f"kimik2_tiny_step_{att}_W{W}_lanes",
                       paged._lane_step.lower(
                           *lane_args(paged, 4, 16, W)).as_text())
    record("kimik2_tiny_generate", tiny._build(5, 12, None).lower(
        tiny.p, sds((2, 5), jnp.int32), sds((2,), jnp.uint32)).as_text())
    paged = tiny.paged(num_slots=4, page_size=4, num_pages=80,
                       max_pages_per_slot=16, warm_start=False)
    k_pool, v_pool = jax.eval_shape(paged.init_pools)
    page = sds((), jnp.int32)
    record("kimik2_tiny_read", paged._read.lower(k_pool, v_pool,
                                                 page).as_text())

    # ---------------- (c'): the blocks with a state a slot, where the tree
    # has them (Kimi-Linear since PR 39, LFM2 since PR 41): the step at the
    # cell's deployment shape, kernel path, and at ``tiny()`` on the CPU
    def state_family(tag, workload, S, P):
        try:
            cell = manifest.cell(manifest.load_manifest(), workload)
        except (SystemExit, ImportError, FileNotFoundError) as e:
            print("skip", tag, type(e).__name__, e)
            return
        cfg, M, R = dict(cell["config"]), cell["model"], cell["reference"]
        dep = cfg["deployment"]
        L = int(cfg["num_hidden_layers"])
        kp = {M.program_name(k, cfg): jax.ShapeDtypeStruct(v, jnp.bfloat16)
              for k, v in R.leaf_shapes(cfg).items()}
        dec = models.TransformerDecoder(
            {}, n_layers=L, n_heads=int(cfg["num_attention_heads"]),
            name=M.NAME, block=M.block_of(cfg, int(dep["max_seq_len"])))
        dec.p = kp
        jax.default_backend = lambda: "tpu"
        paged = dec.paged(num_slots=S, page_size=int(dep["page_size"]),
                          num_pages=int(dep["num_pages"]),
                          max_pages_per_slot=P, warm_start=False,
                          state_snapshots=int(dep["state_snapshots"]))
        assert paged.use_kernel and not paged.kernel_interpret
        record(f"{tag}_step_deploy",
               lower_chip(paged._step_impl, *step_args(paged, S, P),
                          donate=(1, 2)))
        record(f"{tag}_step_deploy_lanes",
               lower_chip(paged._step_impl_lanes, *lane_args(paged, S, P),
                          donate=(1, 2)))
        jax.default_backend = real_backend
        tcfg = M.tiny()
        named = M.make_weights(R, 7, tcfg, jnp.float32)
        tiny = models.TransformerDecoder(
            named, n_layers=tcfg["num_hidden_layers"],
            n_heads=tcfg["num_attention_heads"], name=M.NAME,
            block=M.block_of(tcfg, 64))
        for att in ("gather", "kernel"):
            paged = tiny.paged(num_slots=4, page_size=4, num_pages=80,
                               max_pages_per_slot=16, warm_start=False,
                               attention=att, state_snapshots=6)
            record(f"{tag}_tiny_step_{att}", paged._step.lower(
                *step_args(paged, 4, 16)).as_text())
            record(f"{tag}_tiny_step_{att}_lanes", paged._lane_step.lower(
                *lane_args(paged, 4, 16)).as_text())
        record(f"{tag}_tiny_generate", tiny._build(5, 12, None).lower(
            tiny.p, sds((2, 5), jnp.int32),
            sds((2,), jnp.uint32)).as_text())

    state_family("kimilinear", "kimilinear_agent_2k", 128, 128)
    state_family("lfm2", "lfm2_doc_8k", 32, 288)

    # ---------------- (d): toy default block: paged (fp/int8, gather and
    # interpret kernel, W 1 and 3), draft, generate, beams, MoE
    def toy(**kw):
        cfg = dict(vocab_size=40, d_model=16, n_heads=2, n_layers=2,
                   d_ff=32, max_len=32)
        cfg.update(kw)
        spec = lm(**cfg)
        topo_ = paddle.Topology(spec.cost, extra_outputs=[spec.output])
        return topo_.init_params(jax.random.PRNGKey(0)), cfg

    for tag, kw, dkw in (
            ("mha", {}, {}),
            ("gqa_tied", dict(n_heads=4, n_kv_heads=2,
                              tie_embeddings=True), {}),
            ("moe", dict(moe_experts=4), dict(moe_k=2)),
            ("moe_cf", dict(moe_experts=4),
             dict(moe_k=2, moe_capacity_factor=1.5))):
        try:
            params, cfg = toy(**kw)
        except TypeError as e:
            print("skip", tag, e)
            continue
        dec = models.TransformerDecoder(params, n_layers=cfg["n_layers"],
                                        n_heads=cfg["n_heads"], **dkw)
        for att in ("gather", "kernel"):
            for kvq in (None, "int8"):
                for W in (1, 3):
                    paged = dec.paged(num_slots=3, page_size=4,
                                      num_pages=20, max_pages_per_slot=8,
                                      warm_start=False, attention=att,
                                      window=W, kv_quant=kvq)
                    record(f"toy_{tag}_step_{att}_{kvq or 'fp'}_W{W}",
                           paged._step.lower(
                               *step_args(paged, 3, 8, W)).as_text())
                    if lane_args(paged, 3, 8, W):
                        record(
                            f"toy_{tag}_step_{att}_{kvq or 'fp'}_W{W}_lanes",
                            paged._lane_step.lower(
                                *lane_args(paged, 3, 8, W)).as_text())
        paged = dec.paged(num_slots=3, page_size=4, num_pages=20,
                          max_pages_per_slot=8, warm_start=False,
                          temperature=0.7)
        record(f"toy_{tag}_step_sampled",
               paged._step.lower(*step_args(paged, 3, 8)).as_text())
        draft = DraftDecoder(dec, num_slots=3, max_seq_len=16, window=3,
                             warm_start=False)
        kc, vc = jax.eval_shape(draft.init_caches)
        sw = sds((3, 3), jnp.int32)
        record(f"toy_{tag}_draft_step", draft._step.lower(
            dec.p, kc, vc, sw, sw, sds((3, 3), jnp.bool_)).as_text())
        prompt = sds((2, 5), jnp.int32)
        for temp in (None, 0.8):
            record(f"toy_{tag}_generate_{temp}", dec._build(
                5, 12, temp).lower(dec.p, prompt,
                                   sds((2,), jnp.uint32)).as_text())
        record(f"toy_{tag}_beam", dec._build_beam(5, 12, 3, 0).lower(
            dec.p, prompt).as_text())
        record(f"toy_{tag}_beam_gnmt", dec._build_beam_gnmt(
            5, 12, 3, 0, 0.6).lower(dec.p, prompt).as_text())

with open(os.path.join(out, "hashes.json"), "w") as f:
    json.dump(hashes, f, indent=1, sort_keys=True)
