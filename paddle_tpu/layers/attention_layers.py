"""Multi-head dot-product attention layer with transparent sequence
parallelism.

The 2017 reference's attention story was additive attention built from
mixed/projection primitives (simple_attention, networks.py:1298) — kept in
paddle_tpu.networks. This layer is the modern head-split dot-product form,
and the user-facing handle for the context-parallel machinery: when the
trainer's mesh has an `sp` axis (>1), attention runs as a RING over ICI
(parallel/sequence_parallel.py ring_attention — K/V blocks rotate via
ppermute under an online softmax), otherwise as plain fused attention.
The switch is invisible to the model definition: same layer, same params,
sp is purely a mesh decision — SURVEY §2.4's sequence-parallel row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import LayerMeta, make_layer, register_layer
from paddle_tpu.core.sequence import SequenceBatch


def _split_heads(x: jnp.ndarray, h: int) -> jnp.ndarray:
    b, t, d = x.shape
    return x.reshape(b, t, h, d // h)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, t, h, dh = x.shape
    return x.reshape(b, t, h * dh)


def flash_on_mesh(q, k, v, kv_lens, mesh, *, causal: bool,
                  interpret: bool = False):
    """The flash kernel inside a sharded train step. The compiler
    cannot partition a Mosaic kernel on its own ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a
    shard_map"), and attention is independent per batch row and per
    head: each device runs the kernel on its own rows (the `dp` split
    of the feed) and, under tensor parallelism, its own heads."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.ops import pallas_attention as flash
    from paddle_tpu.parallel._compat import shard_map
    from paddle_tpu.parallel.mesh import DP_AXIS, MP_AXIS

    def axis(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    rows = axis(DP_AXIS, q.shape[0])
    heads = axis(MP_AXIS, q.shape[2])
    qkv = P(rows, None, heads, None)

    def local(q, k, v, lens):
        return flash.flash_attention(q, k, v, kv_lens=lens, causal=causal,
                                     interpret=interpret)

    return shard_map(local, mesh=mesh, in_specs=(qkv, qkv, qkv, P(rows)),
                     out_specs=qkv)(q, k, v, kv_lens)


@register_layer("dot_product_attention")
class DotProductAttentionLayer:
    @staticmethod
    def build(name, cfg, input_metas):
        q, k, v = input_metas
        assert q.seq_level >= 1 and k.seq_level >= 1 and v.seq_level >= 1, \
            "attention inputs must be sequences"
        h = cfg.get("num_heads", 1)
        kv_h = cfg.get("num_kv_heads") or h
        assert h % kv_h == 0, \
            f"num_heads={h} must be a multiple of num_kv_heads={kv_h}"
        assert q.size % h == 0 and k.size % kv_h == 0 \
            and v.size % kv_h == 0, \
            f"head counts ({h}, kv {kv_h}) must divide q/k/v sizes " \
            f"({q.size}, {k.size}, {v.size})"
        assert q.size // h == k.size // kv_h, \
            "q and k head dims must match (grouped-query attention " \
            "shares each k/v head across num_heads/num_kv_heads queries)"
        return LayerMeta(size=(v.size // kv_h) * h, seq_level=1), [], []

    @staticmethod
    def apply(ctx, name, cfg, params, inputs):
        from paddle_tpu.parallel import sequence_parallel as sp_ops
        from paddle_tpu.parallel.mesh import SP_AXIS
        qs, ks, vs = inputs
        h = cfg.get("num_heads", 1)
        kv_h = cfg.get("num_kv_heads") or h
        causal = cfg.get("causal", False)
        q = _split_heads(qs.data, h)
        k = _split_heads(ks.data, kv_h)
        v = _split_heads(vs.data, kv_h)
        if kv_h != h:
            # grouped-query attention: each k/v head serves h/kv_h query
            # heads — repeat to full width for the fused kernels (the
            # decode-time win is the kv_h-sized CACHE, models/decode.py)
            k = jnp.repeat(k, h // kv_h, axis=2)
            v = jnp.repeat(v, h // kv_h, axis=2)
        mesh = getattr(ctx, "mesh", None)
        if mesh is not None and SP_AXIS in mesh.shape and \
                mesh.shape[SP_AXIS] > 1:
            out = sp_ops.ring_attention(q, k, v, mesh, lengths=ks.lengths,
                                        causal=causal)
        else:
            # fused flash kernel on TPU when tile-friendly; XLA otherwise
            from paddle_tpu.config import global_config
            from paddle_tpu.ops import pallas_attention as flash
            if (global_config().use_flash_attention and
                    jax.default_backend() == "tpu" and
                    flash.flash_supported(q, k)):
                if mesh is None:
                    out = flash.flash_attention(
                        q, k, v, kv_lens=ks.lengths, causal=causal)
                else:
                    out = flash_on_mesh(q, k, v, ks.lengths, mesh,
                                        causal=causal)
            else:
                b, tq = q.shape[0], q.shape[1]
                tk = k.shape[1]
                kv_valid = (jnp.arange(tk)[None, :] <
                            ks.lengths[:, None])        # [b, Tk]
                mask = jnp.broadcast_to(kv_valid[:, None, :], (b, tq, tk))
                if causal:
                    tri = jnp.tril(jnp.ones((tq, tk), bool))
                    mask = mask & tri[None]
                out = sp_ops.attention(q, k, v, mask=mask)
        return qs.with_data(_merge_heads(out))


def dot_product_attention(query, key=None, value=None, num_heads: int = 1,
                          num_kv_heads=None, causal: bool = False,
                          name=None, **kw):
    """Multi-head scaled-dot-product attention over sequences.

    query/key/value: sequence layers [b, T, d] (key/value default to
    query — self-attention). Runs ring attention over the mesh `sp` axis
    when one exists; plain attention otherwise. num_kv_heads < num_heads
    is grouped-query attention (each k/v head shared by
    num_heads/num_kv_heads query heads — MQA at num_kv_heads=1)."""
    key = key if key is not None else query
    value = value if value is not None else key
    opts = {"num_kv_heads": num_kv_heads} if num_kv_heads else {}
    return make_layer("dot_product_attention", name, [query, key, value],
                      num_heads=num_heads, causal=causal, **opts)


multi_head_attention = dot_product_attention
