"""Fused flash attention in Pallas — the TPU hot-loop for attention.

Like ops/pallas_rnn.py, this is the one-hop-beyond-XLA fusion: plain
attention materializes the [b, h, Tq, Tk] score matrix in HBM (the
quadratic term that kills long sequences); this kernel streams K/V blocks
through VMEM with online softmax, computing the padding/causal mask
IN-KERNEL from per-row lengths, so primal HBM traffic is linear in
sequence length. Single-chip counterpart of
parallel/sequence_parallel.py's ring attention (the same online-softmax
update run across chips).

Semantics match parallel/sequence_parallel.attention with a
lengths+causal mask exactly (tests assert parity): padded K/V positions
are ignored, q rows at/past their length return 0. Training is fused
both directions (FlashAttention-2 style): the forward saves only the
per-row logsumexp; the backward kernels recompute each block's softmax
from it while streaming dq per q-block and dk/dv per k-block, so HBM
stays linear in T in BOTH passes (~2.8x XLA on the T=4096 train step
with the round-5 exp2 softmax — see docs/perf.md; the round-2 version
fell back to the quadratic XLA vjp). Beyond one chip, ring attention
over the `sp` mesh axis shards the same math.

Used automatically by the attention layer on TPU for tile-friendly
shapes (head_dim % 8 == 0); `interpret=True` runs on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LOG2E = 1.4426950408889634     # log2(e): fold into the dot scale so the
LN2 = 0.6931471805599453       # online softmax runs in exp2 (one fewer
                               # VPU pass per tile than exp)


def _flash_kernel(lens_ref, q_ref, k_ref, v_ref, out_ref, *refs,
                  scale, nk, block_q, block_k, causal, save_lse):
    # the logsumexp residual is only written on the training path; the
    # primal/inference call skips the [bh, Tq, 128] f32 stream entirely
    if save_lse:
        lse_ref, acc_scr, m_scr, l_scr = refs
    else:
        acc_scr, m_scr, l_scr = refs
        lse_ref = None
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # block-skip: nothing to do when this K block is entirely past the
    # row's kv_len, or (causal) entirely above the diagonal
    i = pl.program_id(0)
    q_len = lens_ref[i, 0]
    kv_len = lens_ref[i, 1]
    needed = kk * block_k < kv_len
    if causal:
        needed = needed & (kk * block_k <= j * block_q + block_q - 1)

    # an interior tile needs NO mask at all: every row is under q_len,
    # every col under kv_len, and (causal) the whole tile sits at or
    # below the diagonal — skipping the iota/compare/where VPU work there
    # is the standard flash fast path (most tiles are interior)
    interior = (j * block_q + block_q <= q_len) & \
        (kk * block_k + block_k <= kv_len)
    if causal:
        interior = interior & (kk * block_k + block_k - 1 <= j * block_q)

    def _online_update(s2, p_mask, prec, v):
        """s2 is in BASE-2 units (the dot scale carries log2(e)), so the
        softmax runs on exp2 — the multiply by log2e rides the matmul
        epilogue instead of costing a VPU pass over every [bq, bk] tile.
        m/l scratches hold base-2 running max / exp2-sum; _finish
        converts the logsumexp back to natural units for the backward.
        (A deferred any-row-changed rescale was also tried here and
        REJECTED: the per-tile scalar branch costs more than the two
        rescale passes it saves — numbers in docs/perf.md.)"""
        m_old = m_scr[:]                              # [bq, 128] (bcast)
        s_max = jnp.max(s2, axis=-1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_old, s_max)             # [bq, 128]
        alpha = jnp.exp2(m_old[:, 0:1] - m_new[:, 0:1])
        p = jnp.exp2(s2 - m_new[:, 0:1])              # [bq, bk]
        if p_mask is not None:
            # explicit zero on masked entries: with a finite NEG_INF, a
            # row masked in EVERY block would otherwise see
            # exp2(s - m) == 1 junk
            p = jnp.where(p_mask, p, 0.0)
        l_new = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            precision=prec)
        m_scr[:] = m_new
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(needed & interior)
    def _fast_block():
        q = q_ref[0]                                  # [bq, d]
        k = k_ref[0]                                  # [bk, d]
        v = v_ref[0]                                  # [bk, d]
        prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
        s2 = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec) * (scale * LOG2E)
        _online_update(s2, None, prec, v)

    @pl.when(needed & ~interior)
    def _masked_block():
        q = q_ref[0]                                  # [bq, d]
        k = k_ref[0]                                  # [bk, d]
        v = v_ref[0]                                  # [bk, d]
        # dots in the input dtype (bf16 rides the MXU single-pass), f32
        # accumulation; HIGHEST keeps f32 inputs full-precision
        # (ops/linear convention — default truncates even f32 operands)
        # but is only legal on f32 operands
        prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
        s2 = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec) * (scale * LOG2E)

        # in-kernel mask from lengths (+causal) — nothing quadratic in HBM
        rows = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = (rows < q_len) & (cols < kv_len)
        if causal:
            valid = valid & (cols <= rows)
        s2 = jnp.where(valid, s2, NEG_INF)            # [bq, bk]
        _online_update(s2, valid, prec, v)

    @pl.when(kk == nk - 1)
    def _finish():
        l = l_scr[:][:, 0:1]
        out_ref[0] = jnp.where(l > 0.0, acc_scr[:] / jnp.maximum(l, 1e-30),
                               0.0).astype(out_ref.dtype)
        if save_lse:
            # logsumexp per row in NATURAL units (the backward contract):
            # m is base-2, l is an exp2 sum -> lse = m*ln2 + ln(l)
            m = m_scr[:][:, 0:1]
            lse = jnp.where(l > 0.0,
                            m * LN2 + jnp.log(jnp.maximum(l, 1e-30)),
                            NEG_INF)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_call(q3, k3, v3, lens2, *, scale, block_q, block_k, causal,
                interpret, save_lse=True):
    """q3: [bh, Tq, d]; k3/v3: [bh, Tk, d]; lens2: [bh, 2] int32
    (q_len, kv_len per row). Returns (out, lse[bh, Tq, 128]) with
    save_lse, else just out."""
    bh, tq, d = q3.shape
    tk = k3.shape[1]
    nq = tq // block_q
    nk = tk // block_k

    kernel = functools.partial(_flash_kernel, scale=scale, nk=nk,
                               block_q=block_q, block_k=block_k,
                               causal=causal, save_lse=save_lse)
    lse_specs = [pl.BlockSpec((1, block_q, 128),
                              lambda i, j, kk: (i, j, 0))] if save_lse else []
    lse_shapes = [jax.ShapeDtypeStruct((bh, tq, 128), jnp.float32)] \
        if save_lse else []
    outs = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # lens [bh, 2], whole
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        ] + lse_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q3.dtype),
        ] + lse_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret, name="flash_fwd",
    )(lens2, q3, k3, v3)
    return outs if save_lse else (outs[0], None)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 style): recompute the block softmax
# from the saved logsumexp, stream dq per q-block and dk/dv per k-block —
# HBM stays linear in T, replacing the quadratic XLA vjp


def _recompute_p(q, k, lens_row, lse, jq, kk, *, scale, block_q, block_k,
                 causal):
    """exp(S - lse) for one (q block, k block) tile, fully masked.
    Computed as exp2((S - lse) * log2e) with log2e folded into the dot
    scale — the same VPU-pass saving as the forward; lse (natural units)
    scales by log2e on its cheap [bq, 1] column only."""
    prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    s2 = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec) * (scale * LOG2E)
    rows = jq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = (rows < lens_row[0]) & (cols < lens_row[1])
    if causal:
        valid = valid & (cols <= rows)
    p = jnp.where(valid, jnp.exp2(s2 - lse * LOG2E), 0.0)
    return p, valid, prec


def _flash_bwd_dq_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         dd_ref, dq_ref, dq_scr, *, scale, nk, block_q,
                         block_k, causal):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = kk * block_k < lens_ref[i, 1]
    if causal:
        needed = needed & (kk * block_k <= j * block_q + block_q - 1)

    @pl.when(needed)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        dd = dd_ref[0][:, 0:1]
        p, valid, prec = _recompute_p(
            q, k, (lens_ref[i, 0], lens_ref[i, 1]), lse, j, kk, scale=scale,
            block_q=block_q, block_k=block_k, causal=causal)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = p * (dp - dd) * scale
        dq_scr[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32,
                                 precision=prec)

    @pl.when(kk == nk - 1)
    def _done():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          dd_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                          nq, block_q, block_k, causal):
    i = pl.program_id(0)
    kk = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = j * block_q < lens_ref[i, 0]
    if causal:
        needed = needed & (j * block_q + block_q - 1 >= kk * block_k)

    @pl.when(needed)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        dd = dd_ref[0][:, 0:1]
        p, valid, prec = _recompute_p(
            q, k, (lens_ref[i, 0], lens_ref[i, 1]), lse, j, kk, scale=scale,
            block_q=block_q, block_k=block_k, causal=causal)
        # dV += P^T dO ; dK += dS^T Q
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
        ds = p * (dp - dd) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    @pl.when(j == nq - 1)
    def _done():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_grads(q3, k3, v3, do3, out3, lse, lens2, *, scale, block_q,
                 block_k, causal, interpret):
    bh, tq, d = q3.shape
    tk = k3.shape[1]
    nq = tq // block_q
    nk = tk // block_k
    dd = jnp.sum(do3.astype(jnp.float32) * out3.astype(jnp.float32),
                 axis=-1, keepdims=True)                      # [bh, tq, 1]
    dd = jnp.broadcast_to(dd, (bh, tq, 128))

    common_in = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                # lens
    ]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, nk=nk,
                          block_q=block_q, block_k=block_k, causal=causal),
        grid=(bh, nq, nk),
        in_specs=common_in + [
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_q, 128), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_q, 128), lambda i, j, kk: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret, name="flash_bwd_dq",
    )(lens2, q3, k3, v3, do3, lse, dd)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, nq=nq,
                          block_q=block_q, block_k=block_k, causal=causal),
        grid=(bh, nk, nq),
        in_specs=common_in + [
            pl.BlockSpec((1, block_q, d), lambda i, kk, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kk, j: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kk, j: (i, kk, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, kk, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 128), lambda i, kk, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 128), lambda i, kk, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kk, j: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kk, j: (i, kk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret, name="flash_bwd_dkv",
    )(lens2, q3, k3, v3, do3, lse, dd)
    return dq, dk, dv


def _lens_mask(q_lens, kv_lens, tq, tk, causal):
    """[b, Tq, Tk] bool mask equivalent to the in-kernel computation."""
    rows = jnp.arange(tq, dtype=jnp.int32)
    cols = jnp.arange(tk, dtype=jnp.int32)
    m = (rows[None, :, None] < q_lens[:, None, None]) & \
        (cols[None, None, :] < kv_lens[:, None, None])
    if causal:
        m = m & (cols[None, None, :] <= rows[None, :, None])
    return m


def _reference(q, k, v, mask, scale):
    """XLA attention — also the custom_vjp backward (see module docstring)."""
    prec = jax.lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32,
                        precision=prec) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        # fully-masked rows: softmax over all -inf is uniform; zero them
        any_valid = jnp.any(mask, axis=-1)[:, None, :, None]
        w = jnp.where(any_valid, w, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32,
                      precision=prec).astype(q.dtype)


def _to_heads(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_heads(x3, b, h):
    bh, t, d = x3.shape
    return x3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_lens, kv_lens, causal, scale, block_q, block_k,
           interpret):
    b, tq, h, d = q.shape
    lens2 = jnp.stack([q_lens, kv_lens], axis=1).astype(jnp.int32)  # [b, 2]
    lens2 = jnp.repeat(lens2, h, axis=0)                            # [bh, 2]
    out, _ = _flash_call(_to_heads(q), _to_heads(k), _to_heads(v), lens2,
                         scale=scale, block_q=block_q,
                         block_k=block_k, causal=causal,
                         interpret=interpret, save_lse=False)
    return _from_heads(out, b, h)


def _flash_fwd(q, k, v, q_lens, kv_lens, causal, scale, block_q, block_k,
               interpret):
    b, tq, h, d = q.shape
    lens2 = jnp.stack([q_lens, kv_lens], axis=1).astype(jnp.int32)
    lens2 = jnp.repeat(lens2, h, axis=0)
    q3, k3, v3 = _to_heads(q), _to_heads(k), _to_heads(v)
    out3, lse = _flash_call(q3, k3, v3, lens2, scale=scale, block_q=block_q,
                            block_k=block_k, causal=causal,
                            interpret=interpret)
    return _from_heads(out3, b, h), (q3, k3, v3, out3, lse, lens2, b, h)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, ct):
    """Streaming FlashAttention-2 backward: dq per q-block, dk/dv per
    k-block, block softmax recomputed from the saved logsumexp — HBM
    linear in T (replaces the quadratic XLA vjp the round-2 version ran)."""
    q3, k3, v3, out3, lse, lens2, b, h = res
    do3 = _to_heads(ct)
    dq3, dk3, dv3 = _flash_grads(
        q3, k3, v3, do3, out3, lse, lens2, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, interpret=interpret)
    return (_from_heads(dq3, b, h), _from_heads(dk3, b, h),
            _from_heads(dv3, b, h), None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    q_lens: Optional[jnp.ndarray] = None,
                    kv_lens: Optional[jnp.ndarray] = None,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """Flash attention with ragged-length + causal masking in-kernel.

    q: [b, Tq, h, d]; k, v: [b, Tk, h, d]; q_lens / kv_lens: [b] int
    valid lengths (None = full). Returns [b, Tq, h, d]; q rows at/past
    q_lens are zero. Inputs are padded to block multiples internally.
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if q_lens is None:
        q_lens = jnp.full((b,), tq, jnp.int32)
    if kv_lens is None:
        kv_lens = jnp.full((b,), tk, jnp.int32)
    # round blocks UP to a multiple of 8 (sublane tile) so the compiled
    # Mosaic path never sees ragged block shapes; the inputs are padded
    # to block multiples right below, so rounding is always safe
    block_q = min(block_q, -(-max(tq, 8) // 8) * 8)
    block_k = min(block_k, -(-max(tk, 8) // 8) * 8)
    pad_q = (-tq) % block_q
    pad_k = (-tk) % block_k
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    out = _flash(q, k, v, q_lens, kv_lens, causal, scale, block_q, block_k,
                 interpret)
    if pad_q:
        out = out[:, :tq]
    return out


def flash_supported(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    """Shape gate: MXU-friendly head dim and a sequence long enough that
    streaming K/V beats one fused XLA softmax."""
    d = q.shape[-1]
    return d % 8 == 0 and q.shape[1] >= 8 and k.shape[1] >= 8
