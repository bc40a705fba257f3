"""Fused LSTM/GRU sequence kernels in Pallas.

Reference parity: the hand-fused CUDA recurrences hl_cuda_lstm.cu /
hl_gpu_gru.cuh — the one place the reference found XLA-era fusion
insufficient and wrote kernels by hand. Same story on TPU: a lax.scan
LSTM re-reads h/c from HBM every step; this kernel keeps the recurrent
weight AND state resident in VMEM across the whole sequence (grid over
time — v5e has ~100+ MB of usable VMEM, so even h=1280's [1280,5120]
weight stays resident), and each step is one MXU matmul [b,h]x[h,4h]
plus VPU gate math with zero HBM traffic for the carry.

Training is fused end-to-end for the LSTM (hl_cuda_lstm.cu does both
directions; so do we): the forward kernel streams out the activated
gates and cell sequence as residuals, and a reverse-time backward kernel
carries dh/dc in VMEM while emitting dz — the pre-activation cotangent —
from which the weight/bias/peephole grads fall out as ONE large
MXU-friendly matmul outside the kernel (sum_t h_{t-1}^T dz_t), instead
of T tiny rank-updates.

MXU passes run in the global compute dtype (bf16 under mixed precision,
f32 otherwise) with f32 accumulation; gate math and carries are always
f32. Semantics match ops/recurrent.lstm_scan/gru_scan exactly (tests
assert forward AND gradient parity): padded steps freeze the carry and
zero the output; final state is the last VALID step's state.

Kernels are used on the TPU backend when shapes are tile-friendly
(h % 128 == 0, batch % 8 == 0) and activations are the defaults;
`interpret=True` runs them on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core.sequence import SequenceBatch

# jax renamed pltpu.TPUCompilerParams -> CompilerParams across releases;
# accept whichever this jax ships.
_CompilerParams = getattr(pltpu, "CompilerParams", None) or pltpu.TPUCompilerParams


def _sigmoid(x):
    return jax.nn.sigmoid(x)


def _mxu_dtype():
    from paddle_tpu.ops.linear import compute_dtype
    cd = compute_dtype()
    return jnp.bfloat16 if cd == jnp.bfloat16 else jnp.float32


# ---------------------------------------------------------------------------
# LSTM — forward kernel


def _lstm_kernel(save_res, lens_ref, x4_ref, w_ref, b_ref, peep_ref,
                 *refs):
    # residual streams (c sequence + activated gates) exist only on the
    # training path; the primal/inference call skips them so its HBM
    # write traffic stays one h-stream wide
    if save_res:
        (out_ref, cseq_ref, gates_ref, hT_ref, cT_ref,
         h_scr, c_scr) = refs
    else:
        out_ref, hT_ref, cT_ref, h_scr, c_scr = refs
        cseq_ref = gates_ref = None
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    x4 = x4_ref[0].astype(jnp.float32)                # [b, 4h]
    h = h_scr[:]
    c = c_scr[:]
    hdim = h.shape[-1]

    z = x4 + jnp.dot(h.astype(w_ref.dtype), w_ref[:],
                     preferred_element_type=jnp.float32) \
        + b_ref[0]
    zi = z[:, :hdim]
    zf = z[:, hdim:2 * hdim]
    zc = z[:, 2 * hdim:3 * hdim]
    zo = z[:, 3 * hdim:]
    pi = peep_ref[0:1, :]
    pf = peep_ref[1:2, :]
    po = peep_ref[2:3, :]
    i_g = _sigmoid(zi + pi * c)
    f_g = _sigmoid(zf + pf * c)
    cand = jnp.tanh(zc)
    c_new = f_g * c + i_g * cand
    o_g = _sigmoid(zo + po * c_new)
    h_new = o_g * jnp.tanh(c_new)

    valid = (lens_ref[:] > t)                         # [b, 1] bool
    h_keep = jnp.where(valid, h_new, h)
    c_keep = jnp.where(valid, c_new, c)
    h_scr[:] = h_keep
    c_scr[:] = c_keep
    out_ref[0] = jnp.where(valid, h_new,
                           jnp.zeros_like(h_new)).astype(out_ref.dtype)
    if save_res:
        cseq_ref[0] = c_keep.astype(cseq_ref.dtype)
        gates_ref[0] = jnp.concatenate([i_g, f_g, cand, o_g],
                                       axis=-1).astype(gates_ref.dtype)
    hT_ref[:] = h_keep
    cT_ref[:] = c_keep


# ---------------------------------------------------------------------------
# LSTM — backward kernel (reverse time; dh/dc carried in VMEM)


def _lstm_bwd_kernel(T, lens_ref, w_ref, peep_ref, gates_ref, cseq_ref,
                     cprev_ref, dhseq_ref, dhT_ref, dcT_ref,
                     dz_ref, dh_scr, dc_scr):
    idx = pl.program_id(0)
    t = T - 1 - idx

    @pl.when(idx == 0)
    def _init():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]

    g4 = gates_ref[0].astype(jnp.float32)             # [b, 4h]
    hdim = dh_scr.shape[-1]
    i_g = g4[:, :hdim]
    f_g = g4[:, hdim:2 * hdim]
    cand = g4[:, 2 * hdim:3 * hdim]
    o_g = g4[:, 3 * hdim:]
    c_t = cseq_ref[0].astype(jnp.float32)
    c_prev = cprev_ref[0].astype(jnp.float32)
    c_prev = jnp.where(t > 0, c_prev, jnp.zeros_like(c_prev))
    pi = peep_ref[0:1, :]
    pf = peep_ref[1:2, :]
    po = peep_ref[2:3, :]

    valid = (lens_ref[:] > t)                         # [b, 1]
    dh_t = dh_scr[:] + jnp.where(valid, dhseq_ref[0].astype(jnp.float32),
                                 0.0)
    tc = jnp.tanh(c_t)
    do = dh_t * tc
    dzo = do * o_g * (1.0 - o_g)
    dc_t = dc_scr[:] + dh_t * o_g * (1.0 - tc * tc) + dzo * po
    di = dc_t * cand
    dzi = di * i_g * (1.0 - i_g)
    df = dc_t * c_prev
    dzf = df * f_g * (1.0 - f_g)
    dg = dc_t * i_g
    dzc = dg * (1.0 - cand * cand)
    dz = jnp.concatenate([dzi, dzf, dzc, dzo], axis=-1)
    dz = jnp.where(valid, dz, jnp.zeros_like(dz))

    # dh_{t-1} = dz @ w^T (contract the 4h dim of both)
    dh_prev = jax.lax.dot_general(
        dz.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_prev = dc_t * f_g + dzi * pi + dzf * pf

    dh_scr[:] = jnp.where(valid, dh_prev, dh_scr[:])
    dc_scr[:] = jnp.where(valid, dc_prev, dc_scr[:])
    dz_ref[0] = dz.astype(dz_ref.dtype)


# ---------------------------------------------------------------------------
# LSTM — lax reference (semantics oracle; CPU / odd-shape fallback path)


def _lstm_ref(x4, lens2d, w, bias2d, peep2d):
    """Pure-lax implementation with identical semantics — what the
    fused kernel is tested against (tests/test_pallas_rnn.py pins both
    forward and gradient parity)."""
    b, T, four_h = x4.shape
    h = four_h // 4
    lens = lens2d.reshape(b)
    xt = jnp.moveaxis(x4, 1, 0)

    def body(carry, inp):
        t, x_t = inp
        hh, cc = carry
        z = x_t + hh @ w + bias2d[0]
        zi, zf, zc, zo = (z[:, :h], z[:, h:2*h], z[:, 2*h:3*h], z[:, 3*h:])
        i_g = _sigmoid(zi + peep2d[0] * cc)
        f_g = _sigmoid(zf + peep2d[1] * cc)
        cand = jnp.tanh(zc)
        c_new = f_g * cc + i_g * cand
        o_g = _sigmoid(zo + peep2d[2] * c_new)
        h_new = o_g * jnp.tanh(c_new)
        valid = (t < lens)[:, None]
        h_keep = jnp.where(valid, h_new, hh)
        c_keep = jnp.where(valid, c_new, cc)
        return (h_keep, c_keep), jnp.where(valid, h_new, 0.0)

    init = (jnp.zeros((b, h), x4.dtype), jnp.zeros((b, h), x4.dtype))
    (hT, cT), outs = jax.lax.scan(
        body, init, (jnp.arange(T, dtype=jnp.int32), xt))
    return jnp.moveaxis(outs, 0, 1), hT, cT


# ---------------------------------------------------------------------------
# LSTM — custom-vjp wrapper: fused forward AND fused backward


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _lstm_pallas(x4, lens2d, w, bias2d, peep2d, interpret):
    out, hT, cT = _lstm_fwd_call(x4, lens2d, w, bias2d, peep2d,
                                 interpret, save_res=False)
    return out, hT, cT


def _lstm_fwd_call(x4, lens2d, w, bias2d, peep2d, interpret,
                   save_res=True):
    b, T, four_h = x4.shape
    h = four_h // 4
    mxu = _mxu_dtype()
    xt = jnp.moveaxis(x4, 1, 0).astype(mxu)
    res_out_specs = [
        pl.BlockSpec((1, b, h), lambda t: (t, 0, 0),
                     memory_space=pltpu.VMEM),             # c seq
        pl.BlockSpec((1, b, four_h), lambda t: (t, 0, 0),
                     memory_space=pltpu.VMEM),             # gates
    ] if save_res else []
    res_out_shapes = [
        jax.ShapeDtypeStruct((T, b, h), mxu),
        jax.ShapeDtypeStruct((T, b, four_h), mxu),
    ] if save_res else []
    outs = pl.pallas_call(
        functools.partial(_lstm_kernel, save_res),
        grid=(T,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),            # lens [b,1]
            pl.BlockSpec((1, b, four_h), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),            # x4 block
            pl.BlockSpec(memory_space=pltpu.VMEM),            # w [h,4h]
            pl.BlockSpec(memory_space=pltpu.VMEM),            # bias [1,4h]
            pl.BlockSpec(memory_space=pltpu.VMEM),            # peep [3,h]
        ],
        out_specs=[
            pl.BlockSpec((1, b, h), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),            # h seq
        ] + res_out_specs + [
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, b, h), mxu),     # h stream
        ] + res_out_shapes + [
            jax.ShapeDtypeStruct((b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name="lstm_fwd",
    )(lens2d, xt, w.astype(mxu), bias2d, peep2d)
    if save_res:
        out, cseq, gates, hT, cT = outs
        return jnp.moveaxis(out, 0, 1), hT, cT, cseq, gates
    out, hT, cT = outs
    return jnp.moveaxis(out, 0, 1), hT, cT


def _lstm_fwd(x4, lens2d, w, bias2d, peep2d, interpret):
    out, hT, cT, cseq, gates = _lstm_fwd_call(x4, lens2d, w, bias2d, peep2d,
                                              interpret, save_res=True)
    res = (lens2d, w, peep2d, cseq, gates,
           jnp.moveaxis(out, 1, 0), jnp.zeros((0,), x4.dtype))
    return (out, hT, cT), res


def _lstm_bwd(interpret, res, ct):
    lens2d, w, peep2d, cseq, gates, hseq_tb, x4_token = res
    x4_dtype = x4_token.dtype
    d_out, d_hT, d_cT = ct
    T, b, h = cseq.shape
    four_h = 4 * h
    mxu = _mxu_dtype()
    d_out_tb = jnp.moveaxis(d_out, 1, 0)

    rev = lambda t: (T - 1 - t, 0, 0)                  # noqa: E731
    rev_prev = lambda t: (jnp.maximum(T - 2 - t, 0), 0, 0)  # noqa: E731
    dz = pl.pallas_call(
        functools.partial(_lstm_bwd_kernel, T),
        grid=(T,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),             # lens
            pl.BlockSpec(memory_space=pltpu.VMEM),             # w
            pl.BlockSpec(memory_space=pltpu.VMEM),             # peep
            pl.BlockSpec((1, b, four_h), rev,
                         memory_space=pltpu.VMEM),             # gates
            pl.BlockSpec((1, b, h), rev, memory_space=pltpu.VMEM),   # c_t
            pl.BlockSpec((1, b, h), rev_prev,
                         memory_space=pltpu.VMEM),             # c_{t-1}
            pl.BlockSpec((1, b, h), rev, memory_space=pltpu.VMEM),   # dh_seq
            pl.BlockSpec(memory_space=pltpu.VMEM),             # dhT
            pl.BlockSpec(memory_space=pltpu.VMEM),             # dcT
        ],
        out_specs=[
            pl.BlockSpec((1, b, four_h), rev, memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, b, four_h), mxu)],
        scratch_shapes=[
            pltpu.VMEM((b, h), jnp.float32),
            pltpu.VMEM((b, h), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name="lstm_bwd",
    )(lens2d, w.astype(mxu), peep2d, gates, cseq, cseq, d_out_tb,
      d_hT.astype(jnp.float32), d_cT.astype(jnp.float32))[0]

    # Parameter grads as single large contractions (MXU work, not T tiny
    # rank-1 updates): dw = sum_t h_{t-1}^T dz_t over (t, b).
    hprev = jnp.concatenate(
        [jnp.zeros((1, b, h), hseq_tb.dtype), hseq_tb[:-1]], axis=0)
    dw = jax.lax.dot_general(
        hprev.reshape(T * b, h).astype(mxu),
        dz.reshape(T * b, four_h).astype(mxu),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # f32-ACCUMULATING reductions over the bf16 stream (dtype=f32 keeps
    # the bf16 multiply fused into the reduce; an explicit .astype would
    # materialize a full f32 copy of dz — 0.6 ms at h=1280 in traces)
    dbias = jnp.sum(dz, axis=(0, 1), dtype=jnp.float32).reshape(1, four_h)
    cprev = jnp.concatenate(
        [jnp.zeros((1, b, h), cseq.dtype), cseq[:-1]], axis=0)
    dpi = jnp.sum(dz[..., :h] * cprev, axis=(0, 1), dtype=jnp.float32)
    dpf = jnp.sum(dz[..., h:2 * h] * cprev, axis=(0, 1), dtype=jnp.float32)
    dpo = jnp.sum(dz[..., 3 * h:] * cseq, axis=(0, 1), dtype=jnp.float32)
    dpeep = jnp.stack([dpi, dpf, dpo])
    dx4 = jnp.moveaxis(dz, 0, 1).astype(x4_dtype)
    glens = jnp.zeros(lens2d.shape, jax.dtypes.float0)
    return dx4, glens, dw.astype(w.dtype), dbias, dpeep


_lstm_pallas.defvjp(_lstm_fwd, _lstm_bwd)


def lstm_sequence(x4: jnp.ndarray, lengths: jnp.ndarray, w: jnp.ndarray,
                  bias: Optional[jnp.ndarray],
                  peep: Optional[jnp.ndarray], *,
                  interpret: bool = False
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x4: [b, T, 4h]; returns (h_seq [b,T,h] f32, hT [b,h], cT [b,h]).
    Differentiable: fused Pallas kernels both directions."""
    b, T, four_h = x4.shape
    h = four_h // 4
    lens = lengths.astype(jnp.int32).reshape(b, 1)
    b_arr = (bias if bias is not None
             else jnp.zeros((four_h,), jnp.float32)).reshape(1, four_h) \
        .astype(jnp.float32)
    p_arr = (peep.reshape(3, h) if peep is not None
             else jnp.zeros((3, h), jnp.float32)).astype(jnp.float32)
    return _lstm_pallas(x4, lens, w, b_arr, p_arr, interpret)


# ---------------------------------------------------------------------------
# GRU


def _gru_kernel(lens_ref, x3_ref, wg_ref, wc_ref, b_ref,
                out_ref, hT_ref, h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    x3 = x3_ref[0].astype(jnp.float32)                # [b, 3h]
    h = h_scr[:]
    hdim = h.shape[-1]

    zr = x3[:, :2 * hdim] + jnp.dot(h.astype(wg_ref.dtype), wg_ref[:],
                                    preferred_element_type=jnp.float32) \
        + b_ref[0, :2 * hdim]
    z = _sigmoid(zr[:, :hdim])
    r = _sigmoid(zr[:, hdim:])
    cand = x3[:, 2 * hdim:] + jnp.dot((r * h).astype(wc_ref.dtype), wc_ref[:],
                                      preferred_element_type=jnp.float32) \
        + b_ref[0, 2 * hdim:]
    c = jnp.tanh(cand)
    h_new = (1.0 - z) * h + z * c

    valid = (lens_ref[:] > t)
    h_keep = jnp.where(valid, h_new, h)
    h_scr[:] = h_keep
    out_ref[0] = jnp.where(valid, h_new, jnp.zeros_like(h_new))
    hT_ref[:] = h_keep


def _gru_ref(x3, lens2d, w, bias2d):
    b, T, three_h = x3.shape
    h = three_h // 3
    lens = lens2d.reshape(b)
    xt = jnp.moveaxis(x3, 1, 0)

    def body(carry, inp):
        t, x_t = inp
        hh = carry
        zr = x_t[:, :2*h] + hh @ w[:, :2*h] + bias2d[0, :2*h]
        z = _sigmoid(zr[:, :h])
        r = _sigmoid(zr[:, h:])
        cand = x_t[:, 2*h:] + (r * hh) @ w[:, 2*h:] + bias2d[0, 2*h:]
        h_new = (1.0 - z) * hh + z * jnp.tanh(cand)
        valid = (t < lens)[:, None]
        h_keep = jnp.where(valid, h_new, hh)
        return h_keep, jnp.where(valid, h_new, 0.0)

    hT, outs = jax.lax.scan(
        body, jnp.zeros((b, h), x3.dtype),
        (jnp.arange(T, dtype=jnp.int32), xt))
    return jnp.moveaxis(outs, 0, 1), hT


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gru_pallas(x3, lens2d, w, bias2d, interpret):
    b, T, three_h = x3.shape
    h = three_h // 3
    xt = jnp.moveaxis(x3, 1, 0)
    out, hT = _gru_call(xt, lens2d, w, bias2d, b, T, three_h, h, interpret)
    return jnp.moveaxis(out, 0, 1), hT


def _gru_fwd(x3, lens2d, w, bias2d, interpret):
    # GRU training keeps the lax vjp (one forward + one backward, same
    # cost as the plain scan); only the LSTM has the full fused backward.
    out, vjp = jax.vjp(_gru_ref, x3, lens2d, w, bias2d)
    return out, (vjp, lens2d.shape)


def _gru_bwd(interpret, res, ct):
    vjp, lens_shape = res
    gx3, _, gw, gb = vjp(ct)
    glens = jnp.zeros(lens_shape, jax.dtypes.float0)
    return gx3, glens, gw, gb


_gru_pallas.defvjp(_gru_fwd, _gru_bwd)


def gru_sequence(x3: jnp.ndarray, lengths: jnp.ndarray, w: jnp.ndarray,
                 bias: Optional[jnp.ndarray], *,
                 interpret: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x3: [b, T, 3h]; w: [h, 3h] (gates [h,2h] | cand [h,h]).
    Returns (h_seq [b,T,h], hT [b,h])."""
    b, T, three_h = x3.shape
    lens = lengths.astype(jnp.int32).reshape(b, 1)
    b_arr = (bias if bias is not None
             else jnp.zeros((three_h,), jnp.float32)).reshape(1, three_h) \
        .astype(jnp.float32)
    return _gru_pallas(x3.astype(jnp.float32), lens, w.astype(jnp.float32),
                       b_arr, interpret)


def _gru_call(xt, lens, w, b_arr, b, T, three_h, h, interpret):
    mxu = _mxu_dtype()
    return pl.pallas_call(
        _gru_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),            # lens
            pl.BlockSpec((1, b, three_h), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),            # wg [h,2h]
            pl.BlockSpec(memory_space=pltpu.VMEM),            # wc [h,h]
            pl.BlockSpec(memory_space=pltpu.VMEM),            # bias [1,3h]
        ],
        out_specs=[
            pl.BlockSpec((1, b, h), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        interpret=interpret, name="gru_fwd",
    )(lens, xt.astype(mxu), w[:, :2 * h].astype(mxu),
      w[:, 2 * h:].astype(mxu), b_arr)


# ---------------------------------------------------------------------------
# dispatch


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# v5e-class chips expose ~128 MB of VMEM (measured: a 120 MB scratch
# compiles and runs); leave headroom for double-buffered stream blocks
_VMEM_BUDGET = 96 * 1024 * 1024


def _vmem_bytes(b: int, h: int, gates: int) -> int:
    """Rough VMEM residency of the fused kernel: resident weights + the
    double-buffered per-step stream blocks + state scratches."""
    gh = gates * h
    mxu_bytes = 2 if _mxu_dtype() == jnp.bfloat16 else 4
    return (mxu_bytes * h * gh          # recurrent weight (resident)
            + 2 * mxu_bytes * b * gh    # x block (double-buffered)
            + 2 * mxu_bytes * b * gh    # gates block
            + 4 * gh + 12 * h           # bias + peephole
            + 4 * b * h * 8)            # h/c stream blocks + scratches


def pallas_ok(b: int, h: int, act: str, gate_act: str,
              state_act: str = "tanh", gates: int = 4) -> bool:
    """Use the fused kernel only for tile-friendly shapes that FIT in VMEM
    and default activations (everything else keeps the lax.scan path)."""
    import os
    if os.environ.get("PADDLE_TPU_NO_PALLAS"):
        return False
    return (_on_tpu() and act == "tanh" and gate_act == "sigmoid"
            and state_act == "tanh" and h % 128 == 0 and b % 8 == 0
            and _vmem_bytes(b, h, gates) <= _VMEM_BUDGET)
