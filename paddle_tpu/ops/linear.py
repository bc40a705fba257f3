"""Dense linear algebra ops with TPU dtype policy.

Replaces the GEMM paths of paddle/math (Matrix::mul over cuBLAS,
hl_matrix_mul) and paddle/function/MulOp. On TPU all matmuls go through one
helper that casts to the configured compute dtype (bfloat16 keeps the MXU
fed) while accumulating/returning float32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.config import global_config


def compute_dtype():
    return jnp.dtype(global_config().compute_dtype)


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """MXU-friendly matmul with f32 accumulation.

    compute_dtype float32 -> full-precision MXU passes (precision=highest;
    TPUs otherwise default to bf16 passes even for f32 inputs);
    compute_dtype bfloat16 -> cast inputs, single fast MXU pass.
    """
    cd = compute_dtype()
    if cd != jnp.float32:
        # mixed precision: activations stay in the compute dtype — f32
        # master weights must NOT promote the output (a bf16 x @ f32 w
        # promoting to f32 silently ran every elementwise chain after
        # every fc in f32, doubling HBM traffic; see docs/perf.md)
        out_dtype = cd
        a = a.astype(cd)
        b = b.astype(cd)
        prec = None
    else:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
        prec = jax.lax.Precision.HIGHEST
    return jnp.matmul(a, b, precision=prec,
                      preferred_element_type=jnp.float32).astype(out_dtype)


def einsum_two_terms(spec: str, x: jnp.ndarray, w: jnp.ndarray
                     ) -> jnp.ndarray:
    """``einsum(spec, x, w)`` for float32 activations ``x`` against STORED
    weights ``w``, float32 out, at the activations' own precision for the
    cost of one pass over the weights.

    Where ``w`` is narrower than float32 (served bfloat16 weights) a
    plain product rounds ``x`` to bfloat16 first (0.4 % an element, which
    a 384-way top-8 router downstream turns into moved choices at one
    position in ten). Here ``x`` goes through the MXU as TWO terms of
    ``w``'s dtype, ``hi = round(x)`` and ``lo = round(x - hi)`` (16
    mantissa bits together), stacked on x's leading (token) axis in ONE
    product, and the two halves of the result are added: the weights are
    read once, and at a decode step's 64 rows the stacked 128 are what the
    MXU's height holds anyway. ``spec``'s first operand must lead with its
    token axis, which the output must keep. Float32 weights: the plain
    product at the highest precision."""
    if w.dtype == jnp.float32:
        return jnp.einsum(spec, x.astype(jnp.float32), w,
                          precision=lax.Precision.HIGHEST)
    ins, out = spec.split("->")
    axis = out.index(ins.split(",")[0][0])
    xf = x.astype(jnp.float32)
    # reduce_precision, not a cast there and back: inside a fusion the
    # chip keeps a narrow intermediate at float32 ("excess precision"),
    # which made ``x - round(x)`` nought and the whole product a plain
    # bfloat16 one (my chip run, PR 35: 1.7e-3 of the result against
    # 2.5e-6 on the CPU)
    fi = jnp.finfo(w.dtype)
    hi = lax.reduce_precision(xf, fi.nexp, fi.nmant)
    lo = (xf - hi).astype(w.dtype)
    y = jnp.einsum(spec, jnp.concatenate([hi.astype(w.dtype), lo], axis=0), w,
                   preferred_element_type=jnp.float32)
    a, b = jnp.split(y, 2, axis=axis)
    return a + b


def fc(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """x: [..., in], w: [in, out], b: [out]."""
    y = matmul(x, w)
    if b is not None:
        y = y + b.astype(y.dtype)   # f32 master bias must not promote y
    return y


def dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(a * b, axis=-1)


def outer(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Row-wise outer product [b, m], [b, n] -> [b, m*n] (OuterProdLayer)."""
    o = a[..., :, None] * b[..., None, :]
    return o.reshape(o.shape[:-2] + (o.shape[-2] * o.shape[-1],))


def cos_sim(a: jnp.ndarray, b: jnp.ndarray, scale: float = 1.0,
            eps: float = 1e-8) -> jnp.ndarray:
    """Row-wise cosine similarity (paddle/function/CosSimOp, CosSimLayer)."""
    num = jnp.sum(a * b, axis=-1)
    den = jnp.sqrt(jnp.sum(a * a, axis=-1) * jnp.sum(b * b, axis=-1))
    return scale * num / jnp.maximum(den, eps)


def interpolation(w: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """w*a + (1-w)*b with per-row scalar w [batch, 1] (InterpolationLayer)."""
    return w * a + (1.0 - w) * b


def slope_intercept(x: jnp.ndarray, slope: float, intercept: float) -> jnp.ndarray:
    return slope * x + intercept


def sum_to_one_norm(x: jnp.ndarray, eps: float = 1e-8) -> jnp.ndarray:
    """Row-normalize to sum 1 (SumToOneNormLayer)."""
    return x / jnp.maximum(jnp.sum(x, axis=-1, keepdims=True), eps)
