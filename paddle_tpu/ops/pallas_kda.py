"""The recurrent state of a delta-rule linear-attention layer (Kimi Delta
Attention, KDA; FLA's ``naive_recurrent_kda``): the plain recurrence and
the kernel that applies it to a pool of states in place.

A head's state is a [dk, dv] float32 matrix, zero at a sequence's start.
A token (q, k unit vectors, q scaled; v; a log-decay g <= 0 a key channel;
a rate beta in (0, 1)) moves it so:

    S' = exp(g)[:, None] * S
    u  = beta * (v - S'^T k)
    S  = S' + k u^T
    o  = S^T q

A state is held as written, ``S[k, v]``: the two contractions over k are
then sums down the sublanes against a column (a key channel's number
spread over the lanes), which the vector unit does with adds alone;
held transposed they were reductions along the lanes, three a token, and
the kernel read 35 % of its roofline (my chip run, PR 39).

:func:`short_conv` is a layer's short convolution (depthwise, causal;
the taps from the weights' shape, SiLU or none from the description:
Kimi-Linear's is 4 taps and SiLU, :func:`kda_short_conv`; LFM2's 3 taps
and none) over the same rows, with the last taps - 1 inputs of each
sequence kept in a pool of TAILS and moved in place the same way (kernel
``kda_short_conv`` / ``short_conv``). The tails pool is touched by
kernels alone: left to XLA's scatter, a pool of rows this wide was
re-laid out whole around every layer's write (12-18 ms of a 40 ms step,
my chip runs, PR 39).

:func:`recurrent_kda` is the recurrence over whole sequences as a
``lax.scan`` (the dense decoder's path, and the yardstick).

:func:`kda_state_update` applies it to rows of a state POOL
[L, rows, H, dk, dv]: for each of the B rows fed this step (a decoding
slot's one token, a prefill lane's several) the row's state is brought
from the pool once, moved by the row's tokens in order and written back in
place. ``first`` says where a fed row starts from the pool; a row that
does not (a later lane of the same slot, which follows it directly) goes
on from the state the row before left. A row that is fed nothing is not
read. Its kernel (``kda_state_update``) lets Pallas's pipeline bring the
next row's state while this row is computed; the jnp path walks the rows
with a scan and is what the kernel is held to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: heads a grid step of the kernel holds: [16, 128, 128] float32 is 1 MB,
#: in and out, each double-buffered, are 4 MB of VMEM
_HEADS_PER_BLOCK = 16


def _token(S, q, k, v, g, beta):
    """One token over states S [..., dk, dv]; q, k, g [..., dk];
    v [..., dv]; beta [...]. -> (S', o [..., dv]). Products and sums
    elementwise in float32: no matrix unit, nothing rounded."""
    eg = jnp.exp(g)
    pred = jnp.sum(S * (k * eg)[..., :, None], axis=-2)
    u = beta[..., None] * (v - pred)
    S = S * eg[..., :, None] + k[..., :, None] * u[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def recurrent_kda(S, q, k, v, g, beta, valid=None):
    """S [B, H, dk, dv]; q, k, g [B, T, H, dk]; v [B, T, H, dv];
    beta [B, T, H]; valid [B, T] bool (a token that is not leaves the
    state as it was). -> (o [B, T, H, dv], S after the T tokens)."""
    if valid is None:
        valid = jnp.ones(q.shape[:2], jnp.bool_)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t, ok = x
        new, o = _token(S, q_t, k_t, v_t, g_t, b_t)
        return jnp.where(ok[:, None, None, None], new, S), o

    t_major = lambda a: jnp.moveaxis(a, 1, 0)
    S, o = jax.lax.scan(step, S, tuple(
        t_major(a) for a in (q, k, v, g, beta, valid)))
    return jnp.moveaxis(o, 0, 1), S


def state_kernel_supported(heads: int, dk: int, dv: int) -> bool:
    """Does the kernel take these states: whole [128, 128] float32 tiles,
    the heads in whole blocks."""
    return dk == 128 and dv == 128 and heads % min(heads,
                                                   _HEADS_PER_BLOCK) == 0


def _kda_state_kernel(src_ref, fed_ref, first_ref, cols_ref, v_ref,
                      s_in_ref, o_ref, s_out_ref, *, tokens, chained):
    """Grid (head blocks, B), rows fastest: a row's block of heads is
    [Hb, dk, dv] of the pool, in and out under ONE block index (the
    output aliases the pool), so that a row which follows another of the
    same state finds it in ``s_out_ref`` and nothing is written back in
    between. ``cols_ref`` [1, 1, C, dk, 5*Hb]: a token's q, k, k*exp(g),
    exp(g) and beta as COLUMNS, a key channel a sublane, the block's heads
    side by side on the lanes (a head's column is a static lane of it);
    ``v_ref`` and ``o_ref`` [1, C, Hb, dv]: rows. A head at a time: its
    [dk, dv] state is 16 vector registers."""
    b = pl.program_id(1)
    n = fed_ref[b]
    Hb = s_in_ref.shape[2]

    @pl.when(n > 0)
    def _fed():
        from_pool = first_ref[b] > 0
        for h in range(Hb):
            # a row may go on from the row before it only where the
            # caller says rows chain (lanes; never the slots' own group)
            S = jnp.where(from_pool, s_in_ref[0, 0, h],
                          s_out_ref[0, 0, h]) if chained \
                else s_in_ref[0, 0, h]

            def token(c, S, h=h):
                cols = cols_ref[0, 0, c]                    # [dk, 5*Hb]
                q, k, kd, eg = (cols[:, j * Hb + h:j * Hb + h + 1]
                                for j in range(4))
                beta = cols[0:1, 4 * Hb + h:4 * Hb + h + 1]
                v = v_ref[0, c, h:h + 1, :]                 # [1, dv]
                u = beta * (v - jnp.sum(S * kd, axis=0, keepdims=True))
                S = S * eg + k * u
                o_ref[0, c, h:h + 1, :] = jnp.sum(S * q, axis=0,
                                                  keepdims=True)
                return S

            S = token(0, S) if tokens == 1 else \
                jax.lax.fori_loop(0, n, token, S)
            s_out_ref[0, 0, h] = S


def _visited(rows, fed, junk):
    """The pool row each grid step names: its own where it is fed, else
    the fed row before it (the first fed row for the steps before any),
    so that a row fed nothing moves no block; ``junk`` where nothing is
    fed at all (the one block then visited is written back unwritten)."""
    B = rows.shape[0]
    act = fed > 0
    at = jnp.where(act, jnp.arange(B, dtype=jnp.int32), -1)
    last = jax.lax.cummax(at, axis=0)
    at = jnp.where(last >= 0, last, jnp.argmax(act).astype(jnp.int32))
    return jnp.where(jnp.any(act), rows[at], junk).astype(jnp.int32)


def kda_state_update(pool, q, k, v, g, beta, rows, first, fed, *, layer,
                     junk_row, use_kernel=False, interpret=False):
    """pool [L, R, H, dk, dv] float32 (donated by the step: updated in
    place); q, k, g [B, C, H, dk], v [B, C, H, dv], beta [B, C, H]:
    row b's C tokens, of which the first ``fed[b]`` are fed; rows [B] the
    pool row of each, first [B] whether it starts from the pool or goes
    on from the row before it (None: every row starts from the pool, and
    the kernel never looks); ``layer`` (a Python int) the pool's layer;
    ``junk_row`` a pool row that holds nothing. -> (o [B, C, H, dv]
    float32, of the fed tokens; pool')."""
    B, C, H, dk = q.shape
    dv = v.shape[-1]
    rows = jnp.asarray(rows, jnp.int32)
    fed = jnp.asarray(fed, jnp.int32)
    chained = first is not None
    first = jnp.asarray(first, jnp.bool_) if chained \
        else jnp.ones((B,), jnp.bool_)
    if not use_kernel:
        valid = jnp.arange(C)[None, :] < fed[:, None]

        def row(carry, x):
            pool, prev = carry
            r, start, ok, *tok = x
            S = jnp.where(start, pool[layer, r], prev)
            o, new = recurrent_kda(S[None], *(a[None] for a in tok),
                                   ok[None])
            new = jnp.where(jnp.any(ok), new[0], S)
            pool = jax.lax.dynamic_update_slice(
                pool, new[None, None],
                (layer, jnp.where(jnp.any(ok), r, junk_row), 0, 0, 0))
            return (pool, new), o[0]

        (pool, _), o = jax.lax.scan(
            row, (pool, jnp.zeros(pool.shape[2:], pool.dtype)),
            (rows, first, valid, q, k, v, g, beta))
        return o, pool
    Hb = min(H, _HEADS_PER_BLOCK)
    nb = H // Hb
    # a token's five columns a head, the block's heads side by side
    eg = jnp.exp(g.astype(jnp.float32))
    cols = jnp.stack([q, k, k * eg, eg,
                      jnp.broadcast_to(beta[..., None], q.shape)], axis=2)
    cols = cols.astype(jnp.float32).reshape(B, C, 5, nb, Hb, dk).transpose(
        0, 3, 1, 5, 2, 4).reshape(B, nb, C, dk, 5 * Hb)
    src = _visited(rows, fed, junk_row)

    def _row_map(hb, b, *_):
        return (b, 0, hb, 0)

    def _state_map(hb, b, src, *_):
        return (layer, src[b], hb, 0, 0)

    state_spec = pl.BlockSpec((1, 1, Hb, dk, dv), _state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb, B),
        in_specs=[pl.BlockSpec((1, 1, C, dk, 5 * Hb),
                               lambda hb, b, *_: (b, hb, 0, 0, 0)),
                  pl.BlockSpec((1, C, Hb, dv), _row_map), state_spec],
        out_specs=[pl.BlockSpec((1, C, Hb, dv), _row_map), state_spec])
    o, pool = pl.pallas_call(
        functools.partial(_kda_state_kernel, tokens=C, chained=chained),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, C, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (after the three prefetched scalars, the columns and
        # v) is the pool
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kda_state_update",
    )(src, fed, first.astype(jnp.int32), cols, v.astype(jnp.float32), pool)
    return o, pool


# ------------------------------------------------------- the short convolution
# One convolution for every block that has one (models/block.py): depthwise,
# causal, ``K = w.shape[0]`` taps (Kimi-Linear's 4, LFM2's 3), followed by
# SiLU or by nothing, with the last K - 1 inputs of each sequence kept as a
# row of a TAILS pool.


def conv_windows(x, tail, fed, cont):
    """The short convolution's inputs for a group's rows. x [B, C, ch],
    row b's tokens (the first ``fed[b]`` are fed); tail [B, K - 1, ch], the
    last K - 1 inputs of row b's sequence before this step; ``cont[b]``:
    row b goes on where row b - 1 ended (such a chain's rows lie one after
    the other and all but its last are full). -> (win [B, C, K, ch]: each
    token's inputs K - 1 back to itself, from its chain or, before the
    chain's start, from the tail; the new tail [B, K - 1, ch] after row
    b's last fed token)."""
    B, C, ch = x.shape
    back_n = tail.shape[1]
    rows = jnp.arange(B, dtype=jnp.int32)
    head = jax.lax.cummax(jnp.where(cont, -1, rows), axis=0)  # chain's first
    # tokens of the chain before this row: its rows are full but the last
    off = (rows - head)[:, None] * C + jnp.arange(C, dtype=jnp.int32)[None]
    flat = x.reshape(B * C, ch)
    at = rows[:, None] * C + jnp.arange(C, dtype=jnp.int32)[None, :]

    def back(at, off, n):
        """The input ``n`` before (chain offset ``off``, flat index
        ``at``): the chain's own token, or the tail's."""
        mine = flat[jnp.clip(at - n, 0, B * C - 1)]
        old = jnp.take_along_axis(
            tail, jnp.clip(back_n + off - n, 0, back_n - 1)[..., None],
            axis=1)
        return jnp.where((off >= n)[..., None], mine, old)

    win = jnp.stack([back(at, off, back_n - j) for j in range(back_n)]
                    + [x], axis=2)
    # the tail after the row's last fed token: inputs e - (K - 1) .. e - 1
    # of the chain, e its tokens through this row
    e = (rows - head)[:, None] * C + fed[:, None]            # [B, 1]
    at_e = head[:, None] * C + e
    new = jnp.concatenate([back(at_e, e, back_n - j) for j in range(back_n)],
                          axis=1)
    return win, new


def conv_of_windows(win, w, silu: bool = True):
    """win [..., K, ch], w [K, ch] -> sum_j w[j] * win[..., j, :], through
    SiLU where the block's convolution has one."""
    y = jnp.sum(win * w.astype(jnp.float32), axis=-2)
    return jax.nn.silu(y) if silu else y


def _short_conv_kernel(src_ref, fed_ref, first_ref, x_ref, w_ref, t_in_ref,
                       y_ref, t_out_ref, *, tokens, chained, silu):
    """Grid (B,): row b's tail is one [(K-1)*T, 128] block of the pool, in
    and out under one block index (as the state kernel's); x_ref, y_ref
    [1, C, T, 128]; w_ref [K, T, 128]."""
    b = pl.program_id(0)
    n = fed_ref[b]
    T = x_ref.shape[2]
    back_n = w_ref.shape[0] - 1

    @pl.when(n > 0)
    def _fed():
        src = jnp.where(first_ref[b] > 0, t_in_ref[0, 0], t_out_ref[0, 0]) \
            if chained else t_in_ref[0, 0]
        def token(c, taps):
            x = x_ref[0, c]
            y = w_ref[back_n] * x
            for j in range(back_n):
                y = y + w_ref[j] * taps[j]
            y_ref[0, c] = y * jax.nn.sigmoid(y) if silu else y
            return taps[1:] + (x,)

        taps = tuple(src[j * T:(j + 1) * T] for j in range(back_n))
        taps = token(0, taps) if tokens == 1 else \
            jax.lax.fori_loop(0, n, token, taps)
        t_out_ref[0, 0] = jnp.concatenate(taps, axis=0)


def short_conv(tails, x, w, rows, first, fed, *, layer, junk_row,
               use_kernel=False, interpret=False, silu: bool = True,
               name: str = "short_conv"):
    """tails [L, R, (K-1)*T, 128] float32 (K = w.shape[0] taps, T = ch /
    128; donated by the step: updated in place): the last K - 1 inputs of
    each sequence, one after the other; x [B, C, ch] the rows' inputs, of
    which the first ``fed[b]`` are fed; w [K, ch]; rows, first, fed,
    ``layer``, ``junk_row`` as :func:`kda_state_update`'s; ``name`` the
    kernel's in a device trace. -> (y [B, C, ch] = the causal depthwise
    convolution, through SiLU where ``silu``, of the fed tokens; tails').

    One block shape for both families: a row's tail and a token's channels
    as [., 128] tiles of float32, whole in VMEM (Kimi-Linear's 12,288
    channels x 3 tails are 144 KB a row, LFM2's 2,048 x 2 are 16 KB); the
    grid walks the fed rows."""
    B, C, ch = x.shape
    back_n = w.shape[0] - 1
    rows = jnp.asarray(rows, jnp.int32)
    fed = jnp.asarray(fed, jnp.int32)
    chained = first is not None
    first = jnp.asarray(first, jnp.bool_) if chained \
        else jnp.ones((B,), jnp.bool_)
    x = x.astype(jnp.float32)
    if not use_kernel:
        # a row that goes on from the row before it reads what that row
        # would have written: the windows take it from the chain itself
        win, new = conv_windows(x, tails[layer, rows].reshape(B, back_n, ch),
                                fed, ~first)
        last = (fed > 0) & jnp.concatenate(
            [first[1:], jnp.ones((1,), jnp.bool_)])
        tails = tails.at[layer, jnp.where(last, rows, junk_row)].set(
            new.reshape((B,) + tails.shape[2:]))
        return conv_of_windows(win, w, silu), tails
    T = ch // 128
    src = _visited(rows, fed, junk_row)

    def _row_map(b, *_):
        return (b, 0, 0, 0)

    tail_spec = pl.BlockSpec((1, 1, back_n * T, 128),
                             lambda b, src, *_: (layer, src[b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, C, T, 128), _row_map),
                  pl.BlockSpec((back_n + 1, T, 128), lambda b, *_: (0, 0, 0)),
                  tail_spec],
        out_specs=[pl.BlockSpec((1, C, T, 128), _row_map), tail_spec])
    y, tails = pl.pallas_call(
        functools.partial(_short_conv_kernel, tokens=C, chained=chained,
                          silu=silu),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, C, T, 128), jnp.float32),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(src, fed, first.astype(jnp.int32), x.reshape(B, C, T, 128),
      w.astype(jnp.float32).reshape(back_n + 1, T, 128), tails)
    return y.reshape(B, C, ch), tails


#: Kimi-Linear's: 4 taps and SiLU, under the name its reader knows
#: (benchmarks/layer_metrics, PERF.md section 3)
kda_short_conv = functools.partial(short_conv, name="kda_short_conv")
