"""Block descriptions for the decoders (models/decode.py).

``TransformerDecoder(params, ..., block=None)`` runs the block the
``layer`` DSL trains (pre-LayerNorm, learned positions, MHA/GQA, ReLU or
capacity-routed FFN, tied head): that description is written out in
decode.py itself. A ``block=`` description replaces it: one frozen object
that carries the configuration and the block's pure functions over the
parameter table, used by the dense-cache path (``generate``) and by
``PagedDecoder``'s step alike, so that the two cannot drift.

:class:`LatentBlock` is the DeepSeek-V3 / Kimi-K2 block: RMSNorm, rotary
positions (YaRN) on part of each query head, a low-rank query, multi-head
LATENT attention whose cache row is one ``[c_kv | k_rope]`` per token and
layer (not per head), SwiGLU, leading dense layers, then sigmoid-routed
experts of which this chip holds a share, a shared expert, an untied head.

Precision. The weights and the cache are what the table holds (bfloat16
as served); the residual stream, the norms, the router and every
elementwise step are float32, and a product with a stored weight takes
its float32 activation as two terms of the weight's dtype in one pass
(ops/linear.einsum_two_terms). That is what keeps the router's top-k the
reference's: rounding the stream to bfloat16 moved a 384-way top-8 choice
at one position in ten a layer, and a moved choice on a held expert adds
or drops a whole expert's term.

Parameter table (``pre`` = ``_<name>_``; every norm a gain, no bias):

    <pre>tok_emb.w0 [V, d]   <pre>lm_head.w0 [V, d]   <pre>norm_f.w0 [d]
    <pre>l<i>_attn_norm.w0 [d]     <pre>l<i>_ffn_norm.w0 [d]
    <pre>l<i>_q_down.w0 [d, rq]    <pre>l<i>_q_norm.w0 [rq]
    <pre>l<i>_q_up.w0 [rq, H*(dn+dr)]
    <pre>l<i>_kv_down.w0 [d, rkv+dr]   <pre>l<i>_kv_norm.w0 [rkv]
    <pre>l<i>_kv_up.w0 [rkv, H*(dn+dv)]   <pre>l<i>_proj.w0 [H*dv, d]
    dense layers:  <pre>l<i>_gate.w0, _up.w0 [d, f], _down.w0 [f, d]
    expert layers: <pre>l<i>_router.w0 [d, E], _router.wbias [E],
                   _experts.gate, _experts.up [held, d, fm],
                   _experts.down [held, fm, d],
                   _shared.gate, _shared.up [d, fs], _shared.down [fs, d]
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops.linear import einsum_two_terms as mm

NEG_INF = -1e30


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class LatentBlock:
    """The block's configuration; sizes the table fixes (hidden size,
    ranks, widths, experts held) are read from the table."""

    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    max_positions: int
    first_dense_layers: int = 1
    experts_per_token: int = 8
    routed_scaling_factor: float = 1.0
    expert_rank: int = 0            # this chip among those sharing a layer
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0        # YaRN; 1 is plain RoPE
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # ----------------------------------------------------------- rotary
    def inv_freq(self) -> np.ndarray:
        """[dr / 2] float64: ``theta^(-2j/dr)``, divided by ``factor``
        for the lanes past YaRN's ramp."""
        dr = self.qk_rope_head_dim
        f = self.rope_theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
        if self.rope_factor <= 1.0:
            return f

        def lane(beta):
            return dr * math.log(self.rope_original_max
                                 / (beta * 2 * math.pi)) / (
                2 * math.log(self.rope_theta))
        low = max(math.floor(lane(self.rope_beta_fast)), 0)
        high = min(math.ceil(lane(self.rope_beta_slow)), dr - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0.0, 1.0)
        return f / self.rope_factor * ramp + f * (1.0 - ramp)

    def _yarn_m(self, m: float) -> float:
        if self.rope_factor <= 1.0:
            return 1.0
        return 0.1 * m * math.log(self.rope_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        m = self._yarn_m(self.rope_mscale_all_dim) \
            if self.rope_mscale_all_dim else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    @functools.cache
    def rope_table(self) -> np.ndarray:
        """[2, max_positions, dr / 2] float32: cos and sin of position x
        frequency, times the rotary's own factor (mscale over
        mscale_all_dim), made on the host in float64. A table, not
        ``jnp.cos`` in the step: the chip's float32 cosine of an angle of
        a thousand radians is off in the fourth digit, which reached the
        attention's output at half a percent (PERF.md, PR 35)."""
        ang = np.outer(np.arange(self.max_positions, dtype=np.float64),
                       self.inv_freq())
        m = self._yarn_m(self.rope_mscale) / \
            self._yarn_m(self.rope_mscale_all_dim or 0.0)
        return np.stack([np.cos(ang), np.sin(ang)]).astype(np.float32) * \
            np.float32(m)

    def rope(self, x, pos):
        """x [..., dr] at positions ``pos`` (x's leading dims, or those
        without the heads axis when x is [..., H, dr]); rotate-half
        pairing (j, j + dr/2), float32 inside."""
        cos, sin = jnp.asarray(self.rope_table())[:, pos]
        if x.ndim == cos.ndim + 1:                  # a heads axis
            cos, sin = cos[..., None, :], sin[..., None, :]
        xf = x.astype(jnp.float32)
        a, b = jnp.split(xf, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(x.dtype)

    # ------------------------------------------------------------- sizes
    def sizes(self, p, pre: str) -> dict:
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        rkv = p[f"{pre}l0_kv_norm.w0"].shape[0]
        return {"dn": dn, "dr": dr, "dv": dv, "rkv": rkv,
                "H": p[f"{pre}l0_kv_up.w0"].shape[1] // (dn + dv)}

    def cache_widths(self, p, pre: str) -> tuple:
        """(c_kv lanes, k_rope lanes) of a token's cache row."""
        return (p[f"{pre}l0_kv_norm.w0"].shape[0], self.qk_rope_head_dim)

    # ------------------------------------------------------------- parts
    def embed(self, p, pre, ids):
        return p[f"{pre}tok_emb.w0"][ids].astype(jnp.float32)

    def logits(self, p, pre, x):
        """x [B, t, d] -> float32 logits [B, t, V]."""
        h = rms_norm(x, p[f"{pre}norm_f.w0"], self.rms_eps)
        return mm("btd,vd->btv", h, p[f"{pre}lm_head.w0"])

    def qkv(self, p, pre, i, x, pos):
        """x [B, t, d] at positions pos [B, t] -> (q_nope [B, t, H, dn],
        q_rope [B, t, H, dr] rotated, c_kv [B, t, rkv], k_rope [B, t, dr]
        rotated), float32: a token's cache row is the last two."""
        lp = f"{pre}l{i}_"
        z = self.sizes(p, pre)
        h = rms_norm(x, p[f"{lp}attn_norm.w0"], self.rms_eps)
        c_q = rms_norm(mm("btd,dr->btr", h, p[f"{lp}q_down.w0"]),
                       p[f"{lp}q_norm.w0"], self.rms_eps)
        q = mm("btr,rf->btf", c_q, p[f"{lp}q_up.w0"]).reshape(
            x.shape[:-1] + (z["H"], z["dn"] + z["dr"]))
        ckr = mm("btd,df->btf", h, p[f"{lp}kv_down.w0"])
        c_kv = rms_norm(ckr[..., :z["rkv"]], p[f"{lp}kv_norm.w0"],
                        self.rms_eps)
        return (q[..., :z["dn"]], self.rope(q[..., z["dn"]:], pos), c_kv,
                self.rope(ckr[..., z["rkv"]:], pos))

    def _kv_up(self, p, pre, i):
        """W_uk, W_uv: [rkv, H, dn], [rkv, H, dv]."""
        z = self.sizes(p, pre)
        w = p[f"{pre}l{i}_kv_up.w0"].reshape(
            z["rkv"], z["H"], z["dn"] + z["dv"])
        return w[..., :z["dn"]], w[..., z["dn"]:]

    def absorb_q(self, p, pre, i, q_nope):
        """q_lat_i = q_nope_i W_uk_i^T: [..., H, dn] -> [..., H, rkv]."""
        w_uk, _ = self._kv_up(p, pre, i)
        return mm("bthd,rhd->bthr", q_nope, w_uk)

    def expand_o(self, p, pre, i, o_lat):
        """o_i = o_lat_i W_uv_i: [..., H, rkv] -> [..., H, dv]."""
        _, w_uv = self._kv_up(p, pre, i)
        return mm("bthr,rhd->bthd", o_lat, w_uv)

    def project(self, p, pre, i, attn):
        """concat_i(o_i) W_o: [B, t, H*dv] -> [B, t, d]."""
        return mm("btf,fd->btd", attn, p[f"{pre}l{i}_proj.w0"])

    def attend(self, p, pre, i, q_nope, q_rope, c, kr, mask,
               absorbed: bool = True):
        """Attention of q [B, t, H, .] over the cached rows c [B, T, rkv],
        kr [B, T, dr] under mask [B, t, T] -> [B, t, H*dv]. Both forms
        are the same mathematics: ``absorbed`` scores q_nope W_uk^T
        against c_kv itself and expands the output once; the other
        expands every cached row to per-head keys and values first."""
        sigma = self.softmax_scale
        hp = jax.lax.Precision.HIGHEST
        c, kr = c.astype(jnp.float32), kr.astype(jnp.float32)
        s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, kr, precision=hp)
        if absorbed:
            q_lat = self.absorb_q(p, pre, i, q_nope)
            s = jnp.einsum("bqhr,bkr->bhqk", q_lat, c, precision=hp) + s_rope
        else:
            k_nope = mm("bkr,rhd->bkhd", c, self._kv_up(p, pre, i)[0])
            s = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                           precision=hp) + s_rope
        s = jnp.where(mask[:, None], s * sigma, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        if absorbed:
            o = self.expand_o(p, pre, i, jnp.einsum(
                "bhqk,bkr->bqhr", w, c, precision=hp))
        else:
            o = jnp.einsum("bhqk,bkhd->bqhd", w, mm(
                "bkr,rhd->bkhd", c, self._kv_up(p, pre, i)[1]),
                precision=hp)
        return o.reshape(o.shape[:2] + (-1,))

    def is_expert_layer(self, i: int) -> bool:
        return i >= self.first_dense_layers

    def n_expert_layers(self, n_layers: int) -> int:
        return max(n_layers - self.first_dense_layers, 0)

    def route(self, p, pre, i, h):
        """h [n, d] (the expert layer's normalised input) -> (idx [n, k]
        over ALL router outputs, weights [n, k] float32)."""
        lp = f"{pre}l{i}_"
        return moe_ops.sigmoid_topk_route(
            h, p[f"{lp}router.w0"], p[f"{lp}router.wbias"],
            k=self.experts_per_token, scale=self.routed_scaling_factor)

    def ffn(self, p, pre, i, x, active=None):
        """x [B, t, d] -> (x + FFN_i(RMSNorm(x)), held load int32 [2] or
        None for a dense layer). ``active`` [B, t] bool masks the load
        count (never the result)."""
        lp = f"{pre}l{i}_"
        shape = x.shape
        h = rms_norm(x, p[f"{lp}ffn_norm.w0"], self.rms_eps)
        h = h.reshape(-1, shape[-1])
        if not self.is_expert_layer(i):
            y = moe_ops.swiglu(h, p[f"{lp}gate.w0"], p[f"{lp}up.w0"],
                               p[f"{lp}down.w0"])
            return x + y.reshape(shape), None
        n_held = p[f"{lp}experts.gate"].shape[0]
        lo = n_held * self.expert_rank
        with jax.named_scope("router"):
            idx, wts = self.route(p, pre, i, h)
            comb = moe_ops.held_combine(idx, wts, lo=lo, n_held=n_held)
            act = jnp.ones((h.shape[0],), jnp.bool_) if active is None \
                else active.reshape(-1)
            load = moe_ops.held_load(idx, act, lo=lo, n_held=n_held)
        with jax.named_scope("experts"):
            y = moe_ops.held_experts_ffn(
                h, comb, p[f"{lp}experts.gate"], p[f"{lp}experts.up"],
                p[f"{lp}experts.down"])
        with jax.named_scope("shared_expert"):
            y = y + moe_ops.swiglu(h, p[f"{lp}shared.gate"],
                                   p[f"{lp}shared.up"],
                                   p[f"{lp}shared.down"])
        return x + y.reshape(shape), load
