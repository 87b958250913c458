"""Pallas TPU flash attention with its backward pass: causal / sliding
window, logit softcap, GQA.

Layout: q (B, S, H, D) and k/v (B, S, Hkv, D) enter the kernels as
(B, S, H*D) and (B, S, Hkv*D), a free reshape: the (block, D) tile of head
``h`` is block column ``h``, so no transpose runs outside the kernels. D is a
multiple of 128 (the ops.py wrapper pads it); q head ``h`` reads kv head
``h // groups``.

* ``flash_fwd``: grid (B, H, q blocks, kv blocks), kv innermost. The online
  softmax state (m, l, acc) lives in f32 VMEM scratch; the kernel writes o and
  the per-row log-sum-exp ``lse``.
* ``flash_bwd_dkv``: grid (B, Hkv, kv blocks, groups, q blocks). dk and dv
  accumulate in f32 scratch over the q blocks of every q head that shares
  the kv head, and are written once.
* ``flash_bwd_dq``: grid (B, H, q blocks, kv blocks), kv innermost.

The residuals are q, k, v, o and lse; the backward recomputes P in VMEM as
exp(s - lse) and takes di = sum(o * do, -1) from the wrapper, so no (S, S)
tensor reaches HBM. lse and di are stored as rows (B, H, 1, S): the dkv
kernel works on transposed scores s^T = k q^T of shape (kv, q), where a row
broadcasts as it is; the forward and dq kernels work on s = q k^T and hold
them as columns (the forward writes its columns as rows, and the dq kernel
reads rows into columns, once per q block).

Blocks outside the causal / window band are skipped, and the index maps of
the streamed operands are clamped to the band, so a skipped grid step re-uses
the resident tile instead of fetching a dead one. Only blocks that straddle
a band edge compute the mask. MXU operands stay in the input dtype with f32
accumulation; scores, softmax statistics, dS and di are f32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret


_NEG_INF = -2.0e9
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: a @ b.T
_LANES = 128


class _Spec(NamedTuple):
    """Static description of one attention call (hashable: a custom_vjp
    nondiff argument)."""

    heads: int
    kv_heads: int
    scale: float
    causal: bool
    window: int | None
    softcap: float
    block_q: int
    block_kv: int
    interpret: bool


# ---------------------------------------------------------------------------
# The band of live blocks
# ---------------------------------------------------------------------------

def _kv_band(qi, c: _Spec, num_kv: int):
    """First and last kv block that q block ``qi`` attends to."""
    lo = 0
    hi = num_kv - 1
    if c.causal:
        hi = ((qi + 1) * c.block_q - 1) // c.block_kv
    if c.window is not None:
        lo = jnp.maximum(qi * c.block_q - c.window + 1, 0) // c.block_kv
    return lo, hi


def _q_band(kj, c: _Spec, num_q: int):
    """First and last q block that attends to kv block ``kj``."""
    lo = (kj * c.block_kv) // c.block_q if c.causal else 0
    hi = num_q - 1
    if c.window is not None:
        last = ((kj + 1) * c.block_kv - 1 + c.window - 1) // c.block_q
        hi = jnp.minimum(last, num_q - 1)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _edge(qi, kj, c: _Spec):
    """Whether block (qi, kj) straddles a band edge (needs the mask); None
    when no block ever does."""
    edge = None
    if c.causal:  # some key after some query
        edge = (kj + 1) * c.block_kv - 1 > qi * c.block_q
    if c.window is not None:  # some key at or before query - window
        w = kj * c.block_kv <= (qi + 1) * c.block_q - 1 - c.window
        edge = w if edge is None else edge | w
    return edge


def _run(live, edge, body):
    """body(masked) on the live blocks: masked only where a band edge cuts."""
    if edge is None:
        pl.when(live)(lambda: body(False))
    else:
        pl.when(live & edge)(lambda: body(True))
        pl.when(live & jnp.logical_not(edge))(lambda: body(False))


def _band_mask(qpos, kpos, c: _Spec):
    mask = None
    if c.causal:
        mask = kpos <= qpos
    if c.window is not None:
        w = kpos > qpos - c.window
        mask = w if mask is None else mask & w
    return mask


def _scores(a, b, c: _Spec):
    """Scaled (and softcapped) f32 scores a @ b.T, with tanh for the
    softcap's derivative (None without a softcap)."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    s = s * c.scale
    t = None
    if c.softcap > 0.0:
        t = jnp.tanh(s / c.softcap)
        s = c.softcap * t
    return s, t


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, c: _Spec, num_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def body(masked):
        v = v_ref[...]
        s, _ = _scores(q_ref[...], k_ref[...], c)  # (bq, bkv)
        if masked:
            shape = (c.block_q, c.block_kv)
            qpos = qi * c.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            kpos = ki * c.block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where(_band_mask(qpos, kpos, c), s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_sc[...] = m_new

    lo, hi = _kv_band(qi, c, num_kv)
    _run((ki >= lo) & (ki <= hi), _edge(qi, ki, c), body)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse = jnp.broadcast_to(m_sc[...] + jnp.log(l), (c.block_q, _LANES))
        lse_ref[...] = lse.T[:1]  # column -> row


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, c: _Spec, num_q: int):
    kj = pl.program_id(2)
    g = pl.program_id(3)
    qi = pl.program_id(4)
    groups = c.heads // c.kv_heads

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def body(masked):
        q = q_ref[...]
        do = do_ref[...]
        st, t = _scores(k_ref[...], q, c)  # (bkv, bq)
        if masked:
            shape = (c.block_kv, c.block_q)
            kpos = kj * c.block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            qpos = qi * c.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            st = jnp.where(_band_mask(qpos, kpos, c), st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[...])
        dv_sc[...] += jax.lax.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dpt = jax.lax.dot_general(
            v_ref[...], do, _NT, preferred_element_type=jnp.float32
        )
        dst = pt * (dpt - di_ref[...])
        if t is not None:
            dst = dst * (1.0 - t * t)
        dk_sc[...] += jax.lax.dot(
            dst.astype(q.dtype), q, preferred_element_type=jnp.float32
        )

    lo, hi = _q_band(kj, c, num_q)
    _run((qi >= lo) & (qi <= hi), _edge(qi, kj, c), body)

    @pl.when((g == groups - 1) & (qi == num_q - 1))
    def _finalize():
        dk_ref[...] = (dk_sc[...] * c.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_sc, di_sc, dq_sc, *, c: _Spec, num_kv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        # rows -> columns, once per q block
        lse_sc[...] = jnp.broadcast_to(lse_ref[...], (_LANES, c.block_q)).T
        di_sc[...] = jnp.broadcast_to(di_ref[...], (_LANES, c.block_q)).T

    def body(masked):
        k = k_ref[...]
        s, t = _scores(q_ref[...], k, c)  # (bq, bkv)
        if masked:
            shape = (c.block_q, c.block_kv)
            qpos = qi * c.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            kpos = kj * c.block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where(_band_mask(qpos, kpos, c), s, _NEG_INF)
        p = jnp.exp(s - lse_sc[:, :1])
        dp = jax.lax.dot_general(
            do_ref[...], v_ref[...], _NT, preferred_element_type=jnp.float32
        )
        ds = p * (dp - di_sc[:, :1])
        if t is not None:
            ds = ds * (1.0 - t * t)
        dq_sc[...] += jax.lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    lo, hi = _kv_band(qi, c, num_kv)
    _run((kj >= lo) & (kj <= hi), _edge(qi, kj, c), body)

    @pl.when(kj == num_kv - 1)
    def _finalize():
        dq_ref[...] = (dq_sc[...] * c.scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_calls
# ---------------------------------------------------------------------------

def _tile(rows: int, d: int):
    """A (rows, D) tile of one head of a (B, S, heads*D) array."""
    return (pl.squeezed, rows, d)


def _row(cols: int):
    """A (1, cols) row tile of a (B, H, 1, S) array."""
    return (pl.squeezed, pl.squeezed, 1, cols)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _fwd_call(q, k, v, c: _Spec):
    """q: (B, S, H*D); k/v: (B, S, Hkv*D) -> o like q, lse (B, H, 1, S) f32."""
    B, S, HD = q.shape
    D = HD // c.heads
    groups = c.heads // c.kv_heads
    nq, nkv = S // c.block_q, S // c.block_kv

    def q_map(b, h, qi, ki):
        return (b, qi, h)

    def kv_map(b, h, qi, ki):
        lo, hi = _kv_band(qi, c, nkv)
        return (b, _clamp(ki, lo, hi), h // groups)

    def lse_map(b, h, qi, ki):
        return (b, h, 0, qi)

    return pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, num_kv=nkv),
        grid=(B, c.heads, nq, nkv),
        in_specs=[
            pl.BlockSpec(_tile(c.block_q, D), q_map),
            pl.BlockSpec(_tile(c.block_kv, D), kv_map),
            pl.BlockSpec(_tile(c.block_kv, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec(_tile(c.block_q, D), q_map),
            pl.BlockSpec(_row(c.block_q), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, c.heads, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((c.block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((c.block_q, 1), jnp.float32),  # running sum l
            pltpu.VMEM((c.block_q, D), jnp.float32),  # output accumulator
        ],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=c.interpret,
        name="flash_fwd",
    )(q, k, v)


def _dkv_call(q, k, v, do, lse, di, c: _Spec):
    B, S, HD = q.shape
    D = HD // c.heads
    groups = c.heads // c.kv_heads
    nq, nkv = S // c.block_q, S // c.block_kv

    def kv_map(b, hk, kj, g, qi):
        return (b, kj, hk)

    def q_map(b, hk, kj, g, qi):
        lo, hi = _q_band(kj, c, nq)
        return (b, _clamp(qi, lo, hi), hk * groups + g)

    def row_map(b, hk, kj, g, qi):
        lo, hi = _q_band(kj, c, nq)
        return (b, hk * groups + g, 0, _clamp(qi, lo, hi))

    kv_spec = pl.BlockSpec(_tile(c.block_kv, D), kv_map)
    q_spec = pl.BlockSpec(_tile(c.block_q, D), q_map)
    row_spec = pl.BlockSpec(_row(c.block_q), row_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, c=c, num_q=nq),
        grid=(B, c.kv_heads, nkv, groups, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((c.block_kv, D), jnp.float32),  # dk accumulator
            pltpu.VMEM((c.block_kv, D), jnp.float32),  # dv accumulator
        ],
        compiler_params=_params(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary"
        ),
        interpret=c.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)


def _dq_call(q, k, v, do, lse, di, c: _Spec):
    B, S, HD = q.shape
    D = HD // c.heads
    groups = c.heads // c.kv_heads
    nq, nkv = S // c.block_q, S // c.block_kv

    def q_map(b, h, qi, kj):
        return (b, qi, h)

    def kv_map(b, h, qi, kj):
        lo, hi = _kv_band(qi, c, nkv)
        return (b, _clamp(kj, lo, hi), h // groups)

    def row_map(b, h, qi, kj):
        return (b, h, 0, qi)

    q_spec = pl.BlockSpec(_tile(c.block_q, D), q_map)
    kv_spec = pl.BlockSpec(_tile(c.block_kv, D), kv_map)
    row_spec = pl.BlockSpec(_row(c.block_q), row_map)
    return pl.pallas_call(
        functools.partial(_dq_kernel, c=c, num_kv=nkv),
        grid=(B, c.heads, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((c.block_q, _LANES), jnp.float32),  # lse as columns
            pltpu.VMEM((c.block_q, _LANES), jnp.float32),  # di as columns
            pltpu.VMEM((c.block_q, D), jnp.float32),  # dq accumulator
        ],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=c.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, di)


# ---------------------------------------------------------------------------
# The differentiable attention
# ---------------------------------------------------------------------------

def _fold(x):
    """(B, S, H, D) -> (B, S, H*D)."""
    B, S, H, D = x.shape
    return x.reshape(B, S, H * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, c: _Spec):
    return _flash_fwd(q, k, v, c)[0]


def _flash_fwd(q, k, v, c: _Spec):
    o, lse = _fwd_call(_fold(q), _fold(k), _fold(v), c)
    o = o.reshape(q.shape)
    return o, (q, k, v, o, lse)


def _flash_bwd(c: _Spec, res, do):
    q, k, v, o, lse = res
    B, S, H, _ = q.shape
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.transpose(0, 2, 1).reshape(B, H, 1, S)
    args = (_fold(q), _fold(k), _fold(v), _fold(do), lse, di)
    dk, dv = _dkv_call(*args, c)
    dq = _dq_call(*args, c)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "scale", "block_q", "block_kv",
        "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int | None,
    softcap: float,
    scale: float,
    block_q: int,
    block_kv: int,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, S, Hkv, D); S % block == 0, D % 128 == 0.

    ``scale`` multiplies the f32 scores. Differentiable: the backward runs
    the ``flash_bwd_dkv`` and ``flash_bwd_dq`` kernels.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if S % block_q or S % block_kv:
        raise ValueError(f"S={S} must be divisible by block sizes")
    if H % Hkv:
        raise ValueError(f"H={H} must be a multiple of Hkv={Hkv}")
    c = _Spec(
        heads=H, kv_heads=Hkv, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv,
        interpret=resolve_interpret(interpret),
    )
    return _flash(q, k, v, c)
