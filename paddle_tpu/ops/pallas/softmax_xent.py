"""Fused softmax-cross-entropy head — pallas TPU kernels.

Reference parity: the capability of
``operators/collective/c_softmax_with_cross_entropy_op.cu:1`` and the
fused softmax-CE kernels the reference hand-writes for the LM loss head.
TPU mechanism: the (rows, V) logits NEVER materialise in HBM —

- forward kernel: grid (row-chunks, vocab-tiles); the x chunk stays
  VMEM-resident while W tiles stream through; each step computes the
  logits tile on the MXU and folds it into online (max, sumexp,
  at-label) state; lse and the label logit emerge per row.  Profiled
  r5: the XLA chunked CE spends ~27 ms/step on the flagship writing f32
  logits + re-reading them for max/exp/sum — this kernel's only HBM
  traffic is x, W and two (rows,) vectors.
- backward (``softmax_xent_loss``'s vjp): chunked XLA on the
  kernel-saved lse — recompute the logits tile, fold exp/one-hot into
  the dx/dW matmul reads.  A pallas dlogits-kernel variant
  (``softmax_xent_dlogits``, kept for reference/benchmarking) measured
  131 TF/s plus a 4 GB bf16 materialization and LOST to this XLA
  backward by ~14 ms/step on the flagship.

Numerics: matmul accumulates f32 on the MXU (preferred_element_type),
stats and lse are f32 end-to-end — identical math to the jnp reference
within one exp/log rounding.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["softmax_xent_loss", "softmax_xent_fwd"]

NEG_INF = -1e30


def _fwd_kernel(x_ref, w_ref, lab_ref, lse_ref, at_ref,
                m_scr, l_scr, at_scr, *, block_v: int, nv: int, V: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        at_scr[...] = jnp.zeros_like(at_scr)

    x = x_ref[...]                                   # (C, D)
    w = w_ref[...]                                   # (D, bv)
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (C, bv)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1) + vi * block_v
    # vocab padded up to the lane tile: pad columns contribute
    # exp(NEG_INF) = 0 to the denominator
    s = jnp.where(cols < V, s, NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * jnp.exp(m - m_new) \
        + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    # label logit: the label falls in exactly one vocab tile
    lab = lab_ref[...]                               # (C, 1) int32
    at_scr[...] += jnp.sum(
        jnp.where(cols == lab, s, 0.0), axis=-1, keepdims=True)

    @pl.when(vi == nv - 1)
    def _finalize():
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
        at_ref[...] = at_scr[...]


def _pad_vocab(w, block_v):
    V = w.shape[1]
    Vp = ((V + block_v - 1) // block_v) * block_v
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
    return w, V, Vp


def softmax_xent_fwd(x, w, labels, block_rows: int = 1024,
                     block_v: int = 512, interpret: bool = False):
    """x: (N, D) bf16/f32, w: (D, V), labels: (N,) int32 ->
    (lse (N,) f32, at (N,) f32).  loss = mean(lse - at)."""
    N, D = x.shape
    # the resident x block is double-buffered: past D = 1024 fewer rows
    # keep it at the 1024 x 1024 elements the 16 MB of scoped VMEM hold
    # beside the W tiles and the f32 logits tile (D = 2048 at 1024 rows
    # asked for 21 MB, compiled chip-free for a v5e); a power of two, so
    # that halving it finds a divisor of N (D = 2304: 455 halved to 1)
    fits = (1 << 20) // D
    block_rows = min(block_rows, N, max(128, 1 << (fits.bit_length() - 1)))
    while N % block_rows:
        block_rows //= 2
    w, V, Vp = _pad_vocab(w, block_v)
    nv = Vp // block_v
    lab2 = labels.reshape(N, 1).astype(jnp.int32)
    kernel = functools.partial(_fwd_kernel, block_v=block_v, nv=nv, V=V)
    lse, at = pl.pallas_call(
        kernel,
        grid=(N // block_rows, nv),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda c, v: (c, 0)),
            pl.BlockSpec((D, block_v), lambda c, v: (0, v)),
            pl.BlockSpec((block_rows, 1), lambda c, v: (c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda c, v: (c, 0)),
            pl.BlockSpec((block_rows, 1), lambda c, v: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, lab2)
    return lse[:, 0], at[:, 0]


def _dlogits_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dl_ref,
                    *, block_v: int, V: int):
    vi = pl.program_id(1)
    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (C, bv)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1) + vi * block_v
    s = jnp.where(cols < V, s, NEG_INF)              # pad cols -> p = 0
    p = jnp.exp(s - lse_ref[...])                    # softmax via saved lse
    lab = lab_ref[...]
    p = p - jnp.where(cols == lab, 1.0, 0.0)
    dl_ref[...] = (p * g_ref[0]).astype(dl_ref.dtype)


def softmax_xent_dlogits(x, w, labels, lse, gscale,
                         block_rows: int = 1024, block_v: int = 512,
                         interpret: bool = False):
    """dlogits = (softmax(x@w) - onehot(labels)) * gscale, in x.dtype,
    recomputed tile-by-tile from the saved lse (one matmul pass, no
    (N, V) f32 intermediate).  Returns (N, V) — pad columns sliced."""
    N, D = x.shape
    block_rows = min(block_rows, N)
    while N % block_rows:
        block_rows //= 2
    w, V, Vp = _pad_vocab(w, block_v)
    lab2 = labels.reshape(N, 1).astype(jnp.int32)
    lse2 = lse.reshape(N, 1).astype(jnp.float32)
    g2 = jnp.asarray(gscale, jnp.float32).reshape(1)
    kernel = functools.partial(_dlogits_kernel, block_v=block_v, V=V)
    dl = pl.pallas_call(
        kernel,
        grid=(N // block_rows, Vp // block_v),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda c, v: (c, 0)),
            pl.BlockSpec((D, block_v), lambda c, v: (0, v)),
            pl.BlockSpec((block_rows, 1), lambda c, v: (c, 0)),
            pl.BlockSpec((block_rows, 1), lambda c, v: (c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, block_v),
                               lambda c, v: (c, v)),
        out_shape=jax.ShapeDtypeStruct((N, Vp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, lab2, lse2, g2)
    # returned PADDED: pad columns are exactly zero, so downstream
    # dx/dW matmuls may consume dl as-is (slicing here would copy GBs)
    return dl


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def softmax_xent_loss(x, w, labels, interpret=False, row_weight=None):
    """mean softmax cross-entropy of ``x @ w`` against ``labels`` —
    the whole LM loss head as two fused kernels + two XLA matmuls,
    with no (N, V) logits tensor in the forward and a single bf16
    dlogits tensor in the backward.  ``row_weight`` (N,), where given,
    makes it the weighted mean ``sum_i w_i ce_i / sum_i w_i`` (a row with
    no target carries 0); it takes no gradient."""
    return _sxl_fwd(x, w, labels, interpret, row_weight)[0]


def _sxl_fwd(x, w, labels, interpret, row_weight):
    lse, at = softmax_xent_fwd(x, w, labels, interpret=interpret)
    if row_weight is None:
        return jnp.sum(lse - at) / x.shape[0], (x, w, labels, lse, None)
    scale = row_weight.astype(jnp.float32)
    scale = scale / jnp.sum(scale)
    return jnp.sum((lse - at) * scale), (x, w, labels, lse, scale)


def _sxl_bwd(interpret, res, g):
    """Chunked XLA backward on the kernel-saved lse: per row chunk,
    recompute the logits tile, form dlogits = (softmax - onehot) * g/N
    in registers (XLA fuses the exp/one-hot chain into the consuming
    matmuls), emit dx and accumulate dW.  Measured r5: this beats a
    pallas dlogits-kernel variant by ~14 ms/step on the flagship — the
    XLA emitters win once the separate stat passes are gone, which the
    saved lse provides.  With row weights the scale is a row's own,
    ``g w_i / sum(w)``, and rides the chunks beside lse."""
    x, w, labels, lse, scale = res
    N, D = x.shape
    V = w.shape[1]
    C = min(4096, N)
    while N % C:
        C //= 2
    nc = N // C
    gs = (g / N).astype(jnp.float32)
    rows = (x.reshape(nc, C, D), labels.reshape(nc, C), lse.reshape(nc, C))
    if scale is not None:
        rows += ((g * scale).astype(jnp.float32).reshape(nc, C, 1),)

    def body(dw_acc, args):
        xc, lc, lsec, *own = args
        logits = jax.lax.dot_general(
            xc, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (C, V)
        p = jnp.exp(logits - lsec[:, None])
        onehot = jax.nn.one_hot(lc, V, dtype=jnp.float32)
        pb = ((p - onehot) * (own[0] if own else gs)).astype(x.dtype)
        dx_c = jax.lax.dot_general(
            pb, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        dw_acc = dw_acc + jax.lax.dot_general(
            xc, pb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw_acc, dx_c

    dw, dx = jax.lax.scan(body, jnp.zeros((D, V), jnp.float32), rows)
    return dx.reshape(N, D), dw.astype(w.dtype), None, None


softmax_xent_loss.defvjp(_sxl_fwd, _sxl_bwd)
