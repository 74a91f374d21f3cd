"""Short depth-wise causal convolutions between projections: the one in
front of a linear-attention rule (``gated_causal_conv``) and LFM2's gated
one (``short_conv``, at the end of this module: ``c * conv(b * u)`` over
the three sections of its in-projection).  Two entries, two kernel pairs.

The convolution in front of a linear-attention rule: a short depth-wise
causal convolution over the packed q / k / v projection, SiLU, and the L2
norms of the q and k heads.

Per batch row, with ``s = qkv (T, C)`` and ``w (taps, C)`` a tap a
channel,

    c_t = sum_j w_j s_{t-j},  s_{<0} = 0     causal within the row
    a   = c sigmoid(c)                       SiLU
    q   = a_q / |a_q|  d_k^-1/2              a head's ``head`` columns;
    k   = a_k / |a_k|,   v = a_v             |x| = sqrt(sum x^2 + 1e-6)

with the first ``n_qk`` columns q, the next ``n_qk`` k and the rest v.
``gated_causal_conv`` has two implementations behind one plan
(``ops/pallas/causal_conv.py`` ``plan``, the backend through
``ops.pallas.enabled()``):

- on a TPU, where a head fills whole lanes (a multiple of 128), q, k and
  v are whole heads and T is whole 16-row tiles, one forward and one
  backward Pallas kernel under a ``jax.custom_vjp`` whose residuals are
  the two inputs: ``gdn_conv_fwd`` reads ``qkv`` row-major as the
  projection wrote it and writes q, k and v as three arrays, token-major
  ``(B, T, heads x head)`` (no ``(B, T, C)`` intermediate, no column
  slice after) — the form ``ops/gated_delta_rule.py`` takes with
  ``key_heads``, its kernels reading the same tiles: on a TPU a
  ``reshape(B, T, H, head)`` in between would copy every byte;
  ``gdn_conv_bwd`` runs the convolution again and writes ``dqkv`` and
  ``dconv_w``.  float32 inside, whatever the dtype outside;
- elsewhere ``_reference``: a pad, ``taps`` shifted slices, products in
  the operands' dtype, ``jax.nn.silu``, the norms in float32 —
  autodiff's to differentiate; also the kernels' reference in the tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from . import pallas
from .pallas import causal_conv as _kernels
from .pallas import axes_entry, traced_once

__all__ = ["gated_causal_conv", "short_conv"]


def _causal_conv(s, w):
    """Depth-wise, causal within a row: ``c_t = sum_j w_j * s_{t-j}``,
    ``s_{<0} = 0``.  s: (B, T, C); w: (taps, C)."""
    taps, T = w.shape[0], s.shape[1]
    padded = jnp.pad(s, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[j] * lax.slice_in_dim(
        padded, taps - 1 - j, taps - 1 - j + T, axis=1)
        for j in range(taps))


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                          + _kernels.EPS)


def _reference(qkv, conv_w, n_qk: int, head: int):
    """XLA ops throughout.  -> q, k, v as :func:`gated_causal_conv`."""
    B, T, _ = qkv.shape
    c = jax.nn.silu(_causal_conv(qkv, conv_w))

    def heads(x):
        return x.reshape(B, T, -1, head)

    q = (_l2norm(heads(c[..., :n_qk])) * head ** -0.5).astype(qkv.dtype)
    k = _l2norm(heads(c[..., n_qk:2 * n_qk])).astype(qkv.dtype)
    return (q.reshape(B, T, n_qk), k.reshape(B, T, n_qk),
            c[..., 2 * n_qk:])


@traced_once(2, 3, 4)
def _conv_kernel(qkv, conv_w, n_qk: int, head: int, plan):
    """(An inline jit, like the backward: a model's layers call with the
    same shapes, and the second finds the first one's jaxpr.)"""
    return tuple(_kernels.conv_fwd(qkv, conv_w.astype(jnp.float32),
                                   n_qk=n_qk, head=head, plan=plan))


_conv = jax.custom_vjp(_conv_kernel, nondiff_argnums=(2, 3, 4))


def _conv_fwd(qkv, conv_w, n_qk, head, plan):
    return _conv_kernel(qkv, conv_w, n_qk, head, plan), (qkv, conv_w)


@traced_once(0, 1, 2)
def _conv_bwd(n_qk, head, plan, inputs, d_out):
    """What the forward leaves behind is its inputs: the backward kernel
    runs the convolution again."""
    qkv, conv_w = inputs
    dqkv, dw = _kernels.conv_bwd(qkv, conv_w.astype(jnp.float32), *d_out,
                                 n_qk=n_qk, head=head, plan=plan)
    return dqkv, jnp.sum(dw, axis=0).astype(conv_w.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def gated_causal_conv(qkv, conv_w, *, n_qk: int, head: int, mesh=None,
                      batch_axes=()):
    """The convolution, SiLU and the q / k norms of a linear-attention
    mixer.

    qkv: (B, T, C), the packed projection, columns ``[q | k | v]`` with q
    and k ``n_qk`` wide; conv_w: (taps, C), tap j weighing the token j
    back.  -> q, k: (B, T, n_qk), L2-normalised a head of ``head``
    columns, q also times ``head ** -0.5``; v: (B, T, C - 2 n_qk); all in
    qkv's dtype.  Differentiable in both.

    The kernels (module docstring) are taken where ``plan`` tiles the
    shapes; it refuses — and the XLA math runs — a head that is not a
    multiple of 128 columns (the norm sums whole lane blocks), q, k or v
    that is not whole heads, a T that is not a multiple of 16 (the halo
    block is a 16-row tile), more than 9 taps (the carried history is 8
    rows) and dtypes other than bfloat16 and float32.  Under a mesh of
    more than one device ``batch_axes`` names the axes that shard B: the
    kernels run per shard (``ops.pallas.shard_kernel``)."""
    B, T, C = qkv.shape
    plan = _kernels.plan(B, T, C, conv_w.shape[0], head, qkv.dtype,
                         n_qk=n_qk, interpret=not pallas.on_tpu()) \
        if pallas.enabled() else None
    pallas.note("causal_conv", plan is not None)
    if plan is None:
        return _reference(qkv, conv_w, n_qk, head)
    spec = PartitionSpec(axes_entry(mesh, batch_axes, B))
    return pallas.shard_kernel(
        lambda x, w: _conv(x, w, n_qk, head, plan), mesh,
        (spec, PartitionSpec()), (spec,) * 3)(qkv, conv_w)


# ---------------------------------------------------------------------------
# LFM2's gated short convolution
# ---------------------------------------------------------------------------
def _short_reference(bcu, conv_w):
    """XLA ops throughout: a pad, ``taps`` shifted slices, products in
    the operands' dtype."""
    b, c, u = bcu
    return c * _causal_conv(b * u, conv_w)


@traced_once(2)
def _short_kernel(bcu, conv_w, plan):
    return _kernels.short_conv_fwd(bcu, conv_w.astype(jnp.float32),
                                   plan=plan)


_short = jax.custom_vjp(_short_kernel, nondiff_argnums=(2,))


def _short_fwd(bcu, conv_w, plan):
    return _short_kernel(bcu, conv_w, plan), (bcu, conv_w)


@traced_once(0)
def _short_bwd(plan, inputs, dy):
    """The residuals are the inputs: the backward kernel runs the
    convolution again."""
    bcu, conv_w = inputs
    dbcu, dw = _kernels.short_conv_bwd(bcu, conv_w.astype(jnp.float32), dy,
                                       plan=plan)
    return dbcu, jnp.sum(dw, axis=0).astype(conv_w.dtype)


_short.defvjp(_short_fwd, _short_bwd)


def short_conv(bcu, conv_w, *, mesh=None, batch_axes=()):
    """LFM2's gated short convolution between its two projections.

    bcu: (3, B, T, D), the in-projection's product, the sections b, c, u
    on the leading axis; conv_w: (taps, D), tap j weighing the token j
    back.
    -> ``c * conv(b * u)`` (B, T, D) in bcu's dtype, ``conv`` depth-wise
    and causal within a row.  Differentiable in both.

    On a TPU one forward and one backward Pallas kernel
    (``ops/pallas/causal_conv.py``: ``%short_conv_fwd``,
    ``%short_conv_bwd``) under a ``custom_vjp`` whose residuals are the
    two inputs, float32 inside; they read ``bcu`` and write the result and
    ``d(bcu)`` row-major, as the projections write and read them.
    ``short_plan`` refuses — and ``_short_reference`` runs — a D that is
    not whole 128-lane blocks, a T that is not whole 16-row tiles, more
    than 9 taps and dtypes other than bfloat16 and float32.  Under a mesh
    of more than one device ``batch_axes`` names the axes that shard B:
    the kernels run per shard."""
    _, B, T, D = bcu.shape
    plan = _kernels.short_plan(B, T, D, conv_w.shape[0], bcu.dtype,
                               interpret=not pallas.on_tpu()) \
        if pallas.enabled() else None
    pallas.note("short_conv", plan is not None)
    if plan is None:
        return _short_reference(bcu, conv_w)
    rows = axes_entry(mesh, batch_axes, B)
    return pallas.shard_kernel(
        lambda x, w: _short(x, w, plan), mesh,
        (PartitionSpec(None, rows), PartitionSpec()),
        PartitionSpec(rows))(bcu, conv_w)
