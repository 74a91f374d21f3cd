"""Rotate-half RoPE over the whole head: pairs ``(i, i + hd / 2)`` turned
by the angles ``ang`` (T, hd / 2), cos and sin times a scale (YaRN's
attention factor), in float32.

    y = x cos + concatenate([-x2, x1]) sin,    x = [x1 | x2]

Two entries:

- ``rope_rotate_half(x, ang, scale)``: XLA ops on ``(B, T, H, hd)``, in
  that layout — the models' plain path (LFM2 at head size 64) and the
  kernels' reference in the tests;
- ``rope_to_heads(q, k, ang, scale)``: q and k as the projections write
  them, ``(B, T, H hd)`` and ``(B, T, K hd)``, turned and handed over
  head-major, ``(B, H, T, hd)`` and ``(B, K, T, hd)`` — the layout the
  flash kernels fold to, so that the call that follows moves nothing.
  One plan (``ops/pallas/rope.py`` ``plan``, the backend through
  ``ops.pallas.enabled()``): on a TPU, where a head is whole 128-lane
  blocks and T whole 16-row tiles, the Pallas pair ``rope_fwd`` /
  ``rope_bwd`` under one ``jax.custom_vjp`` whose residuals are the
  tables alone (the rotation is linear); elsewhere ``_reference``,
  ``rope_rotate_half`` whole and a ``swapaxes``.  Both compute the same
  float32 products from the same float32 tables and round once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from . import pallas
from .pallas import rope as _kernels
from .pallas import axes_entry, traced_once

__all__ = ["rope_rotate_half", "rope_to_heads"]


def _tables(ang, scale: float):
    """float32 (T, hd) cos and sin of the angles of both halves, times
    ``scale`` (rounded once, from float64)."""
    both = np.concatenate([ang, ang], -1)
    return (np.asarray(scale * np.cos(both), np.float32),
            np.asarray(scale * np.sin(both), np.float32))


def rope_rotate_half(x, ang, scale: float = 1.0):
    """Rotate-half RoPE in XLA ops; x: (B, T, H, hd)."""
    hd = x.shape[-1]
    cos, sin = map(jnp.asarray, _tables(ang, scale))
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :hd // 2], xf[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos[None, :, None, :]
            + rot * sin[None, :, None, :]).astype(x.dtype)


def _reference(q, k, ang, scale: float):
    """XLA ops throughout.  -> q, k as :func:`rope_to_heads`."""
    hd = 2 * ang.shape[-1]

    def one(x):
        B, T, F = x.shape
        return jnp.swapaxes(
            rope_rotate_half(x.reshape(B, T, F // hd, hd), ang, scale), 1, 2)
    return one(q), one(k)


@traced_once(4)
def _turn_kernel(q, k, cos, sin, plan):
    """(An inline jit, like the backward: a model's layers of one kind
    call with the same shapes, and the second finds the first one's
    jaxpr.)"""
    return tuple(_kernels.rope_fwd(q, k, cos, sin, plan=plan))


_turn = jax.custom_vjp(_turn_kernel, nondiff_argnums=(4,))


def _turn_fwd(q, k, cos, sin, plan):
    return _turn_kernel(q, k, cos, sin, plan), (cos, sin)


@traced_once(0)
def _turn_bwd(plan, tables, d_out):
    """The rotation's transpose, R(-theta): the tables are all it needs;
    they are constants and take no cotangent."""
    dq, dk = _kernels.rope_bwd(*d_out, *tables, plan=plan)
    return dq, dk, None, None


_turn.defvjp(_turn_fwd, _turn_bwd)


def rope_to_heads(q, k, ang, scale: float = 1.0, *, mesh=None,
                  batch_axes=()):
    """Rotate-half RoPE of q and k, handed over head-major.

    q: (B, T, H hd), k: (B, T, K hd) with ``hd = 2 ang.shape[1]``; ang:
    (T, hd / 2) angles; scale: the factor on cos and sin.  -> q (B, H, T,
    hd), k (B, K, T, hd) in the operands' dtypes.  Differentiable in q
    and k.

    The kernels (module docstring) are taken where ``plan`` tiles the
    shapes; it refuses — and the XLA math runs — a head that is not a
    multiple of 128 lanes, a T that is not whole 16-row tiles and dtypes
    other than bfloat16 and float32.  Under a mesh of more than one
    device ``batch_axes`` names the axes that shard B: the kernels run
    per shard (``ops.pallas.shard_kernel``)."""
    B, T, F = q.shape
    hd = 2 * ang.shape[-1]
    plan = _kernels.plan(T, F // hd, k.shape[-1] // hd, hd, q.dtype,
                         interpret=not pallas.on_tpu()) \
        if pallas.enabled() else None
    pallas.note("rope", plan is not None)
    if plan is None:
        return _reference(q, k, ang, scale)
    cos, sin = _tables(ang, scale)
    # the sign of concatenate([-x2, x1]) folded into the sine: exact
    sin[:, :hd // 2] = -sin[:, :hd // 2]
    spec = PartitionSpec(axes_entry(mesh, batch_axes, B))
    return pallas.shard_kernel(
        lambda q, k, c, s: _turn(q, k, c, s, plan), mesh,
        (spec, spec, PartitionSpec(), PartitionSpec()), (spec, spec))(
            q, k, jnp.asarray(cos), jnp.asarray(sin))
