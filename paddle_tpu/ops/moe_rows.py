"""The expert layer's shuffle through the Pallas pair
(``ops/pallas/moe_rows.py``): ``dispatch`` puts the tokens' rows into the
routed-row buffer, ``combine`` sums each token's rows back with its
routing weights folded in.  Each is a ``jax.custom_vjp`` whose reverse
pass is the other kernel:

    dispatch   xs[pos[n, j]] = z[n]             (nought past kept)
      reverse  dz[n] = sum_j valid dxs[pos[n, j]]
    combine    y[n]  = sum_j valid w[n, j] out[pos[n, j]]
      reverse  dout[pos[n, j]] = w[n, j] dy[n],  dw[n, j] = <out[pos], dy[n]>

Sums are in float32, rounded once; the weight is rounded to the rows'
dtype, as the XLA path rounds it.  ``runs`` makes the tables both kernels
read: where each tile of tokens finds its rows in the buffer.
``meta_parallel/moe.py`` decides between these and its XLA
``_take_rows`` / ``_spread_rows``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import moe_rows as _kernels
from .pallas import traced_once

__all__ = ["dispatch", "combine", "runs"]


def runs(pos, valid, group, groups: int, plan):
    """The windows of each tile of tokens (module docstring of
    ``ops/pallas/moe_rows.py``).  pos / valid / group (N, k): each
    assignment's row, whether it has one, its expert (0 .. groups).

    -> (starts (T groups,) int32 each window's first row, rows (T groups,)
    its rows — whole 8-row tiles, none where the tile sends the expert
    nothing —, s (N, k) int32 each assignment's row among its tile's
    staged ones, -1 where it has none)."""
    N, k = pos.shape
    T = N // plan.tokens
    mine = (jnp.where(valid, group, groups).reshape(T, -1, 1)
            == jnp.arange(groups, dtype=jnp.int32))           # (T, tn k, G)
    count = jnp.sum(mine, axis=1, dtype=jnp.int32)
    p = pos.reshape(T, -1, 1)
    first = jnp.min(jnp.where(mine, p, jnp.iinfo(jnp.int32).max), axis=1)
    lo = first // 8 * 8
    hi = (first + count + 7) // 8 * 8
    # a group's rows follow the last one's: a window that would start in
    # the tile the last one ended in starts a tile later
    reached = lax.cummax(jnp.where(count > 0, hi, 0), axis=1)
    before = jnp.pad(reached[:, :-1], ((0, 0), (1, 0)))
    start = jnp.where(count > 0, jnp.maximum(lo, before), 0)
    rows = jnp.where(count > 0, jnp.maximum(hi - start, 0), 0)
    shift = jnp.cumsum(rows, axis=1) - rows - start           # (T, G)
    inside = (p >= start[:, None, :]) & (p < (start + rows)[:, None, :])
    s = p[..., 0] + jnp.sum(jnp.where(inside, shift[:, None, :], 0), axis=2)
    return (start.reshape(-1), rows.reshape(-1),
            jnp.where(valid, s.reshape(N, k), -1))


@traced_once(4, 5, 6, 7)
def _take(x, tiles, kept, w, R: int, cap: int, groups: int, plan):
    starts, rows, s = tiles
    return _kernels.take_rows(x, starts, rows, s.T, kept,
                              None if w is None else w.T, R=R, cap=cap,
                              groups=groups, plan=plan)


@traced_once(3, 4)
def _gather(buf, tiles, w, groups: int, plan):
    return _kernels.gather_rows(buf, *tiles, w, groups=groups, plan=plan)


@traced_once(3, 4)
def _weight_grad(buf, tiles, dy, groups: int, plan):
    return _kernels.gather_rows(buf, *tiles, dy=dy, groups=groups, plan=plan)


def _dispatch_kernel(z, tiles, kept, R, cap, groups, plan):
    return _take(z, tiles, kept, None, R, cap, groups, plan)


def _dispatch_fwd(z, tiles, kept, R, cap, groups, plan):
    return _dispatch_kernel(z, tiles, kept, R, cap, groups, plan), tiles


def _dispatch_bwd(R, cap, groups, plan, tiles, g):
    return _gather(g, tiles, None, groups, plan), None, None


_dispatch = jax.custom_vjp(_dispatch_kernel, nondiff_argnums=(3, 4, 5, 6))
_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine_kernel(out, w, tiles, kept, cap, groups, plan):
    return _gather(out, tiles, w, groups, plan)


def _combine_fwd(out, w, tiles, kept, cap, groups, plan):
    return (_combine_kernel(out, w, tiles, kept, cap, groups, plan),
            (out, w, tiles, kept))


def _combine_bwd(cap, groups, plan, res, g):
    out, w, tiles, kept = res
    dout = _take(g, tiles, kept, w, out.shape[0], cap, groups, plan)
    return dout, _weight_grad(out, tiles, g, groups, plan), None, None


_combine = jax.custom_vjp(_combine_kernel, nondiff_argnums=(4, 5, 6))
_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch(z, tiles, kept, *, R: int, cap: int, groups: int, plan):
    """z (N, D) -> (R, D): ``buf[pos[n, j]] = z[n]`` for every assignment
    with a row, nought past the first ``kept[p]`` rows of rank p's
    ``cap``.  ``tiles`` is :func:`runs`'."""
    return _dispatch(z, tiles, kept, R, cap, groups, plan)


def combine(out, w, tiles, kept, *, cap: int, groups: int, plan):
    """out (R, D), w (N, k) -> y (N, D) in out's dtype: ``sum_j w[n, j]
    out[pos[n, j]]`` over the assignments that have a row.  ``tiles`` and
    ``kept`` as :func:`dispatch`'s."""
    return _combine(out, w.astype(jnp.float32), tiles, kept, cap, groups,
                    plan)
