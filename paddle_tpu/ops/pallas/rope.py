"""Rotate-half RoPE on a TPU: one forward and one backward Pallas kernel
that turn q and k together and change their layout on the way
(``ops/rope.py`` has the mathematics, the plain-XLA path and the
``custom_vjp``).

Per head of ``hd`` lanes, with the tables ``c = cos`` and ``s`` the
sine whose first ``hd / 2`` lanes are negated,

    y = x c + roll(x, hd / 2) s        rope_fwd, R(theta)
    x = y c - roll(y, hd / 2) s        rope_bwd, R(-theta) = R(theta)^T

in float32, rounded to the operands' dtype once: the roll by half a head
brings lane ``i + hd / 2`` to lane ``i`` and back, which with the sign in
the table is ``concatenate([-x2, x1])`` — on a 128-lane head one lane
rotation of a vreg.

- ``rope_fwd`` reads q ``(B, T, H hd)`` and k ``(B, T, K hd)`` token-major,
  as the projections write them, and writes ``(B, H, T, hd)`` and ``(B,
  K, T, hd)`` head-major, the flash kernels' folded layout.  A block of
  ``heads`` heads is the same bytes either way: ``block_t`` rows of
  ``heads hd`` lanes in, ``heads`` tiles of ``block_t x hd`` out.
- ``rope_bwd`` is the same body the other way round: dq, dk head-major
  in, token-major out for the weight-gradient products.

One grid, (token tile, batch row, head block), the head blocks innermost
so that the tables' blocks keep their index — each is fetched once a
tile.  The first ``H / heads`` head blocks are q's, the rest k's; while
the grid is at the other's heads an operand's and a result's block index
stays on the block it met last (or will meet first), so nothing of it
moves (``ops/pallas/causal_conv.py`` pins its three outputs the same
way).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import divisor

__all__ = ["plan", "rope_fwd", "rope_bwd"]

# a sublane tile of a 16-bit type: T is whole tiles of it
_TILE = 16
# the most a grid step takes: tokens (of a 16-bit type), lanes of a head
# block; the rows a chunk of the loop inside a tile
_BLOCK_T = 1024
_LANES = 512
_ROWS = 64


class Plan(NamedTuple):
    block_t: int                # tokens a tile
    heads: int                  # heads a block, dividing both H and K
    rows: int                   # rows a chunk of the loop inside a tile
    interpret: bool


def plan(T: int, H: int, K: int, hd: int, dtype, *,
         interpret: bool) -> Optional[Plan]:
    """The kernels' blocks for q of ``H`` and k of ``K`` heads of ``hd``
    lanes over ``T`` tokens, or None where the shapes do not tile: a head
    fills whole lanes (the roll turns whole lane blocks), T is whole
    16-row tiles, the dtype is bfloat16 or float32."""
    if hd % 128 or T % _TILE \
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    # the same bytes a tile whatever the itemsize: four operand blocks
    # and two float32 tables, twice (the pipeline's buffers), 10 MB of
    # the 16 MiB a kernel may use
    most = _BLOCK_T * 2 // jnp.dtype(dtype).itemsize
    block_t = _TILE * divisor(T // _TILE, most // _TILE)
    heads = divisor(math.gcd(H, K), max(_LANES // hd, 1))
    rows = _TILE * divisor(block_t // _TILE, _ROWS // _TILE)
    return Plan(block_t, heads, rows, interpret)


def _turn(x_ref, o_ref, cos_ref, sin_ref, *, heads: int, rows: int,
          inverse: bool, from_tokens: bool):
    """One operand's tile: ``heads`` heads, a chunk of ``rows`` rows at a
    time, the tables' chunk read once for all of them."""
    block_t, hd = cos_ref.shape

    def head(j, at, tokens: bool):
        return (at, pl.ds(j * hd, hd)) if tokens else (j, at, slice(None))

    def chunk(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        c, s = cos_ref[at, :], sin_ref[at, :]
        for j in range(heads):
            x = x_ref[head(j, at, from_tokens)].astype(jnp.float32)
            r = pltpu.roll(x, hd // 2, 1)
            y = x * c - r * s if inverse else x * c + r * s
            o_ref[head(j, at, not from_tokens)] = y.astype(o_ref.dtype)
        return 0

    lax.fori_loop(0, block_t // rows, chunk, 0)


def _rope(q_ref, k_ref, cos_ref, sin_ref, qo_ref, ko_ref, *, nq: int,
          **kw):
    is_q = pl.program_id(2) < nq
    pl.when(is_q)(lambda: _turn(q_ref, qo_ref, cos_ref, sin_ref, **kw))
    pl.when(~is_q)(lambda: _turn(k_ref, ko_ref, cos_ref, sin_ref, **kw))


def _specs(plan: Plan, hd: int, nq: int, nk: int, tokens: bool):
    """The ``BlockSpec``s of q's and k's blocks on the grid (t, b, head
    block), token-major ``(B, T, heads hd)`` or head-major ``(B, heads,
    T, hd)`` — each pinned while the grid is at the other's head blocks."""
    bt, n = plan.block_t, plan.heads

    def spec(lo: int, count: int):
        def index(t, b, h):
            at = jnp.clip(h - lo, 0, count - 1)
            return (b, t, at) if tokens else (b, at, t, 0)
        return pl.BlockSpec((None, bt, n * hd) if tokens
                            else (None, n, bt, hd), index)

    return spec(0, nq), spec(nq, nk)


def _call(q, k, cos, sin, plan: Plan, *, inverse: bool):
    """One launch over the grid (token tile, batch row, head block)."""
    from_tokens = not inverse
    if from_tokens:
        B, T, F = q.shape
        hd = cos.shape[1]
        H, K = F // hd, k.shape[-1] // hd
    else:
        B, H, T, hd = q.shape
        K = k.shape[1]
    n = plan.heads
    nq, nk = H // n, K // n
    ins = _specs(plan, hd, nq, nk, from_tokens)
    outs = _specs(plan, hd, nq, nk, not from_tokens)
    table = pl.BlockSpec((plan.block_t, hd), lambda t, b, h: (t, 0))
    shape = (lambda m: (B, m, T, hd)) if from_tokens \
        else (lambda m: (B, T, m * hd))
    name = "rope_bwd" if inverse else "rope_fwd"
    return pl.pallas_call(
        functools.partial(_rope, nq=nq, heads=n, rows=plan.rows,
                          inverse=inverse, from_tokens=from_tokens),
        name=name,
        grid=(T // plan.block_t, B, nq + nk),
        in_specs=[*ins, table, table], out_specs=list(outs),
        out_shape=[jax.ShapeDtypeStruct(shape(H), q.dtype),
                   jax.ShapeDtypeStruct(shape(K), k.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=plan.interpret,
    )(q, k, cos, sin)


def rope_fwd(q, k, cos, sin, *, plan: Plan):
    """q: (B, T, H hd), k: (B, T, K hd); cos, sin: (T, hd) float32, sin's
    first ``hd / 2`` lanes negated.  -> (B, H, T, hd), (B, K, T, hd) in
    the operands' dtypes, each head turned by R(theta)."""
    return _call(q, k, cos, sin, plan, inverse=False)


def rope_bwd(dq, dk, cos, sin, *, plan: Plan):
    """The transpose of :func:`rope_fwd`: dq (B, H, T, hd), dk (B, K, T,
    hd) turned by R(-theta), -> (B, T, H hd), (B, T, K hd)."""
    return _call(dq, dk, cos, sin, plan, inverse=True)
