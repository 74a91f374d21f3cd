"""Two short depth-wise causal convolutions on a TPU, each a forward and a
backward Pallas kernel in the row-major layout the projections write and
read (``ops/causal_conv.py`` has the mathematics, the plain-XLA paths and
the ``custom_vjp``s): the delta-rule mixer's, with its SiLU and the q / k
L2 norms — one input, three outputs —, and LFM2's gated one — three
sections in, one product out (the last part of this module).  The two
share the tap sum over a chunk under its carried rows (``_taps``), its
transpose with the taps' gradient (``_taps_t``, ``_tap_grads``) and the
record of a plan, nothing else: which pair runs is the entry the model
calls.

The mixer's kernels run on one grid, (batch row, column block, token
tile), over ``qkv (B, T, C)`` as it comes — channels on lanes, tokens on
sublanes, so a tap's shift is a sublane shift and never crosses lanes.  A
column block is ``block_c`` lanes of one kind — q, k or v, by its index
alone: the first ``n_qk`` columns are q, the next ``n_qk`` k, the rest v —
and a token tile ``block_t`` rows; inside, a loop over the block's heads
and over chunks of ``rows`` rows of one head, a chunk's whole chain in
float32:

    c_t = sum_j w_j s_{t-j},  s_{<0} = 0      the taps, j < taps
    a   = c sigmoid(c)                        SiLU; v stops here
    y   = a rsqrt(sum_lanes a^2 + 1e-6)       q and k, a head's 128 lanes;
                                              q also times d_k^-1/2

- ``_gdn_conv_fwd``: q, k and v are **three outputs**, each written by
  its own column blocks and by no other: while the grid is at another
  kind's columns an output's block index stays on the block it wrote last
  (or will write first), so nothing of it moves — a block is copied out
  when its index changes, as an accumulator's is.  The ``taps - 1`` rows
  before a tile come through a second, 16-row ``BlockSpec`` on the same
  array (the halo; nought before a row's first token); inside the tile
  the loop carries them.
- ``_gdn_conv_bwd``: from dq, dk, dv (each read by its own column blocks
  through the same pinned index), ``qkv`` and the taps it runs the
  convolution again, takes the norm's and the SiLU's derivatives,

      da = (dy - a r^2 sum_lanes(dy a)) r scale,   r = rsqrt(sum a^2 + eps)
      dc = da sigmoid(c) (1 + c (1 - sigmoid(c)))
      ds_t = sum_j w_j dc_{t+j},  dc_{>=T} = 0     the taps transposed
      dw_j = sum_t dc_t s_{t-j}

  and writes ``dqkv (B, T, C)`` whole and ``dconv_w`` a batch row
  ``(B, taps, C)`` in float32, accumulated over the token tiles in an
  output block that stays resident along that (sequential) axis.  Tiles
  and chunks go in reverse: the first rows of ``dc`` of the chunk after
  are carried (across tiles in a VMEM scratch), the rows of ``s`` before
  a chunk are read again (the halo for a tile's first).

The sigmoid is ``tanh``'s (one transcendental, no divide); a head's lane
sums are the cross-lane unit's (``jnp.sum`` over the lanes — off the MXU,
as bfloat16 parts against ones, they measured slower here).  Each kind's
tile is one loop over its heads and, inside, over the chunks: a kernel
body holds the chain three times, once a kind, whatever the block's
width (a launch traces it).  What the layouts buy on the chip, and the ns
a vreg of both kernels: PERF.md section 6, PR 40.

LFM2's pair reads ``bcu (3, B, T, D)``, the in-projection's product as it
lies — the sections b, c, u on the leading axis, each row-major — and
computes, a chunk in float32,

    s = b u,   y_t = sum_j w_j s_{t-j},   out = c y

Both run on one grid, (batch row, channel block of 512 lanes, token
tile); a block of ``bcu`` is the three sections' tiles at once (one
strided copy), and the 16 rows before it come through a second block.

- ``_short_conv_fwd``: inside the tile the loop carries ``s``; ``out (B,
  T, D)`` row-major, as the out-projection reads it.
- ``_short_conv_bwd``: under ``g = d out`` it runs the convolution again
  and takes ``dc = g y``, ``e = g c``, ``ds = conv^T(e)``, ``db = ds u``,
  ``du = ds b`` and ``dw_j = sum_t e_t s_{t-j}``; tiles and chunks in
  reverse as the mixer's, e's first rows carried.  ``d(bcu)`` leaves as
  ``bcu`` came, the three sections' tile a block.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import divisor

__all__ = ["plan", "conv_fwd", "conv_bwd", "EPS", "short_plan",
           "short_conv_fwd", "short_conv_bwd"]

EPS = 1e-6                      # inside the L2 norm's rsqrt
# rows of the halo block: a sublane tile of a 16-bit type, which an
# 8-row carry of float32 fits
_HALO = 16
_CARRY = 8
_LANES = 128
# the most a grid step takes: tokens (of a 16-bit type), lanes; the most
# rows a chunk of the inner loop.  Measured on the chip at (4, 8192, 8192):
# PERF.md section 6, PR 40
_BLOCK_T = 1024
_BLOCK_C = 512
_ROWS = 256


class Plan(NamedTuple):
    block_t: int                # tokens a tile
    block_c: int                # lanes a column block: whole heads of one
                                # kind (the mixer's), whole lanes (LFM2's)
    rows: int                   # rows a chunk of the loop inside a tile
    interpret: bool


def plan(B: int, T: int, C: int, taps: int, head: int, dtype, *,
         n_qk: int, interpret: bool) -> Optional[Plan]:
    """The kernels' blocks for ``qkv (B, T, C)`` whose first ``n_qk``
    columns are q, the next ``n_qk`` k and the rest v, heads of ``head``
    columns, or None where the shapes do not tile: a head fills whole
    lanes (the norm's sum is over whole 128-lane blocks), q, k and v are
    whole heads, T is whole 16-row tiles (a sublane tile of bfloat16, the
    halo block) and the taps' history fits the 8-row carry."""
    n_v = C - 2 * n_qk
    if head % 128 or n_qk % head or n_v <= 0 or n_v % head or T % _HALO \
            or not 1 <= taps <= _CARRY + 1 \
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    # whole halo blocks.  The backward holds five tiles twice (the
    # pipeline's buffers): 10 MB of the 16 MiB a kernel may use, whatever
    # the itemsize
    most = _BLOCK_T * 2 // jnp.dtype(dtype).itemsize
    block_t = _HALO * divisor(T // _HALO, most // _HALO)
    block_c = max(c for c in range(head, max(_BLOCK_C, head) + 1, head)
                  if n_qk % c == 0 and n_v % c == 0)
    rows = _HALO * divisor(block_t // _HALO, _ROWS // _HALO)
    return Plan(block_t, block_c, rows, interpret)


def _lane_sum(x):
    """sum over each row's lanes, on every lane of the row."""
    return jnp.broadcast_to(jnp.sum(x, axis=-1, keepdims=True), x.shape)


def _sigmoid(x):
    """``1 / (1 + e^-x)`` as one transcendental and no divide (absolute
    error a float32 rounding of 1/2; the results here are rounded to the
    outputs' dtype or multiplied by x)."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _taps(ext, w):
    """ext: (8 + rows, head) float32, a chunk under the 8 rows before it;
    w: the taps, (1, head) each.  -> (the chunk shifted down by 0 ..
    taps - 1 rows, their weighted sum c)."""
    shifted = [ext[_CARRY:]] + [pltpu.roll(ext, j, 0)[_CARRY:]
                                for j in range(1, len(w))]
    c = shifted[0] * w[0]
    for s, wj in zip(shifted[1:], w[1:]):
        c = c + s * wj
    return shifted, c


def _taps_t(dc, after, w):
    """The taps transposed: ``ds_t = sum_j w_j dc_{t+j}``.  dc: (rows,
    head) float32, a chunk; after: dc of the 8 rows after it (nought past
    a row's end) -> ds, the chunk over the rows after it shifted up."""
    rows = dc.shape[0]
    ext = jnp.concatenate([dc, after], 0)
    ds = dc * w[0]
    for j in range(1, len(w)):
        ds = ds + pltpu.roll(ext, rows + _CARRY - j, 0)[:rows] * w[j]
    return ds


def _tap_grads(dw, dc, shifted):
    """``dw_j += sum_t dc_t s_{t-j}`` over a chunk, eight partial sums a
    lane (vreg adds; the caller sums the sublanes once a tile).  shifted:
    :func:`_taps`' first result."""
    rows, head = dc.shape
    return tuple(acc + jnp.sum((dc * s).reshape(rows // 8, 8, head), 0)
                 for acc, s in zip(dw, shifted))


def _kind(c, nq: int):
    """Which of q, k, v the column block ``c`` holds, as three flags."""
    return c < nq, (c >= nq) & (c < 2 * nq), c >= 2 * nq


def _gdn_conv_fwd(x_ref, halo_ref, w_ref, q_ref, k_ref, v_ref, *,
                  head: int, rows: int, nq: int, q_scale: float):
    block_t, block_c = x_ref.shape
    taps = w_ref.shape[0]
    first = pl.program_id(2) == 0

    def tile(o_ref, scale: Optional[float]):
        def one_head(h, _):
            lanes = pl.ds(pl.multiple_of(h * head, head), head)
            w = [w_ref[j:j + 1, lanes] for j in range(taps)]
            before = halo_ref[_HALO - _CARRY:, lanes].astype(jnp.float32)

            def chunk(i, prev):
                at = pl.ds(pl.multiple_of(i * rows, rows), rows)
                cur = x_ref[at, lanes].astype(jnp.float32)
                _, c = _taps(jnp.concatenate([prev, cur], 0), w)
                a = c * _sigmoid(c)
                if scale is not None:
                    a = a * (lax.rsqrt(_lane_sum(a * a) + EPS) * scale)
                o_ref[at, lanes] = a.astype(o_ref.dtype)
                return cur[rows - _CARRY:]

            lax.fori_loop(0, block_t // rows, chunk,
                          jnp.where(first, 0.0, before))
            return 0

        lax.fori_loop(0, block_c // head, one_head, 0)

    is_q, is_k, is_v = _kind(pl.program_id(1), nq)
    pl.when(is_q)(lambda: tile(q_ref, q_scale))
    pl.when(is_k)(lambda: tile(k_ref, 1.0))
    pl.when(is_v)(lambda: tile(v_ref, None))


def _gdn_conv_bwd(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, dx_ref,
                  dw_ref, dc_scr, *, head: int, rows: int, nq: int,
                  q_scale: float):
    block_t, block_c = x_ref.shape
    taps = w_ref.shape[0]
    n = block_t // rows
    t = pl.program_id(2)                # tiles in reverse: the row's last
    first = t == pl.num_programs(2) - 1     # the row's first tokens

    @pl.when(t == 0)
    def _start():
        dc_scr[...] = jnp.zeros_like(dc_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def tile(dy_ref, scale: Optional[float]):
        def one_head(h, _):
            lanes = pl.ds(pl.multiple_of(h * head, head), head)
            w = [w_ref[j:j + 1, lanes] for j in range(taps)]
            before = jnp.where(
                first, 0.0,
                halo_ref[_HALO - _CARRY:, lanes].astype(jnp.float32))

            def chunk(i, carry):
                after, dw = carry       # dc's first rows of the chunk after
                i = n - 1 - i
                start = pl.multiple_of(i * rows, rows)
                at = pl.ds(start, rows)
                cur = x_ref[at, lanes].astype(jnp.float32)
                # the rows before the chunk, from its own tile (a 16-row
                # read: a whole sublane tile of a 16-bit type)
                prev = x_ref[pl.ds(pl.multiple_of(
                    jnp.maximum(start - _HALO, 0), _HALO), _HALO),
                    lanes].astype(jnp.float32)[_HALO - _CARRY:]
                prev = jnp.where(i == 0, before, prev)
                shifted, c = _taps(jnp.concatenate([prev, cur], 0), w)
                sig = _sigmoid(c)
                a = c * sig
                da = dy_ref[at, lanes].astype(jnp.float32)
                if scale is not None:
                    r = lax.rsqrt(_lane_sum(a * a) + EPS)
                    da = (da - a * (_lane_sum(da * a) * (r * r))) \
                        * (r * scale)
                dc = da * (sig + a * (1.0 - sig))
                dx_ref[at, lanes] = _taps_t(dc, after, w).astype(
                    dx_ref.dtype)
                dw = _tap_grads(dw, dc, shifted)
                return dc[:_CARRY], dw

            zero = jnp.zeros((_CARRY, head), jnp.float32)
            after, dw = lax.fori_loop(
                0, n, chunk, (dc_scr[:, lanes], (zero,) * taps))
            dc_scr[:, lanes] = after
            for j in range(taps):
                dw_ref[j:j + 1, lanes] += jnp.sum(dw[j], axis=0,
                                                  keepdims=True)
            return 0

        lax.fori_loop(0, block_c // head, one_head, 0)

    is_q, is_k, is_v = _kind(pl.program_id(1), nq)
    pl.when(is_q)(lambda: tile(dq_ref, q_scale))
    pl.when(is_k)(lambda: tile(dk_ref, 1.0))
    pl.when(is_v)(lambda: tile(dv_ref, None))


def _specs(plan: Plan, dims, n_qk: int, taps: int, reverse: bool):
    """The ``BlockSpec``s of a grid (b, column block, token tile), the
    tiles in reverse order where ``reverse``: qkv's tile, its halo (the 16
    rows before the tile; the tile's own first rows, unused, at a row's
    start), the taps, and q's, k's and v's tiles — each of the three
    pinned, while the grid is at another kind's columns, to the block it
    meets first (before its own columns) or met last (after them), so that
    its block index changes only with its own data."""
    _, T, C = dims
    bt, bc = plan.block_t, plan.block_c
    nT, per = T // bt, bt // _HALO
    blocks = (n_qk // bc, n_qk // bc, (C - 2 * n_qk) // bc)
    tok = (lambda t: nT - 1 - t) if reverse else (lambda t: t)

    def pinned(lo: int, count: int):
        def index(b, c, t):
            at = jnp.where(c < lo, tok(0),
                           jnp.where(c < lo + count, tok(t), tok(nT - 1)))
            return b, at, jnp.clip(c - lo, 0, count - 1)
        return pl.BlockSpec((None, bt, bc), index)

    starts = (0, blocks[0], blocks[0] + blocks[1])
    return (pl.BlockSpec((None, bt, bc), lambda b, c, t: (b, tok(t), c)),
            pl.BlockSpec((None, _HALO, bc), lambda b, c, t: (
                b, jnp.maximum(tok(t) * per - 1, 0), c)),
            pl.BlockSpec((taps, bc), lambda b, c, t: (0, c)),
            [pinned(lo, n) for lo, n in zip(starts, blocks)])


def _call(kernel, plan: Plan, dims, n_qk: int, head: int, operands,
          in_specs, out_specs, out_shape, scratch=()):
    """One launch over the grid (batch row, column block, token tile).
    The batch rows are independent; the column blocks are not parallel
    (q's, k's and v's blocks are pinned across them) and the token tiles
    carry (the backward) or are cheap to keep in order (the forward)."""
    B, T, C = dims
    return pl.pallas_call(
        functools.partial(kernel, head=head, rows=plan.rows,
                          nq=n_qk // plan.block_c, q_scale=head ** -0.5),
        name=kernel.__name__.lstrip("_"),
        grid=(B, C // plan.block_c, T // plan.block_t),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=plan.interpret,
    )(*operands)


def conv_fwd(qkv, conv_w, *, n_qk: int, head: int, plan: Plan):
    """qkv: (B, T, C); conv_w: (taps, C) float32.  -> q, k (B, T, n_qk)
    and v (B, T, C - 2 n_qk) in qkv's dtype: convolved, through SiLU, q
    and k L2-normalised a head of ``head`` columns, q times head^-1/2."""
    B, T, C = qkv.shape
    x, halo, w, outs = _specs(plan, qkv.shape, n_qk, conv_w.shape[0],
                              reverse=False)
    return _call(
        _gdn_conv_fwd, plan, (B, T, C), n_qk, head, (qkv, qkv, conv_w),
        [x, halo, w], outs,
        [jax.ShapeDtypeStruct((B, T, n), qkv.dtype)
         for n in (n_qk, n_qk, C - 2 * n_qk)])


def conv_bwd(qkv, conv_w, dq, dk, dv, *, n_qk: int, head: int, plan: Plan):
    """The cotangents of :func:`conv_fwd`'s inputs under dq, dk, dv:
    dqkv (B, T, C) in qkv's dtype and dconv_w a batch row, (B, taps, C)
    float32 (the caller sums them)."""
    B, T, C = qkv.shape
    taps = conv_w.shape[0]
    x, halo, w, dys = _specs(plan, qkv.shape, n_qk, taps, reverse=True)
    return _call(
        _gdn_conv_bwd, plan, (B, T, C), n_qk, head,
        (qkv, qkv, conv_w, dq, dk, dv), [x, halo, w, *dys],
        [x, pl.BlockSpec((None, taps, plan.block_c),
                         lambda b, c, t: (b, 0, c))],
        [jax.ShapeDtypeStruct((B, T, C), qkv.dtype),
         jax.ShapeDtypeStruct((B, taps, C), jnp.float32)],
        scratch=[pltpu.VMEM((_CARRY, plan.block_c), jnp.float32)])


# ---------------------------------------------------------------------------
# LFM2's gated short convolution: three sections in, one product out
# ---------------------------------------------------------------------------
def short_plan(B: int, T: int, D: int, taps: int, dtype, *,
               interpret: bool) -> Optional[Plan]:
    """The blocks of :func:`short_conv_fwd` / :func:`short_conv_bwd` for
    ``bcu (3, B, T, D)``, or None where the shapes do not tile: D whole
    lanes, T whole 16-row tiles (the halo block), the taps' history within
    the 8-row carry, bfloat16 or float32."""
    if D % _LANES or T % _HALO or not 1 <= taps <= _CARRY + 1 \
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return None
    # the backward holds the three sections' tile, the cotangent's and
    # the three gradients' twice (the pipeline's buffers): 14 MB at 1024 x
    # 512 bfloat16 (512 tokens in float32) of the 16 MiB a kernel may use
    most = _BLOCK_T * 2 // jnp.dtype(dtype).itemsize
    block_t = _HALO * divisor(T // _HALO, most // _HALO)
    block_c = _LANES * divisor(D // _LANES, _BLOCK_C // _LANES)
    rows = _HALO * divisor(block_t // _HALO, _ROWS // _HALO)
    return Plan(block_t, block_c, rows, interpret)


def _gate(x_ref, at, lanes):
    """``s = b u`` of the rows ``at``, float32; x_ref: the sections'
    (3, rows, lanes) block."""
    return x_ref[0, at, lanes].astype(jnp.float32) \
        * x_ref[2, at, lanes].astype(jnp.float32)


def _short_conv_fwd(x_ref, halo_ref, w_ref, y_ref, *, rows: int):
    block_t, block_c = y_ref.shape
    taps = w_ref.shape[0]
    first = pl.program_id(2) == 0
    halo = pl.ds(_HALO - _CARRY, _CARRY)

    def group(g, _):
        lanes = pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        before = _gate(halo_ref, halo, lanes)

        def chunk(i, prev):
            at = pl.ds(pl.multiple_of(i * rows, rows), rows)
            s = _gate(x_ref, at, lanes)
            _, y = _taps(jnp.concatenate([prev, s], 0), w)
            y_ref[at, lanes] = (x_ref[1, at, lanes].astype(jnp.float32)
                                * y).astype(y_ref.dtype)
            return s[rows - _CARRY:]

        lax.fori_loop(0, block_t // rows, chunk,
                      jnp.where(first, 0.0, before))
        return 0

    lax.fori_loop(0, block_c // _LANES, group, 0)


def _short_conv_bwd(x_ref, halo_ref, w_ref, g_ref, d_ref, dw_ref,
                    after_scr, *, rows: int):
    block_t, block_c = g_ref.shape
    taps = w_ref.shape[0]
    n = block_t // rows
    t = pl.program_id(2)                # tiles in reverse: the row's last
    first = t == pl.num_programs(2) - 1     # the row's first tokens
    halo = pl.ds(_HALO - _CARRY, _CARRY)

    @pl.when(t == 0)
    def _start():
        after_scr[...] = jnp.zeros_like(after_scr)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def group(gi, _):
        lanes = pl.ds(pl.multiple_of(gi * _LANES, _LANES), _LANES)
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        before = jnp.where(first, 0.0, _gate(halo_ref, halo, lanes))

        def chunk(i, carry):
            after, dw = carry           # e's first rows of the chunk after
            i = n - 1 - i
            start = pl.multiple_of(i * rows, rows)
            at = pl.ds(start, rows)
            b = x_ref[0, at, lanes].astype(jnp.float32)
            u = x_ref[2, at, lanes].astype(jnp.float32)
            # the rows before the chunk, from its own tile (a 16-row read:
            # a whole sublane tile of a 16-bit type)
            prev = _gate(x_ref, pl.ds(pl.multiple_of(
                jnp.maximum(start - _HALO, 0), _HALO), _HALO),
                lanes)[_HALO - _CARRY:]
            prev = jnp.where(i == 0, before, prev)
            shifted, y = _taps(jnp.concatenate([prev, b * u], 0), w)
            g = g_ref[at, lanes].astype(jnp.float32)
            e = g * x_ref[1, at, lanes].astype(jnp.float32)    # dL/dy
            ds = _taps_t(e, after, w)
            d_ref[0, at, lanes] = (ds * u).astype(d_ref.dtype)
            d_ref[1, at, lanes] = (g * y).astype(d_ref.dtype)
            d_ref[2, at, lanes] = (ds * b).astype(d_ref.dtype)
            dw = _tap_grads(dw, e, shifted)
            return e[:_CARRY], dw

        zero = jnp.zeros((_CARRY, _LANES), jnp.float32)
        after, dw = lax.fori_loop(0, n, chunk,
                                  (after_scr[:, lanes], (zero,) * taps))
        after_scr[:, lanes] = after
        for j in range(taps):
            dw_ref[j:j + 1, lanes] += jnp.sum(dw[j], axis=0, keepdims=True)
        return 0

    lax.fori_loop(0, block_c // _LANES, group, 0)


def _short_specs(plan: Plan, dims, taps: int, reverse: bool):
    """The ``BlockSpec``s over the grid (b, channel block, token tile),
    the tiles in reverse order where ``reverse``: the three sections' tile
    of ``bcu`` (one block, a strided copy), the 16 rows before it (the
    halo; the tile's own first rows, unused, at a row's start), the taps,
    and a ``(B, T, D)`` array's tile."""
    _, _, T, _ = dims
    bt, bc = plan.block_t, plan.block_c
    nT, per = T // bt, bt // _HALO
    tok = (lambda t: nT - 1 - t) if reverse else (lambda t: t)
    return ([pl.BlockSpec((3, None, bt, bc),
                          lambda b, c, t: (0, b, tok(t), c)),
             pl.BlockSpec((3, None, _HALO, bc), lambda b, c, t: (
                 0, b, jnp.maximum(tok(t) * per - 1, 0), c)),
             pl.BlockSpec((taps, bc), lambda b, c, t: (0, c))],
            pl.BlockSpec((None, bt, bc), lambda b, c, t: (b, tok(t), c)))


def short_conv_fwd(bcu, conv_w, *, plan: Plan):
    """bcu: (3, B, T, D), the sections b, c, u; conv_w: (taps, D) float32.
    -> ``c * conv(b * u)`` (B, T, D) in bcu's dtype."""
    _, B, T, D = bcu.shape
    ins, out = _short_specs(plan, bcu.shape, conv_w.shape[0], reverse=False)
    return pl.pallas_call(
        functools.partial(_short_conv_fwd, rows=plan.rows),
        name="short_conv_fwd",
        grid=(B, D // plan.block_c, T // plan.block_t),
        in_specs=ins, out_specs=out,
        out_shape=jax.ShapeDtypeStruct((B, T, D), bcu.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=plan.interpret,
    )(bcu, bcu, conv_w)


def short_conv_bwd(bcu, conv_w, dy, *, plan: Plan):
    """The cotangents of :func:`short_conv_fwd`'s inputs under dy (B, T,
    D): ``d(bcu)`` (3, B, T, D) in bcu's dtype — db, dc, du — and dconv_w
    a batch row, (B, taps, D) float32 (the caller sums them)."""
    _, B, T, D = bcu.shape
    taps, bt, bc = conv_w.shape[0], plan.block_t, plan.block_c
    (x, halo, w), g = _short_specs(plan, bcu.shape, taps, reverse=True)
    return pl.pallas_call(
        functools.partial(_short_conv_bwd, rows=plan.rows),
        name="short_conv_bwd",
        grid=(B, D // bc, T // bt),
        in_specs=[x, halo, w, g],
        out_specs=[x, pl.BlockSpec((None, taps, bc),
                                   lambda b, c, t: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((B, taps, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_CARRY, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=plan.interpret,
    )(bcu, bcu, conv_w, dy)
