"""The expert layer's shuffle on a TPU: rows of a token-major array into the
routed-row buffer's order and back, touching only the rows that are live
(``ops/moe_rows.py`` has the ``custom_vjp`` pair and the tables below,
``meta_parallel/moe.py`` the plan that says where every assignment goes).

Within an expert the buffer's rows follow the tokens, so the assignments
of a tile of tokens to one expert fill one run of buffer rows.  A DMA
moves whole HBM tiles of 8 rows (one row of a ``(rows, D)`` array cannot
be sliced out), so a run is read as the 8-row tiles it touches — its
*window* — and a tile of tokens stages its windows side by side in VMEM,
a window that starts in the tile the last one ended in starting a tile
later.  ``s[n, j]`` is the staged row of assignment ``(n, j)``, -1 where
it has none.  Between the staged rows and the tile of tokens the rows
move by a one-hot product on the MXU, a block of 128 staged rows at a
time: one nonzero a (token, staged row), exact in float32.  Every other
row enters the product with weight nought, so a non-finite row is not
kept to its own assignments as XLA's gathers keep it (nought times
infinity): an infinite buffer row reaches every token of the tile that
stages it, an infinite token row every row its tile writes.

- ``moe_gather_rows`` (the combine; with ``w = 1`` the dispatch's
  reverse pass): ``y[n] = sum_j w[n, j] buf[pos[n, j]]``, the windows
  read, the sum in float32 rounded once; with ``dy`` (``moe_gather_dots``,
  the combine's weight gradient) ``dw[n, j] = <buf[pos[n, j]], dy[n]>``.
- ``moe_take_rows`` (the dispatch; with ``w`` the combine's reverse
  pass): ``buf[pos[n, j]] = w[n, j] x[n]`` — each window read, its rows of
  this tile's assignments replaced, written back (a window's other rows
  belong to a neighbouring tile's run and keep what they hold), the grid
  in order of tiles; every row past a rank's ``kept`` written as nought.

Each reads a token's row once and the buffer's live rows once, with up to
14 rows of neighbours a run.  The tables come in as scalar prefetch:
``starts`` / ``rows`` (tiles x groups) each window's first row and rows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["plan", "take_rows", "gather_rows"]

# rows of one HBM tile: the least a DMA moves along the rows
_SLOT = 8
# staged rows moved a block at a time by the one-hot product
_BLOCK = 128
# rows of nought a DMA writes past a rank's live rows
_ZEROS = 64
# the most VMEM a tile's staged rows, float32 sums and pipelined blocks
# of tokens take
_TILE_BYTES = 32 << 20


class Plan(NamedTuple):
    tokens: int                 # tokens a tile
    stage: int                  # rows a tile stages at most
    vmem: int                   # the kernels' VMEM limit, bytes
    interpret: bool


def _staged_rows(tokens: int, k: int, groups: int) -> int:
    """The most rows a tile of ``tokens`` stages: a token's k assignments
    reach min(k, groups) runs, and a window reads up to 14 rows more than
    its run; in whole blocks of the product."""
    most = tokens * min(k, groups) + 14 * groups
    return -(-most // _BLOCK) * _BLOCK


def plan(N: int, k: int, D: int, cap: int, groups: int, dtype, *,
         interpret: bool) -> Optional[Plan]:
    """The kernels' tile of tokens for ``N`` tokens of ``k`` assignments
    over ``groups`` experts into a buffer of ranks of ``cap`` rows, rows of
    ``D`` lanes, or None where the shapes do not tile: D whole lanes,
    bfloat16 or float32, ``cap`` whole 8-row tiles, a tile of tokens (a
    power of two from 8 to 256, whole sublane tiles) that divides N and
    whose staged rows, float32 sums and pipelined blocks fit 32 MB."""
    dtype = jnp.dtype(dtype)
    if D % 128 or cap % _SLOT \
            or dtype not in (jnp.bfloat16, jnp.float32):
        return None
    sub = 32 // dtype.itemsize // 2          # a tile's rows: 16 bf16, 8 f32
    for tn in (256, 128, 64, 32, 16, 8):
        need = D * (_staged_rows(tn, k, groups) * dtype.itemsize
                    + _ZEROS * dtype.itemsize
                    + tn * (4 + 4 * dtype.itemsize))
        if N % tn == 0 and tn >= sub and need <= _TILE_BYTES:
            return Plan(tn, _staged_rows(tn, k, groups), need + (8 << 20),
                        interpret)
    return None


def _windows(start_ref, rows_ref, buf_hbm, stage, sem, t, groups: int, *,
             read: bool):
    """Start the DMAs between the tile's windows in HBM and the staged
    rows, 8-row tiles; -> the rows staged (the DMAs to wait for / 8)."""
    def copy(at, to):
        hbm = buf_hbm.at[pl.ds(pl.multiple_of(at, _SLOT), _SLOT)]
        vmem = stage.at[pl.ds(pl.multiple_of(to, _SLOT), _SLOT)]
        return pltpu.make_async_copy(hbm, vmem, sem) if read \
            else pltpu.make_async_copy(vmem, hbm, sem)

    def window(g, to):
        at, n = start_ref[t * groups + g], rows_ref[t * groups + g]

        def one(q, carry):
            copy(at + q * _SLOT, to + q * _SLOT).start()
            return carry
        lax.fori_loop(0, n // _SLOT, one, 0)
        return to + n

    return lax.fori_loop(0, groups, window, 0)


def _wait(src, dst, sem, n):
    """Wait for ``n`` DMAs of ``src``'s shape on ``sem``."""
    def one(q, carry):
        pltpu.make_async_copy(src, dst, sem).wait()
        return carry
    lax.fori_loop(0, n, one, 0)


def _one_hot(s_ref, w_ref, at, k: int, transposed: bool):
    """The (tokens, 128) selection of staged rows ``at ..`` — or its
    transpose — each nonzero the assignment's weight (1 without ``w``),
    and whether each staged row is some assignment's."""
    if transposed:          # s, w (k, tokens): staged rows on sublanes
        row = lax.broadcasted_iota(jnp.int32, (_BLOCK, 1), 0) + at
        hits = [s_ref[j:j + 1, :] == row for j in range(k)]
        ws = [w_ref[j:j + 1, :] if w_ref is not None else 1.0
              for j in range(k)]
    else:                   # s, w (tokens, k): staged rows on lanes
        row = lax.broadcasted_iota(jnp.int32, (1, _BLOCK), 1) + at
        hits = [s_ref[:, j:j + 1] == row for j in range(k)]
        ws = [w_ref[:, j:j + 1] if w_ref is not None else 1.0
              for j in range(k)]
    pick = sum(jnp.where(h, w, 0.0) for h, w in zip(hits, ws))
    return pick, hits


def _gather_kernel(start_ref, rows_ref, s_ref, *refs, k: int, groups: int,
                   weighted: bool, dot: bool):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    dy_ref = refs.pop(0) if dot else None
    buf_hbm, o_ref, acc, stage, sem = refs
    dt = stage.dtype
    precision = lax.Precision.HIGHEST if dt == jnp.float32 else None
    staged = _windows(start_ref, rows_ref, buf_hbm, stage, sem,
                      pl.program_id(0), groups, read=True)
    _wait(buf_hbm.at[pl.ds(0, _SLOT)], stage.at[pl.ds(0, _SLOT)], sem,
          staged // _SLOT)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def block(c, carry):
        at = pl.multiple_of(c * _BLOCK, _BLOCK)
        rows = stage[pl.ds(at, _BLOCK), :]
        # rows past the staged ones hold what an earlier tile left there
        live = lax.broadcasted_iota(jnp.int32, (_BLOCK, 1), 0) + at < staged
        rows = jnp.where(live, rows, jnp.zeros_like(rows))
        pick, hits = _one_hot(s_ref, w_ref, at, k, transposed=False)
        if dot:
            # every token's dot product with every staged row; each
            # assignment keeps its own
            d = lax.dot_general(dy_ref[...], rows, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
            for j in range(k):
                acc[:, j:j + 1] += jnp.sum(jnp.where(hits[j], d, 0.0),
                                           axis=1, keepdims=True)
        else:
            acc[...] += jnp.dot(pick.astype(dt), rows, precision=precision,
                                preferred_element_type=jnp.float32)
        return carry

    lax.fori_loop(0, (staged + _BLOCK - 1) // _BLOCK, block, 0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def gather_rows(buf, starts, rows, s, w=None, dy=None, *, groups: int,
                plan: Plan):
    """buf (R, D); starts / rows (T groups,) int32 the windows of each
    tile of tokens; s (N, k) int32 each assignment's staged row, -1
    where it has none; w (N, k) float32 its weight, or None for 1.  -> y
    (N, D) in buf's dtype (the weight rounded to it); with ``dy`` (N, D)
    instead -> dw (N, k) float32, ``<buf[pos], dy[n]>``."""
    R, D = buf.shape
    N, k = s.shape
    tn = plan.tokens
    dot = dy is not None
    weighted = w is not None
    pair = pl.BlockSpec((tn, k), lambda t, *_: (t, 0))
    tile = pl.BlockSpec((tn, D), lambda t, *_: (t, 0))
    in_specs = [pair] + [pair] * weighted + [tile] * dot
    if dot:
        out_spec, out_shape = pair, jax.ShapeDtypeStruct((N, k), jnp.float32)
    else:
        out_spec, out_shape = tile, jax.ShapeDtypeStruct((N, D), buf.dtype)
    return pl.pallas_call(
        functools.partial(_gather_kernel, k=k, groups=groups,
                          weighted=weighted, dot=dot),
        name="moe_gather_dots" if dot else "moe_gather_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // tn,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((tn, k if dot else D), jnp.float32),
                pltpu.VMEM((plan.stage, D), buf.dtype),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=plan.vmem),
        interpret=plan.interpret,
    )(starts, rows, s, *([w] if weighted else []), *([dy] if dot else []),
      buf)


def _take_kernel(start_ref, rows_ref, kept_ref, s_ref, *refs, k: int,
                 groups: int, cap: int, weighted: bool):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    x_ref, o_hbm, stage, zeros, tail, sem, fill = refs
    t, last = pl.program_id(0), pl.num_programs(0) - 1
    dt = stage.dtype
    precision = lax.Precision.HIGHEST if dt == jnp.float32 else None
    ranks = kept_ref.shape[0]
    whole = zeros.at[pl.ds(0, _ZEROS)]
    part = zeros.at[pl.ds(0, _SLOT)]

    def dead(p):
        """Rank p's rows past its live ones, from the next whole 8-row
        tile: (first row, rows in whole _ZEROS, the 8-row tiles before)."""
        lo = p * cap + (kept_ref[p] + _SLOT - 1) // _SLOT * _SLOT
        n = (p + 1) * cap - lo
        few = n % _ZEROS // _SLOT
        return lo, n // _ZEROS, few

    # first: every whole 8-row tile past a rank's live rows is nought;
    # waited for at the end (no window reaches one)
    @pl.when(t == 0)
    def _():
        zeros[...] = jnp.zeros(zeros.shape, dt)
        for p in range(ranks):
            lo, many, few = dead(p)

            def small(q, carry):
                at = pl.multiple_of(lo + q * _SLOT, _SLOT)
                pltpu.make_async_copy(part, o_hbm.at[pl.ds(at, _SLOT)],
                                      fill.at[0]).start()
                return carry

            def big(q, carry):
                at = pl.multiple_of(lo + few * _SLOT + q * _ZEROS, _SLOT)
                pltpu.make_async_copy(whole, o_hbm.at[pl.ds(at, _ZEROS)],
                                      fill.at[1]).start()
                return carry
            lax.fori_loop(0, few, small, 0)
            lax.fori_loop(0, many, big, 0)

    # the tile's windows as they stand (the last tile's writes are done)
    one = (o_hbm.at[pl.ds(0, _SLOT)], stage.at[pl.ds(0, _SLOT)])
    staged = _windows(start_ref, rows_ref, o_hbm, stage, sem, t, groups,
                      read=True)
    _wait(*one, sem, staged // _SLOT)

    def block(c, carry):
        at = pl.multiple_of(c * _BLOCK, _BLOCK)
        pick, hits = _one_hot(s_ref, w_ref, at, k, transposed=True)
        mine = sum(jnp.sum(h.astype(jnp.int32), axis=1, keepdims=True)
                   for h in hits) > 0
        new = jnp.dot(pick.astype(dt), x_ref[...], precision=precision,
                      preferred_element_type=jnp.float32)
        rows = stage[pl.ds(at, _BLOCK), :]
        stage[pl.ds(at, _BLOCK), :] = jnp.where(mine, new.astype(dt), rows)
        return carry

    lax.fori_loop(0, (staged + _BLOCK - 1) // _BLOCK, block, 0)
    _windows(start_ref, rows_ref, o_hbm, stage, sem, t, groups, read=False)
    _wait(*one, sem, staged // _SLOT)

    # last: the rows of the tile a rank's live rows end in that no
    # assignment wrote are nought too; the fills are done
    @pl.when(t == last)
    def _():
        for p in range(ranks):
            live = kept_ref[p] % _SLOT

            @pl.when(live > 0)
            def _():
                at = pl.multiple_of(p * cap + kept_ref[p] // _SLOT * _SLOT,
                                    _SLOT)
                block = o_hbm.at[pl.ds(at, _SLOT)]
                pltpu.make_async_copy(block, tail, sem).start()
                pltpu.make_async_copy(block, tail, sem).wait()
                row = lax.broadcasted_iota(jnp.int32, (_SLOT, 1), 0)
                tail[...] = jnp.where(row < live, tail[...],
                                      jnp.zeros(tail.shape, dt))
                pltpu.make_async_copy(tail, block, sem).start()
                pltpu.make_async_copy(tail, block, sem).wait()

            _, many, few = dead(p)
            _wait(part, o_hbm.at[pl.ds(0, _SLOT)], fill.at[0], few)
            _wait(whole, o_hbm.at[pl.ds(0, _ZEROS)], fill.at[1], many)


def take_rows(x, starts, rows, s_t, kept, w_t=None, *, R: int, cap: int,
              groups: int, plan: Plan):
    """x (N, D); starts / rows (T groups,) int32 the windows of each tile
    of tokens; s_t (k, N) int32 each assignment's staged row, -1 where it
    has none; kept (ranks,) int32 the live rows of each rank's ``cap``;
    w_t (k, N) float32 each assignment's weight, or None for 1.  -> (R, D)
    in x's dtype: ``buf[pos[n, j]] = w[n, j] x[n]`` (the weight rounded
    to the dtype), nought past each rank's live rows."""
    N, D = x.shape
    k = s_t.shape[0]
    tn = plan.tokens
    weighted = w_t is not None
    pair = pl.BlockSpec((k, tn), lambda t, *_: (0, t))
    return pl.pallas_call(
        functools.partial(_take_kernel, k=k, groups=groups, cap=cap,
                          weighted=weighted),
        name="moe_take_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // tn,),
            in_specs=[pair] + [pair] * weighted
            + [pl.BlockSpec((tn, D), lambda t, *_: (t, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((plan.stage, D), x.dtype),
                pltpu.VMEM((_ZEROS, D), x.dtype),
                pltpu.VMEM((_SLOT, D), x.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=plan.vmem),
        interpret=plan.interpret,
    )(starts, rows, kept, s_t, *([w_t] if weighted else []), x)
