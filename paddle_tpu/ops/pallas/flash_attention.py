"""Flash attention — pallas TPU kernels (forward AND backward).

Reference parity: the capability of ``operators/fused/fused_attention_op.cu``
(+ cuDNN attention) — attention without materialising the (T, T) score
matrix in HBM.  Mechanism is the TPU one: pallas kernels that hold or
stream K/V through VMEM, keeping the softmax statistics in f32 while the
matmuls ride the MXU.

Which kernels a call runs is decided once, by ``_plan``, from the layout,
the per-shard shapes and the chip's VMEM — never from an option.  The
selection name is what ``ops.pallas.selections()`` counts:

========================  ===============  ========================  =====  ========  ==========
layout and lengths        selection        kernels, forward /        cell   d_v != d  window
                                           backward
========================  ===============  ========================  =====  ========  ==========
stacked, T <= 512         packed_small     _qkv_fwd_kernel /         none   (one d)   (none)
                                           _qkv_bwd_kernel
stacked, T <= 2048        packed_mid       _qkv_fwd_kernel /         GPT    (one d)   (none)
                                           _qkv_mid_bwd_kernel
stacked, anything else    (split; then as the folded layout below)
folded, T, Tk <= 1024     small            _small_fwd_kernel /       none   XLA math  the
                                           _small_bwd_kernel         resident pair
                                           (Tk <= 512),                     below
                                           _tiled_bwd_kernel
                                           (beyond)
folded, T, Tk <= 4096     mid              _small_fwd_kernel /       none   XLA math  the
                                           _tiled_bwd_kernel                resident pair
folded, longer, resident  stream and       _resident_fwd_kernel /    LFM2   kernels   kernels,
budget fits the chip      stream_resident  _resident_bwd_kernel      Q3N              the band
                                                                     Mellum2          alone:
                                                                                      _window
split keys, as the        stream and       _latent_fwd_kernel /      JoyAI  (dv)      (none)
resident pair above       stream_resident  _latent_bwd_kernel
                          _latent
folded, longer, it does   stream           _fwd_kernel_pipelined /   none   XLA math  XLA math
not fit                                    _bwd_dq_kernel,
                                           _bwd_dkv_kernel
a length no multiple of   (XLA math, both directions; counted as     none             the mask
128, causal T > Tk, off   ``flash_attention.xla``)
a TPU without FORCE
========================  ===============  ========================  =====  ========  ==========

stacked is (3, B, T, H*d), folded (B*H, T, d), split keys the token-major
operands of ``flash_attention_latent`` (below).  GPT is the benchmark cell
``gpt2-medium.train-t1024``, LFM2 is ``lfm2-24b-a2b.train-t8192`` (d =
64), Q3N ``qwen3-next-80b-a3b.train-t8192`` (d = 256), JoyAI
``joyai-llm-flash.train-t8192``.  ``d`` is the head size of q and k,
``d_v`` that of v and the output: ``flash_attention`` takes a ``d_v`` of
its own (192 over 128, the last column: latent attention's concatenated
heads; the split-key kernels budget by that plan).  The resident pair
carries it — V rows, the output, dO, the output accumulator and the
dV accumulator at ``d_v``, q, K, dq and dK at ``d``, the VMEM budget from
both —, every other regime hands such a call to the XLA math, counted
``flash_attention.xla``; no cell depends on that.

Latent attention (JoyAI) calls ``flash_attention_latent`` instead: a
query head is [q_n | q_r], a key head [k_n | k_r] with one k_r shared by
every head, and the operands come token-major, (B, T, H*d) as their
projections write them — q_n, k_n and v a 128-lane column block a head,
q_r two heads of 64 a block, k_r once a row.  Where ``_plan`` takes the
resident pair for the concatenated heads and the sizes are those
(``_latent_tiles``), the split-key kernels run the pair's tile math on s
= q_n k_nᵀ + q_r k_rᵀ, each grid step's DMA fetching one head's columns
(no fold, no concat, no repeat of k_r in HBM), and hand back what the
pair hands back: out and lse, dq and dk [own | rotated] wide, dv,
head-major.  Elsewhere the concatenated heads run under the same plan,
as ``flash_attention`` runs them.

``window`` (a sliding window, causal only: query i sees key j where ``0
<= i + Tk - T - j < window``) is an argument of the call.  A windowed
call takes the resident pair at any length its budget fits — the small
and mid regimes would take their whole rows — and runs each q block over
the key chunks of its band alone: ``_live_chunks`` gives the lower edge
beside the diagonal, the mask is built on the chunks either edge
crosses, and dK/dV accumulate over the same band.  Where the pair does
not fit, the XLA math takes the call with the same mask.  The selection
is ``flash_attention.stream_resident_window`` and the two calls carry
``name=`` (``flash_window_fwd``, ``flash_window_bwd``), which a call with
no window does not: the device trace tells a window layer's pair from a
full layer's, whose shapes are the same.  A window that reaches every
key (``window >= Tk``) is causal attention, and the call is one.
The Mellum2 cell is ``mellum2-12b-a2.5b.train-t8192`` (window 1024 in
three layers of four, d = 128).
"stacked" takes its kernels when the head size is 32, 64 or 128 and the
heads fill 128-lane column blocks.  The regimes:

- small: whole K/V rows and the whole (block_q, Tk) score row in VMEM, G
  batch-heads a grid step, one fused backward that rebuilds lse and delta
  in-kernel; residuals are (q, k, v) alone.
- mid: the same design, q blocks tiled.  What bounds it is the f32
  (block_q, Tk) score row and its companions, not K and V.  In a causal
  call q block qi takes the key columns up to its diagonal alone —
  [0, extent(qi)), extent = (qi + 1) * block_q + Tk - T rounded up to the
  plan's granule — and builds the mask on the tile the diagonal crosses:
  the extent is static, one kernel body a distinct extent under
  ``pl.when`` (at most 8; ``_for_extent``), so the K/V rows, the score
  row and the dK/dV accumulators are sliced, and what the mask would
  throw away is never computed.  The GPT cell: two q blocks of 512,
  extents 512 and 1024, 3 of 4 score tiles.  The folded small and mid
  kernels and packed_small's forward take the same extents wherever
  they tile q blocks.
- stream: the score intermediates are bounded to (block_q, chunk) whatever
  Tk is.  *Resident* form: K and V rows stay in VMEM for all q blocks of a
  head (fetched once a head); a ``fori_loop`` runs over the key chunks the
  causal mask leaves live for this q block and builds the mask only on the
  chunks the diagonal crosses; the forward carries the online softmax and
  emits lse; ONE fused backward (5 matmuls and one exponential pass a live
  tile) writes dq per q block and accumulates dK/dV in f32 VMEM scratch.
  Its VMEM is ``_resident_vmem_bytes``: 35.7 MB at Tk = 8192, d = 64 or
  128 (49.0 MB at 192 over 128: K rows padded to 256 lanes, V rows 128),
  bf16, blocks of 512 (the compiler takes 28 to 32 MB for the
  backward, 20 MB for the forward at 1024 x 1024) — over Mosaic's default
  scoped limit (16 MiB, a compiler default and not the chip's VMEM), so the
  pair asks for its budget and a quarter more through ``vmem_limit_bytes``
  (44.6 MB) and is taken when that is within ``_RESIDENT_VMEM_SHARE`` of
  what the installed jax reports for the chip (128 MiB a core on a v5e:
  rows to Tk = 16384 in bf16).  *Grid-streamed* form, for rows whose
  budget does not fit: K/V blocks ride the innermost grid dimension with
  the online-softmax state in scratch, dq and dk/dv are two kernels; dead
  tiles are still grid steps, but their index maps are clamped to the last
  live block, so they fetch nothing.  Both forms keep ``out`` and ``lse``
  for the backward under the checkpoint names ``RESIDUAL_NAMES``: a remat
  policy that lists them never runs the forward a second time.  ``out`` is
  kept in the caller's (B, T, H, d) layout, so a model that saves its
  attention output saves these bytes once.

Under every kernel lies one copy of the tile math: ``_causal_mask``,
``_row_fwd`` / ``_row_bwd`` (a score row, whole or to its extent) and
``_online_softmax_step`` / ``_saved_lse_bwd_tile`` (one key chunk; the
split-key kernels form their scores themselves and call the scores-in
forms ``_online_softmax_scores`` / ``_saved_lse_ds``).  A kernel body
holds only what is its own: how it slices its refs and where it
accumulates.

On a TPU the kernels are always compiled; ``PADDLE_PALLAS_FORCE=1`` takes
them in interpret mode off-TPU (the kernel unit tests).  Under a mesh of
more than one device the public entries take the mesh and the axes that
shard batch and heads, and run the kernels per shard
(``ops.pallas.shard_kernel``) — GSPMD cannot partition a Mosaic call.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from . import (NN, NT, TN, axes_entry, dot, enabled, note, on_tpu,
               shard_kernel, traced_once)

__all__ = ["flash_attention", "flash_attention_latent",
           "flash_attention_stacked"]

NEG_INF = -1e30

# Largest K-length whose full (T, T) score block comfortably fits VMEM
# f32 alongside the resident K/V blocks — the "small-T" kernel regime.
SMALL_T_MAX = 1024
# Largest K-length whose whole (block_q, Tk) f32 score row stays in VMEM
# (the "mid" regime: q-block-tiled forward + one fused backward with
# in-kernel lse/delta).  Bounded by the backward's ~3-5 live f32
# (block_q, Tk) intermediates under Mosaic's default 16 MiB scoped
# limit, which these kernels do not raise.  Beyond this the "stream"
# regime bounds the score tile to (block_q, chunk): K/V rows resident
# under a requested VMEM limit while they fit, grid-streamed after.
MID_T_MAX = 4096
# Names the stream regime's residuals are saved under (a remat policy
# that lists them keeps the forward from running twice).
RESIDUAL_NAMES = ("flash_out", "flash_lse")
# Share of the chip's VMEM the resident pair may ask for; the rest is
# Mosaic's own (internal scratch, semaphores, spills).
_RESIDENT_VMEM_SHARE = 0.75
# The names a windowed call's forward and backward carry into the device
# trace (a call with no window carries none: its names are the ones the
# benchmark's patterns have found since PR 27)
WINDOW_NAMES = ("flash_window_fwd", "flash_window_bwd")


# ---------------------------------------------------------------------------
# the plan: which kernels a call runs, and with which numbers
# ---------------------------------------------------------------------------
class _Plan(NamedTuple):
    """What one call runs, fixed before its ``custom_vjp`` so that the
    forward and the backward rule read the same record."""
    name: str                   # the selection as note() counts it, or "xla"
    interpret: bool = False     # False on a TPU, always
    # (block_q, key columns, batch rows or batch-heads a grid step) of each
    # direction; None where the kernel takes the rows whole.  The key
    # columns are the chunk a step of the stream kernels takes or, in a
    # causal call of a whole-row kernel that tiles its q blocks, the
    # granule a q block's extent is rounded up to (_causal_extents)
    fwd: tuple = (None, None, 1)
    bwd: tuple = (None, None, 1)
    vmem_limit: Optional[int] = None    # the resident pair's request
    window: Optional[int] = None        # the call's sliding window


def _kernels_apply(T: int, Tk: int, causal: bool) -> bool:
    """Whether a call of these lengths takes kernels at all — what a
    public entry needs before the per-shard shapes are known: XLA math
    is left to GSPMD, kernels are wrapped per shard.

    The kernels are Mosaic/TPU-only and tile in 128-row blocks.  causal
    with T > Tk has fully-masked query rows whose lse degenerates to
    NEG_INF (float cancellation makes exp(s - lse) == 1 in the backward
    instead of 1/Tk) — that configuration stays on the XLA path."""
    return T % 128 == 0 and Tk % 128 == 0 and not (causal and T > Tk) \
        and enabled()


def _block(n: int, want: int) -> int:
    """The block a length is tiled in: ``want`` rows where they divide
    it, else 128 (which every length that reaches a kernel is a multiple
    of)."""
    block = want if n % want == 0 else 128
    assert n % block == 0, (n, want)
    return block


def _dividing(n: int, cap: int) -> int:
    """The largest of cap, cap/2, cap/4 .. that divides n (at least 1)."""
    cap = max(1, cap)
    while n % cap:
        cap //= 2
    return cap


def _granule(T: int, Tk: int, block_q: int, causal: bool) -> Optional[int]:
    """The key columns a causal whole-row kernel rounds each q block's
    extent up to (:func:`_causal_extents`; None: every q block takes the
    whole row): whole q blocks and whole 128-lane tiles, and so many of
    them that a kernel holds at most 8 bodies, one a distinct extent."""
    if not causal or T == block_q:
        return None
    step = math.lcm(block_q, 128)
    return step * -(-Tk // (8 * step))


def _resident_vmem_bytes(Tk: int, d: int, itemsize: int, block_q: int,
                         chunk: int, d_v: Optional[int] = None) -> int:
    """VMEM the fused backward (the larger of the pair) holds: every
    BlockSpec'd operand twice (Mosaic double-buffers them), rows padded
    to whole 128-lane tiles.  q, K and their gradients are ``d`` wide, V,
    dO and dV ``d_v`` (``d`` where it is left out)."""
    lanes = -(-d // 128) * 128
    lanes_v = lanes if d_v is None else -(-d_v // 128) * 128
    rows = Tk * (lanes + lanes_v)            # a K row and a V row
    resident = 2 * (rows * itemsize          # K, V
                    + rows * itemsize)       # dK, dV output blocks
    accumulators = rows * 4                  # dK, dV in f32
    q_sized = 2 * ((2 * lanes + lanes_v) * block_q * itemsize  # q, dq, dO
                   + 2 * block_q * 128 * 4)           # lse, delta: 1 lane
    dq_acc = block_q * lanes * 4
    # s, p, dp, ds in f32, p and ds again in the operand dtype, and the
    # transposes of those two for the contractions over rows
    tiles = 8 * block_q * chunk * 4
    return resident + accumulators + q_sized + dq_acc + tiles


def _vmem_capacity() -> int:
    """VMEM bytes of one core as the installed jax reports for the
    attached chip; with no chip attached (interpret mode, a device-less
    compile) the smallest of the generations it lists beyond v3."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except ValueError:          # the device is no TPU jax knows
        return 64 << 20


def _plan(layout: str, B: int, T: int, Tk: int, heads: int, d: int,
          itemsize: int, causal: bool, d_v: Optional[int] = None,
          window: Optional[int] = None) -> _Plan:
    """The one place that turns a call's (per-shard) shapes into kernels
    and block sizes.  ``layout`` is "stacked" ((3, B, T, heads*d)) or
    "folded" ((B*heads, T, d)); a stacked call whose shape the stacked
    kernels do not take gets the folded plan it then runs split.  ``d``
    is the head size of q and k, ``d_v`` that of v and the output where
    it differs (latent attention: 192 over 128); the resident pair alone
    takes such a call, every other regime hands it to the XLA math.  A
    ``window`` takes the resident pair at any length, or the XLA math."""
    if not _kernels_apply(T, Tk, causal):
        return _Plan("xla", window=window)
    unequal = d_v is not None and d_v != d
    interpret = not on_tpu()
    # G, the rows (batch rows, batch-heads) a grid step takes, is at most
    # what VMEM holds at this T — so many tiles of 512 x 512 scores —
    # halved until it divides their number
    tile = 512 * 512

    # v5e, bf16, d=64, B*H=1536 (profiled round 4): XLA's attention at
    # T=512 materialises f32 (T, T) score tensors in the backward and
    # costs ~21 ms/layer fwd+bwd; the small-T kernel pair (full-K
    # resident, G batch-heads per grid step, one fused backward) beats
    # it.  The mid kernels carry the same design to T<=MID_T_MAX (4096);
    # the stream regime owns anything longer.
    small = Tk <= SMALL_T_MAX and T <= SMALL_T_MAX and window is None
    mid = not small and Tk <= MID_T_MAX and T <= MID_T_MAX \
        and window is None
    if unequal and (small or mid):
        return _Plan("xla")

    if layout == "stacked" and (small or mid) and T <= 2048 \
            and d in (32, 64, 128) and heads % max(1, 128 // d) == 0:
        # packed_small: T <= 512, whole rows a step.  packed_mid:
        # 512 < T <= 2048 — the q-block-tiled backward with dK/dV scratch
        # accumulation per 128-lane column block keeps VMEM bounded.
        # T=4096, odd head sizes and head counts that do not fill a
        # column block run split.
        if T <= 512:
            bq = _block(T, 512)
            # backward: ~4 f32 (T, T) intermediates per unrolled batch
            # row: one row a step at T=512, more as the row shortens
            return _Plan(
                "packed_small", interpret,
                fwd=(bq, _granule(T, T, bq, causal),
                     _dividing(B, min(4, 4 * tile // (bq * T)))),
                bwd=(None, None, _dividing(B, min(2, tile // (T * T)))))
        # ~4 live f32 (block_q, T) intermediates + 2 f32 (T, 128) scratch
        # accumulators + 2 resident (T, 128) K/V column blocks + the
        # backward's resident (3, T, 128) output block, the blocks double-
        # buffered: bf16 at block_q=256/T=2048 totals ~14 MB of the 16 MB
        # scoped VMEM; f32 doubles every block and measured 17.30 MB at
        # block_q=128/T=2048 and 16.14 MB at 64 (the resident blocks alone
        # are 12 MB), so f32 takes 128 to T = 1024 and 32 past it.
        # bf16 takes the longest q block whose f32 score row is 2 MB, now
        # that a causal q block stops at its diagonal: a shorter block
        # skips more of what the mask throws away and still loses, because
        # short rows run the MXU and the vector passes worse.  One call
        # alone on a v5e, B*H = 512, T = 1024, d = 64, bf16, causal, ms
        # (PERF.md, PR 34; live = share of the score tiles computed):
        #   block_q          forward  backward  live
        #   256, whole rows  1.876    5.017     16/16   (to PR 33)
        #   512, whole rows  1.762    4.417     4/4
        #   128, extents     2.187    4.620     36/64
        #   256, extents     1.494    3.466     10/16
        #   512, extents     1.283    3.159     3/4     (taken)
        #   512 in two halves of 256, each to its own extent (10/16):
        #                    1.346    3.429
        if itemsize >= 4:
            bq = 128 if T <= 1024 else 32
        else:
            bq = 512 if T <= 1024 else 256
        bq = _dividing(T, bq)
        tiles = (bq, _granule(T, T, bq, causal), 1)
        return _Plan("packed_mid", interpret, fwd=tiles, bwd=tiles)

    BH = B * heads
    if small or mid:
        # forward (_small_fwd_kernel): the head-batching scales down as
        # the resident (block_q, Tk) score block grows so that the
        # per-step VMEM footprint stays ~flat
        want = 512 if Tk <= 1024 else 256
        bq = _block(T, want)
        G = min(8, 8 * tile // (bq * Tk)) if small \
            else 4 * tile // (want * Tk)
        if small and Tk <= 512:
            # the fully-unrolled whole-row backward holds several f32
            # (T, Tk) intermediates per unrolled group and shrinks G as
            # the row grows; beyond Tk=512 its ~5 live (T, Tk) tensors
            # brush the 16M VMEM limit (ADVICE r4) and the tiled backward
            # is the same math with bounded residency
            bwd = (None, None,
                   _dividing(BH, min(2, 2 * tile // (T * Tk))))
        else:
            # _tiled_bwd_kernel: ~5 live f32 (block_q, Tk) intermediates
            # + 2 f32 (Tk, d) scratch accumulators: at Tk=4096,
            # block_q=256 measured 22.2M and even 128 sat 176K over the
            # 16M scoped VMEM — 64 leaves ~5M headroom
            bq_bwd = _block(T, want if Tk <= 2048 else 64)
            bwd = (bq_bwd, _granule(T, Tk, bq_bwd, causal), 1)
        return _Plan("small" if small else "mid", interpret,
                     fwd=(bq, _granule(T, Tk, bq, causal), _dividing(BH, G)),
                     bwd=bwd)

    # stream.  The resident pair's blocks: the largest power-of-two
    # multiples of 128 that divide the lengths, up to what a v5e measured
    # best at T = 8192, d = 64 (PERF.md, PR 31).  The forward wants long
    # chunks — its per-chunk rescaling of the (block_q, 1) statistics and
    # of the accumulator costs as much as a 256-column slab of scores:
    # 33.7 ms at 512 x 512, 21.9 at 1024 x 1024, 23.7 at 1024 x 2048.  The
    # backward has no such pass and is flat from 512 x 512 (41.7 ms) to
    # 1024 x 1024 (42.3); it takes the smaller tiles for their VMEM.
    bwd = (_dividing(T, 512), _dividing(Tk, 512), 1)
    need = _resident_vmem_bytes(Tk, d, itemsize, *bwd[:2], d_v)
    # a quarter on top for what Mosaic allocates beside the operands
    limit = need + need // 4
    if limit <= _RESIDENT_VMEM_SHARE * _vmem_capacity():
        return _Plan("stream_resident", interpret,
                     fwd=(_dividing(T, 1024), _dividing(Tk, 1024), 1),
                     bwd=bwd, vmem_limit=limit, window=window)
    if unequal or window is not None:
        return _Plan("xla", window=window)
    return _Plan("stream", interpret,
                 fwd=(_block(T, 256), _block(Tk, 512), 1),
                 bwd=(_block(T, 256), _block(Tk, 256), 1))


# ---------------------------------------------------------------------------
# the tile math: one copy, under every kernel.  The operation order in each
# routine is that of the kernels the cells run (_qkv_fwd_kernel,
# _qkv_mid_bwd_kernel, _resident_*), so that those trace to the programs
# that were measured; the two chunk routines take the scratch refs they
# update, so that the scratch is read and written where it was among the
# matmuls.  The matmul itself, ``dot``, is the package's.
# ---------------------------------------------------------------------------
def _causal_mask(shape, qi, block_q: int, offset: int, j=None,
                 chunk: int = 0, window: Optional[int] = None):
    """True where a score tile's query row sees its key column: row r of
    q block ``qi`` sees columns <= qi * block_q + r + offset, and with a
    ``window`` only the last ``window`` of them.  The tile is key chunk
    ``j`` of ``chunk`` columns, or (j None) starts at column 0 (no
    window there: the whole-row kernels take none)."""
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    if j is None:
        # the whole-row kernels add the offset (a static 0 wherever
        # T = Tk) to the rows, the chunk kernels to the block's origin:
        # each as it was measured, so that the LFM2 cell's kernels and
        # the whole-row kernels of one q block trace to the programs
        # they did before there was one mask
        return rows + qi * block_q + offset \
            >= lax.broadcasted_iota(jnp.int32, shape, 1)
    rows = rows + (qi * block_q + offset)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1) + j * chunk
    if window is None:
        return rows >= cols
    return (rows >= cols) & (rows - cols < window)


def _mask_row(s, mask):
    """A (bq, Tk) score row with its masked scores at NEG_INF.  ``mask``:
    None, or (start, shape -> bool): the columns from ``start`` (whole
    128-lane tiles in front of it) lie under the tile the callable
    builds, those in front of it hold no masked score and are not
    touched."""
    if mask is None:
        return s
    start, tile = mask
    if not start:
        return jnp.where(tile(s.shape), s, NEG_INF)
    tail = s[:, start:]
    return jnp.concatenate(
        [s[:, :start], jnp.where(tile(tail.shape), tail, NEG_INF)], axis=1)


def _row_fwd(q, k, v, mask, scale: float):
    """Attention of (bq, d) queries over whole (Tk, d) K/V rows -> the
    (bq, d) output in f32.  ``mask`` as for :func:`_mask_row` (applied
    once the scores exist).  scale folds into the f32 scores."""
    s = _mask_row(dot(q, k, NT) * scale, mask)           # (bq, Tk)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return dot(p.astype(v.dtype), v, NN) / l


def _row_bwd(q, k, v, do, mask, scale: float):
    """The fused whole-row backward from (q, k, v, do) alone — lse and
    delta are rebuilt in VMEM (2 extra vector passes, zero extra matmuls
    vs. the 7 a two-kernel backward spends) -> (dq, dk, dv).  dq is
    final for these rows (every key was seen) and comes back in the
    operand dtype; dk and dv are this q block's share, in f32."""
    s = _mask_row(dot(q, k, NT) * scale, mask)           # (bq, Tk)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / l                                            # softmax, f32
    dp = dot(do, v, NT)                                  # (bq, Tk)
    # delta_i = sum_j p_ij dp_ij  (== rowsum(dO * O), derived in-kernel
    # so O need not be a residual)
    delta = jnp.sum(p * dp, axis=-1, keepdims=True)
    dv = dot(p.astype(do.dtype), do, TN)                 # (Tk, d)
    ds = (p * (dp - delta)).astype(q.dtype)
    dq = (scale * dot(ds, k, NN)).astype(q.dtype)
    dk = scale * dot(ds, q, TN)                          # (Tk, d)
    return dq, dk, dv


def _zero_on_first(i, *scratch):
    """Clear accumulators at step 0 of the grid dim they live across."""
    @pl.when(i == 0)
    def _init():
        for ref in scratch:
            ref[...] = jnp.zeros_like(ref)


def _online_softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax_step(q, k, v, mask, scale: float, m_scr, l_scr,
                         acc_scr):
    """One (bq, chunk) tile of the streamed forward, folded into the
    running max, sum and accumulator.  ``mask`` as for :func:`_row_fwd`.
    Chunk 0 is live for every row (column 0 is), so m is finite from the
    first step on and a row wholly masked in a later chunk adds
    exp(NEG_INF - m) = 0."""
    _online_softmax_scores(dot(q, k, NT) * scale, v, mask, m_scr, l_scr,
                           acc_scr)


def _online_softmax_scores(s, v, mask, m_scr, l_scr, acc_scr):
    """:func:`_online_softmax_step` from a tile's scaled scores ``s``
    (bq, chunk), however they were formed."""
    if mask is not None:
        s = jnp.where(mask(s.shape), s, NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + dot(p.astype(v.dtype), v, NN)
    m_scr[...] = m_new


def _online_softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    lse_ref[0] = m_scr[...] + jnp.log(l)


def _saved_lse_bwd_tile(q, k, v, do, lse, delta, mask, scale: float, *,
                        dq=None, dk=None, dv=None, scale_each=False):
    """One (bq, chunk) tile of the streamed backward: p = exp(s - lse)
    from the saved lse, ds, and those of the three products a kernel
    gives a place: each of ``dq`` / ``dk`` / ``dv`` is None or a
    (scratch ref, index) the product is added into.  A masked score is
    zeroed after the exponential alone (masking s first as well, as the
    grid-streamed kernels once did, gives the same zeros).  The products
    lack ``scale``: the resident kernel applies it once to the finished
    sums; the grid-streamed kernels scale every tile's product
    (``scale_each``), as they always have, so that their sums round as
    they did."""
    ds = _saved_lse_ds(dot(q, k, NT) * scale, v, do, lse, delta, mask,
                       dv).astype(q.dtype)
    fold = (lambda x: scale * x) if scale_each else (lambda x: x)
    if dq is not None:
        ref, at = dq
        ref[at] += fold(dot(ds, k, NN))
    if dk is not None:      # s = scale q k^T  =>  dK += scale dS^T q
        ref, at = dk
        ref[at] += fold(dot(ds, q, TN))                  # (chunk, d)


def _saved_lse_ds(s, v, do, lse, delta, mask, dv=None):
    """:func:`_saved_lse_bwd_tile` from a tile's scaled scores ``s``, up
    to dS in float32 (dV added where ``dv`` gives it a place); the
    products with q and k are the caller's."""
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask(s.shape), p, 0.0)
    dp = dot(do, v, NT)                                  # (bq, chunk)
    if dv is not None:      # dV += P^T dO
        ref, at = dv
        ref[at] += dot(p.astype(do.dtype), do, TN)       # (chunk, d)
    return p * (dp - delta)


# ---------------------------------------------------------------------------
# stream regime, grid-streamed form
# ---------------------------------------------------------------------------
def _live_chunks(qi, block_q: int, chunk: int, offset: int, nk: int,
                 causal: bool = True, window: Optional[int] = None):
    """(n_lo, n_clean, n_full, n_live) for q block ``qi``, whose row r
    sees columns <= r + offset (and with a ``window`` only the last
    ``window`` of them): key chunks [n_clean, n_full) hold no masked
    score, chunks [n_full, n_live) are crossed by the diagonal, chunks
    [n_lo, n_clean) by the window's lower edge (0 and 0 without a
    window), chunks outside [n_lo, n_live) are dead.  ``qi`` may be a
    Python int or a traced scalar."""
    if not causal:
        return 0, 0, nk, nk
    n_full = jnp.minimum(nk, (qi * block_q + offset + 1) // chunk)
    n_live = jnp.minimum(nk, ((qi + 1) * block_q - 1 + offset) // chunk + 1)
    if window is None:
        return 0, 0, n_full, n_live
    # the block's first row sees down to its diagonal less window - 1,
    # its last row only its own: every chunk from there on is clean
    n_lo = jnp.maximum(0, (qi * block_q + offset - window + 1) // chunk)
    lowest = (qi + 1) * block_q + offset - window
    n_clean = jnp.clip(-(-lowest // chunk), n_lo, n_live)
    return n_lo, n_clean, jnp.maximum(n_full, n_clean), n_live


def _grid_tile(qi, ki, causal: bool, block_q: int, block_k: int,
               offset: int):
    """(live, mask) of grid step (q block qi, k block ki): whether the
    causal mask leaves the tile any score, and its mask (every live tile
    is masked, not the diagonal ones alone)."""
    if not causal:
        return True, None
    return (qi + 1) * block_q - 1 + offset >= ki * block_k, \
        lambda shape: _causal_mask(shape, qi, block_q, offset, ki, block_k)


def _fwd_kernel_pipelined(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                          acc_scr, *, scale: float, causal: bool,
                          block_q: int, block_k: int, nk: int, offset: int):
    """K-blocks ride the innermost ('arbitrary') grid dimension so Mosaic
    double-buffers the K/V block DMAs against the matmuls; the online
    softmax state lives in VMEM scratch across those grid steps."""
    ki = pl.program_id(2)
    live, mask = _grid_tile(pl.program_id(1), ki, causal, block_q, block_k,
                            offset)

    @pl.when(ki == 0)
    def _init():
        _online_softmax_init(m_scr, l_scr, acc_scr)

    @pl.when(live)
    def _compute():
        _online_softmax_step(q_ref[0], k_ref[0], v_ref[0], mask, scale,
                             m_scr, l_scr, acc_scr)

    @pl.when(ki == nk - 1)
    def _finalize():
        _online_softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _clamped_k_map(block_q, block_k, offset, nk, causal):
    """Index map of a K/V block over a (b, q block i, k block j) grid: a
    step the causal mask leaves dead names the last live block again, so
    that Mosaic (which fetches only when the index changes) fetches
    nothing for it."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def index(b, i, j):
        n_live = _live_chunks(i, block_q, block_k, offset, nk)[-1]
        return b, jnp.minimum(j, n_live - 1), 0
    return index


def _clamped_q_map(block_q, block_k, offset, causal):
    """The same over a (b, k block j, q block i) grid, for a q-sized
    block: the q blocks in front of the first whose last row sees column
    ``j * block_k`` are dead steps and name that one."""
    if not causal:
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (
        b, jnp.maximum(i, (j * block_k - offset) // block_q), 0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool, block_q: int,
                   block_k: int, nk: int, offset: int):
    """dQ: grid over q blocks, the k blocks innermost."""
    ki = pl.program_id(2)
    live, mask = _grid_tile(pl.program_id(1), ki, causal, block_q, block_k,
                            offset)

    _zero_on_first(ki, dq_scr)

    @pl.when(live)
    def _compute():
        _saved_lse_bwd_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                            lse_ref[0], delta_ref[0], mask, scale,
                            dq=(dq_scr, ...), scale_each=True)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int, nq: int,
                    offset: int):
    """dK/dV: grid over k blocks, the q blocks innermost."""
    qi = pl.program_id(2)
    live, mask = _grid_tile(qi, pl.program_id(1), causal, block_q, block_k,
                            offset)

    _zero_on_first(qi, dk_scr, dv_scr)

    @pl.when(live)
    def _compute():
        _saved_lse_bwd_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                            lse_ref[0], delta_ref[0], mask, scale,
                            dk=(dk_scr, ...), dv=(dv_scr, ...),
                            scale_each=True)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _delta(do, o):
    """D_i = rowsum(dO * O), (BH, T, 1) f32 — one fused elementwise
    reduce in XLA."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)


def _flash_bwd(q, k, v, o, lse, do, scale: float, causal: bool,
               plan: _Plan):
    BH, T, d = q.shape
    Tk = k.shape[1]
    block_q, block_k, _ = plan.bwd
    nq, nk = T // block_q, Tk // block_k
    delta = _delta(do, o)
    params = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, offset=Tk - T)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec(
        (1, block_k, d), _clamped_k_map(block_q, block_k, Tk - T, nk, causal))
    r_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **params),
        grid=(BH, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=semantics,
        interpret=plan.interpret,
    )(q, k, v, do, lse, delta)

    # dkv grid: (BH, k blocks, q blocks) — same specs re-indexed
    q_map = _clamped_q_map(block_q, block_k, Tk - T, causal)
    qs = pl.BlockSpec((1, block_q, d), q_map)
    ks = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rs = pl.BlockSpec((1, block_q, 1), q_map)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **params),
        grid=(BH, nk, nq),
        in_specs=[qs, ks, ks, qs, rs, rs],
        out_specs=[ks, ks],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (k, v)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=semantics,
        interpret=plan.interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# stream regime, resident form: K/V rows stay in VMEM for every q block of
# a head, a loop inside the kernel runs over the key chunks the causal
# mask leaves live, one fused backward
# ---------------------------------------------------------------------------
def _for_live_chunks(step, qi, live, causal: bool, block_q: int, chunk: int,
                     offset: int, window: Optional[int] = None):
    """``step(rows, mask)`` on every key chunk ``live`` (what
    :func:`_live_chunks` gave for q block ``qi``) names: with a window
    first the chunks its lower edge crosses, with their mask; then the
    chunks that hold no masked score; then, with their mask, those the
    diagonal crosses.  ``rows`` slices the chunk out of a resident (Tk,
    d) row block."""
    n_lo, n_clean, n_full, n_live = live

    def run(j, masked):
        step(pl.ds(pl.multiple_of(j * chunk, chunk), chunk),
             (lambda shape: _causal_mask(shape, qi, block_q, offset, j,
                                         chunk, window)) if masked else None)

    if window is not None:
        lax.fori_loop(n_lo, n_clean, lambda j, c: run(j, True), None)
    lax.fori_loop(n_clean, n_full, lambda j, c: run(j, False), None)
    if causal:
        lax.fori_loop(n_full, n_live, lambda j, c: run(j, True), None)


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                         acc_scr, *, scale: float, causal: bool,
                         block_q: int, chunk: int, nk: int, offset: int,
                         window: Optional[int] = None):
    """The online softmax over the live key chunks of the resident rows.
    A row wholly masked in the first chunk of a window's band adds 1s
    there; its next chunk holds a live column, and the correction
    exp(NEG_INF - m) = 0 takes them out again."""
    qi = pl.program_id(1)
    live = _live_chunks(qi, block_q, chunk, offset, nk, causal, window)
    _online_softmax_init(m_scr, l_scr, acc_scr)
    q = q_ref[0]                                         # (bq, d)

    def step(rows, mask):
        _online_softmax_step(q, k_ref[0, rows, :], v_ref[0, rows, :], mask,
                             scale, m_scr, l_scr, acc_scr)

    _for_live_chunks(step, qi, live, causal, block_q, chunk, offset, window)
    _online_softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _resident_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                         *, scale: float, causal: bool, block_q: int,
                         chunk: int, nq: int, nk: int, offset: int,
                         window: Optional[int] = None):
    """q blocks ride the inner ('arbitrary') grid dim; for each, the
    live key chunks of the resident K/V rows: dq accumulated over the
    chunks and written per q block, dK/dV accumulated in f32 scratch
    rows until the head's last q block (a window's band alone: rows
    below it keep their zeros)."""
    qi = pl.program_id(1)
    live = _live_chunks(qi, block_q, chunk, offset, nk, causal, window)

    _zero_on_first(qi, dk_scr, dv_scr)

    dq_scr[...] = jnp.zeros_like(dq_scr)
    q = q_ref[0]                                         # (bq, d)
    do = do_ref[0]
    lse = lse_ref[0]                                     # (bq, 1)
    delta = delta_ref[0]

    def step(rows, mask):
        _saved_lse_bwd_tile(q, k_ref[0, rows, :], v_ref[0, rows, :], do,
                            lse, delta, mask, scale, dq=(dq_scr, ...),
                            dk=(dk_scr, (rows, slice(None))),
                            dv=(dv_scr, (rows, slice(None))))

    _for_live_chunks(step, qi, live, causal, block_q, chunk, offset, window)
    dq_ref[0] = (scale * dq_scr[...]).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (scale * dk_scr[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _stream_flash_fwd(q, k, v, scale: float, causal: bool, plan: _Plan):
    """q/k: (BH, T, d), v: (BH, Tk, d_v) -> (out (BH, T, d_v), lse (BH,
    T, 1) f32), the K/V rows resident or their blocks on the grid, as the
    plan says (``d_v`` other than ``d``: the resident form alone)."""
    BH, T, d = q.shape
    Tk, d_v = k.shape[1], v.shape[2]
    block_q, block_k, _ = plan.fwd
    nk = Tk // block_k
    params = dict(scale=scale, causal=causal, block_q=block_q, nk=nk,
                  offset=Tk - T)
    if plan.name == "stream_resident":
        kernel = functools.partial(_resident_fwd_kernel, chunk=block_k,
                                   window=plan.window, **params)
        grid, semantics = (BH, T // block_q), ("parallel", "arbitrary")
        q_map = lambda b, i: (b, i, 0)                          # noqa: E731
        k_spec = pl.BlockSpec((1, Tk, d), lambda b, i: (b, 0, 0))
        v_spec = pl.BlockSpec((1, Tk, d_v), lambda b, i: (b, 0, 0))
    else:
        kernel = functools.partial(_fwd_kernel_pipelined, block_k=block_k,
                                   **params)
        grid = (BH, T // block_q, nk)
        semantics = ("parallel", "parallel", "arbitrary")
        q_map = lambda b, i, j: (b, i, 0)                       # noqa: E731
        v_spec = k_spec = pl.BlockSpec(
            (1, block_k, d),
            _clamped_k_map(block_q, block_k, Tk - T, nk, causal))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_q, d), q_map), k_spec, v_spec],
        out_specs=[pl.BlockSpec((1, block_q, d_v), q_map),
                   pl.BlockSpec((1, block_q, 1), q_map)],
        out_shape=[jax.ShapeDtypeStruct((BH, T, d_v), q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=plan.vmem_limit),
        interpret=plan.interpret,
        name=WINDOW_NAMES[0] if plan.window else None,
    )(q, k, v)


def _resident_flash_bwd(q, k, v, o, lse, do, scale: float, causal: bool,
                        plan: _Plan):
    """-> (dq, dk (BH, ., d), dv (BH, Tk, d_v)), from one kernel; v, o
    and do are ``d_v`` wide."""
    BH, T, d = q.shape
    Tk, d_v = k.shape[1], v.shape[2]
    block_q, chunk, _ = plan.bwd
    nq = T // block_q
    delta = _delta(do, o)
    qs = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    dos = pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0))
    ks = pl.BlockSpec((1, Tk, d), lambda b, i: (b, 0, 0))
    vs = pl.BlockSpec((1, Tk, d_v), lambda b, i: (b, 0, 0))
    rs = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_resident_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, chunk=chunk, nq=nq,
                          nk=Tk // chunk, offset=Tk - T, window=plan.window),
        grid=(BH, nq),
        in_specs=[qs, ks, vs, dos, rs, rs],
        out_specs=[qs, ks, vs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((Tk, d), jnp.float32),
                        pltpu.VMEM((Tk, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit),
        interpret=plan.interpret,
        name=WINDOW_NAMES[1] if plan.window else None,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# stream regime, resident form, split keys (latent attention): a head's q
# and k are a 128-lane part of their own and a rotated part, and every head
# shares one rotated key part.  The operands come token-major, (B, T, H*d)
# as the projections write them, and each grid step's DMA takes one head's
# column block; the scores are the sum of the two parts' contractions.
# The results keep the layout of the pair above: out, lse, dq, dk and dv
# head-major, dq and dk [own part | rotated part] wide.
# ---------------------------------------------------------------------------
def _latent_tiles(dn: int, dr: int, dv: int, heads: int) -> bool:
    """Whether the split kernels take these head sizes: the own parts of
    q and k and the v head whole 128-lane column blocks, the rotated part
    64 wide, so that two heads fill one block of q_r."""
    return dn % 128 == 0 and dv % 128 == 0 and dr == 64 and heads % 2 == 0


def _head_part(block, h):
    """Head ``h``'s 64-wide rotated part of q out of the 128-lane block
    its DMA fetched, the two heads' parts side by side: an odd head's
    half turned to the front (in float32: Mosaic rotates 32-bit lanes
    alone)."""
    wide = block.astype(jnp.float32)
    part = jnp.where(h % 2 == 1, pltpu.roll(wide, 64, 1), wide)
    return part[:, :64].astype(block.dtype)


def _latent_specs(heads: int, block_q: int, Tk: int, dn: int, dr: int,
                  dv: int):
    """BlockSpecs over a (b, h, q block i) grid of the token-major
    operands — q's parts and dO a block of rows per q block (q_r the
    block of two heads that holds h's part), k's own part and v whole
    rows per head, the shared rotated key part whole rows per batch
    row — and of the head-major results."""
    return dict(
        qn=pl.BlockSpec((1, block_q, dn), lambda b, h, i: (b, i, h)),
        qr=pl.BlockSpec((1, block_q, 128), lambda b, h, i: (b, i, h // 2)),
        kn=pl.BlockSpec((1, Tk, dn), lambda b, h, i: (b, 0, h)),
        kr=pl.BlockSpec((1, Tk, dr), lambda b, h, i: (b, 0, 0)),
        v=pl.BlockSpec((1, Tk, dv), lambda b, h, i: (b, 0, h)),
        do=pl.BlockSpec((1, block_q, dv), lambda b, h, i: (b, i, h)),
        # head-major results, (B*H, ., .)
        out=lambda width: pl.BlockSpec(
            (1, block_q, width), lambda b, h, i: (b * heads + h, i, 0)),
        rows=lambda width: pl.BlockSpec(
            (1, Tk, width), lambda b, h, i: (b * heads + h, 0, 0)))


def _latent_scores(qn, qr, kn, kr, scale: float):
    return (dot(qn, kn, NT) + dot(qr, kr, NT)) * scale


def _latent_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                       lse_ref, m_scr, l_scr, acc_scr, *, scale: float,
                       causal: bool, block_q: int, chunk: int, nk: int,
                       offset: int):
    """:func:`_resident_fwd_kernel` over split keys."""
    qi = pl.program_id(2)
    live = _live_chunks(qi, block_q, chunk, offset, nk, causal)
    _online_softmax_init(m_scr, l_scr, acc_scr)
    qn = qn_ref[0]                                       # (bq, dn)
    qr = _head_part(qr_ref[0], pl.program_id(1))         # (bq, 64)

    def step(rows, mask):
        _online_softmax_scores(
            _latent_scores(qn, qr, kn_ref[0, rows, :], kr_ref[0, rows, :],
                           scale),
            v_ref[0, rows, :], mask, m_scr, l_scr, acc_scr)

    _for_live_chunks(step, qi, live, causal, block_q, chunk, offset)
    _online_softmax_finish(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _latent_bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, o_ref,
                       lse_ref, dq_ref, dk_ref, dv_ref, dqn_scr, dqr_scr,
                       dkn_scr, dkr_scr, dv_scr, *, scale: float,
                       causal: bool, block_q: int, chunk: int, nq: int,
                       nk: int, offset: int):
    """:func:`_resident_bwd_kernel` over split keys: dq = [dS k_n | dS
    k_r] and dk = [dSᵀ q_n | dSᵀ q_r], each part accumulated apart and
    written into its lanes of the one result; delta = rowsum(dO * O) is
    formed here from dO's token-major block and out's head-major one."""
    qi = pl.program_id(2)
    live = _live_chunks(qi, block_q, chunk, offset, nk, causal)

    _zero_on_first(qi, dkn_scr, dkr_scr, dv_scr)

    dqn_scr[...] = jnp.zeros_like(dqn_scr)
    dqr_scr[...] = jnp.zeros_like(dqr_scr)
    qn = qn_ref[0]                                       # (bq, dn)
    qr = _head_part(qr_ref[0], pl.program_id(1))         # (bq, 64)
    do = do_ref[0]
    lse = lse_ref[0]                                     # (bq, 1)
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)

    def step(rows, mask):
        kn, kr = kn_ref[0, rows, :], kr_ref[0, rows, :]
        ds = _saved_lse_ds(_latent_scores(qn, qr, kn, kr, scale),
                           v_ref[0, rows, :], do, lse, delta, mask,
                           (dv_scr, (rows, slice(None)))).astype(qn.dtype)
        dqn_scr[...] += dot(ds, kn, NN)
        dqr_scr[...] += dot(ds, kr, NN)
        dkn_scr[rows, :] += dot(ds, qn, TN)
        dkr_scr[rows, :] += dot(ds, qr, TN)

    _for_live_chunks(step, qi, live, causal, block_q, chunk, offset)
    dn = qn.shape[-1]
    dq_ref[0, :, :dn] = (scale * dqn_scr[...]).astype(dq_ref.dtype)
    dq_ref[0, :, dn:] = (scale * dqr_scr[...]).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, :, :dn] = (scale * dkn_scr[...]).astype(dk_ref.dtype)
        dk_ref[0, :, dn:] = (scale * dkr_scr[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@traced_once(5, 6, 7)
def _latent_flash_fwd(q_n, q_r, k_n, k_r, v, scale: float, causal: bool,
                      plan: _Plan):
    """q_n (B, T, H*dn), q_r (B, T, H*dr), k_n (B, Tk, H*dn), k_r (B, Tk,
    dr), v (B, Tk, H*dv) -> (out (B*H, T, dv), lse (B*H, T, 1) f32)."""
    B, T, _ = q_n.shape
    Tk, dr = k_r.shape[1:]
    H = q_r.shape[-1] // dr
    dn, dv = q_n.shape[-1] // H, v.shape[-1] // H
    block_q, chunk, _ = plan.fwd
    specs = _latent_specs(H, block_q, Tk, dn, dr, dv)
    return pl.pallas_call(
        functools.partial(_latent_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, chunk=chunk, nk=Tk // chunk,
                          offset=Tk - T),
        grid=(B, H, T // block_q),
        in_specs=[specs[n] for n in ("qn", "qr", "kn", "kr", "v")],
        out_specs=[specs["out"](dv), specs["out"](1)],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, dv), q_n.dtype),
                   jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit),
        interpret=plan.interpret,
    )(q_n, q_r, k_n, k_r, v)


@traced_once(8, 9, 10)
def _latent_flash_bwd(q_n, q_r, k_n, k_r, v, out, lse, do, scale: float,
                      causal: bool, plan: _Plan):
    """-> (dq, dk (B*H, ., dn + dr), dv (B*H, Tk, dv)), head-major, from
    the operands of :func:`_latent_flash_fwd`, its results ``out`` and
    ``lse`` and ``do`` token-major, (B, T, H*dv)."""
    B, T, _ = q_n.shape
    Tk, dr = k_r.shape[1:]
    H = q_r.shape[-1] // dr
    dn, dv = q_n.shape[-1] // H, v.shape[-1] // H
    block_q, chunk, _ = plan.bwd
    nq = T // block_q
    specs = _latent_specs(H, block_q, Tk, dn, dr, dv)
    return pl.pallas_call(
        functools.partial(_latent_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, chunk=chunk, nq=nq,
                          nk=Tk // chunk, offset=Tk - T),
        grid=(B, H, nq),
        in_specs=[*(specs[n] for n in ("qn", "qr", "kn", "kr", "v", "do")),
                  specs["out"](dv), specs["out"](1)],
        out_specs=[specs["out"](dn + dr), specs["rows"](dn + dr),
                   specs["rows"](dv)],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, dn + dr), q_n.dtype),
                   jax.ShapeDtypeStruct((B * H, Tk, dn + dr), k_n.dtype),
                   jax.ShapeDtypeStruct((B * H, Tk, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, dn), jnp.float32),
                        pltpu.VMEM((block_q, dr), jnp.float32),
                        pltpu.VMEM((Tk, dn), jnp.float32),
                        pltpu.VMEM((Tk, dr), jnp.float32),
                        pltpu.VMEM((Tk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit),
        interpret=plan.interpret,
    )(q_n, q_r, k_n, k_r, v, do, out, lse)


# ---------------------------------------------------------------------------
# small and mid regimes, folded layout: full K/V rows resident in VMEM, G
# batch-heads per grid step.  At the flagship regime (T=512, d=64,
# B*H=1536) the streaming kernels' grid has 1536+ steps of tiny matmuls and
# the per-step DMA/bookkeeping dominates (~27 TFLOP/s effective, profiled
# r4); batching G consecutive batch-heads per step amortises it, and with
# the whole row in VMEM the softmax needs no online rescaling.
# ---------------------------------------------------------------------------
def _row_mask(causal: bool, qi, block_q: int, offset: int, start: int = 0):
    """The ``mask`` argument of the whole-row routines for q block qi,
    built on the columns from ``start`` on."""
    if not causal:
        return None
    return start, lambda shape: _causal_mask(shape, qi, block_q,
                                             offset - start)


def _causal_extents(block_q: int, Tk: int, offset: int, granule: int):
    """The key columns each q block of a causal whole-row kernel takes,
    as [(lo, hi, extent, start)]: q blocks lo..hi attend over columns
    [0, extent) — the end of their diagonal, (qi + 1) * block_q + offset,
    rounded up to ``granule`` — of which [0, start) hold no masked score
    for any of them.  All static: a kernel holds one body an entry."""
    groups = {}
    for qi in range((Tk - offset) // block_q):
        end = (qi + 1) * block_q + offset
        groups.setdefault(min(Tk, -(-end // granule) * granule),
                          []).append(qi)
    return [(qs[0], qs[-1], extent, (qs[0] * block_q + offset) // 128 * 128)
            for extent, qs in groups.items()]


def _for_extent(rows, qi, causal: bool, block_q: int, Tk: int, offset: int,
                granule: Optional[int]):
    """``rows(extent, mask)`` for q block ``qi`` (a ``program_id``) of a
    whole-row kernel: once, over the whole row, where the plan gives no
    granule (not causal, or one q block); else one body a distinct
    extent, under ``pl.when`` of the q blocks that have it, so that a
    kernel slices its K/V rows, its score row and its dK/dV
    accumulators to a static ``extent`` and the mask is built on the
    tile the diagonal crosses alone.  What the causal mask throws away
    beyond the extent is never computed."""
    if granule is None:
        return rows(Tk, _row_mask(causal, qi, block_q, offset))
    for lo, hi, extent, start in _causal_extents(block_q, Tk, offset,
                                                 granule):
        # a body of one q block knows its rows: the mask is a constant
        at = lo if lo == hi else qi
        pl.when(qi == lo if lo == hi else (qi >= lo) & (qi <= hi))(
            functools.partial(
                rows, extent, _row_mask(True, at, block_q, offset, start)))


def _small_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                      causal: bool, block_q: int, granule: Optional[int],
                      offset: int, G: int):
    def rows(extent, mask):
        for g in range(G):
            o_ref[g] = _row_fwd(q_ref[g], k_ref[g, :extent],
                                v_ref[g, :extent], mask,
                                scale).astype(o_ref.dtype)

    _for_extent(rows, pl.program_id(1), causal, block_q, k_ref.shape[1],
                offset, granule)


def _small_flash_fwd(q, k, v, scale: float, causal: bool, plan: _Plan):
    """q/k/v: (BH, T, d) -> out (BH, T, d), q blocks tiled beyond the
    small regime.  No lse output: the fused backward rebuilds it from
    the inputs, so the custom_vjp residuals are pure inputs and remat
    policies never re-run this kernel."""
    BH, T, d = q.shape
    Tk = k.shape[1]
    block_q, granule, G = plan.fwd
    kernel = functools.partial(_small_fwd_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               granule=granule, offset=Tk - T, G=G)
    return pl.pallas_call(
        kernel,
        grid=(BH // G, T // block_q),
        in_specs=[
            pl.BlockSpec((G, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((G, Tk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((G, Tk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((G, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=plan.interpret,
    )(q, k, v)


def _small_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                      *, scale: float, causal: bool, offset: int, G: int):
    mask = _row_mask(causal, 0, 0, offset)
    for g in range(G):
        dq_ref[g], dk, dv = _row_bwd(q_ref[g], k_ref[g], v_ref[g],
                                     do_ref[g], mask, scale)
        dk_ref[g] = dk.astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)


def _tiled_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                      dk_scr, dv_scr, *, scale: float, causal: bool,
                      block_q: int, granule: Optional[int], nq: int,
                      offset: int):
    """The whole-row backward with q blocks riding the inner
    ('arbitrary') grid dim and the full K/V rows resident: dq written
    per block, dK/dV accumulated in f32 scratch until the last q block."""
    qi = pl.program_id(1)

    _zero_on_first(qi, dk_scr, dv_scr)

    def rows(extent, mask):
        dq_ref[0], dk, dv = _row_bwd(
            q_ref[0], k_ref[0, :extent], v_ref[0, :extent], do_ref[0], mask,
            scale)
        dk_scr[:extent] += dk
        dv_scr[:extent] += dv

    _for_extent(rows, qi, causal, block_q, k_ref.shape[1], offset, granule)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _row_flash_bwd(q, k, v, do, scale: float, causal: bool, plan: _Plan):
    """(BH, T, d) fused backward of the small and mid regimes: one step
    of G whole batch-heads where the plan tiles no q blocks, else q
    blocks with dK/dV scratch."""
    BH, T, d = q.shape
    Tk = k.shape[1]
    block_q, granule, G = plan.bwd
    if block_q is None:
        kernel = functools.partial(_small_bwd_kernel, scale=scale,
                                   causal=causal, offset=Tk - T, G=G)
        grid, semantics, scratch = (BH // G,), ("arbitrary",), []
        qs = pl.BlockSpec((G, T, d), lambda b: (b, 0, 0))
        ks = pl.BlockSpec((G, Tk, d), lambda b: (b, 0, 0))
    else:
        nq = T // block_q
        kernel = functools.partial(_tiled_bwd_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   granule=granule, nq=nq, offset=Tk - T)
        grid, semantics = (BH, nq), ("parallel", "arbitrary")
        scratch = [pltpu.VMEM((Tk, d), jnp.float32)] * 2
        qs = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
        ks = pl.BlockSpec((1, Tk, d), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qs, ks, ks, qs],
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=plan.interpret,
    )(q, k, v, do)


# ---------------------------------------------------------------------------
# stacked-QKV kernels: q, k and v arrive as ONE (3, B, T, H*d) array —
# three row-major (B, T, H*d) sections with the heads side by side on the
# last axis, which is what one projection matmul writes when its output
# puts the q/k/v axis first, and what its backward matmuls read.  Each
# grid step takes one 128-lane column block (= P = 128//d heads, e.g. a
# head pair at d=64) of each section and slices the per-head (rows, d)
# operands in VMEM, so no head-split, transpose or relayout lands in HBM,
# and the backward writes dq, dk, dv into the sections of one array of
# the same form: nothing is packed between a kernel and a matmul.  T = Tk
# here, so the causal offset is 0.
# ---------------------------------------------------------------------------
def _qkv_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                    causal: bool, block_q: int, granule: Optional[int],
                    G: int, P: int, d: int):
    def rows(extent, mask):
        for g in range(G):
            for h in range(P):
                head = slice(h * d, (h + 1) * d)
                o_ref[g, :, head] = _row_fwd(
                    q_ref[g][:, head], k_ref[g, :extent][:, head],
                    v_ref[g, :extent][:, head], mask,
                    scale).astype(o_ref.dtype)

    _for_extent(rows, pl.program_id(2), causal, block_q, k_ref.shape[1], 0,
                granule)


def _heads_bwd(q_ref, k_ref, v_ref, do_ref, g: int, extent: int, mask,
               scale: float, P: int, d: int):
    """:func:`_row_bwd` of each of the P heads of batch row ``g`` of a
    column block over its first ``extent`` key rows -> (dq, dk, dv)
    lists of per-head results, which the caller concatenates into single
    full-lane-block stores (Mosaic requires provably 128-aligned
    stores)."""
    parts = [], [], []
    for h in range(P):
        head = slice(h * d, (h + 1) * d)
        grads = _row_bwd(q_ref[g][:, head], k_ref[g, :extent][:, head],
                         v_ref[g, :extent][:, head], do_ref[g][:, head],
                         mask, scale)
        for part, x in zip(parts, grads):
            part.append(x)
    return parts


def _qkv_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dqkv_ref, *, scale: float,
                    causal: bool, G: int, P: int, d: int):
    """Small-T backward: whole rows of one 128-lane column block per
    (b, hp) grid cell, G batch rows a step, dq/dk/dv into the three
    sections of the (3, G, T, 128) output block."""
    mask = _row_mask(causal, 0, 0, 0)
    for g in range(G):
        grads = _heads_bwd(q_ref, k_ref, v_ref, do_ref, g, k_ref.shape[1],
                           mask, scale, P, d)
        for section, parts in enumerate(grads):
            dqkv_ref[section, g] = jnp.concatenate(
                [x.astype(dqkv_ref.dtype) for x in parts], axis=-1)


def _qkv_mid_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dqkv_ref, dk_scr,
                        dv_scr, *, scale: float, causal: bool,
                        block_q: int, granule: Optional[int], nq: int,
                        P: int, d: int):
    """Mid-regime backward: one 128-lane column block (= P heads) of
    q/k/v per (b, hp) grid cell, q blocks riding the inner 'arbitrary'
    dim with dK/dV accumulated in f32 scratch across them (the
    _tiled_bwd_kernel design applied to the stacked layout).  The
    (3, 1, T, 128) output block stays in VMEM across the q blocks: each
    writes its rows of the dq section, the last one the dk and dv
    sections, and the block goes to HBM once."""
    qi = pl.program_id(2)

    _zero_on_first(qi, dk_scr, dv_scr)

    def rows(extent, mask):
        dq_parts, dk_parts, dv_parts = _heads_bwd(
            q_ref, k_ref, v_ref, do_ref, 0, extent, mask, scale, P, d)
        dqkv_ref[0, 0,
                 pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)] \
            = jnp.concatenate(dq_parts, axis=-1)
        dk_scr[:extent] += jnp.concatenate(dk_parts, axis=-1)
        dv_scr[:extent] += jnp.concatenate(dv_parts, axis=-1)

    _for_extent(rows, qi, causal, block_q, k_ref.shape[1], 0, granule)

    @pl.when(qi == nq - 1)
    def _finalize():
        dqkv_ref[1, 0] = dk_scr[...].astype(dqkv_ref.dtype)
        dqkv_ref[2, 0] = dv_scr[...].astype(dqkv_ref.dtype)


def _section(s: int, G: int, rows: int, whole: bool = False):
    """BlockSpec over a (b, hp, i) grid for section ``s`` (0 q, 1 k, 2 v)
    of a stacked (3, B, T, H*d) operand: G batch rows of 128-lane column
    block hp, q block i of the rows or (``whole``) all of them."""
    return pl.BlockSpec(
        (None, G, rows, 128),
        (lambda b, hp, i: (s, b, 0, hp)) if whole
        else (lambda b, hp, i: (s, b, i, hp)))


@traced_once(1, 2, 3, 4)
def _qkv_fwd(qkv, num_heads: int, scale: float, causal: bool, plan: _Plan):
    """qkv: (3, B, T, H*d) -> ctx (B, T, H*d): whole rows and G batch
    rows a step (packed_small), or q blocks with K/V rows resident."""
    _, B, T, F = qkv.shape
    d = F // num_heads
    P = 128 // d                       # heads per 128-lane column block
    block_q, granule, G = plan.fwd
    kernel = functools.partial(_qkv_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, granule=granule, G=G, P=P,
                               d=d)
    return pl.pallas_call(
        kernel,
        grid=(B // G, num_heads // P, T // block_q),
        in_specs=[_section(0, G, block_q), _section(1, G, T, whole=True),
                  _section(2, G, T, whole=True)],
        out_specs=pl.BlockSpec((G, block_q, 128),
                               lambda b, hp, i: (b, i, hp)),
        out_shape=jax.ShapeDtypeStruct((B, T, F), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=plan.interpret,
    )(qkv, qkv, qkv)


@traced_once(2, 3, 4, 5)
def _qkv_bwd(qkv, do, num_heads: int, scale: float, causal: bool,
             plan: _Plan):
    """-> dqkv (3, B, T, H*d) for qkv as in :func:`_qkv_fwd`: one fused
    step per (batch group, column block) where the plan tiles no q
    blocks, else q blocks with dK/dV accumulated in scratch."""
    _, B, T, F = qkv.shape
    d = F // num_heads
    P = 128 // d
    block_q, granule, G = plan.bwd
    if block_q is None:
        block_q, scratch = T, []
        kernel = functools.partial(_qkv_bwd_kernel, scale=scale,
                                   causal=causal, G=G, P=P, d=d)
    else:
        scratch = [pltpu.VMEM((T, 128), jnp.float32)] * 2
        kernel = functools.partial(_qkv_mid_bwd_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   granule=granule, nq=T // block_q, P=P,
                                   d=d)
    return pl.pallas_call(
        kernel,
        grid=(B // G, num_heads // P, T // block_q),
        in_specs=[_section(0, G, block_q), _section(1, G, T, whole=True),
                  _section(2, G, T, whole=True),
                  pl.BlockSpec((G, block_q, 128),
                               lambda b, hp, i: (b, i, hp))],
        out_specs=pl.BlockSpec((3, G, T, 128),
                               lambda b, hp, i: (0, b, 0, hp)),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=plan.interpret,
    )(qkv, qkv, qkv, do)


# ---------------------------------------------------------------------------
# XLA fallback + custom_vjp stitching: every rule reads the plan its public
# entry made; the primal is the forward rule's first result
# ---------------------------------------------------------------------------
def _xla_attention(q, k, v, scale, causal, window=None):
    # (BH, T, d) reference math for the short-sequence / CPU path
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        if window is not None:
            mask &= jnp.triu(jnp.ones((Tq, Tk), bool),
                             k=Tk - Tq - window + 1)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv(qkv, num_heads, scale, causal, plan):
    return _flash_qkv_vjp_fwd(qkv, num_heads, scale, causal, plan)[0]


def _flash_qkv_vjp_fwd(qkv, num_heads, scale, causal, plan):
    # the residual is the raw input: under remat it rebuilds from the
    # (cheap) projection, never by re-running the kernel
    return _qkv_fwd(qkv, num_heads, scale, causal, plan), qkv


def _flash_qkv_vjp_bwd(num_heads, scale, causal, plan, qkv, g):
    return (_qkv_bwd(qkv, g, num_heads, scale, causal, plan),)


_flash_qkv.defvjp(_flash_qkv_vjp_fwd, _flash_qkv_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, causal, plan):
    """(BH, T, d) attention of the small and mid regimes and of the XLA
    math: residuals are the inputs alone."""
    return _flash_vjp_fwd(q, k, v, scale, causal, plan)[0]


def _flash_vjp_fwd(q, k, v, scale, causal, plan):
    # residuals are the raw inputs: under remat they rebuild from the
    # (cheap) qkv projection, never by re-running the kernel
    if plan.name == "xla":
        return _xla_attention(q, k, v, scale, causal, plan.window) \
            .astype(q.dtype), (q, k, v)
    return _small_flash_fwd(q, k, v, scale, causal, plan), (q, k, v)


def _flash_vjp_bwd(scale, causal, plan, res, g):
    q, k, v = res
    if plan.name == "xla":
        _, vjp = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, scale, causal,
                                           plan.window).astype(q.dtype),
            q, k, v)
        return vjp(g)
    return _row_flash_bwd(q, k, v, g, scale, causal, plan)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _fold(x):
    """(B, T, H, d) -> (B*H, T, d)."""
    b, t, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, t, d)


def _unfold(x, b: int):
    """(B*H, T, d) -> (B, T, H, d)."""
    bh, t, d = x.shape
    return jnp.swapaxes(x.reshape(b, bh // b, t, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_stream(q, k, v, scale, causal, plan):
    """(B, T, H, d) attention of the stream regime.  Its backward needs
    the forward's own ``out`` and ``lse``; the vjp is cut on the
    caller's layout so that the ``out`` it keeps is the array the caller
    holds (a model that saves its attention output saves these bytes
    once), and both carry names a remat policy can list — q, k and v
    rebuild from the (cheap) projection, out and lse only by running
    the kernel again."""
    return _flash_stream_vjp_fwd(q, k, v, scale, causal, plan)[0]


def _flash_stream_vjp_fwd(q, k, v, scale, causal, plan):
    out, lse = _stream_flash_fwd(_fold(q), _fold(k), _fold(v), scale,
                                 causal, plan)
    # lse is kept as (BH, T): a trailing axis of 1 is padded to 128 lanes
    # in HBM (0.5 GB at BH=128, T=8192 where this holds 4 MB).  The
    # barrier ties the compact copy to out, so that it is made before
    # anything reads out and the padded array dies here, not at the
    # backward
    out, lse = lax.optimization_barrier(
        (_unfold(out, q.shape[0]), lse[..., 0]))
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse)


def _flash_stream_vjp_bwd(scale, causal, plan, res, g):
    q, k, v, out, lse = res
    bwd = _resident_flash_bwd if plan.name == "stream_resident" \
        else _flash_bwd
    grads = bwd(_fold(q), _fold(k), _fold(v), _fold(out), lse[..., None],
                _fold(g), scale, causal, plan)
    return tuple(_unfold(x, q.shape[0]) for x in grads)


_flash_stream.defvjp(_flash_stream_vjp_fwd, _flash_stream_vjp_bwd)


def _attend(q, k, v, scale: float, causal: bool, plan: _Plan):
    """(B, T, H, d) attention under a folded-layout plan; counts the
    selection.  A stream call counts as ``stream`` and, where the
    resident pair runs it, as ``stream_resident`` too (a windowed one as
    ``stream_resident_window``)."""
    kernels = plan.name != "xla"
    stream = plan.name.startswith("stream")
    name = "stream" if stream else plan.name
    note(f"flash_attention.{name}" if kernels else "flash_attention",
         kernels)
    if stream:
        if plan.name == "stream_resident":
            note("flash_attention.stream_resident"
                 + ("_window" if plan.window else ""), True)
        return _flash_stream(q, k, v, scale, causal, plan)
    out = _flash(_fold(q), _fold(k), _fold(v), scale, causal, plan)
    return _unfold(out, q.shape[0])


def _by_tokens(x, b: int):
    """(B*H, T, d) -> (B, T, H*d)."""
    return _unfold(x, b).reshape(b, x.shape[1], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_latent(q_n, q_r, k_n, k_r, v, scale, causal, plan):
    """Split-key attention of the resident pair, token-major in and out.
    Its backward needs the forward's own ``out`` and ``lse``: both are
    kept as the kernel wrote them, head-major, under the checkpoint
    names of :func:`_flash_stream`'s; the output projection reads the
    token-major view of ``out``."""
    return _flash_latent_vjp_fwd(q_n, q_r, k_n, k_r, v, scale, causal,
                                 plan)[0]


def _flash_latent_vjp_fwd(q_n, q_r, k_n, k_r, v, scale, causal, plan):
    out, lse = _latent_flash_fwd(q_n, q_r, k_n, k_r, v, scale, causal, plan)
    # lse compact, tied to out: as in _flash_stream_vjp_fwd
    out, lse = lax.optimization_barrier((out, lse[..., 0]))
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return _by_tokens(out, q_n.shape[0]), (q_n, q_r, k_n, k_r, v, out, lse)


def _flash_latent_vjp_bwd(scale, causal, plan, res, g):
    q_n, q_r, k_n, k_r, v, out, lse = res
    B, (Tk, dr) = q_n.shape[0], k_r.shape[1:]
    dq, dk, dv = _latent_flash_bwd(q_n, q_r, k_n, k_r, v, out,
                                   lse[..., None], g, scale, causal, plan)
    dn = dq.shape[-1] - dr
    # every head's rotated key part is the one k_r: its gradient is the
    # sum of theirs
    dk_r = dk[..., dn:].reshape(B, -1, Tk, dr).sum(
        axis=1, dtype=jnp.float32).astype(k_r.dtype)
    return (_by_tokens(dq[..., :dn], B), _by_tokens(dq[..., dn:], B),
            _by_tokens(dk[..., :dn], B), dk_r, _by_tokens(dv, B))


_flash_latent.defvjp(_flash_latent_vjp_fwd, _flash_latent_vjp_bwd)


def flash_attention_stacked(qkv, num_heads: int, *, causal: bool = False,
                            scale=None, mesh=None, batch_axes=(),
                            head_axes=()):
    """Attention between a model's projection matmuls, in their layout.

    qkv: (3, B, T, H*d) — q, k and v stacked on the leading axis, each
    section row-major with the heads side by side on its last axis
    [h0 .. h{H-1}] -> ctx (B, T, H*d), ready for the output projection;
    the cotangent comes back as one array of qkv's form.  A projection
    that writes this form (``einsum("btd,dse->sbte")``, or the q/k/v
    axis of a (B, T, 3, H*d) result moved to the front) hands it over,
    and takes its gradient back, with no relayout copy and no packing
    update in HBM.  Takes the split + generic path when the stacked
    kernels don't apply.

    Under a ``mesh`` of more than one device the kernels run per shard:
    batch split over ``batch_axes``, heads over ``head_axes`` (each
    where the mesh has the axis and it divides the dim) — a contiguous
    split of the last axis gives every shard whole heads of all three.
    """
    _, B, T, F = qkv.shape
    d = F // num_heads
    s = float(scale) if scale is not None else float(1.0 / np.sqrt(d))

    def local(x):                      # one shard: (3, b, T, h*d)
        _, b, _, f = x.shape
        h = f // d
        plan = _plan("stacked", b, T, T, h, d, x.dtype.itemsize, causal)
        if plan.name.startswith("packed"):
            note(f"flash_attention.{plan.name}", True)
            return _flash_qkv(x, h, s, causal, plan)
        q, k, v = (x[i].reshape(b, T, h, d) for i in range(3))
        return _attend(q, k, v, s, causal, plan).reshape(b, T, f)

    if not _kernels_apply(T, T, causal):
        return local(qkv)              # XLA math: GSPMD partitions it
    b_ax = axes_entry(mesh, batch_axes, B)
    h_ax = axes_entry(mesh, head_axes, num_heads)
    return shard_kernel(
        local, mesh, PartitionSpec(None, b_ax, None, h_ax),
        PartitionSpec(b_ax, None, h_ax))(qkv)


def flash_attention(q, k, v, *, causal: bool = False, scale=None,
                    window: Optional[int] = None, mesh=None, batch_axes=(),
                    head_axes=()):
    """q/k: (B, S, H, D), v: (B, S, H, Dv) paddle layout -> (B, S, H,
    Dv).  ``Dv`` may differ from ``D`` (latent attention attends with a
    192-wide q/k head over a 128-wide v head): the stream regime's
    resident pair takes such a call, every other shape runs it as XLA
    math (``flash_attention.xla``).

    ``window``: a sliding window over a causal call — query i sees key j
    where ``0 <= i + Tk - S - j < window`` (a query sees ``window`` keys,
    itself included, as HF's ``sliding_window``).  The resident pair
    takes it at any length and computes the band alone; where the pair
    does not fit, XLA math with the same mask.

    All kernels go through the folded (B*H, T, d) layout — TPU tiling
    forbids blocking the head dim of (B, T, H, d) directly (the last
    two array dims must tile (8, 128)).  Models that want the
    transpose-free hot path should call :func:`flash_attention_stacked`
    on a projection output of that form instead.

    ``mesh`` / ``batch_axes`` / ``head_axes``: as for
    :func:`flash_attention_stacked` — under a mesh of more than one
    device the kernels run per shard.
    """
    B, T, H, D = q.shape
    Tk = k.shape[1]
    s = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"a window of {window} over a call with "
                             f"causal={causal}: a window is >= 1 and causal")
        if window >= Tk:        # every key in reach: causal attention
            window = None

    def local(q, k, v):
        b, _, h, _ = q.shape
        plan = _plan("folded", b, T, Tk, h, D, q.dtype.itemsize, causal,
                     v.shape[-1], window)
        return _attend(q, k, v, s, causal, plan)

    if not _kernels_apply(T, Tk, causal):
        return local(q, k, v)          # XLA math: GSPMD partitions it
    b_ax = axes_entry(mesh, batch_axes, B)
    h_ax = axes_entry(mesh, head_axes, H)
    spec = PartitionSpec(b_ax, None, h_ax, None)
    return shard_kernel(local, mesh, (spec, spec, spec), spec)(q, k, v)


def flash_attention_latent(q_n, q_r, k_n, k_r, v, *, causal: bool = False,
                           mesh=None, batch_axes=()):
    """Latent attention's heads in the layout their projections write.

    q_n (B, S, H*dn) and q_r (B, S, H*dr) — a query head is [own part |
    rotated part] —, k_n (B, Sk, H*dn), k_r (B, Sk, dr): the one rotated
    key part every head shares, not repeated; v (B, Sk, H*dv).  All
    token-major, a head's columns side by side on the last axis ->
    (B, S, H*dv), ready for the output projection; the gradients come
    back in the operands' forms (k_r's summed over the heads).  The
    scores are q_n k_nᵀ + q_r k_rᵀ, scaled by 1/sqrt(dn + dr): attention
    over the concatenated heads.

    Where ``_plan`` takes the resident pair for the concatenated heads
    and their sizes tile (``_latent_tiles``), the split-key kernels run
    — counted ``flash_attention.stream_resident_latent`` — and each grid
    step's DMA reads one head's column blocks, so no head is folded or
    concatenated in HBM.  Elsewhere the concatenated heads run under the
    same plan, as :func:`flash_attention` runs them.  ``mesh`` /
    ``batch_axes``: as for :func:`flash_attention`; the heads stay whole
    on every shard.
    """
    B, T, _ = q_n.shape
    Tk, dr = k_r.shape[1:]
    H = q_r.shape[-1] // dr
    dn, dv = q_n.shape[-1] // H, v.shape[-1] // H
    s = float(1.0 / np.sqrt(dn + dr))

    def local(q_n, q_r, k_n, k_r, v):
        b = q_n.shape[0]
        plan = _plan("folded", b, T, Tk, H, dn + dr, q_n.dtype.itemsize,
                     causal, dv)
        if plan.name == "stream_resident" and _latent_tiles(dn, dr, dv, H):
            note("flash_attention.stream", True)
            note("flash_attention.stream_resident_latent", True)
            return _flash_latent(q_n, q_r, k_n, k_r, v, s, causal, plan)
        q = jnp.concatenate([q_n.reshape(b, T, H, dn),
                             q_r.reshape(b, T, H, dr)], axis=-1)
        k = jnp.concatenate([k_n.reshape(b, Tk, H, dn), jnp.broadcast_to(
            k_r[:, :, None], (b, Tk, H, dr))], axis=-1)
        return _attend(q, k, v.reshape(b, Tk, H, dv), s, causal,
                       plan).reshape(b, T, H * dv)

    if not _kernels_apply(T, Tk, causal):
        return local(q_n, q_r, k_n, k_r, v)    # XLA math: GSPMD partitions
    spec = PartitionSpec(axes_entry(mesh, batch_axes, B), None, None)
    return shard_kernel(local, mesh, (spec,) * 5, spec)(q_n, q_r, k_n, k_r,
                                                        v)
