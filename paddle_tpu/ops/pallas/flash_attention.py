"""Flash attention — pallas TPU kernels (forward AND backward).

Reference parity: the capability of ``operators/fused/fused_attention_op.cu``
(+ cuDNN attention) — attention without materialising the (T, T) score
matrix in HBM.  Mechanism is the TPU one: pallas kernels that hold or
stream K/V through VMEM, keeping the softmax statistics in f32 while the
matmuls ride the MXU.

Which kernels own which key length Tk (``_pallas_mode``):

- Tk <= 1024, "small": whole K/V rows and the whole (block_q, Tk) score
  row in VMEM, G batch-heads a grid step, one fused backward that
  rebuilds lse and delta in-kernel; residuals are (q, k, v) alone.
- Tk <= 4096, "mid": the same design, q blocks tiled.  What bounds it is
  the f32 (block_q, Tk) score row and its companions, not K and V.
- Tk > 4096, "stream": the score intermediates are bounded to
  (block_q, chunk) whatever Tk is.  Two forms, chosen by shape:

  * **resident** (``_resident_flash_fwd`` / ``_resident_flash_bwd``):
    the mid design with a loop inside the kernel.  K and V rows stay in
    VMEM for all q blocks of a head (fetched once a head, not once a q
    block); a ``fori_loop`` runs over the key chunks the causal mask
    leaves live for this q block and builds the mask only on the chunks
    the diagonal crosses; the forward carries the online softmax and
    emits lse; ONE fused backward (5 matmuls and one exponential pass a
    live tile) writes dq per q block and accumulates dK/dV in f32 VMEM
    scratch across the q blocks.  Its VMEM is
    ``_resident_vmem_bytes(Tk, d, itemsize, block_q, chunk)`` =
    2 x (K + V + dK + dV blocks) + 2 f32 (Tk, d) accumulators + the
    q-sized blocks + 8 f32 (block_q, chunk) tiles, every row padded to
    128 lanes: 35.7 MB at Tk = 8192, d = 64 or 128, bf16, blocks of
    512 (the compiler takes between 28 and 32 MB for the backward,
    20 MB for the forward at 1024 x 1024).  That is over Mosaic's
    default scoped limit (16 MiB, a compiler default and not the chip's
    VMEM), so the pair asks for its budget and a quarter more through
    ``vmem_limit_bytes`` (44.6 MB) and is taken when that is within
    ``_RESIDENT_VMEM_SHARE`` of what the installed jax reports for the
    chip (128 MiB a core on a v5e: rows to Tk = 16384 in bf16).
  * **grid-streamed** (``_flash_fwd`` / ``_flash_bwd``), for rows whose
    budget does not fit: K/V blocks ride the innermost grid dimension
    with the online-softmax state in scratch, dq and dk/dv are two
    kernels.  Dead tiles are still grid steps, but their index maps are
    clamped to the last live block, so they fetch nothing.

  Both forms keep ``out`` and ``lse`` for the backward, under the
  checkpoint names ``RESIDUAL_NAMES``: a remat policy that lists them
  (the step builder's ``ctx`` policies do) never runs the forward a
  second time.  ``out`` is kept in the caller's (B, T, H, d) layout, so a
  model that saves its attention output saves these bytes once.

On a TPU the kernels are always compiled; off-TPU, for lengths that are
not a multiple of 128 and for causal ``seq_q > seq_k`` both directions
run plain XLA math (``PADDLE_PALLAS_FORCE=1`` takes the kernels in
interpret mode off-TPU — the kernel unit tests).  Each public call
records the regime it selected (``ops.pallas.selections()``).

Under a mesh of more than one device the public entries take the mesh
and the axes that shard batch and heads, and run the kernels per shard
(``ops.pallas.shard_kernel``) — GSPMD cannot partition a Mosaic call.
"""
from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from . import enabled, note, on_tpu, shard_kernel

__all__ = ["flash_attention", "flash_attention_stacked",
           "flash_attention_qkv"]

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


def _pallas_mode(seq_q: int, seq_k: int, causal: bool):
    """(mode, interpret) — static decision from shapes + platform so the
    forward and backward of one call always agree.  mode is one of
    "small" (full-K-resident batched kernel), "mid" (full-K-resident,
    q-block-tiled), "stream" (online-softmax streaming kernel for
    arbitrarily long sequences), "xla" (plain XLA math).  ``interpret``
    is False on a TPU, always.

    causal with seq_q > seq_k has fully-masked query rows whose lse
    degenerates to NEG_INF (float cancellation makes exp(s - lse) == 1 in
    the backward instead of 1/seq_k) — that configuration stays on the XLA
    path.
    """
    aligned = seq_q % 128 == 0 and seq_k % 128 == 0
    if (causal and seq_q > seq_k) or not aligned or not enabled():
        # the kernels are Mosaic/TPU-only and tile in 128-row blocks
        return "xla", False
    # v5e, bf16, d=64, B*H=1536 (profiled round 4): XLA's attention at
    # T=512 materialises f32 (T, T) score tensors in the backward and
    # costs ~21 ms/layer fwd+bwd; the small-T kernel pair (full-K
    # resident, G batch-heads per grid step, one fused backward) beats
    # it.  The mid kernels carry the same design to T<=MID_T_MAX (4096);
    # the stream regime owns anything longer (resident K/V with a loop
    # over live key chunks while that fits VMEM, grid-streamed beyond).
    small = seq_k <= SMALL_T_MAX and seq_q <= SMALL_T_MAX
    mid = not small and seq_k <= MID_T_MAX and seq_q <= MID_T_MAX
    return ("small" if small else "mid" if mid else "stream"), \
        not on_tpu()


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel_pipelined(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                          acc_scr, *, scale: float, causal: bool,
                          block_q: int, block_k: int, nk: int,
                          seq_q: int, seq_k: int):
    """K-blocks ride the innermost ('arbitrary') grid dimension so Mosaic
    double-buffers the K/V block DMAs against the matmuls; the online
    softmax state lives in VMEM scratch across those grid steps."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    offset = seq_k - seq_q

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if causal:
        last_q_row = (qi + 1) * block_q - 1 + offset
        live = last_q_row >= ki * block_k
    else:
        live = True

    @pl.when(live)
    def _compute():
        # operands stay in input dtype: bf16 x bf16 -> f32 runs the MXU
        # at full rate; scale folds into the f32 scores
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
                + qi * block_q + offset
            cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
                + ki * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _block_sizes(T, Tk, block_q, block_k):
    block_q = block_q if T % block_q == 0 else 128
    block_k = block_k if Tk % block_k == 0 else 128
    assert T % block_q == 0 and Tk % block_k == 0, (T, Tk, block_q, block_k)
    return block_q, block_k


def _clamped_k_map(block_q, block_k, offset, nk, causal):
    """Index map of a K/V block over a (b, q block i, k block j) grid: a
    step the causal mask leaves dead names the last live block again, so
    that Mosaic (which fetches only when the index changes) fetches
    nothing for it."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def index(b, i, j):
        _, n_live = _live_chunks(i, block_q, block_k, offset, nk)
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


def _flash_fwd(q, k, v, scale: float, causal: bool,
               block_q: int = 256, block_k: int = 512,
               interpret: bool = False):
    """q/k/v: (BH, T, d) -> (out (BH, T, d), lse (BH, T, 1) f32)."""
    BH, T, d = q.shape
    Tk = k.shape[1]
    block_q, block_k = _block_sizes(T, Tk, block_q, block_k)
    nk = Tk // block_k
    grid = (BH, T // block_q, nk)
    kernel = functools.partial(_fwd_kernel_pipelined, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, nk=nk, seq_q=T, seq_k=Tk)
    k_spec = pl.BlockSpec(
        (1, block_k, d), _clamped_k_map(block_q, block_k, Tk - T, nk, causal))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            k_spec, k_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# small-T kernels: full K/V rows resident in VMEM, G batch-heads per grid
# step.  At the flagship regime (T=512, d=64, B*H=1536) the streaming
# kernels' grid has 1536+ steps of tiny matmuls and the per-step
# DMA/bookkeeping dominates (~27 TFLOP/s effective, profiled r4); batching
# G consecutive batch-heads per step amortises it, and with the whole row
# in VMEM the softmax needs no online rescaling.
# ---------------------------------------------------------------------------
def _small_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                      causal: bool, block_q: int, seq_q: int, seq_k: int,
                      G: int):
    qi = pl.program_id(1)
    offset = seq_k - seq_q
    for g in range(G):
        q = q_ref[g]                                     # (bq, d)
        k = k_ref[g]                                     # (Tk, d)
        v = v_ref[g]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, Tk)
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * block_q + offset
            cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[g] = (pv / l).astype(o_ref.dtype)


def _small_flash_fwd(q, k, v, scale: float, causal: bool,
                     block_q: int = 512, G: int = None,
                     interpret: bool = False):
    """q/k/v: (BH, T, d) -> out (BH, T, d).  No lse output: the fused
    backward rebuilds it from the inputs, so the custom_vjp residuals
    are pure inputs and remat policies never re-run this kernel."""
    if G is None:
        G = int(os.environ.get("PADDLE_FLASH_G_FWD", "8"))
    BH, T, d = q.shape
    Tk = k.shape[1]
    block_q, _ = _block_sizes(T, Tk, block_q, Tk)
    # scale the head-batching down as the resident (block_q, Tk) score
    # block grows so the per-step VMEM footprint stays ~flat
    G = max(1, min(G, (8 * 512 * 512) // (block_q * Tk)))
    while BH % G:
        G //= 2
    grid = (BH // G, T // block_q)
    kernel = functools.partial(_small_fwd_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               seq_q=T, seq_k=Tk, G=G)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((G, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((G, Tk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((G, Tk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((G, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# stacked-QKV kernels: q, k and v arrive as ONE (3, B, T, H*d) array —
# three row-major (B, T, H*d) sections with the heads side by side on the
# last axis, which is what one projection matmul writes when its output
# puts the q/k/v axis first, and what its backward matmuls read.  Each
# grid step takes one 128-lane column block (= 128//d heads, e.g. a head
# pair at d=64) of each section and slices the per-head (rows, d)
# operands in VMEM, so no head-split, transpose or relayout lands in HBM,
# and the backward writes dq, dk, dv into the sections of one array of
# the same form: nothing is packed between a kernel and a matmul.
# ---------------------------------------------------------------------------
def _qkv_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                    causal: bool, block_q: int, seq_q: int, seq_k: int,
                    G: int, P: int, d: int):
    qi = pl.program_id(2)
    offset = seq_k - seq_q
    for g in range(G):
        for h in range(P):
            q = q_ref[g][:, h * d:(h + 1) * d]           # (bq, d)
            k = k_ref[g][:, h * d:(h + 1) * d]           # (Tk, d)
            v = v_ref[g][:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                    + qi * block_q + offset
                cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[g, :, h * d:(h + 1) * d] = (pv / l).astype(o_ref.dtype)


def _qkv_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dqkv_ref,
                    *, scale: float, causal: bool, seq_q: int, seq_k: int,
                    G: int, P: int, d: int):
    """Small-T backward: whole rows of one 128-lane column block per
    (b, hp) grid cell, G batch rows a step, dq/dk/dv into the three
    sections of the (3, G, T, 128) output block.  Per-head results
    concatenate into single full-lane-block stores (Mosaic requires
    provably 128-aligned stores)."""
    offset = seq_k - seq_q
    for g in range(G):
        dq_parts, dk_parts, dv_parts = [], [], []
        for h in range(P):
            q = q_ref[g][:, h * d:(h + 1) * d]           # (T, d)
            k = k_ref[g][:, h * d:(h + 1) * d]
            v = v_ref[g][:, h * d:(h + 1) * d]
            do = do_ref[g][:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) + offset
                cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            e = jnp.exp(s - m)
            l = jnp.sum(e, axis=-1, keepdims=True)
            p = e / l
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            delta = jnp.sum(p * dp, axis=-1, keepdims=True)
            pb = p.astype(do.dtype)
            dv_parts.append(jax.lax.dot_general(
                pb, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32
            ).astype(dqkv_ref.dtype))
            ds = (p * (dp - delta)).astype(q.dtype)
            dq_parts.append((scale * jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ).astype(dqkv_ref.dtype))
            dk_parts.append((scale * jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ).astype(dqkv_ref.dtype))
        dqkv_ref[0, g] = jnp.concatenate(dq_parts, axis=-1)
        dqkv_ref[1, g] = jnp.concatenate(dk_parts, axis=-1)
        dqkv_ref[2, g] = jnp.concatenate(dv_parts, axis=-1)


def _qkv_mid_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dqkv_ref, dk_scr,
                        dv_scr, *, scale: float, causal: bool,
                        block_q: int, nq: int, seq_q: int, seq_k: int,
                        P: int, d: int):
    """Mid-regime backward: one 128-lane column block (= P heads) of
    q/k/v per (b, hp) grid cell, q blocks riding the inner 'arbitrary'
    dim with dK/dV accumulated in f32 scratch across them (the
    _tiled_bwd_kernel design applied to the stacked layout).  The
    (3, 1, T, 128) output block stays in VMEM across the q blocks: each
    writes its rows of the dq section, the last one the dk and dv
    sections, and the block goes to HBM once.  Per-head results
    concatenate into single full-lane-block stores (Mosaic requires
    provably 128-aligned stores)."""
    qi = pl.program_id(2)
    offset = seq_k - seq_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    dq_parts, dk_parts, dv_parts = [], [], []
    for h in range(P):
        q = q_ref[0][:, h * d:(h + 1) * d]               # (bq, d)
        k = k_ref[0][:, h * d:(h + 1) * d]               # (Tk, d)
        v = v_ref[0][:, h * d:(h + 1) * d]
        do = do_ref[0][:, h * d:(h + 1) * d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, Tk)
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + qi * block_q + offset
            cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        p = e / l
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, Tk)
        delta = jnp.sum(p * dp, axis=-1, keepdims=True)
        pb = p.astype(do.dtype)
        dv_parts.append(jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))         # (Tk, d)
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_parts.append((scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)).astype(dqkv_ref.dtype))
        dk_parts.append(scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))         # (Tk, d)
    dqkv_ref[0, 0, pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)] \
        = jnp.concatenate(dq_parts, axis=-1)
    dk_scr[...] += jnp.concatenate(dk_parts, axis=-1)
    dv_scr[...] += jnp.concatenate(dv_parts, axis=-1)

    @pl.when(qi == nq - 1)
    def _finalize():
        dqkv_ref[1, 0] = dk_scr[...].astype(dqkv_ref.dtype)
        dqkv_ref[2, 0] = dv_scr[...].astype(dqkv_ref.dtype)


def _qkv_mid_block_q(T: int, Tk: int, itemsize: int) -> int:
    # ~4 live f32 (block_q, Tk) intermediates + 2 f32 (Tk, 128) scratch
    # accumulators + 2 resident (Tk, 128) K/V column blocks + the
    # backward's resident (3, Tk, 128) output block, the blocks double-
    # buffered: bf16 at block_q=256/Tk=2048 totals ~14 MB of the 16 MB
    # scoped VMEM; f32 doubles every block and measured 17.30 MB at
    # block_q=128/Tk=2048 and 16.14 MB at 64 (the resident blocks alone
    # are 12 MB), so f32 halves block_q, and past 1024 takes an eighth
    block_q = 256 if Tk <= 2048 else 128
    if itemsize >= 4:
        block_q //= 2 if Tk <= 1024 else 8
    block_q, _ = _block_sizes(T, Tk, block_q, Tk)
    return block_q


def _batch_rows(B: int, env: str, default: int, cap: int) -> int:
    """Batch rows a small-regime grid step takes: the tuning variable's
    value (else ``default``), at most ``cap`` (what VMEM holds at this
    T), halved until it divides B."""
    G = max(1, min(int(os.environ.get(env, default)), cap))
    while B % G:
        G //= 2
    return G


def _section(s: int, G: int, rows: int, whole: bool = False):
    """BlockSpec over a (b, hp, i) grid for section ``s`` (0 q, 1 k, 2 v)
    of a stacked (3, B, T, H*d) operand: G batch rows of 128-lane column
    block hp, q block i of the rows or (``whole``) all of them."""
    return pl.BlockSpec(
        (None, G, rows, 128),
        (lambda b, hp, i: (s, b, 0, hp)) if whole
        else (lambda b, hp, i: (s, b, i, hp)))


def _qkv_fwd(qkv, num_heads: int, scale: float, causal: bool,
             interpret: bool = False):
    """qkv: (3, B, T, H*d) -> ctx (B, T, H*d).  T <= 512: the small
    regime, whole rows and G batch rows a step; beyond, the mid regime's
    q blocks with K/V rows resident."""
    _, B, T, F = qkv.shape
    d = F // num_heads
    P = 128 // d                       # heads per 128-lane column block
    if T <= 512:
        block_q, _ = _block_sizes(T, T, 512, T)
        G = _batch_rows(B, "PADDLE_FLASH_G_FWD", 4,
                        (4 * 512 * 512) // (block_q * T))
    else:
        block_q, G = _qkv_mid_block_q(T, T, qkv.dtype.itemsize), 1
    kernel = functools.partial(_qkv_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, seq_q=T, seq_k=T, G=G,
                               P=P, d=d)
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
        interpret=interpret,
    )(qkv, qkv, qkv)


def _qkv_bwd(qkv, do, num_heads: int, scale: float, causal: bool,
             interpret: bool = False):
    """-> dqkv (3, B, T, H*d) for qkv as in :func:`_qkv_fwd`.  T <= 512:
    one fused step per (batch group, column block); beyond, q blocks
    with dK/dV accumulated in scratch."""
    _, B, T, F = qkv.shape
    d = F // num_heads
    P = 128 // d
    if T <= 512:
        # ~4 f32 (T, T) intermediates per unrolled batch row: one row a
        # step at T=512, more as the row shortens
        G = _batch_rows(B, "PADDLE_FLASH_G_BWD", 2, (512 * 512) // (T * T))
        block_q, scratch = T, []
        kernel = functools.partial(_qkv_bwd_kernel, scale=scale,
                                   causal=causal, seq_q=T, seq_k=T, G=G,
                                   P=P, d=d)
    else:
        block_q, G = _qkv_mid_block_q(T, T, qkv.dtype.itemsize), 1
        scratch = [pltpu.VMEM((T, 128), jnp.float32)] * 2
        kernel = functools.partial(_qkv_mid_bwd_kernel, scale=scale,
                                   causal=causal, block_q=block_q,
                                   nq=T // block_q, seq_q=T, seq_k=T,
                                   P=P, d=d)
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
        interpret=interpret,
    )(qkv, qkv, qkv, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_qkv(qkv, num_heads, scale, causal):
    _, interpret = _pallas_mode(qkv.shape[2], qkv.shape[2], causal)
    return _qkv_fwd(qkv, num_heads, scale, causal, interpret)


def _flash_qkv_vjp_fwd(qkv, num_heads, scale, causal):
    # the residual is the raw input: under remat it rebuilds from the
    # (cheap) projection, never by re-running the kernel
    return _flash_qkv(qkv, num_heads, scale, causal), qkv


def _flash_qkv_vjp_bwd(num_heads, scale, causal, qkv, g):
    _, interpret = _pallas_mode(qkv.shape[2], qkv.shape[2], causal)
    return (_qkv_bwd(qkv, g, num_heads, scale, causal, interpret),)


_flash_qkv.defvjp(_flash_qkv_vjp_fwd, _flash_qkv_vjp_bwd)


def _axes_entry(mesh, axes, dim: int):
    """PartitionSpec entry for one array dim: those of ``axes`` the mesh
    has with size > 1, when together they divide ``dim`` (else None —
    the dim stays whole on every shard)."""
    keep = tuple(a for a in axes if mesh.shape.get(a, 1) > 1) \
        if mesh is not None else ()
    if not keep or dim % int(np.prod([mesh.shape[a] for a in keep])):
        return None
    return keep if len(keep) > 1 else keep[0]


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
    mode, _ = _pallas_mode(T, T, causal)

    def local(x):                      # one shard: (3, b, T, h*d)
        _, b, _, f = x.shape
        h = f // d
        if mode in ("small", "mid") and T <= 2048 \
                and d in (32, 64, 128) and h % max(1, 128 // d) == 0:
            # small: T <= 512, whole rows a step.  mid: 512 < T <= 2048
            # — the q-block-tiled backward with dK/dV scratch
            # accumulation per 128-lane column block keeps VMEM bounded
            # (measured 1.23x/1.13x over split+generic at T=1024/2048
            # end-to-end, profiled r5).  T=4096, odd head sizes and head
            # counts that do not fill a column block stay on the split +
            # generic path.
            note("flash_attention.packed_small" if T <= 512
                 else "flash_attention.packed_mid", True)
            return _flash_qkv(x, h, s, causal)
        q, k, v = (x[i].reshape(b, T, h, d) for i in range(3))
        return flash_attention(q, k, v, causal=causal, scale=s) \
            .reshape(b, T, f)

    if mode == "xla":
        return local(qkv)              # XLA math: GSPMD partitions it
    b_ax = _axes_entry(mesh, batch_axes, B)
    h_ax = _axes_entry(mesh, head_axes, num_heads)
    return shard_kernel(
        local, mesh, PartitionSpec(None, b_ax, None, h_ax),
        PartitionSpec(b_ax, None, h_ax))(qkv)


def flash_attention_qkv(qkv, num_heads: int, *, causal: bool = False,
                        scale=None, mesh=None, batch_axes=(),
                        head_axes=()):
    """Attention from one fused projection output, batch first.

    qkv: (B, T, 3*H*d) laid out [q_h0 .. q_h{H-1} | k_h0 .. | v_h0 ..]
    (the ``reshape(B, T, 3H, d)`` + ``split`` convention), or the same
    bytes as (B, T, 3, H*d) -> ctx (B, T, H*d), ready for the output
    projection.  :func:`flash_attention_stacked` behind one transpose of
    the q/k/v axis to the front (and one of the cotangent back): a model
    whose projection can write the stacked form should call that.

    ``mesh`` / ``batch_axes`` / ``head_axes``: as for
    :func:`flash_attention_stacked`.  A head-sharded caller must pass
    the 4-D form with its last axis sharded: a contiguous split of the
    packed 3*H*d axis is not head-aligned per q/k/v section.
    """
    if qkv.ndim == 3:
        qkv = qkv.reshape(*qkv.shape[:2], 3, qkv.shape[2] // 3)
    return flash_attention_stacked(
        jnp.moveaxis(qkv, 2, 0), num_heads, causal=causal, scale=scale,
        mesh=mesh, batch_axes=batch_axes, head_axes=head_axes)


def _mid_flash_fwd(q, k, v, scale: float, causal: bool,
                   interpret: bool = False):
    """Full-K-resident forward for the mid regime (1024 < T <= 4096):
    the small-T kernel with q-block tiling and VMEM-scaled batching.
    No lse output — the fused tiled backward rebuilds it in-kernel, so
    residuals stay pure inputs (remat never re-runs the kernel)."""
    Tk = k.shape[1]
    block_q = 512 if Tk <= 1024 else 256
    G = max(1, (4 * 512 * 512) // (block_q * Tk))
    return _small_flash_fwd(q, k, v, scale, causal, block_q=block_q,
                            G=G, interpret=interpret)


def _tiled_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                      dk_scr, dv_scr, *, scale: float, causal: bool,
                      block_q: int, nq: int, seq_q: int, seq_k: int):
    """One fused backward for the mid regime: q blocks ride the inner
    ('arbitrary') grid dim with the full K/V rows resident, lse and
    delta derived in-kernel from the full score row (no online
    rescaling, no residuals), dq written per block and dK/dV
    accumulated in f32 scratch until the last q block."""
    qi = pl.program_id(1)
    offset = seq_k - seq_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0]                                         # (bq, d)
    k = k_ref[0]                                         # (Tk, d)
    v = v_ref[0]
    do = do_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (bq, Tk)
    if causal:
        rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + qi * block_q + offset
        cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / l
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bq, Tk)
    delta = jnp.sum(p * dp, axis=-1, keepdims=True)
    pb = p.astype(do.dtype)
    dv_scr[...] += jax.lax.dot_general(
        pb, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (Tk, d)
    ds = (p * (dp - delta)).astype(q.dtype)
    dq_ref[0] = (scale * jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)).astype(dq_ref.dtype)
    dk_scr[...] += scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (Tk, d)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _tiled_flash_bwd(q, k, v, do, scale: float, causal: bool,
                     interpret: bool = False):
    """(BH, T, d) fused backward, full-K-resident, q-block tiled."""
    BH, T, d = q.shape
    Tk = k.shape[1]
    # ~5 live f32 (block_q, Tk) intermediates + 2 f32 (Tk, d) scratch
    # accumulators: at Tk=4096, block_q=256 measured 22.2M and even 128
    # sat 176K over the 16M scoped VMEM — 64 leaves ~5M headroom
    block_q = 512 if Tk <= 1024 else 256 if Tk <= 2048 else 64
    block_q, _ = _block_sizes(T, Tk, block_q, Tk)
    nq = T // block_q
    qs = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    ks = pl.BlockSpec((1, Tk, d), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_tiled_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, nq=nq, seq_q=T, seq_k=Tk),
        grid=(BH, nq),
        in_specs=[qs, ks, ks, qs],
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((Tk, d), jnp.float32),
                        pltpu.VMEM((Tk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do)


def _small_bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                      *, scale: float, causal: bool, seq_q: int,
                      seq_k: int, G: int):
    offset = seq_k - seq_q
    for g in range(G):
        q = q_ref[g]                                     # (T, d)
        k = k_ref[g]
        v = v_ref[g]
        do = do_ref[g]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (T, Tk)
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) + offset
            cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            live = rows >= cols
            s = jnp.where(live, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        p = e / l                                        # softmax, f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (T, Tk)
        # delta_i = sum_j p_ij dp_ij  (== rowsum(dO * O), derived
        # in-kernel so O need not be a residual)
        delta = jnp.sum(p * dp, axis=-1, keepdims=True)
        pb = p.astype(do.dtype)
        dv_ref[g] = jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        ds = (p * (dp - delta)).astype(q.dtype)          # (T, Tk)
        dq_ref[g] = (scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)).astype(dq_ref.dtype)
        dk_ref[g] = (scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)).astype(dk_ref.dtype)


def _small_flash_bwd(q, k, v, do, scale: float, causal: bool,
                     G: int = None, interpret: bool = False):
    """One fused kernel: dq/dk/dv from (q, k, v, do) alone — lse and
    delta are rebuilt in-VMEM (2 extra vector passes, zero extra
    matmuls vs. the 7 the two-kernel streaming backward spends)."""
    if G is None:
        G = int(os.environ.get("PADDLE_FLASH_G_BWD", "2"))
    BH, T, d = q.shape
    Tk = k.shape[1]
    # the backward holds several f32 (T, Tk) intermediates per unrolled
    # group; shrink G as the row grows so VMEM stays bounded
    G = max(1, min(G, (2 * 512 * 512) // (T * Tk)))
    while BH % G:
        G //= 2
    kernel = functools.partial(_small_bwd_kernel, scale=scale,
                               causal=causal, seq_q=T, seq_k=Tk, G=G)
    qs = pl.BlockSpec((G, T, d), lambda b: (b, 0, 0))
    ks = pl.BlockSpec((G, Tk, d), lambda b: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH // G,),
        in_specs=[qs, ks, ks, qs],
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(q, k, v, do)


# ---------------------------------------------------------------------------
# backward — dQ kernel (grid over q blocks, scan k blocks)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool, block_q: int,
                   block_k: int, nk: int, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    offset = seq_k - seq_q

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if causal:
        live = (qi + 1) * block_q - 1 + offset >= ki * block_k
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
                + qi * block_q + offset
            cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
                + ki * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                       # (bq, bk)
        if causal:
            p = jnp.where(rows >= cols, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0])).astype(k.dtype)
        dq_scr[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward — dK/dV kernel (grid over k blocks, scan q blocks)
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int, nq: int,
                    seq_q: int, seq_k: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    offset = seq_k - seq_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if causal:
        live = (qi + 1) * block_q - 1 + offset >= ki * block_k
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
                + qi * block_q + offset
            cols = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
                + ki * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                       # (bq, bk)
        if causal:
            p = jnp.where(rows >= cols, p, 0.0)
        # dV += P^T dO
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v = v_ref[0]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0])).astype(q.dtype)
        # dK += scale * dS^T q  [s = scale qk^T => ds/dk = scale ds^T q]
        dk_scr[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

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
               block_q: int = 256, block_k: int = 256,
               interpret: bool = False):
    BH, T, d = q.shape
    Tk = k.shape[1]
    block_q, block_k = _block_sizes(T, Tk, block_q, block_k)
    nq, nk = T // block_q, Tk // block_k
    delta = _delta(do, o)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec(
        (1, block_k, d), _clamped_k_map(block_q, block_k, Tk - T, nk, causal))
    r_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          seq_q=T, seq_k=Tk),
        grid=(BH, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dkv grid: (BH, k blocks, q blocks) — same specs re-indexed
    q_map = _clamped_q_map(block_q, block_k, Tk - T, causal)
    qs = pl.BlockSpec((1, block_q, d), q_map)
    ks = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rs = pl.BlockSpec((1, block_q, 1), q_map)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          seq_q=T, seq_k=Tk),
        grid=(BH, nk, nq),
        in_specs=[qs, ks, ks, qs, rs, rs],
        out_specs=[ks, ks],
        out_shape=[jax.ShapeDtypeStruct((BH, Tk, d), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# stream regime, resident form: K/V rows stay in VMEM for every q block of
# a head, a loop inside the kernel runs over the key chunks the causal
# mask leaves live, one fused backward
# ---------------------------------------------------------------------------
# Share of the chip's VMEM the resident pair may ask for; the rest is
# Mosaic's own (internal scratch, semaphores, spills).
_RESIDENT_VMEM_SHARE = 0.75


def _live_chunks(qi, block_q: int, chunk: int, offset: int, nk: int,
                 causal: bool = True):
    """(n_full, n_live) for q block ``qi``: key chunks [0, n_full) hold
    no masked score, chunks [n_full, n_live) are crossed by the diagonal
    (row r sees columns <= r + offset), chunks from n_live on are dead.
    ``qi`` may be a Python int or a traced scalar."""
    if not causal:
        return nk, nk
    n_full = jnp.minimum(nk, (qi * block_q + offset + 1) // chunk)
    n_live = jnp.minimum(nk, ((qi + 1) * block_q - 1 + offset) // chunk + 1)
    return n_full, n_live


def _causal_mask(qi, j, block_q: int, chunk: int, offset: int):
    rows = lax.broadcasted_iota(jnp.int32, (block_q, chunk), 0) \
        + (qi * block_q + offset)
    cols = lax.broadcasted_iota(jnp.int32, (block_q, chunk), 1) + j * chunk
    return rows >= cols


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                         acc_scr, *, scale: float, causal: bool,
                         block_q: int, chunk: int, seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    offset = seq_k - seq_q
    n_full, n_live = _live_chunks(qi, block_q, chunk, offset,
                                  seq_k // chunk, causal)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]                                         # (bq, d)

    def step(j, masked):
        rows = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        k = k_ref[0, rows, :]                            # (chunk, d)
        v = v_ref[0, rows, :]
        # operands stay in input dtype: bf16 x bf16 -> f32 runs the MXU
        # at full rate; scale folds into the f32 scores
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, chunk)
        if masked:
            s = jnp.where(_causal_mask(qi, j, block_q, chunk, offset),
                          s, NEG_INF)
        # chunk 0 is live for every row (column 0 is), so m is finite
        # from the first step on and a row wholly masked in a later
        # chunk adds exp(NEG_INF - m) = 0
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    lax.fori_loop(0, n_full, lambda j, c: step(j, False), None)
    if causal:
        lax.fori_loop(n_full, n_live, lambda j, c: step(j, True), None)
    l = l_scr[...]
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    lse_ref[0] = m_scr[...] + jnp.log(l)


def _resident_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                         *, scale: float, causal: bool, block_q: int,
                         chunk: int, nq: int, seq_q: int, seq_k: int):
    """q blocks ride the inner ('arbitrary') grid dim; for each, the
    live key chunks of the resident K/V rows: p = exp(s - lse) from the
    saved lse, dq accumulated over the chunks and written per q block,
    dK/dV accumulated in f32 scratch rows until the head's last q block."""
    qi = pl.program_id(1)
    offset = seq_k - seq_q
    n_full, n_live = _live_chunks(qi, block_q, chunk, offset,
                                  seq_k // chunk, causal)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    dq_scr[...] = jnp.zeros_like(dq_scr)
    q = q_ref[0]                                         # (bq, d)
    do = do_ref[0]
    lse = lse_ref[0]                                     # (bq, 1)
    delta = delta_ref[0]

    def step(j, masked):
        rows = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)
        k = k_ref[0, rows, :]                            # (chunk, d)
        v = v_ref[0, rows, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, chunk)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(_causal_mask(qi, j, block_q, chunk, offset),
                          p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, chunk)
        dv_scr[rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (chunk, d)
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[rows, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (chunk, d)

    lax.fori_loop(0, n_full, lambda j, c: step(j, False), None)
    if causal:
        lax.fori_loop(n_full, n_live, lambda j, c: step(j, True), None)
    dq_ref[0] = (scale * dq_scr[...]).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (scale * dk_scr[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _resident_blocks(T: int, Tk: int, backward: bool):
    """(block_q, chunk): the largest power-of-two multiples of 128 that
    divide the lengths, up to what a v5e measured best at T = 8192,
    d = 64 (PERF.md, PR 31).  The forward wants long chunks — its
    per-chunk rescaling of the (block_q, 1) statistics and of the
    accumulator costs as much as a 256-column slab of scores: 33.7 ms
    at 512 x 512, 21.9 at 1024 x 1024, 23.7 at 1024 x 2048.  The
    backward has no such pass and is flat from 512 x 512 (41.7 ms) to
    1024 x 1024 (42.3); it takes the smaller tiles for their VMEM."""
    cap = 512 if backward else 1024

    def dividing(n):
        b = cap
        while n % b:
            b //= 2
        return b
    return dividing(T), dividing(Tk)


def _resident_vmem_bytes(Tk: int, d: int, itemsize: int, block_q: int,
                         chunk: int) -> int:
    """VMEM the fused backward (the larger of the pair) holds: every
    BlockSpec'd operand twice (Mosaic double-buffers them), rows padded
    to whole 128-lane tiles."""
    lanes = -(-d // 128) * 128
    rows = Tk * lanes
    resident = 2 * (2 * rows * itemsize      # K, V
                    + 2 * rows * itemsize)   # dK, dV output blocks
    accumulators = 2 * rows * 4              # dK, dV in f32
    q_sized = 2 * (3 * block_q * lanes * itemsize     # q, dO, dq
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


def _resident_vmem_limit(T: int, Tk: int, d: int, itemsize: int):
    """``vmem_limit_bytes`` for the resident pair at this shape, or None
    when its budget is over the share of VMEM the pair may take (the
    grid-streamed kernels then own the shape)."""
    need = _resident_vmem_bytes(Tk, d, itemsize,
                                *_resident_blocks(T, Tk, backward=True))
    # a quarter on top for what Mosaic allocates beside the operands
    limit = need + need // 4
    return limit if limit <= _RESIDENT_VMEM_SHARE * _vmem_capacity() \
        else None


def _resident_flash_fwd(q, k, v, scale: float, causal: bool,
                        block_q: int = None, chunk: int = None,
                        vmem_limit: int = None, interpret: bool = False):
    """q/k/v: (BH, T, d) -> (out (BH, T, d), lse (BH, T, 1) f32)."""
    BH, T, d = q.shape
    Tk = k.shape[1]
    bq, ck = _resident_blocks(T, Tk, backward=False)
    block_q, chunk = _block_sizes(T, Tk, block_q or bq, chunk or ck)
    qs = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    ks = pl.BlockSpec((1, Tk, d), lambda b, i: (b, 0, 0))
    rs = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_resident_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, chunk=chunk, seq_q=T, seq_k=Tk),
        grid=(BH, T // block_q),
        in_specs=[qs, ks, ks],
        out_specs=[qs, rs],
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(q, k, v)


def _resident_flash_bwd(q, k, v, o, lse, do, scale: float, causal: bool,
                        block_q: int = None, chunk: int = None,
                        vmem_limit: int = None, interpret: bool = False):
    """-> (dq, dk, dv), each (BH, ., d), from one kernel."""
    BH, T, d = q.shape
    Tk = k.shape[1]
    bq, ck = _resident_blocks(T, Tk, backward=True)
    block_q, chunk = _block_sizes(T, Tk, block_q or bq, chunk or ck)
    nq = T // block_q
    delta = _delta(do, o)
    qs = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    ks = pl.BlockSpec((1, Tk, d), lambda b, i: (b, 0, 0))
    rs = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_resident_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, chunk=chunk, nq=nq, seq_q=T,
                          seq_k=Tk),
        grid=(BH, nq),
        in_specs=[qs, ks, ks, qs, rs, rs],
        out_specs=[qs, ks, ks],
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((Tk, d), jnp.float32),
                        pltpu.VMEM((Tk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# XLA fallback + custom_vjp stitching
# ---------------------------------------------------------------------------
def _xla_attention(q, k, v, scale, causal):
    # (BH, T, d) reference math for the short-sequence / CPU path
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _stream_flash_fwd(q, k, v, scale, causal, interpret):
    """The stream regime's forward: resident K/V where the pair's VMEM
    budget fits the chip, grid-streamed beyond."""
    limit = _resident_vmem_limit(q.shape[1], k.shape[1], q.shape[2],
                                 q.dtype.itemsize)
    if limit is None:
        return _flash_fwd(q, k, v, scale, causal, interpret=interpret)
    return _resident_flash_fwd(q, k, v, scale, causal, vmem_limit=limit,
                               interpret=interpret)


def _stream_flash_bwd(q, k, v, o, lse, do, scale, causal, interpret):
    limit = _resident_vmem_limit(q.shape[1], k.shape[1], q.shape[2],
                                 q.dtype.itemsize)
    if limit is None:
        return _flash_bwd(q, k, v, o, lse, do, scale, causal,
                          interpret=interpret)
    return _resident_flash_bwd(q, k, v, o, lse, do, scale, causal,
                               vmem_limit=limit, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, scale, causal):
    """(BH, T, d) attention of the small, mid and xla modes: residuals
    are the inputs alone."""
    mode, interpret = _pallas_mode(q.shape[1], k.shape[1], causal)
    if mode == "small":
        return _small_flash_fwd(q, k, v, scale, causal,
                                interpret=interpret)
    if mode == "mid":
        return _mid_flash_fwd(q, k, v, scale, causal,
                              interpret=interpret)
    return _xla_attention(q, k, v, scale, causal).astype(q.dtype)


def _flash_vjp_fwd(q, k, v, scale, causal):
    mode, interpret = _pallas_mode(q.shape[1], k.shape[1], causal)
    if mode == "small":
        # residuals are the raw inputs: under remat they rebuild from
        # the (cheap) qkv projection, never by re-running the kernel
        out = _small_flash_fwd(q, k, v, scale, causal,
                               interpret=interpret)
        return out, (q, k, v)
    if mode == "mid":
        out = _mid_flash_fwd(q, k, v, scale, causal, interpret=interpret)
        return out, (q, k, v)
    return _xla_attention(q, k, v, scale, causal).astype(q.dtype), \
        (q, k, v)


def _flash_vjp_bwd(scale, causal, res, g):
    q, k, v = res
    mode, interpret = _pallas_mode(q.shape[1], k.shape[1], causal)
    if mode == "small":
        if k.shape[1] > 512:
            # the fully-unrolled small backward holds ~5 live f32
            # (T, Tk) tensors: beyond T=512 that brushes the 16M VMEM
            # limit (ADVICE r4) — the tiled backward is the same math
            # with bounded residency
            return _tiled_flash_bwd(q, k, v, g, scale, causal,
                                    interpret=interpret)
        return _small_flash_bwd(q, k, v, g, scale, causal,
                                interpret=interpret)
    if mode == "mid":
        return _tiled_flash_bwd(q, k, v, g, scale, causal,
                                interpret=interpret)
    _, vjp = jax.vjp(lambda q, k, v: _xla_attention(q, k, v, scale, causal)
                     .astype(q.dtype), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _fold(x):
    """(B, T, H, d) -> (B*H, T, d)."""
    b, t, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, t, d)


def _unfold(x, b: int):
    """(B*H, T, d) -> (B, T, H, d)."""
    bh, t, d = x.shape
    return jnp.swapaxes(x.reshape(b, bh // b, t, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_stream(q, k, v, scale, causal):
    """(B, T, H, d) attention of the stream mode.  Its backward needs
    the forward's own ``out`` and ``lse``; the vjp is cut on the
    caller's layout so that the ``out`` it keeps is the array the caller
    holds (a model that saves its attention output saves these bytes
    once), and both carry names a remat policy can list — q, k and v
    rebuild from the (cheap) projection, out and lse only by running
    the kernel again."""
    _, interpret = _pallas_mode(q.shape[1], k.shape[1], causal)
    out, _ = _stream_flash_fwd(_fold(q), _fold(k), _fold(v), scale, causal,
                               interpret)
    return _unfold(out, q.shape[0])


def _flash_stream_vjp_fwd(q, k, v, scale, causal):
    _, interpret = _pallas_mode(q.shape[1], k.shape[1], causal)
    out, lse = _stream_flash_fwd(_fold(q), _fold(k), _fold(v), scale,
                                 causal, interpret)
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


def _flash_stream_vjp_bwd(scale, causal, res, g):
    q, k, v, out, lse = res
    _, interpret = _pallas_mode(q.shape[1], k.shape[1], causal)
    grads = _stream_flash_bwd(
        _fold(q), _fold(k), _fold(v), _fold(out), lse[..., None], _fold(g),
        scale, causal, interpret)
    return tuple(_unfold(x, q.shape[0]) for x in grads)


_flash_stream.defvjp(_flash_stream_vjp_fwd, _flash_stream_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = False, scale=None,
                    mesh=None, batch_axes=(), head_axes=()):
    """q/k/v: (B, S, H, D) paddle layout -> (B, S, H, D).

    All modes go through the folded (B*H, T, d) layout — TPU tiling
    forbids blocking the head dim of (B, T, H, d) directly (the last
    two array dims must tile (8, 128)).  Models that want the
    transpose-free hot path should call :func:`flash_attention_stacked`
    on a projection output of that form instead.

    ``mesh`` / ``batch_axes`` / ``head_axes``: as for
    :func:`flash_attention_qkv` — under a mesh of more than one device
    the kernels run per shard.
    """
    B, T, H, D = q.shape
    Tk = k.shape[1]
    s = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    mode, _ = _pallas_mode(T, Tk, causal)

    def local(q, k, v):
        note(f"flash_attention.{mode}" if mode != "xla"
             else "flash_attention", mode != "xla")
        if mode == "stream":
            if _resident_vmem_limit(T, Tk, D, q.dtype.itemsize) is not None:
                note("flash_attention.stream_resident", True)
            return _flash_stream(q, k, v, s, causal)
        out = _flash(_fold(q), _fold(k), _fold(v), s, causal)
        return _unfold(out, q.shape[0])

    if mode == "xla":
        return local(q, k, v)          # XLA math: GSPMD partitions it
    b_ax = _axes_entry(mesh, batch_axes, B)
    h_ax = _axes_entry(mesh, head_axes, H)
    spec = PartitionSpec(b_ax, None, h_ax, None)
    return shard_kernel(local, mesh, (spec, spec, spec), spec)(q, k, v)
