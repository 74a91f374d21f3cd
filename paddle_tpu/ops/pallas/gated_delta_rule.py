"""The gated delta rule on a TPU: the prep of a chunk and the loop over
chunks as Pallas kernels (``ops/gated_delta_rule.py`` has the mathematics,
the plain-XLA path and what stays XLA's here: the in-chunk sums of the
log-decay, ``last``, the loop over batch rows).

Five kernels, one grid: (batch row, block of ``heads`` value heads, block
of ``chunks`` chunks).  A grid step loops over its chunks and, inside, over
its heads (independent: the scheduler overlaps them).

**The prep** — no state crosses chunks, every grid axis is parallel.  Per
chunk and *key* head ``K K^T`` and ``Q K^T`` once (operands as they come,
float32 accumulation), then for each value head that key head serves, with
``G`` the log-decay summed inside the chunk and ``beta`` as (1, C) rows:

    decay = e^{G_i - G_j}, j <= i      the mask on the exponent: what it
    A     = beta_i (K K^T) decay, j < i    throws away is never an overflow
    inv   = (I + A)^-1                 float32, in VMEM
    w_v   = inv~ (v beta)~             inv~: the inverse in the operands'
    w_k   = inv~ (k beta e^G)~         dtype, as ``_prep`` casts it
    attn  = (Q K^T) decay,  q_dec = q e^G,  k_dec = k e^{G_C - G}

- ``_delta_rule_prep``: the above; q, k, v token-major ``(B, T, H d)`` (a
  key head's columns are read once for all its value heads), the operands
  out in the ``(B, n, H, C, .)`` layout the loop takes.  ``decay``, ``A``
  and the inverse are made for a *pack* of value heads at once — as many
  heads of one key head as fill the 128 lanes, two at chunk 64, side by
  side ``(C, pack C)``: ``k [k; k]^T`` is ``[K K^T | K K^T]``.  For the
  backward it also writes ``inv`` in float32.
- ``_delta_rule_prep_bwd``: the reverse pass by hand, head by head, from q,
  k, v, G, beta, ``inv`` and the cotangents of the five operands.  With
  ``d_inv = d_w_v (v beta)~^T + d_w_k (k beta e^G)~^T``:

      dA   = -(inv^T d_inv inv^T), j < i     two float32 products, no solve
      dKK  = dA beta decay,  dQK = d_attn decay
      E    = (dA beta KK + d_attn QK) decay   the exponent's gradient
      dG_i = sum_j E_ij - sum_j E_ji + the e^G and e^{G_C - G} terms
      dq   = dQK k + d_q_dec e^G             dq, dk summed over the value
      dk   = (dKK + dKK^T) k + dQK^T q + ... heads of the key head, float32

**The inverse** (``_unit_lower_inverses``), for all heads of a grid step's
chunk together.  The 16-wide diagonal blocks first, every block of every
head compressed into one ``(16 packs, 128)`` array: column-oriented
forward substitution, ``(I + A)^-1 = (I - a_14 e_14^T) ... (I - a_0
e_0^T)`` with ``a_r`` column r of a block — 15 rank-one updates whose
dependent path is one sublane broadcast, a multiply and a subtract on 8
vregs for 8 heads; column r spread over its block's lanes comes off the
MXU, exactly (the three bfloat16 parts of ``A`` against zeros and ones).
Then blocks doubled, ``[[L, 0], [M, N]]^-1 = [[L^-1, 0], [-N^-1 M L^-1,
N^-1]]``: two float32 products (``Precision.HIGHEST``) a pack and level,
two levels at chunk 64, every block of the pack in the one matmul.  Exact
in the sense forward substitution is: no power of ``A`` is ever formed
(the nilpotent product ``(I - A)(I + A^2)(I + A^4) ...`` cancels binomials
up to 1e18 where beta -> 1 over parallel keys).  What the layouts buy, on
the chip: PERF.md section 6, PR 38.

**The loop** — per batch row b, value head h and chunk j it takes ``w_k``,
``w_v``, ``attn``, ``q_dec``, ``k_dec`` and the scalar ``last`` and
carries the float32 state ``S`` (d_k x d_v) from chunk to chunk, the chunk
axis sequential, ``S`` a scratch of ``heads`` states that never leaves
VMEM:

    u  = w_v - w_k S~                  S~, u~: S and u in the operands'
    o  = q_dec S~ + attn u~            dtype; every product accumulates
    S <- last S + k_dec^T u~           in float32

- ``_delta_rule_fwd``: the loop as above, ``o`` written token-major
  ``(B, T, H d_v)`` — the layout the caller wants, no transpose after.
- ``_delta_rule_states``: the same recurrence without the read, writing what
  the backward needs: each chunk's starting ``S`` (float32) and ``u~``.
- ``_delta_rule_bwd``: the reverse pass by hand, chunk blocks and chunks in
  reverse order, carrying ``dS`` (float32, VMEM).  With ``dS'`` the
  gradient of the state after the chunk:

      du~     = attn^T do + k_dec dS'        d_w_v = du~
      d_attn  = do u~^T                      d_w_k = -du~ S~^T
      d_q_dec = do S~^T                      d_k_dec = u~ dS'^T
      d_last  = sum(dS' * S)  (float32)
      dS      = last dS' + q_dec^T do - w_k^T du~

A cotangent enters a product in the operands' dtype, in both reverse
passes, as XLA's default precision takes a float32 operand on a TPU.

``prep`` / ``prep_vjp`` are the prep pair; ``chunk_scan`` is the loop's
forward; ``chunk_scan_vjp`` the state pass and the reverse pass.  The
caller's ``custom_vjp`` keeps the rule's inputs only.  ``last`` comes and
``d_last`` goes as a lane-wide row ``(…, 1, d_v)``: a scalar a (chunk,
head) has no tile of its own.

Blocks (``plan``): at the benchmark's (4, 8192, 32 heads, d 128, chunk
64) a (chunk, head) pair is ~7 MFLOP forward in the loop — less than a
grid step costs — so a step takes several of both; from 16 pairs a step
on the kernels' time is the pairs' own (the tables measured on the chip
are in PERF.md section 6, PR 36 and PR 38).
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

from . import NN, NT, TN, divisor, dot

__all__ = ["plan", "prep", "prep_vjp", "chunk_scan", "chunk_scan_vjp"]

# a grid step's (heads, chunks): the forward and the state pass, the
# reverse pass (more operands a pair, so fewer pairs a step), the prep and
# its reverse pass.  From 16 pairs a step on the time no longer depends on
# the block (PERF.md)
_FWD_BLOCK = (8, 8)
_BWD_BLOCK = (8, 4)
_PREP_BLOCK = (8, 4)
# the diagonal blocks the inverse starts from (forward substitution), the
# least chunk ``plan`` takes; above them every level doubles the block with
# two float32 products
_INVERSE_BASE = 16


class Plan(NamedTuple):
    fwd: tuple                  # (heads, chunks) a grid step
    bwd: tuple
    prep: tuple
    group: int                  # value heads a key head serves
    pack: int                   # of them, side by side along the lanes
    interpret: bool


def _prep_heads(H: int, group: int, most: int) -> int:
    """The prep's value heads a grid step: whole key heads (each is read
    once), and a sublane tile of the (H, C) decays or all of them."""
    fit = [h for h in range(group, H + 1, group)
           if H % h == 0 and (h % 8 == 0 or h == H)]
    return max([h for h in fit if h <= most] or fit[:1])


def plan(n: int, H: int, C: int, dk: int, dv: int, interpret: bool,
         H_k: Optional[int] = None) -> Optional[Plan]:
    """The kernels' blocks for ``n`` chunks of ``C`` tokens, ``H`` value
    heads and ``H_k`` key heads (``H`` where not given), or None where
    the shapes do not tile: the head sizes fill whole lanes, and a chunk
    is 16 tokens (a sublane tile of a 16-bit type, the inverse's base
    block) doubled up to three times.  One plan for the prep and the
    loop: both are kernels or neither is."""
    if dk % 128 or dv % 128 \
            or C not in (_INVERSE_BASE << e for e in range(4)):
        return None
    group = H // (H_k or H)
    loop = [(divisor(H, h), divisor(n, c)) for h, c in (_FWD_BLOCK,
                                                        _BWD_BLOCK)]
    prep = (_prep_heads(H, group, _PREP_BLOCK[0]),
            divisor(n, _PREP_BLOCK[1]))
    pack = max(p for p in range(1, group + 1)
               if group % p == 0 and (p * C <= 128 or p == 1))
    return Plan(*loop, prep, group, pack, interpret)


def _delta_rule_fwd(wk_ref, wv_ref, attn_ref, qd_ref, kd_ref, last_ref,
                    o_ref, S_scr, *, heads: int, chunks: int):
    C, dv = wv_ref.shape[-2:]
    dtype = wk_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        S_scr[...] = jnp.zeros_like(S_scr)

    def chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * C, C), C)
        for h in range(heads):
            S = S_scr[h]
            Sb = S.astype(dtype)
            ub = (wv_ref[j, h] - dot(wk_ref[j, h], Sb, NN)).astype(dtype)
            o = dot(qd_ref[j, h], Sb, NN) + dot(attn_ref[j, h], ub, NN)
            o_ref[rows, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
            S_scr[h] = last_ref[j, h] * S + dot(kd_ref[j, h], ub, TN)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _delta_rule_states(wk_ref, wv_ref, kd_ref, last_ref, s_ref, u_ref,
                       S_scr, *, heads: int, chunks: int):
    dtype = wk_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        S_scr[...] = jnp.zeros_like(S_scr)

    def chunk(j, carry):
        for h in range(heads):
            S = S_scr[h]
            s_ref[j, h] = S
            ub = (wv_ref[j, h]
                  - dot(wk_ref[j, h], S.astype(dtype), NN)).astype(dtype)
            u_ref[j, h] = ub
            S_scr[h] = last_ref[j, h] * S + dot(kd_ref[j, h], ub, TN)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _delta_rule_bwd(do_ref, wk_ref, attn_ref, qd_ref, kd_ref, last_ref,
                    s_ref, u_ref, dwk_ref, dwv_ref, dattn_ref, dqd_ref,
                    dkd_ref, dlast_ref, dS_scr, *, heads: int, chunks: int):
    C, dv = u_ref.shape[-2:]
    dtype = wk_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dS_scr[...] = jnp.zeros_like(dS_scr)

    def chunk(i, carry):
        j = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(j * C, C), C)
        for h in range(heads):
            dS = dS_scr[h]                    # of the state after chunk j
            dSb = dS.astype(dtype)
            S = s_ref[j, h]                   # the state chunk j starts from
            Sb = S.astype(dtype)
            ub = u_ref[j, h]
            do = do_ref[rows, h * dv:(h + 1) * dv]
            du = dot(attn_ref[j, h], do, TN) + dot(kd_ref[j, h], dSb, NN)
            dub = du.astype(dtype)
            dwv_ref[j, h] = du.astype(dwv_ref.dtype)
            dwk_ref[j, h] = (-dot(dub, Sb, NT)).astype(dwk_ref.dtype)
            dattn_ref[j, h] = dot(do, ub, NT).astype(dattn_ref.dtype)
            dqd_ref[j, h] = dot(do, Sb, NT).astype(dqd_ref.dtype)
            dkd_ref[j, h] = dot(ub, dSb, NT).astype(dkd_ref.dtype)
            dlast_ref[j, h] = jnp.sum(dS * S, axis=0, keepdims=True)
            dS_scr[h] = last_ref[j, h] * dS \
                + dot(qd_ref[j, h], do, TN) - dot(wk_ref[j, h], dub, TN)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


# ---------------------------------------------------------------------------
# the prep: what a chunk's loop operands are made from, no state in it
# ---------------------------------------------------------------------------

def _mm32(a, b, dims=NN):
    """A float32 product that keeps float32's mantissa."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _column(row, eye):
    """A (1, C) row as a (C, 1) column: the diagonal of its broadcast."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column, eye):
    """A (C, 1) column as a (1, C) row."""
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _bf16_parts(x):
    """A float32 array as three bfloat16 arrays whose sum it is, exactly."""
    parts = []
    for _ in range(3):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    return parts


def _rows_of(blocks, r: int, b: int):
    """Row ``r`` of every ``b``-row block of ``blocks`` (n b, W), each over
    its block's rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(blocks[m + r:m + r + 1], (b, blocks.shape[1]))
         for m in range(0, blocks.shape[0], b)], axis=0)


def _diagonal_blocks(A, b: int, C: int):
    """The ``b``-wide diagonal blocks of every (C, C) matrix in ``A`` (C,
    W) — matrices side by side along the lanes — compressed to (b, W):
    row i, lane l holds ``A[b m + i, l]`` with m the block lane l lies
    in."""
    block = (lax.broadcasted_iota(jnp.int32, (b, A.shape[1]), 1) % C) // b
    out = A[:b]
    for m in range(1, C // b):
        out = jnp.where(block == m, A[m * b:(m + 1) * b], out)
    return out


def _inverse_masks(C: int, W: int):
    """What ``_unit_lower_inverses`` multiplies and selects by, the same
    for every chunk (made once a grid step): for each row r of a base
    block the (W, W) zeros and ones that spread lane r of every b-wide
    lane group over its group, and for each doubling from s the two
    block-diagonal selections of the right factors."""
    b = _INVERSE_BASE
    l1 = lax.broadcasted_iota(jnp.int32, (W, W), 0)
    l2 = lax.broadcasted_iota(jnp.int32, (W, W), 1)
    spread = [jnp.where((l1 // b == l2 // b) & (l1 % b == r), 1.0,
                        0.0).astype(jnp.bfloat16) for r in range(b - 1)]
    levels = []
    s = b
    while s < C:
        levels.append((l1 // s == l2 // s,
                       ((l1 % C) // s % 2 == 1) & (l1 // s == l2 // s + 1)))
        s *= 2
    return spread, levels


def _unit_lower_inverses(As, C: int, masks):
    """(I + A)^-1 in float32 for every strictly lower-triangular (C, C)
    ``A`` in ``As``, a list of (C, W) arrays of W / C matrices side by
    side along the lanes (a *pack*) -> the inverses, packed alike.
    ``masks``: ``_inverse_masks(C, W)``.

    The matrices' ``b = _INVERSE_BASE``-wide diagonal blocks are inverted
    together, compressed to b rows a pack (``_diagonal_blocks``) and the
    packs stacked: column-oriented forward substitution, ``(I + A)^-1 =
    (I - a_{b-2} e_{b-2}^T) ... (I - a_0 e_0^T)`` with ``a_r`` column r of
    a block, so step r takes ``a_r e_r^T U`` from every block — row r of
    ``U`` over the block's rows (a sublane broadcast, the only thing on
    the dependent path) times column r of ``A`` over the block's lanes.
    That column spread comes off the MXU, exactly: the bfloat16 parts of
    ``A`` against zeros and ones.

    Then blocks of s double to 2 s, pack by pack: ``[[L, 0], [M, N]]^-1 =
    [[L^-1, 0], [-N^-1 M L^-1, N^-1]]``.  In the compressed layout the
    even s-wide lane groups hold the ``L^-1`` and the odd ones the
    ``N^-1``; both products of all a pack's blocks are one matmul each
    (``_mm32``), the left factors side by side against the right factors
    on a block diagonal."""
    W = As[0].shape[1]
    b = _INVERSE_BASE
    n = len(As)
    spread, levels = masks
    U = jnp.where(lax.broadcasted_iota(jnp.int32, (n * b, W), 0) % b
                  == lax.broadcasted_iota(jnp.int32, (n * b, W), 1) % b,
                  1.0, 0.0)
    parts = jnp.concatenate(_bf16_parts(jnp.concatenate(
        [_diagonal_blocks(A, b, C) for A in As], axis=0)), axis=0)
    for r in range(b - 1):
        # exact: each part against zeros and ones, the parts stacked so
        # that the zeros and ones reach the MXU once
        column = dot(parts, spread[r], NN)
        U = U - (column[:n * b] + column[n * b:2 * n * b]
                 + column[2 * n * b:]) * _rows_of(U, r, b)
    Ts = [U[u * b:(u + 1) * b] for u in range(n)]
    s = b
    for same, under in levels:
        group = (lax.broadcasted_iota(jnp.int32, (s, W), 1) % C) // s
        for u, (A, T) in enumerate(zip(As, Ts)):
            L = jnp.where(group % 2 == 0, T, 0.0)
            N = T - L
            # the blocks M of A under the L, in the L's lanes
            M = jnp.zeros((s, W), jnp.float32)
            for v in range(0, C, 2 * s):
                M = jnp.where(group == v // s, A[v + s:v + 2 * s], M)
            # M L^-1: M's columns against the L^-1 on a block diagonal
            P = _mm32(M, jnp.where(same, jnp.tile(L, (W // s, 1)), 0.0))
            # N^-1 (M L^-1): the N^-1's columns, in the odd groups,
            # against each block's product in the even group in front
            X = _mm32(N, jnp.where(under, jnp.tile(P, (W // s, 1)), 0.0))
            Ts[u] = jnp.concatenate([L, N - X], axis=0)
        s *= 2
    return Ts


def _chunk_gates(G_row, beta_row, i, j):
    """The cumulative log-decay and beta of a (chunk, value head), (1, C)
    float32 rows -> G, beta, e^G and e^{G_C - G} as (C, 1) columns."""
    C = i.shape[0]
    eye = i == j
    G = _column(G_row, eye)
    G_last = jnp.sum(jnp.where(j == C - 1, G_row, 0.0), axis=1, keepdims=True)
    return G, _column(beta_row, eye), jnp.exp(G), jnp.exp(G_last - G)


def _decay(G, G_row, i, j):
    """e^{G_i - G_j}, j <= i: the mask goes on the exponent, so that what
    it throws away is never an overflow.  ``j`` the column inside its
    (C, C) matrix."""
    return jnp.exp(jnp.where(i >= j, G - G_row, -jnp.inf))


def _delta_rule_prep(q_ref, k_ref, v_ref, g_ref, b_ref, wk_ref, wv_ref,
                     attn_ref, qd_ref, kd_ref, *inv_ref, heads: int,
                     chunks: int, group: int, pack: int):
    C, dk = wk_ref.shape[-2:]
    dv = wv_ref.shape[-1]
    dtype = q_ref.dtype
    W = pack * C
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # a pack: ``pack`` value heads of one key head side by side along the
    # lanes, (C, W) — the decays, A and the inverse are made so
    iw = lax.broadcasted_iota(jnp.int32, (C, W), 0)
    lw = lax.broadcasted_iota(jnp.int32, (C, W), 1)
    jw = lw % C

    masks = _inverse_masks(C, W)

    def packed(columns):
        """(C, 1) columns, one a head of the pack -> (C, W)."""
        out = columns[0]
        for e in range(1, pack):
            out = jnp.where(lw >= e * C, columns[e], out)
        return out

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        keys, gates, As, attns = [], [], [], []
        for kh in range(heads // group):
            q = q_ref[rows, kh * dk:(kh + 1) * dk]
            k = k_ref[rows, kh * dk:(kh + 1) * dk]
            keys.append((q, k))
            kk = jnp.concatenate([k] * pack, axis=0)
            KK, QK = dot(k, kk, NT), dot(q, kk, NT)           # (C, W)
            for h in range(kh * group, (kh + 1) * group, pack):
                mine = [_chunk_gates(g_ref[c, h + e:h + e + 1, :],
                                     b_ref[c, h + e:h + e + 1, :], i, j)
                        for e in range(pack)]
                gates += mine
                G = packed([g[0] for g in mine])
                G_row = jnp.sum(jnp.where(iw == jw, G, 0.0), axis=0,
                                keepdims=True)
                decay = _decay(G, G_row, iw, jw)
                As.append(jnp.where(
                    iw > jw, packed([g[1] for g in mine]) * KK * decay, 0.0))
                attns.append((QK * decay).astype(dtype))
        invs = _unit_lower_inverses(As, C, masks)
        for h in range(heads):
            q, k = keys[h // group]
            _, beta, eG, e_last = gates[h]
            at = slice(h % pack * C, (h % pack + 1) * C)
            inv = invs[h // pack][:, at]
            for ref in inv_ref:
                ref[c, h] = inv
            inv = inv.astype(dtype)
            v = v_ref[rows, h * dv:(h + 1) * dv]
            wv_ref[c, h] = dot(inv, (v * beta).astype(dtype), NN)
            wk_ref[c, h] = dot(inv, (k * (beta * eG)).astype(dtype),
                               NN).astype(dtype)
            attn_ref[c, h] = attns[h // pack][:, at]
            qd_ref[c, h] = (q * eG).astype(dtype)
            kd_ref[c, h] = (k * e_last).astype(dtype)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _delta_rule_prep_bwd(q_ref, k_ref, v_ref, g_ref, b_ref, inv_ref,
                         dwk_ref, dwv_ref, dattn_ref, dqd_ref, dkd_ref,
                         dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *,
                         heads: int, chunks: int, group: int):
    C, dk = dwk_ref.shape[-2:]
    dv = dwv_ref.shape[-1]
    dtype = q_ref.dtype
    f32 = jnp.float32
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = i == j

    def lanes(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * C, C), C)
        for kh in range(heads // group):
            q = q_ref[rows, kh * dk:(kh + 1) * dk]
            k = k_ref[rows, kh * dk:(kh + 1) * dk]
            KK, QK = dot(k, k, NT), dot(q, k, NT)
            dq = jnp.zeros((C, dk), f32)
            dk_ = jnp.zeros((C, dk), f32)
            for h in range(kh * group, (kh + 1) * group):
                G, beta, eG, e_last = _chunk_gates(
                    g_ref[c, h:h + 1, :], b_ref[c, h:h + 1, :], i, j)
                decay = _decay(G, g_ref[c, h:h + 1, :], i, j)
                inv = inv_ref[c, h]
                invb = inv.astype(dtype)
                v = v_ref[rows, h * dv:(h + 1) * dv]
                d_wv = dwv_ref[c, h].astype(dtype)
                d_wk = dwk_ref[c, h]
                d_attn = dattn_ref[c, h].astype(f32)
                d_qd = dqd_ref[c, h].astype(f32)
                d_kd = dkd_ref[c, h].astype(f32)
                # w_v = inv (v beta), w_k = inv (k beta e^G)
                d_inv = dot(d_wv, (v * beta).astype(dtype), NT) \
                    + dot(d_wk, (k * (beta * eG)).astype(dtype), NT)
                d_vb = dot(invb, d_wv, TN)
                d_kb = dot(invb, d_wk, TN)
                # inv = (I + A)^-1: dA = -inv^T d_inv inv^T, below the
                # diagonal
                dA = jnp.where(
                    i > j, -_mm32(_mm32(inv, d_inv, TN), inv, NT), 0.0)
                dAb = dA * beta
                # A = beta KK decay, attn = QK decay; E = d_decay decay is
                # the gradient of the exponent G_i - G_j
                dKK = (dAb * decay).astype(dtype)
                dQK = (d_attn * decay).astype(dtype)
                E = (dAb * KK + d_attn * QK) * decay
                k_kb = lanes(d_kb * k)
                d_beta = lanes(dA * KK * decay) + lanes(d_vb * v) \
                    + k_kb * eG
                k_kd = lanes(d_kd * k) * e_last
                d_G = lanes(E) + (k_kb * beta + lanes(d_qd * q)) * eG - k_kd
                dg_ref[c, h:h + 1, :] = _row(d_G, eye) \
                    - jnp.sum(E, axis=0, keepdims=True) \
                    + jnp.where(j[:1] == C - 1,
                                jnp.sum(k_kd, axis=0, keepdims=True), 0.0)
                db_ref[c, h:h + 1, :] = _row(d_beta, eye)
                dv_ref[rows, h * dv:(h + 1) * dv] = \
                    (d_vb * beta).astype(dv_ref.dtype)
                dq += dot(dQK, k, NN) + d_qd * eG
                dk_ += dot(dKK, k, NN) + dot(dKK, k, TN) \
                    + dot(dQK, q, TN) + d_kb * (beta * eG) + d_kd * e_last
            dq_ref[rows, kh * dk:(kh + 1) * dk] = dq.astype(dq_ref.dtype)
            dk_ref[rows, kh * dk:(kh + 1) * dk] = dk_.astype(dk_ref.dtype)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)


def _specs(block, n: int, reverse: bool = False):
    """-> (spec of a ``(B, n, H, rows, cols)`` operand — or ``(B, n, H,
    cols)``, a row a (chunk, head) —, spec of a token-major ``(B, T,
    H d)`` one) for ``block`` = (heads, chunks) a step; ``reverse`` walks
    the chunk blocks from the last to the first."""
    heads, chunks = block
    at = (lambda j: n // chunks - 1 - j) if reverse else (lambda j: j)

    def per_pair(*tile: int):
        return pl.BlockSpec((None, chunks, heads, *tile),
                            lambda b, hb, j: (b, at(j), hb) + (0,) * len(tile))

    def token_major(C: int, d: int, heads: int = heads):
        return pl.BlockSpec((None, chunks * C, heads * d),
                            lambda b, hb, j: (b, at(j), hb))
    return per_pair, token_major


def _call(kernel, block, dims, operands, in_specs, out_specs, out_shape,
          state, interpret: bool, **static):
    """One of the kernels over the grid (batch row, head block, chunk
    block).  The call carries the kernel's name: the TPU compiler names
    the custom call after it (``%delta_rule_fwd.3``) wherever the call
    sits — in a loop over rows, under a caller's scope.  ``dims`` is
    (B, n, H); ``state`` the shape of one head's carried matrix — the
    chunk axis is then sequential — or None where no chunk needs
    another (the prep)."""
    B, n, H = dims
    heads, chunks = block
    # the blocks of a step, twice (the pipeline's two buffers; a tile
    # narrower than (8, 128) is padded to it), and room for what the
    # body keeps live
    outs = jax.tree.leaves(out_shape)
    per_step = sum(
        2 * x.dtype.itemsize * max(spec.block_shape[-2], 8)
        * max(spec.block_shape[-1], 128)
        * math.prod(d or 1 for d in spec.block_shape[:-2])
        for spec, x in zip([*in_specs, *jax.tree.leaves(out_specs)],
                           [*operands, *outs]))
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, chunks=chunks, **static),
        name=kernel.__name__.lstrip("_"),
        grid=(B, H // heads, n // chunks),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[] if state is None else
        [pltpu.VMEM((heads, *state), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel" if state is None
                                 else "arbitrary"),
            vmem_limit_bytes=per_step + (16 << 20)),
        interpret=interpret,
    )(*operands)


def _lanes(last, dv: int):
    """``last`` (B, n, H, 1, 1) as a row a (chunk, head): (…, 1, d_v)."""
    return jnp.broadcast_to(last, (*last.shape[:3], 1, dv))


def chunk_scan(w_k, w_v, attn, q_dec, k_dec, last, *, out_dtype, plan: Plan):
    """w_k, q_dec, k_dec: (B, n, H, C, d_k); w_v: (B, n, H, C, d_v)
    float32; attn: (B, n, H, C, C); last: (B, n, H, 1, 1) float32.
    -> o token-major, (B, n C, H d_v), in ``out_dtype``."""
    B, n, H, C, dk = w_k.shape
    dv = w_v.shape[-1]
    pair, token_major = _specs(plan.fwd, n)
    return _call(
        _delta_rule_fwd, plan.fwd, (B, n, H),
        (w_k, w_v, attn, q_dec, k_dec, _lanes(last, dv)),
        [pair(C, dk), pair(C, dv), pair(C, C), pair(C, dk), pair(C, dk),
         pair(1, dv)],
        token_major(C, dv),
        jax.ShapeDtypeStruct((B, n * C, H * dv), out_dtype),
        (dk, dv), plan.interpret)


def _chunk_states(w_k, w_v, k_dec, lanes, plan: Plan):
    """-> (the state every chunk starts from (B, n, H, d_k, d_v) float32,
    u~ (B, n, H, C, d_v) in the operands' dtype)."""
    B, n, H, C, dk = w_k.shape
    dv = w_v.shape[-1]
    pair, _ = _specs(plan.fwd, n)
    return _call(
        _delta_rule_states, plan.fwd, (B, n, H), (w_k, w_v, k_dec, lanes),
        [pair(C, dk), pair(C, dv), pair(C, dk), pair(1, dv)],
        [pair(dk, dv), pair(C, dv)],
        [jax.ShapeDtypeStruct((B, n, H, dk, dv), jnp.float32),
         jax.ShapeDtypeStruct(w_v.shape, w_k.dtype)],
        (dk, dv), plan.interpret)


def _chunk_reverse(do, w_k, attn, q_dec, k_dec, lanes, S, u, plan: Plan):
    """The reverse pass -> the cotangents of w_k, w_v, attn, q_dec, k_dec
    and ``lanes``."""
    B, n, H, C, dk = w_k.shape
    dv = u.shape[-1]
    pair, token_major = _specs(plan.bwd, n, reverse=True)
    like = [(w_k, w_k.dtype), (u, jnp.float32), (attn, attn.dtype),
            (q_dec, q_dec.dtype), (k_dec, k_dec.dtype),
            (lanes, jnp.float32)]
    return _call(
        _delta_rule_bwd, plan.bwd, (B, n, H),
        (do, w_k, attn, q_dec, k_dec, lanes, S, u),
        [token_major(C, dv), pair(C, dk), pair(C, C), pair(C, dk),
         pair(C, dk), pair(1, dv), pair(dk, dv), pair(C, dv)],
        [pair(C, dk), pair(C, dv), pair(C, C), pair(C, dk), pair(C, dk),
         pair(1, dv)],
        [jax.ShapeDtypeStruct(x.shape, dt) for x, dt in like],
        (dk, dv), plan.interpret)


def chunk_scan_vjp(w_k, w_v, attn, q_dec, k_dec, last, do, *, plan: Plan):
    """The cotangents of ``chunk_scan``'s six operands under ``do``
    (B, n C, H d_v), each in its operand's shape and dtype: the state
    pass (every chunk's starting state and u~) and the reverse pass."""
    lanes = _lanes(last, w_v.shape[-1])
    S, u = _chunk_states(w_k, w_v, k_dec, lanes, plan)
    *grads, d_lanes = _chunk_reverse(do, w_k, attn, q_dec, k_dec, lanes, S,
                                     u, plan)
    return (*grads, jnp.sum(d_lanes, axis=-1, keepdims=True))


def _head_sizes(q, v, H: int, plan: Plan):
    """(d_k, d_v) of token-major q (…, H_k d_k) and v (…, H d_v)."""
    return q.shape[-1] * plan.group // H, v.shape[-1] // H


def prep(q, k, v, G, beta, *, plan: Plan, with_inverse: bool = False):
    """The loop's operands from a row's inputs, in VMEM.  q, k: token-
    major, (B, T, H_k d_k) — the key heads are ``H / plan.group`` —; v:
    (B, T, H d_v); G (the log-decay summed inside its chunk) and beta:
    (B, n, H, C) float32.  -> (w_k, w_v, attn, q_dec, k_dec as
    ``chunk_scan`` takes them; ``with_inverse``, the float32 inverse
    (B, n, H, C, C) for ``prep_vjp``, else None)."""
    B, n, H, C = G.shape
    dk, dv = _head_sizes(q, v, H, plan)
    heads, _ = plan.prep
    pair, token_major = _specs(plan.prep, n)
    keys = token_major(C, dk, heads // plan.group)
    shapes = [((C, dk), q.dtype), ((C, dv), jnp.float32), ((C, C), q.dtype),
              ((C, dk), q.dtype), ((C, dk), q.dtype)] \
        + [((C, C), jnp.float32)] * with_inverse
    out = _call(
        _delta_rule_prep, plan.prep, (B, n, H), (q, k, v, G, beta),
        [keys, keys, token_major(C, dv), pair(C), pair(C)],
        [pair(*tile) for tile, _ in shapes],
        [jax.ShapeDtypeStruct((B, n, H, *tile), dt) for tile, dt in shapes],
        None, plan.interpret, group=plan.group, pack=plan.pack)
    return tuple(out[:5]), out[5] if with_inverse else None


def prep_vjp(q, k, v, G, beta, inv, d_wk, d_wv, d_attn, d_qd, d_kd, *,
             plan: Plan):
    """The cotangents of ``prep``'s q, k, v, G and beta under those of its
    five operands; ``inv`` the inverse it wrote.  dq, dk, dv token-major
    as q, k, v; dq, dk summed over the value heads a key head serves."""
    B, n, H, C = G.shape
    dk, dv = _head_sizes(q, v, H, plan)
    heads, _ = plan.prep
    pair, token_major = _specs(plan.prep, n)
    keys = token_major(C, dk, heads // plan.group)
    return _call(
        _delta_rule_prep_bwd, plan.prep, (B, n, H),
        (q, k, v, G, beta, inv, d_wk, d_wv, d_attn, d_qd, d_kd),
        [keys, keys, token_major(C, dv), pair(C), pair(C), pair(C, C),
         pair(C, dk), pair(C, dv), pair(C, C), pair(C, dk), pair(C, dk)],
        [keys, keys, token_major(C, dv), pair(C), pair(C)],
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, G, beta)],
        None, plan.interpret, group=plan.group)
