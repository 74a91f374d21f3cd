"""The gated delta rule: a linear-attention recurrence with a decayed,
error-correcting matrix state, in its chunked form.

Per batch row and head a state ``S`` (d_k x d_v) starts at nought and,
for every token t,

    S   <- exp(g_t) S                      the gate decays it (g_t <= 0)
    u_t  = beta_t (v_t - S^T k_t)          what the state gets wrong of v_t
    S   <- S + k_t u_t^T                   the delta-rule write
    o_t  = S^T q_t                         the read

(``gated_delta_rule_reference`` is exactly that, one token a step: 8192
dependent steps a row at the benchmark's length.)  ``gated_delta_rule``
computes the same in chunks of ``chunk`` tokens.  With ``G_i = sum_{j<=i}
g_j`` inside a chunk and ``S_0`` the state the chunk starts from,

    (I + A) U = beta (V - diag(e^G) K S_0),
        A_ij = beta_i e^{G_i - G_j} (k_i . k_j)  for j < i, else 0
    O    = diag(e^G) Q S_0 + (Q K^T * e^{G_i - G_j}, j <= i) U
    S_C  = e^{G_C} S_0 + (diag(e^{G_C - G}) K)^T U

so a chunk is matrix products and one inverse of a unit lower-triangular
``chunk x chunk`` matrix, and only the state goes from chunk to chunk (a
``lax.scan``).  Every exponent is a difference ``G_i - G_j`` with i >= j,
never ``e^{G_i} e^{-G_j}``: a strong gate underflows to nought and cannot
overflow.  ``g``, the cumulative decays, the inverse and the carried
state are float32; the matrix products take their operands in the dtype
of ``q`` (bfloat16 in a bf16 step) and accumulate in float32.

The rule is three parts.  The *gates* (``_gates``): the log-decay summed
inside its chunk, beta in float32 and ``last = e^{G_C}`` — arrays of a
megabyte, XLA ops on either path, autodiff's to differentiate.  The *prep*
makes what a row's chunks need that no state enters — the decays, ``K
K^T``, ``A`` and its inverse, ``w_k``, ``w_v``, ``attn``, ``q_dec``,
``k_dec``.  The *loop* carries the state over the chunks.  Prep and loop
have two implementations behind one plan (``ops/pallas/gated_delta_rule.py``
``plan``: the backend through ``ops.pallas.enabled()``, and the shapes);
both are kernels or neither is:

- on a TPU, where the head sizes fill whole lanes (multiples of 128) and a
  chunk whole 16-bit sublane tiles, five Pallas kernels under one
  ``jax.custom_vjp`` whose residuals are the rule's five inputs.  Forward:
  ``delta_rule_prep`` (a chunk's prep in VMEM: the key heads read once and
  never repeated in HBM, the inverse by forward substitution and block
  doubling in float32) and ``delta_rule_fwd`` (the state in VMEM).
  Backward: the prep again — this time it also writes the float32 inverse
  —, ``delta_rule_states`` and ``delta_rule_bwd`` (the loop's reverse pass
  by hand), ``delta_rule_prep_bwd`` (the prep's reverse pass by hand: no
  solve, two products with the inverse), then the gates' VJP.  XLA's are
  the gates and the loop over rows.  Both passes take the batch rows one
  at a time (``lax.map``): the chunk-sized intermediates of one row in the
  backward — operands, their cotangents, the inverse, every chunk's
  starting state — are over a gigabyte at the benchmark's (4, 8192, 32,
  128), and the four rows' together do not fit beside the model's state;
  the forward's would, and gained 0.5 % for compiler-made copies under no
  name (PERF.md section 6, PR 36);
- elsewhere ``_prep`` — XLA ops, the key heads repeated, the inverse by
  ``solve_triangular``, autodiff's to differentiate; also the kernels'
  reference in the tests — and ``lax.scan`` (``_chunk_scan_xla``), the
  backward autodiff through both, batch rows one at a time (``lax.map``),
  each recomputed for its own backward (``jax.checkpoint``).

Either way the output carries the checkpoint name ``RESIDUAL_NAMES[0]``:
a remat policy that lists it keeps the output and does not run the loop
again when it recomputes the layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec

from . import pallas
from .pallas import gated_delta_rule as _kernels
from .pallas import axes_entry, traced_once

__all__ = ["gated_delta_rule", "gated_delta_rule_reference",
           "RESIDUAL_NAMES"]

# the rule's output under a checkpoint name: a remat policy that lists it
# (``models/gpt_spmd.py`` ``ctx``) does not run the loop again
RESIDUAL_NAMES = ("delta_rule_out",)


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence token by token, in float32.  q, k: (B, T, H, d_k);
    v: (B, T, H, d_v); g, beta: (B, T, H).  -> o (B, T, H, d_v)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    B, _, H, dk = q.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x            # (B, H, d) and (B, H)
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, S0, tuple(jnp.moveaxis(x, 1, 0)
                                    for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _prep(q, k, v, g, beta, chunk: int):
    """What a row's chunks need that no state enters.  q, k: (T, H_k,
    d_k); v: (T, H, d_v); g, beta: (T, H); T a multiple of ``chunk``.
    -> the loop's six operands, each (n, H, ...) over the n chunks:
    w_k, q_dec, k_dec (C, d_k) and attn (C, C) in q's dtype, w_v (C, d_v)
    and last (1, 1) float32."""
    T, H, _ = v.shape
    C, n = chunk, T // chunk
    dtype = q.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def chunks(x):                              # (T, H, ...) -> (n, H, C, ...)
        return jnp.moveaxis(x.reshape(n, C, *x.shape[1:]), 2, 1)

    # key head j serves value heads j r .. j r + r - 1
    q, k = (jnp.repeat(chunks(x), H // x.shape[1], axis=1) for x in (q, k))
    v = chunks(v)
    beta = chunks(beta.astype(jnp.float32))[..., None]        # (n, H, C, 1)
    G = jnp.cumsum(chunks(g.astype(jnp.float32)), axis=-1)    # (n, H, C)
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # e^{G_i - G_j} where j <= i: the mask goes on the exponent, so that
    # what it throws away is never an overflow
    decay = jnp.exp(jnp.where(i >= j, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    A = jnp.where(i > j, beta * mm("nhcd,nhsd->nhcs", k, k) * decay, 0.0)
    inv = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=jnp.float32),
        jnp.broadcast_to(jnp.eye(C, dtype=jnp.float32), A.shape),
        lower=True, unit_diagonal=True).astype(dtype)
    eG = jnp.exp(G)[..., None]
    w_v = mm("nhcs,nhsd->nhcd", inv, (v * beta).astype(dtype))
    w_k = mm("nhcs,nhsd->nhcd", inv,
             (k * (beta * eG)).astype(dtype)).astype(dtype)
    attn = (mm("nhcd,nhsd->nhcs", q, k) * decay).astype(dtype)
    q_dec = (q * eG).astype(dtype)
    k_dec = (k * jnp.exp(G[..., -1:] - G)[..., None]).astype(dtype)
    last = jnp.exp(G[..., -1])[..., None, None]               # (n, H, 1, 1)
    return w_k, w_v, attn, q_dec, k_dec, last


def _chunk_scan_xla(w_k, w_v, attn, q_dec, k_dec, last, out_dtype):
    """The loop over a row's chunks as a ``lax.scan`` carrying the
    float32 state.  -> o (T, H, d_v) in ``out_dtype``."""
    n, H, C, dk = w_k.shape
    dtype = w_k.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def step(S, x):
        w_k, w_v, attn, q_dec, k_dec, last = x
        u = w_v - mm("hcd,hde->hce", w_k, S.astype(dtype))
        o = mm("hcd,hde->hce", q_dec, S.astype(dtype)) \
            + mm("hcs,hse->hce", attn, u.astype(dtype))
        S = last * S + mm("hcd,hce->hde", k_dec, u.astype(dtype))
        return S, o

    S0 = jnp.zeros((H, dk, w_v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, S0, (w_k, w_v, attn, q_dec, k_dec, last))
    return jnp.moveaxis(o.astype(out_dtype), 1, 2).reshape(n * C, H, -1)


def _row(q, k, v, g, beta, chunk: int):
    """One batch row, XLA ops throughout.  -> o (T, H, d_v) in v's
    dtype."""
    return _chunk_scan_xla(*_prep(q, k, v, g, beta, chunk), v.dtype)


def _gates(g, beta, chunk: int):
    """What of a row's prep stays XLA's on the kernel path, arrays of a
    megabyte: g, beta (T, H) -> the log-decay summed inside its chunk and
    beta, float32 (n, H, C) — a row a (chunk, head), as the prep kernels
    read them —, and ``last`` (n, H, 1, 1)."""
    T, H = g.shape

    def rows(x):                                # (T, H) -> (n, H, C)
        return jnp.moveaxis(
            x.astype(jnp.float32).reshape(T // chunk, chunk, H), 1, 2)

    G = jnp.cumsum(rows(g), axis=-1)
    return G, rows(beta), jnp.exp(G[..., -1])[..., None, None]


def _operands(q, k, v, gates, plan, with_inverse=False):
    """A row's prep as the kernels make and take it, a batch of one, from
    its token-major q, k (T, H_k d_k), v (T, H d_v) and its ``_gates``.
    -> (the loop's six operands, the float32 inverse for ``prep_vjp`` or
    None)."""
    G, beta, last = gates
    operands, inv = _kernels.prep(
        q[None], k[None], v[None], G[None], beta[None], plan=plan,
        with_inverse=with_inverse)
    return (*operands, last[None]), inv


@traced_once(5, 6)
def _rule_kernels(q, k, v, g, beta, chunk: int, plan):
    """The rule in the kernels, a batch row at a time, q, k, v and the
    output token-major: the gates (XLA ops), ``prep``, then
    ``chunk_scan``.  (An inline jit, like the backward: a model's layers
    call with the same shapes, and the second finds the first one's jaxpr
    — kernel bodies traced once, ``setup_s``.)"""
    def row(x):
        q, k, v, g, beta = x
        operands, _ = _operands(q, k, v, _gates(g, beta, chunk), plan)
        return _kernels.chunk_scan(*operands, out_dtype=v.dtype,
                                   plan=plan)[0]
    return lax.map(row, (q, k, v, g, beta))


_rule = jax.custom_vjp(_rule_kernels, nondiff_argnums=(5, 6))


def _rule_fwd(q, k, v, g, beta, chunk, plan):
    return _rule_kernels(q, k, v, g, beta, chunk, plan), (q, k, v, g, beta)


def _row_vjp(q, k, v, g, beta, do, chunk: int, plan):
    """The cotangents of a row's five inputs under ``do``: the prep again
    (it also writes the inverse), the loop's own backward, the prep's,
    then the gates' (autodiff)."""
    gates, gates_vjp = jax.vjp(functools.partial(_gates, chunk=chunk), g,
                               beta)
    operands, inv = _operands(q, k, v, gates, plan, with_inverse=True)
    *d_operands, d_last = _kernels.chunk_scan_vjp(*operands, do[None],
                                                  plan=plan)
    dq, dk, dv, dG, d_beta = _kernels.prep_vjp(
        q[None], k[None], v[None], gates[0][None], gates[1][None], inv,
        *d_operands, plan=plan)
    return (dq[0], dk[0], dv[0],
            *gates_vjp((dG[0], d_beta[0], d_last[0])))


@traced_once(0, 1)
def _rule_bwd(chunk, plan, inputs, do):
    """A row at a time.  What the forward leaves behind is the rule's
    inputs."""
    return lax.map(lambda x: _row_vjp(*x, chunk, plan), (*inputs, do))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, key_heads=None,
                     mesh=None, batch_axes=()):
    """The gated delta rule in chunks of ``chunk`` tokens (any T: the
    tail is padded with tokens that neither decay, write nor read).

    q, k: (B, T, H_k, d_k), as the caller wants them read and written
    (the usual L2 norms and the 1 / sqrt(d_k) on q are the caller's);
    H_k divides H, key head j serving value heads ``j H / H_k ..``;
    v: (B, T, H, d_v); g: (B, T, H) log-decay a token, <= 0; beta:
    (B, T, H) the write strength.  -> o (B, T, H, d_v) in v's dtype,
    named ``RESIDUAL_NAMES[0]`` for a remat policy to save.
    Differentiable in all five.

    Or *token-major*, the heads folded into the columns: q, k (B, T,
    H_k d_k) with ``key_heads = H_k`` (a shape, which three axes no
    longer say; H is g's), v (B, T, H d_v) -> o (B, T, H d_v).  That is
    the form the kernels read and write — and the convolution's in front
    of them (``ops/causal_conv.py``) —, so on a TPU nothing is copied
    between them: there ``(B, T, H, d)`` tiles 16 heads of one token
    where ``(B, T, H d)`` tiles 16 tokens of one head, and a reshape
    from one to the other moves every byte (PERF.md section 7).

    Under a mesh of more than one device ``batch_axes`` names the axes
    that shard B: the kernels run per shard
    (``ops.pallas.shard_kernel``)."""
    B, T, H = g.shape
    folded = v.ndim == 3
    if not folded:
        key_heads = q.shape[2]
        q, k, v = (x.reshape(B, T, -1) for x in (q, k, v))
    elif key_heads is None:
        raise ValueError("token-major q, k (B, T, H_k d_k) need key_heads")
    dk, dv = q.shape[-1] // key_heads, v.shape[-1] // H
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
    plan = _kernels.plan((T + pad) // chunk, H, chunk, dk, dv,
                         interpret=not pallas.on_tpu(), H_k=key_heads) \
        if pallas.enabled() else None
    pallas.note("gated_delta_rule", plan is not None)
    if plan is None:
        # the XLA math goes by heads; off the TPU a reshape is free
        row = jax.checkpoint(functools.partial(_row, chunk=chunk))
        o = lax.map(lambda x: row(*x), (
            q.reshape(B, -1, key_heads, dk), k.reshape(B, -1, key_heads, dk),
            v.reshape(B, -1, H, dv), g, beta)).reshape(B, -1, H * dv)
    else:
        b_ax = axes_entry(mesh, batch_axes, B)
        spec = PartitionSpec(b_ax)
        o = pallas.shard_kernel(
            lambda *x: _rule(*x, chunk, plan), mesh, (spec,) * 5,
            spec)(q, k, v, g, beta)
    o = checkpoint_name(o[:, :T], RESIDUAL_NAMES[0])
    return o if folded else o.reshape(B, T, H, dv)
