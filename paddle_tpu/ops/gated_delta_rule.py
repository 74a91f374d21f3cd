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

The backward is autodiff through the chunked form.  Batch rows go one at
a time (``lax.map``), each recomputed for its own backward
(``jax.checkpoint``): what a row keeps between the passes is its inputs,
and the chunk-sized intermediates of one row are all that is live: at
the benchmark's (4, 8192, 32, 128) the four rows' together would not fit
beside the model's state.

XLA ops throughout; a Pallas kernel is a later change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gated_delta_rule", "gated_delta_rule_reference"]


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


def _row(q, k, v, g, beta, chunk: int):
    """One batch row.  q, k: (T, H_k, d_k); v: (T, H, d_v); g, beta:
    (T, H); T a multiple of ``chunk``.  -> o (T, H, d_v) in v's dtype."""
    T, H, _ = v.shape
    dk = q.shape[-1]
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

    def step(S, x):
        w_k, w_v, attn, q_dec, k_dec, last = x
        u = w_v - mm("hcd,hde->hce", w_k, S.astype(dtype))
        o = mm("hcd,hde->hce", q_dec, S.astype(dtype)) \
            + mm("hcs,hse->hce", attn, u.astype(dtype))
        S = last * S + mm("hcd,hce->hde", k_dec, u.astype(dtype))
        return S, o

    S0 = jnp.zeros((H, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, S0, (w_k, w_v, attn, q_dec, k_dec, last))
    return jnp.moveaxis(o.astype(v.dtype), 1, 2).reshape(T, H, -1)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """The gated delta rule in chunks of ``chunk`` tokens (any T: the
    tail is padded with tokens that neither decay, write nor read).

    q, k: (B, T, H_k, d_k), as the caller wants them read and written
    (the usual L2 norms and the 1 / sqrt(d_k) on q are the caller's);
    H_k divides H, key head j serving value heads ``j H / H_k ..``;
    v: (B, T, H, d_v); g: (B, T, H) log-decay a token, <= 0; beta:
    (B, T, H) the write strength.  -> o (B, T, H, d_v) in v's dtype.
    Differentiable in all five."""
    T = q.shape[1]
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    row = jax.checkpoint(functools.partial(_row, chunk=chunk))
    o = lax.map(lambda x: row(*x), (q, k, v, g, beta))
    return o[:, :T]
