"""Fused epilogue ops (bias + dropout + residual + layernorm).

Reference parity: ``operators/fused/fused_dropout_helper.h`` (the
LayernormResidualDropoutBias functor family) — the epilogue the reference
fuses into its fused_attention / fused_feedforward CUDA ops.  Here the op
is one pallas kernel on TPU (ops/pallas/fused_ln.py); the XLA math
produces bit-identical results (shared counter-based hash RNG), so
``FLAGS_use_pallas`` flips the implementation without changing numerics.

Backward recomputes the dropout mask from (seed, index) — no stored mask
tensor — and runs the layernorm backward in plain XLA (fused by the
compiler into the surrounding backward graph).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import dispatch, get_kernel, register_kernel
from ..core.random import default_generator
from ..core.tensor import Tensor, to_tensor

__all__ = ["fused_bias_dropout_residual_layer_norm"]


def _fused_math(x, residual, bias, gamma, beta, seed, *, p, eps):
    """Pure-jnp reference math — shared by the XLA backend and the
    backward recompute; bit-identical to the pallas kernel."""
    from .pallas.fused_ln import hash_uniform
    N, D = x.shape
    h = x.astype(jnp.float32) + bias.astype(jnp.float32)
    if p > 0.0:
        u = hash_uniform(seed, (N, D))
        h = jnp.where(u >= p, h / (1.0 - p), 0.0)
    z = residual.astype(jnp.float32) + h
    mean = jnp.mean(z, axis=-1, keepdims=True)
    zc = z - mean
    var = jnp.mean(zc * zc, axis=-1, keepdims=True)
    y = zc * jax.lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused(x, residual, bias, gamma, beta, seed, p, eps, use_pallas):
    from .pallas import note
    interpret = note("fused_ln", use_pallas)
    if use_pallas:
        from .pallas.fused_ln import fused_ln_pallas
        return fused_ln_pallas(x, residual, bias, gamma, beta, seed,
                               p=p, eps=eps, interpret=interpret)
    return _fused_math(x, residual, bias, gamma, beta, seed, p=p, eps=eps)


def _fused_fwd(x, residual, bias, gamma, beta, seed, p, eps, use_pallas):
    out = _fused(x, residual, bias, gamma, beta, seed, p, eps, use_pallas)
    return out, (x, residual, bias, gamma, beta, seed)


def _fused_bwd(p, eps, use_pallas, res, g):
    x, residual, bias, gamma, beta, seed = res
    _, vjp = jax.vjp(
        lambda a, r, b, ga, be: _fused_math(a, r, b, ga, be, seed,
                                            p=p, eps=eps),
        x, residual, bias, gamma, beta)
    dx, dres, dbias, dgamma, dbeta = vjp(g)
    dseed = np.zeros(jnp.shape(seed), jax.dtypes.float0)
    return dx, dres, dbias, dgamma, dbeta, dseed


_fused.defvjp(_fused_fwd, _fused_bwd)


def _fbdrln_xla(x, residual, bias, gamma, beta, seed, *, p, eps):
    return _fused(x, residual, bias, gamma, beta, seed, p, eps, False)


def _fbdrln_pallas(x, residual, bias, gamma, beta, seed, *, p, eps):
    return _fused(x, residual, bias, gamma, beta, seed, p, eps, True)


register_kernel("fused_bias_dropout_residual_layer_norm", "xla")(_fbdrln_xla)
register_kernel("fused_bias_dropout_residual_layer_norm",
                "pallas")(_fbdrln_pallas)


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True, name=None):
    """``LayerNorm(residual + dropout(x + bias))`` in one kernel.

    Reference: ``incubate.nn.functional.fused_bias_dropout_residual_layer_norm``
    backed by ``fused_dropout_helper.h``.  Accepts (..., D) inputs; the
    fusion runs over flattened rows.
    """
    x, residual = to_tensor(x), to_tensor(residual)
    shape = list(x.shape)
    D = int(shape[-1])
    bias = to_tensor(bias) if bias is not None else \
        to_tensor(jnp.zeros((D,), x._data.dtype))
    ln_scale = to_tensor(ln_scale) if ln_scale is not None else \
        to_tensor(jnp.ones((D,), jnp.float32))
    ln_bias = to_tensor(ln_bias) if ln_bias is not None else \
        to_tensor(jnp.zeros((D,), jnp.float32))
    p = float(dropout_rate) if training else 0.0
    seed_t = to_tensor(jnp.asarray(
        jax.random.randint(default_generator.next_key(), (), 0, 2**31 - 1),
        jnp.uint32))

    # backend-aware registry selection (get_kernel consults
    # preferred_backend); the reshape wrapper below is backend-neutral
    impl = get_kernel("fused_bias_dropout_residual_layer_norm")

    def op(a, r, b, ga, be, sd, *, p, eps):
        flat = a.reshape(-1, D)
        out = impl(flat, r.reshape(-1, D), b, ga, be, sd, p=p, eps=eps)
        return out.reshape(a.shape)

    return dispatch("fused_bias_dropout_residual_layer_norm", op,
                    (x, residual, bias, ln_scale, ln_bias, seed_t),
                    dict(p=p, eps=float(ln_epsilon)))
