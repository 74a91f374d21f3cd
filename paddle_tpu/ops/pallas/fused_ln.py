"""Fused bias + dropout + residual-add + layernorm — pallas TPU kernel.

Reference parity: ``operators/fused/fused_dropout_helper.h`` and
``fused_attention_op.cu``'s epilogue — the reference hand-fuses
bias-add, dropout, residual-add and LayerNorm into one CUDA kernel to
avoid four HBM round-trips.  Here one pallas kernel does the same per
row-block in VMEM: one read of (x, residual), one write of out.

Dropout uses a counter-based hash RNG (Murmur3-style finalizer over the
global element index, seeded per call): a pure function of (seed, index),
so the XLA fallback produces bit-identical masks and the backward pass
*recomputes* the mask instead of storing an (N, D) mask tensor — saving
the mask write the reference's kernel performs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_ln_pallas", "hash_uniform"]


def hash_uniform(seed, shape, offset=0):
    """Uniform [0,1) from a Murmur3-finalizer hash of the element index.

    Pure jnp — used inside the pallas kernel, by the XLA fallback, and by
    the backward's mask recompute; all three see identical bits.
    ``seed`` is a uint32 scalar (array or python int); ``offset`` is the
    linear index of shape[0,0] in the full array.
    """
    idx = lax.broadcasted_iota(jnp.uint32, shape, 0)
    if len(shape) > 1:
        idx = idx * jnp.uint32(shape[1]) + \
            lax.broadcasted_iota(jnp.uint32, shape, 1)
    h = idx + jnp.asarray(offset, jnp.uint32)
    h = (h ^ jnp.asarray(seed, jnp.uint32)) * jnp.uint32(0x9E3779B1)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # 24 random bits: the value is < 2**24, so the route through int32
    # is exact (Mosaic has no uint32 -> float32 cast)
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) \
        * (1.0 / (1 << 24))


def _kernel(x_ref, res_ref, bias_ref, gamma_ref, beta_ref, seed_ref,
            out_ref, *, p: float, eps: float, block_rows: int, D: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    if p > 0.0:
        seed = seed_ref[0, 0]
        u = hash_uniform(seed, (block_rows, D), offset=i * block_rows * D)
        x = jnp.where(u >= p, x / (1.0 - p), 0.0)
    z = res_ref[...].astype(jnp.float32) + x
    mean = jnp.mean(z, axis=-1, keepdims=True)
    zc = z - mean
    var = jnp.mean(zc * zc, axis=-1, keepdims=True)
    y = zc * lax.rsqrt(var + eps)
    y = y * gamma_ref[...].astype(jnp.float32) + \
        beta_ref[...].astype(jnp.float32)
    out_ref[...] = y.astype(out_ref.dtype)


def fused_ln_pallas(x, residual, bias, gamma, beta, seed, *, p: float,
                    eps: float, interpret: bool = False):
    """x/residual: (N, D); bias/gamma/beta: (D,); seed: uint32 scalar."""
    N, D = x.shape
    # row blocks tile in sublanes (8 rows of 32 bits, 16 of bf16): pad
    # a ragged row count up (the hash indexes elements by row * D + col,
    # so real rows keep their bits) and slice the pad rows off the result
    sub = 32 // x.dtype.itemsize
    Np = -(-N // sub) * sub
    if Np != N:
        x, residual = (jnp.pad(a, ((0, Np - N), (0, 0)))
                       for a in (x, residual))
    block_rows = next(b for b in (256, 128, 64, 32, 16, 8)
                      if Np % b == 0 and b >= sub)
    grid = (Np // block_rows,)
    row_spec = pl.BlockSpec((block_rows, D), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, D), lambda i: (0, 0))
    one_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, p=p, eps=eps, block_rows=block_rows, D=D),
        grid=grid,
        in_specs=[row_spec, row_spec, vec_spec, vec_spec, vec_spec, one_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((Np, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, residual, bias.reshape(1, D), gamma.reshape(1, D),
      beta.reshape(1, D), jnp.asarray(seed, jnp.uint32).reshape(1, 1))
    return out[:N] if Np != N else out
