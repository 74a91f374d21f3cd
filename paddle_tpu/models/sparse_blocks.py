"""What the sparse functional models share (``lfm2_moe``, ``qwen3_next``,
``joyai_flash``, ``mellum2``): the pieces of a block that are the same mathematics
under each of them, kept once.  Each model keeps what is its own — its
mixers, its norm where that differs (Qwen3-Next's is zero-centred), its
RoPE pairing — and the scopes it names its parts with.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

__all__ = ["rms_norm", "rope_angles", "swiglu", "dense_ffn", "held_experts",
           "batch_axes_of", "moe_counters", "leaf_name"]


def rms_norm(x, g, eps):
    """``g * x rsqrt(mean(x^2) + eps)``: the statistics in float32, the
    gain (initialised 1) applied in the compute type."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * g.astype(x.dtype)


def rope_angles(T: int, theta: float, rotary: int, yarn=None):
    """(T, rotary / 2) float64 angles ``t * theta^(-2i / rotary)`` of
    RoPE over ``rotary`` components; which components pair up is the
    caller's (rotate-half: ``ops/rope.py``; interleaved: JoyAI's own).

    ``yarn``: (factor, original_max_position_embeddings, beta_fast,
    beta_slow) — YaRN's frequencies (HF ``rope_type: yarn``, truncated
    correction range): ``inv_i = inv_i / factor (1 - e_i) + inv_i e_i``
    with ``e_i = 1 - clamp((i - lo) / (hi - lo), 0, 1)``, ``lo =
    floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))``, ``c(r) = rotary
    ln(original / (2 pi r)) / (2 ln theta)``: the fast components keep
    their frequency, the slow ones are interpolated by the factor.  The
    attention factor that scales cos and sin is the caller's."""
    inv = 1.0 / (theta ** (np.arange(0, rotary, 2, dtype=np.float64)
                           / rotary))
    if yarn is not None:
        factor, original, beta_fast, beta_slow = yarn

        def c(r):
            return rotary * math.log(original / (2 * math.pi * r)) \
                / (2 * math.log(theta))

        lo = max(math.floor(c(beta_fast)), 0)
        hi = min(math.ceil(c(beta_slow)), rotary - 1)
        ramp = np.clip((np.arange(rotary // 2) - lo) / max(hi - lo, 1e-3),
                       0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
    return np.arange(T, dtype=np.float64)[:, None] * inv[None, :]


def swiglu(z, w1, w3, w2):
    return (jax.nn.silu(z @ w1) * (z @ w3)) @ w2


def dense_ffn(p, x, eps):
    """``x + SwiGLU(RMS(x))`` of a leading dense layer."""
    with jax.named_scope("dense_ffn"):
        z = rms_norm(x, p["ffn_norm"], eps)
        return x + swiglu(z, p["w1"], p["w3"], p["w2"])


def held_experts(z, p, cfg, mesh: Mesh, batch_axes, bias=None, **routing):
    """``routed_experts`` over the experts a model holds of one layer
    (``p``: ``router_w``, ``w1``, ``w3``, ``w2``; ``cfg``:
    ``num_experts_per_tok``, ``first_expert``, ``moe_rows``): the
    routed-row buffer sized per shard of the batch, the exchange over
    ``ep`` where the mesh has it.  ``routing`` is handed through
    (``scaling=`` of the default sigmoid routing, or ``routing=``).
    -> (y, counts, overflow)."""
    from ..distributed.fleet.meta_parallel.moe import routed_experts
    ep = mesh.shape.get("ep", 1) > 1
    shards = int(np.prod([mesh.shape[a] for a in batch_axes])) \
        if batch_axes else 1
    return routed_experts(
        z, p["router_w"], bias, p["w1"], p["w3"], p["w2"],
        top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        rows=cfg.moe_rows(z.shape[0] * z.shape[1] // shards),
        mesh=mesh, token_axes=batch_axes or (),
        ep_axis="ep" if ep else None, **routing)


def batch_axes_of(mesh: Mesh, model: str) -> Optional[tuple]:
    """The mesh axes that shard a sparse model's batch (None on one
    device); a mesh with an axis these models have no path for is
    refused."""
    for axis in ("pp", "sp", "mp"):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"the {model} step runs on one device, dp and ep; the "
                f"mesh has {axis}={mesh.shape[axis]}")
    return tuple(a for a in ("dp", "sharding", "ep")
                 if mesh.shape.get(a, 1) > 1) or None


def moe_counters(counted):
    """The step's counters from each expert layer's (counts, overflow);
    none for a model with no expert layer."""
    if not counted:
        return {}
    return {"moe_counts": jnp.stack([c for c, _ in counted]),
            "moe_overflow": sum(o for _, o in counted)}


def leaf_name(path):
    return getattr(path[-1], "key", None)
