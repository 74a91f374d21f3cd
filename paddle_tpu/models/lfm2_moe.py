"""LFM2-MoE: a hybrid of gated short convolutions and grouped-query
attention with sparse experts, as a functional model for the SPMD step.

The family's block (``model_type: lfm2_moe``): pre-RMSNorm, no bias, no
position table.  Every layer is ``h = x + Op(RMS(x))``, ``x' = h +
FFN(RMS(h))`` with

- ``Op`` by ``layer_types[l]``: ``"conv"`` — the gated short convolution
  ``[b, c, u] = split(z W_in)``, ``y_t = sum_j w_j * (b * u)_{t-j}``
  (depth-wise, causal, ``conv_L_cache`` taps), ``(c * y) W_out``; or
  ``"full_attention"`` — grouped-query attention with a per-head RMSNorm
  on q and k and rotate-half RoPE over the whole head;
- ``FFN``: SwiGLU of width ``intermediate_size`` in the first
  ``num_dense_layers`` layers, after them ``num_experts_per_tok`` of
  ``num_experts`` routed SwiGLU experts of width ``moe_intermediate_size``
  (sigmoid scores, a selection bias that takes no gradient, weights
  normalised over the chosen experts, no shared expert, no auxiliary
  loss): ``distributed.fleet.meta_parallel.moe.routed_experts``.

The model holds ``num_experts_held`` experts of each layer from
``first_expert`` on — one chip's share of a deployment whose experts are
spread over chips; routing still runs over all ``num_experts``.

``build_spmd_train_step`` (models/gpt_spmd.py) asks ``spmd_parts`` for
what is the model's own: the parameters, their shardings, the trunk from
ids to the final hidden states, and which leaves stay float32 or take no
update.  The cast, remat, loss head, AdamW and the jit are the step
builder's, shared with GPT.  Parameters are one dict per layer (no
stacked ``(L, ...)`` arrays: the layers are of four kinds).
"""
from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ..ops.rope import rope_rotate_half
from .sparse_blocks import (batch_axes_of, dense_ffn as _dense_ffn,
                            held_experts, leaf_name, moe_counters,
                            rms_norm as _rms, rope_angles)

__all__ = ["Lfm2MoeConfig", "init_lfm2_moe_params",
           "lfm2_moe_param_shardings"]

_PERIOD = ("conv", "conv", "full_attention", "conv")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    layer_types: Tuple[str, ...] = _PERIOD * 10
    num_dense_layers: int = 2
    num_experts: int = 64                  # the router's width
    num_experts_per_tok: int = 4
    num_experts_held: Optional[int] = None  # None: all of them
    first_expert: int = 0
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    routed_scaling_factor: float = 1.0
    # routed-row buffer as a multiple of the rows a uniform router sends
    # to the held experts (tokens * k * held / num_experts); None: every
    # row a router could send, so that nothing can overflow
    moe_rows_factor: Optional[float] = None

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self) -> int:
        return self.num_experts if self.num_experts_held is None \
            else self.num_experts_held

    def moe_rows(self, tokens: int) -> Optional[int]:
        """Rows of the routed-row buffer for ``tokens`` tokens."""
        from ..distributed.fleet.meta_parallel.moe import routed_rows
        return routed_rows(tokens, self.num_experts_per_tok, self.held,
                           self.num_experts, self.moe_rows_factor)

    def spmd_parts(self, mesh: Mesh):
        """What ``build_spmd_train_step`` asks of a model."""
        return _spmd_parts(self, mesh)


def init_lfm2_moe_params(cfg: Lfm2MoeConfig, key) -> Dict:
    """Float32 parameters: weights normal(0, 0.02), gains 1, the router's
    selection bias normal(0, 0.01)."""
    D, hd = cfg.hidden_size, cfg.head_dim
    keys = iter(jax.random.split(key, 12 * cfg.num_layers + 2))

    def normal(*shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    layers = []
    for l, kind in enumerate(cfg.layer_types):
        p = {"op_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,))}
        if kind == "conv":
            p.update(conv_in_w=normal(D, 3, D),
                     conv_w=normal(cfg.conv_L_cache, D),
                     conv_out_w=normal(D, D))
        elif kind == "full_attention":
            kv = cfg.num_key_value_heads * hd
            p.update(q_w=normal(D, D), k_w=normal(D, kv), v_w=normal(D, kv),
                     q_norm=jnp.ones((hd,)), k_norm=jnp.ones((hd,)),
                     o_w=normal(D, D))
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        if l < cfg.num_dense_layers:
            F = cfg.intermediate_size
            p.update(w1=normal(D, F), w3=normal(D, F), w2=normal(F, D))
        else:
            E, H, F = cfg.num_experts, cfg.held, cfg.moe_intermediate_size
            p.update(router_w=normal(D, E),
                     router_bias=normal(E, std=0.01),
                     w1=normal(H, D, F), w3=normal(H, D, F),
                     w2=normal(H, F, D))
        layers.append(p)
    return {"wte": normal(cfg.vocab_size, D), "layers": layers,
            "out_norm": jnp.ones((D,)),
            "head_w": normal(D, cfg.vocab_size)}


def lfm2_moe_param_shardings(mesh: Mesh, cfg: Lfm2MoeConfig) -> Dict:
    """Everything whole on every device, but the experts' leading axis
    over ``ep`` where the mesh has one."""
    from ..distributed.fleet.meta_parallel.moe import held_expert_shardings
    return held_expert_shardings(mesh, jax.eval_shape(
        lambda: init_lfm2_moe_params(cfg, jax.random.PRNGKey(0))))


def _rope(x, theta):
    """Rotate-half RoPE over the whole head; x: (B, T, H, hd)."""
    return rope_rotate_half(x, rope_angles(x.shape[1], theta, x.shape[-1]))


def _short_conv(p, x, eps, mesh, batch_axes):
    from ..ops.causal_conv import short_conv
    with jax.named_scope("short_conv"):
        z = _rms(x, p["op_norm"], eps)
        # the three sections on a leading axis, (3, B, T, D) row-major:
        # what the convolution's kernels read, and for the weight's
        # gradient a product per section in the parameter's own order
        bcu = jnp.einsum("btd,dse->sbte", z, p["conv_in_w"])
        y = short_conv(bcu, p["conv_w"], mesh=mesh, batch_axes=batch_axes)
        return x + y @ p["conv_out_w"]


def _gqa(p, x, cfg, mesh, batch_axes):
    from ..ops.pallas.flash_attention import flash_attention
    B, T, _ = x.shape
    H, K, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("gqa_qkv"):
        z = _rms(x, p["op_norm"], cfg.norm_eps)
        q = (z @ p["q_w"]).reshape(B, T, H, hd)
        k = (z @ p["k_w"]).reshape(B, T, K, hd)
        v = (z @ p["v_w"]).reshape(B, T, K, hd)
        q = _rope(_rms(q, p["q_norm"], cfg.norm_eps), cfg.rope_theta)
        k = _rope(_rms(k, p["k_norm"], cfg.norm_eps), cfg.rope_theta)
        # KV head j serves query heads j * H/K ... : the kernels take
        # equal head counts, so the KV heads are repeated
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    # like GPT's, the attention call stays outside every scope: a scope
    # around a pallas_call renames the Mosaic custom call
    ctx = flash_attention(q, k, v, causal=True, mesh=mesh,
                          batch_axes=batch_axes)
    ctx = checkpoint_name(ctx.reshape(B, T, H * hd), "attn_ctx")
    with jax.named_scope("gqa_out"):
        return x + ctx @ p["o_w"]


def _expert_ffn(p, x, cfg, mesh, batch_axes):
    with jax.named_scope("moe_route"):
        z = _rms(x, p["ffn_norm"], cfg.norm_eps)
    y, counts, overflow = held_experts(
        z, p, cfg, mesh, batch_axes, p["router_bias"],
        scaling=cfg.routed_scaling_factor)
    with jax.named_scope("moe_combine"):
        return x + y, counts, overflow


def _spmd_parts(cfg: Lfm2MoeConfig, mesh: Mesh):
    batch_axes = batch_axes_of(mesh, "LFM2-MoE")

    def block(l):
        conv = cfg.layer_types[l] == "conv"

        def fn(p, x):
            x = _short_conv(p, x, cfg.norm_eps, mesh, batch_axes or ()) \
                if conv else _gqa(p, x, cfg, mesh, batch_axes or ())
            if l < cfg.num_dense_layers:
                return _dense_ffn(p, x, cfg.norm_eps), None
            x, counts, overflow = _expert_ffn(p, x, cfg, mesh, batch_axes)
            return x, (counts, overflow)
        return fn

    blocks = [block(l) for l in range(cfg.num_layers)]

    def trunk(params, ids, remat):
        """ids -> (final hidden states, the step's counters)."""
        with jax.named_scope("embed"):
            x = params["wte"][ids]
        counted = []
        for fn, p in zip(blocks, params["layers"]):
            x, aux = remat(fn)(p, x)
            if aux is not None:
                counted.append(aux)
        with jax.named_scope("final_norm"):
            x = _rms(x, params["out_norm"], cfg.norm_eps)
        return x, moe_counters(counted)

    return SimpleNamespace(
        init=lambda key: init_lfm2_moe_params(cfg, key),
        shardings=lfm2_moe_param_shardings(mesh, cfg),
        trunk=trunk, batch_axes=batch_axes,
        step_name="lfm2_moe_spmd_train_step",
        # the router computes in float32; its selection bias only
        # selects: no gradient, no AdamW update
        keep_float32=lambda path: leaf_name(path) in ("router_w",
                                                      "router_bias"),
        frozen=lambda path: leaf_name(path) == "router_bias")
