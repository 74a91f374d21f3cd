"""Mellum2: grouped-query attention in two kinds of layers — three over a
sliding window to one over the whole causal prefix with YaRN RoPE —
each followed by a softmax-routed expert layer; a functional model for
the SPMD step.

The family's block (``model_type: mellum``).  ``RMS(x; g) = g x
rsqrt(mean(x^2) + rms_norm_eps)`` (g initialised 1); no bias, no
position table.  Layer ``l``: ``h = x + Attn_l(RMS(x; op_norm))``, ``x'
= h + MoE(RMS(h; ffn_norm))``, then a final RMSNorm and an untied head.

- ``Attn_l``: ``q = z W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = z W_k``, ``v = z W_v`` (``num_key_value_heads``
  heads), no q/k norm; rotate-half RoPE over the whole head of q and k,
  by ``layer_types[l]``: ``"sliding_attention"`` — angles ``t
  theta^(-2i / hd)`` at the sliding section's theta; ``"full_attention"``
  — YaRN's frequencies (``sparse_blocks.rope_angles``) with cos and sin
  times the attention factor.  KV head j serves query heads ``j g .. j g
  + g - 1``; ``softmax(q k^T / sqrt(hd) + M_l) v`` with ``M_l`` causal,
  and on a window layer also ``i - j < sliding_window`` (a query sees
  ``sliding_window`` keys, itself included); ``ctx W_o``.
- ``MoE``: ``p = softmax(z W_r)`` over the router's whole width in
  float32, the top ``num_experts_per_tok`` chosen and renormalised
  (``norm_topk_prob``), the chosen experts' SwiGLU of width
  ``moe_intermediate_size`` summed (``routed_experts`` with
  ``softmax_topk_routing``).  No shared expert, no auxiliary loss.

The model holds ``num_experts_held`` experts of each layer from
``first_expert`` on — one chip's share of a deployment; routing runs
over all ``num_experts``.  RoPE is ``ops/rope.py``'s ``rope_to_heads``:
on a TPU a Pallas pair that takes q and k from the projections and hands
them to the flash kernels head-major.  A window layer's attention is the
flash kernels' ``window=`` (``ops/pallas/flash_attention.py``: the
resident pair over the band of key chunks alone).  ``build_spmd_train_step``
asks ``spmd_parts(mesh)`` for the model's own; cast, remat, loss head,
AdamW and the jit are the builder's.  One dict of parameters a layer.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ..ops.rope import rope_to_heads
from .sparse_blocks import (batch_axes_of, held_experts, leaf_name,
                            moe_counters, rms_norm as _rms, rope_angles)

__all__ = ["Mellum2Config", "init_mellum2_params", "mellum2_param_shardings"]

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclass(frozen=True)
class Mellum2Config:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    # the published list; the model runs its first num_hidden_layers
    layer_types: Tuple[str, ...] = _PERIOD * 7
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta_sliding: float = 500000.0
    rope_theta_full: float = 500000.0
    # YaRN of the full layers: (factor, original_max_position_embeddings,
    # beta_fast, beta_slow) and the factor on cos and sin
    yarn: Tuple[float, int, float, float] = (16.0, 8192, 32.0, 1.0)
    yarn_attention_factor: float = 1.2772588722239782
    rms_norm_eps: float = 1e-6
    num_experts: int = 64                  # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    num_experts_held: Optional[int] = None  # None: all of them
    first_expert: int = 0
    # routed-row buffer as a multiple of the rows a uniform router sends
    # to the held experts; None: every row a router could send
    moe_rows_factor: Optional[float] = None

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def held(self) -> int:
        return self.num_experts if self.num_experts_held is None \
            else self.num_experts_held

    def window_of(self, l: int) -> Optional[int]:
        """Layer ``l``'s sliding window, or None for a full layer."""
        kind = self.layer_types[l]
        if kind not in _PERIOD:
            raise ValueError(f"unknown layer type {kind!r}")
        return self.sliding_window if kind == "sliding_attention" else None

    def moe_rows(self, tokens: int) -> Optional[int]:
        """Rows of the routed-row buffer for ``tokens`` tokens."""
        from ..distributed.fleet.meta_parallel.moe import routed_rows
        return routed_rows(tokens, self.num_experts_per_tok, self.held,
                           self.num_experts, self.moe_rows_factor)

    def spmd_parts(self, mesh: Mesh):
        """What ``build_spmd_train_step`` asks of a model."""
        return _spmd_parts(self, mesh)


def init_mellum2_params(cfg: Mellum2Config, key) -> Dict:
    """Float32 parameters: weights normal(0, 0.02), gains 1, the input
    embedding normal(0, 1) — at 0.02 the attention outputs, several times
    larger, would make every token's hidden state nearly one vector and
    send every token to the same experts."""
    D, hd = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    E, held, F = cfg.num_experts, cfg.held, cfg.moe_intermediate_size
    keys = iter(jax.random.split(key, 8 * cfg.num_layers + 2))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    layers = [{"op_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,)),
               "q_w": normal(D, q), "k_w": normal(D, kv),
               "v_w": normal(D, kv), "o_w": normal(q, D),
               "router_w": normal(D, E), "w1": normal(held, D, F),
               "w3": normal(held, D, F), "w2": normal(held, F, D)}
              for _ in range(cfg.num_layers)]
    wte = jax.random.normal(next(keys), (cfg.vocab_size, D), jnp.float32)
    return {"wte": wte, "layers": layers,
            "out_norm": jnp.ones((D,)),
            "head_w": normal(D, cfg.vocab_size)}


def mellum2_param_shardings(mesh: Mesh, cfg: Mellum2Config) -> Dict:
    """Everything whole on every device, but the experts' leading axis
    over ``ep`` where the mesh has one."""
    from ..distributed.fleet.meta_parallel.moe import held_expert_shardings
    return held_expert_shardings(mesh, jax.eval_shape(
        lambda: init_mellum2_params(cfg, jax.random.PRNGKey(0))))


def _rope(cfg: Mellum2Config, T: int, window: Optional[int]):
    """The layer kind's RoPE over the whole head: (angles, the factor on
    cos and sin)."""
    hd = cfg.head_dim
    if window is not None:
        return rope_angles(T, cfg.rope_theta_sliding, hd), 1.0
    return (rope_angles(T, cfg.rope_theta_full, hd, cfg.yarn),
            cfg.yarn_attention_factor)


def _attention(p, x, cfg, mesh, batch_axes, window):
    from ..ops.pallas.flash_attention import flash_attention
    B, T, _ = x.shape
    H, K, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("mellum_qkv"):
        z = _rms(x, p["op_norm"], cfg.rms_norm_eps)
        # q and k turned and handed over head-major, (B, heads, T, hd):
        # the layout the flash kernels fold to
        q, k = rope_to_heads(z @ p["q_w"], z @ p["k_w"],
                             *_rope(cfg, T, window), mesh=mesh,
                             batch_axes=batch_axes)
        v = (z @ p["v_w"]).reshape(B, T, K, hd)
        # the kernels take equal head counts: the KV heads are repeated
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=2)
    # outside every scope, like the other models' attention: a scope
    # around a pallas_call renames the Mosaic custom call (a window
    # layer's calls carry names of their own, the kernels' ``name=``).
    # The entry takes (B, T, H, hd) and folds it to (B H, T, hd): the two
    # swaps of q and k cancel
    ctx = flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), v,
                          causal=True, window=window, mesh=mesh,
                          batch_axes=batch_axes)
    ctx = checkpoint_name(ctx.reshape(B, T, H * hd), "attn_ctx")
    with jax.named_scope("mellum_out"):
        return x + ctx @ p["o_w"]


def _expert_ffn(p, x, cfg, mesh, batch_axes):
    from ..distributed.fleet.meta_parallel.moe import softmax_topk_routing
    with jax.named_scope("moe_route"):
        z = _rms(x, p["ffn_norm"], cfg.rms_norm_eps)
    y, counts, overflow = held_experts(
        z, p, cfg, mesh, batch_axes,
        routing=functools.partial(
            softmax_topk_routing, top_k=cfg.num_experts_per_tok,
            renormalize=cfg.norm_topk_prob))
    with jax.named_scope("moe_combine"):
        return x + y, counts, overflow


def _spmd_parts(cfg: Mellum2Config, mesh: Mesh):
    batch_axes = batch_axes_of(mesh, "Mellum2")

    def block(l):
        window = cfg.window_of(l)

        def fn(p, x):
            x = _attention(p, x, cfg, mesh, batch_axes or (), window)
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
            counted.append(aux)
        with jax.named_scope("final_norm"):
            x = _rms(x, params["out_norm"], cfg.rms_norm_eps)
        return x, moe_counters(counted)

    return SimpleNamespace(
        init=lambda key: init_mellum2_params(cfg, key),
        shardings=mellum2_param_shardings(mesh, cfg),
        trunk=trunk, batch_axes=batch_axes,
        step_name="mellum2_spmd_train_step",
        # the router computes in float32
        keep_float32=lambda path: leaf_name(path) == "router_w",
        frozen=None)
