"""Qwen3-Next: three gated delta-rule (linear-attention) layers to one
gated softmax-attention layer, every FFN a sparse expert layer beside a
gated shared expert — a functional model for the SPMD step.

The family's block (``model_type: qwen3_next``).  ``N(x; w) = x
rsqrt(mean(x^2) + rms_norm_eps) (1 + w)`` in float32 (a zero-centred
RMSNorm: ``w`` starts at 0); no bias, no position table.  Layer ``l``:
``h = x + Mix_l(N(x; op_norm))``, ``x' = h + MoE(N(h; ffn_norm))``.

- ``Mix_l`` where ``(l + 1) % full_attention_interval == 0`` — **gated
  attention**: ``[q | gate] = z W_q`` per head, ``k = z W_k``, ``v = z
  W_v``; q and k through ``N`` over the head size (one gain shared by the
  heads); rotate-half RoPE on the first ``partial_rotary_factor *
  head_dim`` components; causal softmax attention, KV head j serving
  query heads ``j g .. j g + g - 1``; ``(ctx * sigmoid(gate)) W_o``.
- ``Mix_l`` elsewhere — the **gated delta rule**: ``[q, k, v, g_z] = z
  W_qkvz`` (columns in that order: ``linear_num_key_heads`` heads of q,
  of k, ``linear_num_value_heads`` heads of v, of g_z), ``[b, a] = z
  W_ba``; a causal depth-wise convolution of ``linear_conv_kernel_dim``
  taps over concat(q, k, v) and SiLU; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)`` in float32; q and k heads repeated
  to the v heads (k head j serves v heads ``j r .. j r + r - 1``),
  L2-normalised, q scaled by ``1 / sqrt(d_k)``; the recurrence
  (``ops/gated_delta_rule.py``, chunked); per head ``gdn_norm * o
  rsqrt(mean(o^2) + eps) * silu(g_z)`` (``gdn_norm`` starts at 1: not
  zero-centred); ``W_out``.
- ``MoE``: ``p = softmax(z W_r)`` over the router's whole width in
  float32, the top ``num_experts_per_tok`` chosen and renormalised
  (``norm_topk_prob``), the chosen experts' SwiGLU summed
  (``meta_parallel.moe.routed_experts`` with ``softmax_topk_routing``);
  plus the shared expert ``sigmoid(z w_sg) (silu(z W1s) * z W3s) W2s``,
  whole on every chip and outside the exchange.  No auxiliary loss.

The model holds ``num_experts_held`` experts of each layer from
``first_expert`` on — one chip's share of a deployment; routing runs
over all ``num_experts``.  ``build_spmd_train_step`` asks
``spmd_parts(mesh)`` for the model's own (parameters, shardings, trunk,
the float32 leaves); cast, remat, loss head, AdamW and the jit are the
builder's, shared with GPT and LFM2-MoE.  One dict of parameters a layer.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from .sparse_blocks import (batch_axes_of, held_experts, leaf_name,
                            moe_counters, rope_angles, swiglu)

__all__ = ["Qwen3NextConfig", "init_qwen3_next_params",
           "qwen3_next_param_shardings"]


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512                 # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    num_experts_held: Optional[int] = None  # None: all of them
    first_expert: int = 0
    # routed-row buffer as a multiple of the rows a uniform router sends
    # to the held experts; None: every row a router could send
    moe_rows_factor: Optional[float] = None
    gdn_chunk: int = 64                    # tokens a chunk of the rule

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def held(self) -> int:
        return self.num_experts if self.num_experts_held is None \
            else self.num_experts_held

    def is_attention(self, l: int) -> bool:
        return (l + 1) % self.full_attention_interval == 0

    def moe_rows(self, tokens: int) -> Optional[int]:
        """Rows of the routed-row buffer for ``tokens`` tokens."""
        from ..distributed.fleet.meta_parallel.moe import routed_rows
        return routed_rows(tokens, self.num_experts_per_tok, self.held,
                           self.num_experts, self.moe_rows_factor)

    def spmd_parts(self, mesh: Mesh):
        """What ``build_spmd_train_step`` asks of a model."""
        return _spmd_parts(self, mesh)


def init_qwen3_next_params(cfg: Qwen3NextConfig, key) -> Dict:
    """Float32 parameters: weights normal(0, 0.02), the zero-centred
    gains 0, ``gdn_norm`` 1, ``A_log = log(U(0, 16))``, ``dt_bias`` 1."""
    D = cfg.hidden_size
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    H, K, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    E, held, F = cfg.num_experts, cfg.held, cfg.moe_intermediate_size
    Fs = cfg.shared_expert_intermediate_size
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 2))

    def normal(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    layers = []
    for l in range(cfg.num_layers):
        p = {"op_norm": jnp.zeros((D,)), "ffn_norm": jnp.zeros((D,))}
        if cfg.is_attention(l):
            p.update(q_w=normal(D, H * 2 * hd), k_w=normal(D, K * hd),
                     v_w=normal(D, K * hd), q_norm=jnp.zeros((hd,)),
                     k_norm=jnp.zeros((hd,)), o_w=normal(H * hd, D))
        else:
            conv = 2 * Hk * dk + Hv * dv
            p.update(
                qkvz_w=normal(D, conv + Hv * dv), ba_w=normal(D, 2 * Hv),
                conv_w=normal(cfg.linear_conv_kernel_dim, conv),
                A_log=jnp.log(jax.random.uniform(
                    next(keys), (Hv,), jnp.float32, 1e-4, 16.0)),
                dt_bias=jnp.ones((Hv,)), gdn_norm=jnp.ones((dv,)),
                out_w=normal(Hv * dv, D))
        p.update(router_w=normal(D, E), w1=normal(held, D, F),
                 w3=normal(held, D, F), w2=normal(held, F, D),
                 shared_w1=normal(D, Fs), shared_w3=normal(D, Fs),
                 shared_w2=normal(Fs, D), shared_gate_w=normal(D, 1))
        layers.append(p)
    return {"wte": normal(cfg.vocab_size, D), "layers": layers,
            "out_norm": jnp.zeros((D,)),
            "head_w": normal(D, cfg.vocab_size)}


def qwen3_next_param_shardings(mesh: Mesh, cfg: Qwen3NextConfig) -> Dict:
    """Everything whole on every device (the shared expert too), but the
    routed experts' leading axis over ``ep`` where the mesh has one."""
    from ..distributed.fleet.meta_parallel.moe import held_expert_shardings
    return held_expert_shardings(mesh, jax.eval_shape(
        lambda: init_qwen3_next_params(cfg, jax.random.PRNGKey(0))))


def _norm(x, w, eps):
    """The zero-centred RMSNorm ``N(x; w)``, in float32."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _partial_rope(x, theta: float, rotary: int):
    """Rotate-half RoPE on the first ``rotary`` components of every head
    (pairs ``(i, i + rotary / 2)``), the rest untouched; x: (B, T, H, hd)."""
    ang = rope_angles(x.shape[1], theta, rotary)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :rotary // 2], xf[..., rotary // 2:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., rotary:]],
        axis=-1).astype(x.dtype)


def _gated_attention(p, x, cfg, mesh, batch_axes):
    from ..ops.pallas.flash_attention import flash_attention
    B, T, _ = x.shape
    H, K, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    rotary = int(cfg.partial_rotary_factor * hd)
    with jax.named_scope("gattn_qkv"):
        z = _norm(x, p["op_norm"], eps)
        qg = (z @ p["q_w"]).reshape(B, T, H, 2, hd)
        q, gate = qg[..., 0, :], qg[..., 1, :]
        k = (z @ p["k_w"]).reshape(B, T, K, hd)
        v = (z @ p["v_w"]).reshape(B, T, K, hd)
        q = _partial_rope(_norm(q, p["q_norm"], eps), cfg.rope_theta, rotary)
        k = _partial_rope(_norm(k, p["k_norm"], eps), cfg.rope_theta, rotary)
        # the kernels take equal head counts: the KV heads are repeated
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    # outside every scope, like the other models' attention: a scope
    # around a pallas_call renames the Mosaic custom call
    ctx = flash_attention(q, k, v, causal=True, mesh=mesh,
                          batch_axes=batch_axes)
    ctx = checkpoint_name(ctx, "attn_ctx")
    with jax.named_scope("gattn_out"):
        y = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
        return x + y.reshape(B, T, H * hd) @ p["o_w"]


def _by_heads(x, heads: int):
    """Token-major (B, T, heads d) -> (B, T / 8, heads, 8, d): a head's
    ``d`` columns last, to reduce over, the axes in the order a TPU tiles
    the token-major array in — 8 tokens by 128 lanes a tile, a head a
    tile of the row — so that the view costs nothing there.  (B, T,
    heads, d) is another tiling, 8 heads of one token a tile: every byte
    copied (PERF.md section 7)."""
    B, T, _ = x.shape
    rows = 8 if T % 8 == 0 else 1
    return jnp.swapaxes(x.reshape(B, T // rows, rows, heads, -1), 2, 3)


def _gated_delta_net(p, x, cfg, mesh, batch_axes):
    from ..ops.causal_conv import gated_causal_conv
    from ..ops.gated_delta_rule import gated_delta_rule
    B, T, _ = x.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    n_qk, n_v = Hk * dk, Hv * dv
    with jax.named_scope("gdn_in"):
        z = _norm(x, p["op_norm"], cfg.rms_norm_eps)
        # two products from one weight: each result is an array of its
        # own (a slice of one (B, T, 12288) result would be copied out)
        qkv = z @ p["qkvz_w"][:, :2 * n_qk + n_v]
        gz = z @ p["qkvz_w"][:, 2 * n_qk + n_v:]
        ba = z @ p["ba_w"]
    with jax.named_scope("gdn_conv"):
        # convolution, SiLU and the q / k L2 norms: on a TPU a kernel pair
        # (``%gdn_conv_fwd``, ``%gdn_conv_bwd``) inside the scope, found
        # by the path as the rule's are; q, k, v come as three arrays,
        # token-major (B, T, heads x d) — as the rule's kernels read them
        q, k, v = gated_causal_conv(qkv, p["conv_w"], n_qk=n_qk, head=dk,
                                    mesh=mesh, batch_axes=batch_axes)
        beta = jax.nn.sigmoid(ba[..., :Hv].astype(jnp.float32))
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., Hv:].astype(jnp.float32)
            + p["dt_bias"].astype(jnp.float32))
    with jax.named_scope("gdn_scan"):
        # the rule repeats the key heads to the value heads; its kernels
        # (``%delta_rule_*``) sit inside the scope, which is how
        # ``gdn_scan_roofline`` finds them: by the path, not by a name
        o = gated_delta_rule(q, k, v, g, beta, chunk=cfg.gdn_chunk,
                             key_heads=Hk, mesh=mesh, batch_axes=batch_axes)
    with jax.named_scope("gdn_out"):
        of = _by_heads(o, Hv).astype(jnp.float32)
        y = of * lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True)
                           + cfg.rms_norm_eps)
        y = y * p["gdn_norm"].astype(jnp.float32) \
            * jax.nn.silu(_by_heads(gz, Hv).astype(jnp.float32))
        y = jnp.swapaxes(y.astype(x.dtype), 2, 3).reshape(B, T, n_v)
        return x + y @ p["out_w"]


def _expert_ffn(p, x, cfg, mesh, batch_axes):
    from ..distributed.fleet.meta_parallel.moe import softmax_topk_routing
    with jax.named_scope("moe_route"):
        z = _norm(x, p["ffn_norm"], cfg.rms_norm_eps)
    y, counts, overflow = held_experts(
        z, p, cfg, mesh, batch_axes,
        routing=functools.partial(
            softmax_topk_routing, top_k=cfg.num_experts_per_tok,
            renormalize=cfg.norm_topk_prob))
    with jax.named_scope("shared_expert"):
        # every chip computes it alike: outside the exchange
        gate = jax.nn.sigmoid((z @ p["shared_gate_w"]).astype(jnp.float32))
        shared = gate.astype(z.dtype) * swiglu(
            z, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    with jax.named_scope("moe_combine"):
        return x + y + shared, counts, overflow


def _spmd_parts(cfg: Qwen3NextConfig, mesh: Mesh):
    batch_axes = batch_axes_of(mesh, "Qwen3-Next")

    def block(l):
        attention = cfg.is_attention(l)

        def fn(p, x):
            x = _gated_attention(p, x, cfg, mesh, batch_axes or ()) \
                if attention \
                else _gated_delta_net(p, x, cfg, mesh, batch_axes or ())
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
            x = _norm(x, params["out_norm"], cfg.rms_norm_eps)
        return x, moe_counters(counted)

    return SimpleNamespace(
        init=lambda key: init_qwen3_next_params(cfg, key),
        shardings=qwen3_next_param_shardings(mesh, cfg),
        trunk=trunk, batch_axes=batch_axes,
        step_name="qwen3_next_spmd_train_step",
        # the router and the decay's two parameters compute in float32
        keep_float32=lambda path: leaf_name(path) in (
            "router_w", "A_log", "dt_bias"),
        frozen=None)
