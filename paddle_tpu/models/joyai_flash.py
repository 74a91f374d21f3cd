"""JoyAI-LLM-Flash: latent attention (MLA) in every layer, sigmoid-routed
experts beside a shared expert, and a multi-token-prediction module that
is scored by the one loss head a second time — a functional model for the
SPMD step.

The family's block (``model_type: joyai_llm_flash``; every key is one of
the DeepSeek-V3 family's).  ``N(x; w) = w x rsqrt(mean(x^2) +
rms_norm_eps)``, statistics in float32, ``w`` starts at 1; no bias but the
router's selection bias; no position table.  Layer ``l``: ``h = x +
MLA(N(x; op_norm))``, ``x' = h + FFN_l(N(h; ffn_norm))``.

- ``MLA``: ``c_q = N(z W_qa; q_a_norm)`` (``q_lora_rank``), ``q = c_q
  W_qb`` per head ``[q_n (qk_nope_head_dim) | q_r (qk_rope_head_dim)]``;
  ``[c | k_r] = z W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``),
  ``c_kv = N(c; kv_a_norm)``, ``c_kv W_kvb`` per head ``[k_n | v
  (v_head_dim)]``.  RoPE in interleaved pairing ``(2i, 2i + 1)`` on
  ``q_r`` of every head and on the one ``k_r``, which all heads share:
  ``q_h = [q_n | R(q_r)]``, ``k_h = [k_n | R(k_r)]`` (``R(y) = y cos +
  y' sin`` with ``y'`` the same input through the projection's
  pair-swapped columns, ``_pair_swap``: the rotation shuffles a small
  weight, never the activations' lanes).  Causal ``softmax(q
  k^T / sqrt(qk_nope + qk_rope)) v``, then ``W_o``.  In training nothing
  is absorbed and no latent is cached: every product is token-major,
  (B, T, heads x width), and the flash entry's kernels read a head's
  q_n, q_r, k_n and v and the one k_r where the products wrote them
  (``ops/pallas/flash_attention.py``, ``flash_attention_latent``).
- ``FFN_l``: dense SwiGLU of width ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``num_experts_per_tok`` of
  ``n_routed_experts`` routed SwiGLU experts (``topk_method: noaux_tc``
  with one group: ``s = sigmoid(z W_r)``, the top of ``s + b`` chosen,
  ``w_e = s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor``:
  ``meta_parallel.moe.routed_experts``) plus the shared expert
  ``(silu(z W1s) * z W3s) W2s``, no gate, whole on every chip.
- The **MTP module** (``num_nextn_predict_layers`` 1; DeepSeek-V3 report,
  section 2.2).  With ``h_i`` the trunk's state after its last block,
  before the final norm, and ``t_{i+1} = labels_i``: ``u_i = [N(h_i;
  h_norm) | N(wte[t_{i+1}]; e_norm)] eh_proj`` (computed as the two
  halves' products summed), one expert layer of its own on the row ``u``,
  ``N(.; out_norm)``, and the SHARED ``head_w`` against ``t_{i+2} =
  labels_{i+1}``; the last position of a row has no such target and
  carries weight 0.  The step's loss is ``main + mtp_loss_weight * mtp``.

The model holds ``num_experts_held`` experts of each layer from
``first_expert`` on — one chip's share of a deployment; routing runs over
all ``n_routed_experts``.  ``build_spmd_train_step`` asks
``spmd_parts(mesh)`` for the model's own; cast, remat, the loss head (run
once a prediction depth), AdamW and the jit are the builder's, shared
with GPT, LFM2-MoE and Qwen3-Next.  One dict of parameters a layer.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from .sparse_blocks import (batch_axes_of, dense_ffn, held_experts,
                            leaf_name, moe_counters, rms_norm, rope_angles,
                            swiglu)

__all__ = ["JoyAIFlashConfig", "init_joyai_flash_params",
           "joyai_flash_param_shardings"]


@dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 3.2e7
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 256            # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1      # 0: no MTP module
    # the module's weight in the loss (the DeepSeek-V3 report's early
    # value; the published config has none)
    mtp_loss_weight: float = 0.3
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
        return self.n_routed_experts if self.num_experts_held is None \
            else self.num_experts_held

    def moe_rows(self, tokens: int) -> Optional[int]:
        """Rows of the routed-row buffer for ``tokens`` tokens."""
        from ..distributed.fleet.meta_parallel.moe import routed_rows
        return routed_rows(tokens, self.num_experts_per_tok, self.held,
                           self.n_routed_experts, self.moe_rows_factor)

    def spmd_parts(self, mesh: Mesh):
        """What ``build_spmd_train_step`` asks of a model."""
        return _spmd_parts(self, mesh)


def init_joyai_flash_params(cfg: JoyAIFlashConfig, key) -> Dict:
    """Float32 parameters: weights normal(0, 0.02), gains 1, the router's
    selection bias normal(0, 0.01)."""
    if cfg.num_nextn_predict_layers not in (0, 1):
        raise NotImplementedError(
            f"one multi-token-prediction module at most, not "
            f"{cfg.num_nextn_predict_layers}")
    D, H = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    keys = iter(jax.random.split(key, 16 * (cfg.num_layers + 1) + 4))

    def normal(*shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def layer(dense: bool):
        p = {"op_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,)),
             "q_a_w": normal(D, cfg.q_lora_rank),
             "q_a_norm": jnp.ones((cfg.q_lora_rank,)),
             "q_b_w": normal(cfg.q_lora_rank, H * qk),
             "kv_a_w": normal(D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
             "kv_a_norm": jnp.ones((cfg.kv_lora_rank,)),
             "kv_b_w": normal(cfg.kv_lora_rank,
                              H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
             "o_w": normal(H * cfg.v_head_dim, D)}
        if dense:
            F = cfg.intermediate_size
            p.update(w1=normal(D, F), w3=normal(D, F), w2=normal(F, D))
        else:
            E, held, F = cfg.n_routed_experts, cfg.held, \
                cfg.moe_intermediate_size
            Fs = cfg.n_shared_experts * F
            p.update(router_w=normal(D, E), router_bias=normal(E, std=0.01),
                     w1=normal(held, D, F), w3=normal(held, D, F),
                     w2=normal(held, F, D), shared_w1=normal(D, Fs),
                     shared_w3=normal(D, Fs), shared_w2=normal(Fs, D))
        return p

    params = {"wte": normal(cfg.vocab_size, D),
              "layers": [layer(l < cfg.first_k_dense_replace)
                         for l in range(cfg.num_layers)],
              "out_norm": jnp.ones((D,)),
              "head_w": normal(D, cfg.vocab_size)}
    if cfg.num_nextn_predict_layers:
        params["mtp"] = {"h_norm": jnp.ones((D,)), "e_norm": jnp.ones((D,)),
                         "eh_proj": normal(2 * D, D), "layer": layer(False),
                         "out_norm": jnp.ones((D,))}
    return params


def joyai_flash_param_shardings(mesh: Mesh, cfg: JoyAIFlashConfig) -> Dict:
    """Everything whole on every device (the shared expert too), but the
    routed experts' leading axis over ``ep`` where the mesh has one."""
    from ..distributed.fleet.meta_parallel.moe import held_expert_shardings
    return held_expert_shardings(mesh, jax.eval_shape(
        lambda: init_joyai_flash_params(cfg, jax.random.PRNGKey(0))))


def _pair_swap(w):
    """The columns of a projection ``w`` (in, ..., r) whose product is the
    pair-swapped product of ``w``: with ``y = z w`` and pairs ``(2i, 2i +
    1)`` of its last axis, ``(z _pair_swap(w))[2i] = -y[2i + 1]`` and
    ``[2i + 1] = y[2i]`` — i times the complex number ``y_2i + i
    y_2i+1``.  On the weight, so that no activation is shuffled across
    lanes (a roll of the activations by one lane cost 10 ms a block and
    pass on a v5e: PERF.md, PR 37)."""
    pairs = w.reshape(*w.shape[:-1], w.shape[-1] // 2, 2)
    return jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1) \
        .reshape(w.shape)


def _rotate(y, y_swapped, theta: float):
    """RoPE in interleaved pairing: ``y cos + y_swapped sin`` with pair
    ``(2i, 2i + 1)`` of the last axis turned by ``t theta^(-2i / r)``;
    ``y_swapped`` is ``y`` through :func:`_pair_swap`.  y: (B, T, H, r);
    in float32, back in y's type."""
    ang = np.repeat(rope_angles(y.shape[1], theta, y.shape[-1]), 2, axis=-1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    return (y.astype(jnp.float32) * cos
            + y_swapped.astype(jnp.float32) * sin).astype(y.dtype)


def _mla(p, x, cfg, mesh, batch_axes):
    from ..ops.pallas.flash_attention import flash_attention_latent
    B, T, _ = x.shape
    H, eps, theta = cfg.num_attention_heads, cfg.rms_norm_eps, cfg.rope_theta
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank

    def cols(w, lo, hi):
        """Columns [lo, hi) of every head of a (in, H * width) weight,
        as one (in, H * (hi - lo)) weight."""
        return w.reshape(w.shape[0], H, -1)[..., lo:hi].reshape(
            w.shape[0], -1)

    def heads(y):
        return y.reshape(B, T, H, -1)

    # every product is token-major, (B, T, heads x width), the layout the
    # flash entry's DMAs read a head's columns from; several products from
    # one weight, here and below: each result is an array of its own (a
    # slice of one result would be copied out), and the rotated parts come
    # with their pair-swapped twins
    with jax.named_scope("mla_q"):
        z = rms_norm(x, p["op_norm"], eps)
        c_q = rms_norm(z @ p["q_a_w"], p["q_a_norm"], eps)
        q_n = c_q @ cols(p["q_b_w"], 0, dn)
        w = cols(p["q_b_w"], dn, dn + dr)
        q_r = _rotate(heads(c_q @ w), heads(c_q @ _pair_swap(w)),
                      theta).reshape(B, T, H * dr)
    with jax.named_scope("mla_kv"):
        c_kv = rms_norm(z @ p["kv_a_w"][:, :r], p["kv_a_norm"], eps)
        w = p["kv_a_w"][:, r:]
        # the one rotated key part serves every head
        k_r = _rotate((z @ w)[:, :, None, :],
                      (z @ _pair_swap(w))[:, :, None, :], theta)[:, :, 0]
        k_n = c_kv @ cols(p["kv_b_w"], 0, dn)
        v = c_kv @ cols(p["kv_b_w"], dn, dn + dv)
    # outside every scope of its own, like the other models' attention: a
    # scope around a pallas_call renames the Mosaic custom call
    ctx = flash_attention_latent(q_n, q_r, k_n, k_r, v, causal=True,
                                 mesh=mesh, batch_axes=batch_axes)
    with jax.named_scope("mla_out"):
        return x + ctx @ p["o_w"]


def _expert_ffn(p, x, cfg, mesh, batch_axes):
    from ..distributed.fleet.meta_parallel.moe import sigmoid_topk_routing
    with jax.named_scope("moe_route"):
        z = rms_norm(x, p["ffn_norm"], cfg.rms_norm_eps)
    y, counts, overflow = held_experts(
        z, p, cfg, mesh, batch_axes, p["router_bias"],
        routing=functools.partial(
            sigmoid_topk_routing, top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor, eps=1e-20))
    with jax.named_scope("shared_expert"):
        # every chip computes it alike: outside the exchange
        shared = swiglu(z, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    with jax.named_scope("moe_combine"):
        return x + y + shared, counts, overflow


def _spmd_parts(cfg: JoyAIFlashConfig, mesh: Mesh):
    batch_axes = batch_axes_of(mesh, "JoyAI-LLM-Flash")

    def dense_block(p, x):
        x = _mla(p, x, cfg, mesh, batch_axes or ())
        return dense_ffn(p, x, cfg.rms_norm_eps), None

    def expert_block(p, x):
        x = _mla(p, x, cfg, mesh, batch_axes or ())
        x, counts, overflow = _expert_ffn(p, x, cfg, mesh, batch_axes)
        return x, (counts, overflow)

    def trunk(params, ids, remat, labels):
        """ids, labels -> (final hidden states, the step's counters, the
        further prediction depths: the MTP module's hidden states with
        their labels, row weights and weight in the loss)."""
        eps = cfg.rms_norm_eps
        with jax.named_scope("embed"):
            x = params["wte"][ids]
        counted = []
        for l, p in enumerate(params["layers"]):
            x, aux = remat(dense_block if l < cfg.first_k_dense_replace
                           else expert_block)(p, x)
            if aux is not None:
                counted.append(aux)
        with jax.named_scope("final_norm"):
            out = rms_norm(x, params["out_norm"], eps)
        further = ()
        if cfg.num_nextn_predict_layers:
            m = params["mtp"]
            D, T = cfg.hidden_size, ids.shape[1]
            with jax.named_scope("embed"):
                ahead = params["wte"][labels]
            with jax.named_scope("mtp_in"):
                # [a | b] M as a M[:D] + b M[D:]: no (B, T, 2 D) array
                u = rms_norm(x, m["h_norm"], eps) @ m["eh_proj"][:D] \
                    + rms_norm(ahead, m["e_norm"], eps) @ m["eh_proj"][D:]
            # the module's block carries the trunk's scope names under a
            # parent of its own
            with jax.named_scope("mtp"):
                x, aux = remat(expert_block)(m["layer"], u)
            counted.append(aux)
            with jax.named_scope("final_norm"):
                x = rms_norm(x, m["out_norm"], eps)
            # position i predicts labels[i + 1]; a row's last has none
            further = ({
                "name": "mtp", "hidden": x,
                "labels": jnp.roll(labels, -1, axis=1),
                "row_weight": jnp.broadcast_to(
                    jnp.arange(T) < T - 1, labels.shape),
                "loss_weight": cfg.mtp_loss_weight},)
        return out, moe_counters(counted), further

    return SimpleNamespace(
        init=lambda key: init_joyai_flash_params(cfg, key),
        shardings=joyai_flash_param_shardings(mesh, cfg),
        trunk=trunk, batch_axes=batch_axes, further_depths=True,
        step_name="joyai_flash_spmd_train_step",
        # the router computes in float32; its selection bias only
        # selects: no gradient, no AdamW update
        keep_float32=lambda path: leaf_name(path) in ("router_w",
                                                      "router_bias"),
        frozen=lambda path: leaf_name(path) == "router_bias")
