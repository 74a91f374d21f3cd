"""Fully-compiled SPMD GPT trainer: one jitted step over the hybrid mesh.

This is the compiled twin of models/gpt.py — the "static graph path" of
the flagship (reference parity: the ERNIE/BERT-large static+fleet config,
BASELINE config 5).  Everything is one XLA program:

- dp: batch sharded over ``dp`` (gradient all-reduce by GSPMD),
- mp: Megatron-style qkv/ffn shardings over ``mp`` via PartitionSpecs,
- pp: blocks stacked on a leading layer dim, sharded over ``pp``, run
  through the ppermute micro-batch pipeline (spmd_pipeline) inside a
  partial-manual shard_map ({'pp'} manual, dp/mp left to GSPMD),
- sp: sequence axis reserved (ring attention wires in via
  distributed.fleet.meta_parallel.sequence_parallel).

The optimizer is an inline functional AdamW whose state inherits the
parameter shardings (slots live sharded over mp/pp like their params).
"""
from __future__ import annotations

import types
from functools import partial
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.gated_delta_rule import RESIDUAL_NAMES as _RULE_NAMES
from ..ops.pallas.flash_attention import RESIDUAL_NAMES as _FLASH_NAMES
from .gpt import GPTConfig

# what the operators name for a remat policy to save: a program that
# emits none of a name is compiled as if the name were not listed
RESIDUAL_NAMES = (*_FLASH_NAMES, *_RULE_NAMES)

__all__ = ["init_gpt_params", "gpt_param_shardings",
           "build_spmd_train_step"]


def _glorot(key, shape):
    fan_in, fan_out = shape[-2], shape[-1]
    std = np.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, jnp.float32) * std


def init_gpt_params(cfg: GPTConfig, key) -> Dict:
    """Param pytree with blocks stacked on a leading layer dim (the
    layout spmd_pipeline shards over pp)."""
    V, D, L = cfg.vocab_size, cfg.hidden_size, cfg.num_layers
    H = cfg.ffn_mult * D
    ks = jax.random.split(key, 8)
    blocks = {
        "ln1_g": jnp.ones((L, D)), "ln1_b": jnp.zeros((L, D)),
        # q/k/v sections on their own axis: sharding the last (head-major)
        # axis over mp gives every shard whole heads of all three — a
        # contiguous split of a packed 3*D axis would not
        "qkv_w": _glorot(ks[0], (L, D, 3 * D)).reshape(L, D, 3, D),
        "qkv_b": jnp.zeros((L, 3, D)),
        "out_w": _glorot(ks[1], (L, D, D)), "out_b": jnp.zeros((L, D)),
        "ln2_g": jnp.ones((L, D)), "ln2_b": jnp.zeros((L, D)),
        "up_w": _glorot(ks[2], (L, D, H)), "up_b": jnp.zeros((L, H)),
        "down_w": _glorot(ks[3], (L, H, D)), "down_b": jnp.zeros((L, D)),
    }
    return {
        "wte": jax.random.normal(ks[4], (V, D)) * 0.02,
        "wpe": jax.random.normal(ks[5], (cfg.max_seq_len, D)) * 0.02,
        "blocks": blocks,
        "ln_f_g": jnp.ones((D,)), "ln_f_b": jnp.zeros((D,)),
        "head_w": _glorot(ks[6], (D, V)),
    }


def gpt_param_shardings(mesh: Mesh, cfg: GPTConfig) -> Dict:
    """PartitionSpecs: vocab/ffn over mp, stacked layer dim over pp."""
    def ns(*spec):
        spec = tuple(s if s in mesh.axis_names else None
                     if isinstance(s, str) else s for s in spec)
        return NamedSharding(mesh, P(*spec))

    blocks = {
        "ln1_g": ns("pp", None), "ln1_b": ns("pp", None),
        "qkv_w": ns("pp", None, None, "mp"),
        "qkv_b": ns("pp", None, "mp"),
        "out_w": ns("pp", "mp", None), "out_b": ns("pp", None),
        "ln2_g": ns("pp", None), "ln2_b": ns("pp", None),
        "up_w": ns("pp", None, "mp"), "up_b": ns("pp", "mp"),
        "down_w": ns("pp", "mp", None), "down_b": ns("pp", None),
    }
    return {
        "wte": ns("mp", None), "wpe": ns(None, None),
        "blocks": blocks,
        "ln_f_g": ns(None), "ln_f_b": ns(None),
        "head_w": ns(None, "mp"),
    }


def _layernorm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def make_block_fn(cfg: GPTConfig, sp_axis: Optional[str] = None,
                  mesh: Optional[Mesh] = None):
    """One transformer block; with sp_axis set, attention runs as ring
    attention over that manual mesh axis (sequence/context parallel).
    Under a ``mesh`` the flash kernels run per shard: batch over
    dp/sharding, heads over mp."""
    h, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads

    def block_fn(p, x):
        from ..ops.pallas.flash_attention import flash_attention_stacked
        # x: (mb, T_local, D)
        B, T, D = x.shape
        with jax.named_scope("attn_qkv"):
            y = _layernorm(x, p["ln1_g"], p["ln1_b"])
            qkv = jnp.einsum("btd,dse->btse", y, p["qkv_w"]) + p["qkv_b"]
            # q/k/v axis to the front: the matmul then writes q, k and v
            # each as a row-major (B, T, D) section, the form the kernels
            # read and write — with the axis third, XLA lays the result
            # out T-minor and pays relayout copies and packing updates on
            # both sides of every kernel (57 of 779 ms a step, PERF.md
            # PR 29).  A transpose in name only: it sets the layout.
            qkv = jnp.moveaxis(qkv, 2, 0)
        # The attention call and its checkpoint name stay OUTSIDE every
        # scope: the TPU compiler names a Mosaic custom call after the
        # last name-stack component in front of pallas_call, and the
        # benchmark's roofline metrics find the kernels by those names.
        if sp_axis is not None:
            from ..distributed.fleet.meta_parallel.sequence_parallel \
                import ring_attention
            q, k, v = (qkv[i].reshape(B, T, h, hd) for i in range(3))
            ctx = ring_attention(q, k, v, sp_axis, causal=True)
            ctx = ctx.reshape(B, T, D)
        else:
            # attention straight off the projection output: no
            # head-split, transpose or relayout in HBM
            ctx = flash_attention_stacked(
                qkv, h, causal=True, mesh=mesh,
                batch_axes=("dp", "sharding"), head_axes=("mp",))
        ctx = checkpoint_name(ctx, "attn_ctx")
        with jax.named_scope("attn_out"):
            x = x + ctx @ p["out_w"] + p["out_b"]
        with jax.named_scope("ffn"):
            y = _layernorm(x, p["ln2_g"], p["ln2_b"])
            up = checkpoint_name(jax.nn.gelu(y @ p["up_w"] + p["up_b"]),
                                 "ffn_up")
            x = x + up @ p["down_w"] + p["down_b"]
        return x
    return block_fn


def build_spmd_train_step(cfg, mesh: Mesh,
                          num_microbatches: int = 1,
                          learning_rate: float = 1e-3,
                          weight_decay: float = 0.01,
                          compute_dtype=jnp.float32,
                          schedule_mode: str = "F-then-B",
                          sharding_stage: int = 1,
                          offload: bool = False,
                          remat_policy: str = "full"):
    """Returns (jitted_step, init_fn).

    step(params, opt_state, ids, labels) -> (loss, params, opt_state);
    init_fn(seed) -> (params, opt_state) placed onto the mesh.

    ``cfg`` is a ``GPTConfig`` or a configuration that brings its own
    model: an object with ``spmd_parts(mesh)`` (``Lfm2MoeConfig``,
    ``Qwen3NextConfig``, ``JoyAIFlashConfig``) whose result gives
    ``init(key)``, ``shardings``, ``trunk(params, ids, remat) -> (final
    hidden states, counters)``, ``batch_axes``, ``step_name`` and the
    leaves that ``keep_float32`` or are ``frozen`` (no gradient, no
    update).  A model that predicts further ahead than the next token
    says ``further_depths=True``; its trunk is then ``trunk(params, ids,
    remat, labels) -> (final hidden states, counters, depths)`` (a
    multi-token-prediction module embeds the labels) and each of
    ``depths`` is a dict: ``name``, ``hidden`` (B, T, D), ``labels`` (B,
    T), ``row_weight`` (B, T; 0 where a position has no target that far
    on) and ``loss_weight``.  The builder runs its ONE head over the main
    hidden states and over each depth's and returns ``loss_main +
    sum(loss_weight * loss_<name>)``, with every term among the counters.
    What is the step's own is kept here once for every model: the cast to
    ``compute_dtype``, the remat policies, the fused / chunked loss head,
    AdamW, ZeRO, the jit and its donation.  A model whose trunk counts
    something on the device (routed assignments per expert, overflow)
    gets those counters as a fourth result: (loss, params, opt_state,
    counters).

    ``schedule_mode`` (reference section_worker.cc:62): "F-then-B" runs
    the fill-drain forward pipeline and lets jax.grad build the backward
    pipeline (activations O(M)); "1F1B" uses the interleaved
    spmd_pipeline_1f1b schedule (activations O(num_stages)).

    ``sharding_stage``/``offload`` (reference sharding_optimizer.py:45 +
    offload_helper.py): ZeRO over the mesh's ``sharding`` axis — see
    fleet/meta_optimizers/zero.py.  The sharding axis co-shards the
    global batch (reference hybrid topology [dp, pp, sharding, mp]).

    The step names its parts in the device trace with ``jax.named_scope``
    (metadata only: no switch, no run-time cost): ``embed``, ``unstack``,
    ``attn_qkv``, ``attn_out``, ``ffn``, ``final_ln``, ``loss_head``,
    ``optimizer``.  An operation's ``op_name`` then reads ``jvp(ffn)``
    forward, ``transpose(jvp(ffn))`` backward and
    ``rematted_computation/ffn`` when recomputed.  The Pallas calls
    (attention, the fused loss head) are siblings of these scopes, never
    children: a scope around a ``pallas_call`` renames the Mosaic custom
    call (tests/test_step_scopes.py guards the names).  The jitted
    function is ``gpt_spmd_train_step``: the name is a symbol, so the
    compile cache, whose key ignores scopes, tells it from any other step.
    """
    from ..distributed.fleet.meta_parallel.spmd_pipeline import (
        spmd_pipeline, spmd_pipeline_1f1b)
    from ..distributed.fleet.meta_optimizers.zero import (
        shard_tree, zero_state_shardings)

    own = cfg.spmd_parts(mesh) if hasattr(cfg, "spmd_parts") else None
    pp = mesh.shape.get("pp", 1)
    sp = mesh.shape.get("sp", 1)
    sharding_n = mesh.shape.get("sharding", 1)
    use_pp, use_sp = pp > 1, sp > 1
    use_zero = sharding_n > 1
    # only axes actually present in the mesh shard the batch (a pp-only
    # mesh has no dp axis at all; size-1 axes are no-ops)
    batch_axes = tuple(a for a in ("dp", "sharding")
                       if mesh.shape.get(a, 1) > 1) or None
    sp_axis = "sp" if use_sp else None
    block_fn = None if own else make_block_fn(cfg, sp_axis=sp_axis,
                                              mesh=mesh)

    # remat policy (reference recompute_optimizer checkpoints attr):
    #   full — recompute everything in backward (min HBM, +1/3 flops)
    #   ctx  — save each block's attention output, and what a flash
    #          kernel whose backward needs its own forward's results
    #          names (the stream regime's out and lse; no GPT-length
    #          kernel names anything): the backward never runs a
    #          flash-attention forward again (the costliest recompute);
    #          and the gated delta rule's output, so that its loop over
    #          chunks is not run again either
    #   dots — save all matmul outputs (XLA's dots_saveable)
    #   none — no remat: XLA keeps what backward needs (max HBM)
    if remat_policy == "none":
        def maybe_remat(f):
            return f
    elif remat_policy == "ctx":
        def maybe_remat(f):
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.save_only_these_names(
                    "attn_ctx", *RESIDUAL_NAMES))
    elif remat_policy == "ctx_ffn":
        # save attention outputs AND the gelu(ffn-up) activation: the
        # backward skips the two biggest recomputed matmuls; fits only
        # because the chunked CE freed the (B, T, V) logits HBM
        def maybe_remat(f):
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.save_only_these_names(
                    "attn_ctx", "ffn_up", *RESIDUAL_NAMES))
    elif remat_policy == "dots":
        def maybe_remat(f):
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.dots_saveable)
    else:
        maybe_remat = jax.checkpoint
    M = num_microbatches
    L = cfg.num_layers
    if use_pp and L % pp != 0:
        raise ValueError(f"num_layers {L} must divide pp {pp}")

    def _cast(params, keep=None):
        """float32 masters -> ``compute_dtype`` (AMP O2: bf16 matmuls on
        the MXU), but the leaves a model keeps in float32."""
        if compute_dtype == jnp.float32:
            return params
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a.astype(compute_dtype)
            if a.dtype == jnp.float32 and not (keep and keep(path))
            else a, params)

    def trunk(params, ids, labels):
        """ids -> (final hidden states, the model's counters, its further
        prediction depths): the cast (under ``embed``, where the GPT trace
        has always shown it) and then the model's own trunk, which sees
        the labels where it predicts further ahead than the next token
        (a multi-token-prediction module embeds them)."""
        with jax.named_scope("embed"):
            params = _cast(params, model.keep_float32)
        if getattr(model, "further_depths", False):
            return model.trunk(params, ids, maybe_remat, labels)
        return (*model.trunk(params, ids, maybe_remat), ())

    def gpt_trunk(params, ids, remat):
        """Non-pp/non-sp forward minus the head matmul: the shared path
        for plain forward() and the chunked-CE loss.

        The layer loop is UNROLLED, not lax.scan: inside a scan body the
        per-layer weights are dynamic-slices of the stacked (L, ...)
        arrays and the weight grads accumulate through dynamic-update-
        slices — XLA fuses both into the adjacent convolutions and picks
        an EmitAllBatchInSublanes emitter that runs those matmuls at
        ~half rate (88 vs 185 TFLOP/s for the FFN down-projection,
        profiled r4/r5; the same shapes isolated run full-rate).
        Unrolling makes every weight a plain slice (bitcast view) and
        every weight grad a plain tensor (dblocks rebuilt by concat in
        the split transpose), dodging the bad emitter everywhere.
        """
        with jax.named_scope("embed"):
            x = params["wte"][ids] + params["wpe"][:ids.shape[1]][None]
        blocks = params["blocks"]
        with jax.named_scope("unstack"):
            split = {k: jnp.split(v, L, axis=0) for k, v in blocks.items()}
        for i in range(L):
            with jax.named_scope("unstack"):
                p_i = {k: jnp.squeeze(split[k][i], axis=0) for k in split}
                # materialize the per-layer weight slices: left as bitcast
                # views of the stacked (L, ...) arrays, XLA fuses the slice
                # into the consuming convolution and picks a half-rate
                # batch-in-sublanes emitter (profiled r5: the down-proj+LN
                # fusion ran 3.43 ms vs 1.81 with materialized weights —
                # the copies themselves are ~0.1 ms/layer)
                p_i = lax.optimization_barrier(p_i)
            x = remat(block_fn)(p_i, x)
        with jax.named_scope("final_ln"):
            return _layernorm(x, params["ln_f_g"], params["ln_f_b"]), {}

    model = own or types.SimpleNamespace(
        init=lambda key: init_gpt_params(cfg, key),
        shardings=gpt_param_shardings(mesh, cfg), trunk=gpt_trunk,
        batch_axes=batch_axes, step_name="gpt_spmd_train_step",
        keep_float32=None, frozen=None)
    batch_axes = model.batch_axes

    def forward(params, ids):
        """The pp / sp paths' logits (GPT only)."""
        B, T = ids.shape
        with jax.named_scope("embed"):
            params = _cast(params)
            x = params["wte"][ids] + params["wpe"][:T][None]
        if use_pp:
            # (M, mb, T, D): micro-batch dim unsharded, per-mb batch over
            # dp, sequence over sp (ring attention inside the blocks)
            xm = x.reshape(M, B // M, T, cfg.hidden_size)
            xm = lax.with_sharding_constraint(
                xm, NamedSharding(mesh, P(None, batch_axes, sp_axis)))
            x_spec = P(None, None, "sp") if use_sp else P(None)

            def piped(bp, xi):
                # remat per block here too — same HBM posture as the
                # non-pipelined scan branch below
                return spmd_pipeline(maybe_remat(block_fn), bp, xi,
                                     axis="pp", num_stages=pp,
                                     num_microbatches=M)

            xm = jax.shard_map(
                piped, mesh=mesh, in_specs=(P("pp"), x_spec),
                out_specs=x_spec, axis_names={"pp"} | ({"sp"} if use_sp
                                                       else set()),
                check_vma=False)(params["blocks"], xm)
            x = xm.reshape(B, T, cfg.hidden_size)
        else:
            # sequence parallel without pp: shard T over sp, ring
            # attention inside; blocks scanned locally
            def seq_par(bp, xi):
                def body(h, p):
                    return maybe_remat(block_fn)(p, h), None
                h, _ = lax.scan(body, xi, bp)
                return h
            x = jax.shard_map(
                seq_par, mesh=mesh, in_specs=(P(None), P(None, "sp")),
                out_specs=P(None, "sp"), axis_names={"sp"},
                check_vma=False)(params["blocks"], x)
        with jax.named_scope("final_ln"):
            x = _layernorm(x, params["ln_f_g"], params["ln_f_b"])
        with jax.named_scope("loss_head"):
            return x @ params["head_w"]

    # The loss head is the single biggest HBM consumer at bench shapes:
    # full (B, T, V) bf16 logits are 4 GB (B=128 T=512 V=30k), and the
    # reference hand-fuses exactly this op
    # (operators/collective/c_softmax_with_cross_entropy_op.cu:1).  The
    # TPU translation is a CHUNKED head: scan over row blocks, each
    # chunk computes its logits + CE and the backward recomputes them
    # (jax.checkpoint), so live logits are chunk x V instead of BT x V.
    CE_CHUNK = 4096

    def _ce_rows(xc, head_w, lc, wc=None):
        # xc: (C, D) hidden rows; lc: (C,) labels; wc: (C,) row weights
        # or None -> summed (weighted) CE.  The
        # logits come out of the MXU in f32 directly (free on TPU), so
        # no separate (C, V) bf16->f32 subtract/convert pass ever
        # materialises (profiled r4: that pass alone was ~4% of step)
        logits = jax.lax.dot_general(
            xc, head_w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (C, V) f32
        m = jax.lax.stop_gradient(jnp.max(logits, -1, keepdims=True))
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
        at = jnp.take_along_axis(logits, lc[:, None], axis=-1)[..., 0]
        return jnp.sum(lse - at if wc is None else wc * (lse - at))

    def chunked_ce(x, head_w, labels, row_weight=None):
        B, T, D = x.shape
        n = B * T
        # per row: (hidden, label) and, where rows are weighted, the weight
        rows = [x.reshape(n, D), labels.reshape(n)]
        if row_weight is not None:
            rows.append(row_weight.reshape(n).astype(jnp.float32))
        ce = jax.checkpoint(_ce_rows)
        nc = n // CE_CHUNK
        total = jnp.zeros((), jnp.float32)
        if nc:
            def body(acc, args):
                xc, lc, *wc = args
                return acc + ce(xc, head_w, lc, *wc), None
            head_n = nc * CE_CHUNK
            total, _ = lax.scan(body, total, tuple(
                r[:head_n].reshape(nc, CE_CHUNK, *r.shape[1:])
                for r in rows))
        if n % CE_CHUNK:
            # remainder rows get their own (still-checkpointed) chunk so
            # odd batch sizes never fall back to whole-logits CE
            xc, lc, *wc = (r[nc * CE_CHUNK:] for r in rows)
            total = total + ce(xc, head_w, lc, *wc)
        return total / (n if row_weight is None else jnp.sum(rows[2]))

    def loss_fn(params, ids, labels):
        if use_pp or use_sp:
            # pipelined/sequence-parallel paths keep the fused whole-
            # logits CE (head runs inside their shard_map schedules)
            logits = forward(params, ids)
            with jax.named_scope("loss_head"):
                m = jax.lax.stop_gradient(
                    jnp.max(logits, axis=-1, keepdims=True))
                shifted = (logits - m).astype(jnp.float32)
                lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
                at_label = jnp.take_along_axis(shifted, labels[..., None],
                                               axis=-1)[..., 0]
                return jnp.mean(lse - at_label), {}
        x, counters, further = trunk(params, ids, labels)
        head_w = params["head_w"].astype(x.dtype)
        from ..ops import pallas
        fused = pallas.enabled() and mesh.size == 1

        def head(x, labels, row_weight=None):
            """The one loss head, called once a prediction depth: mean
            cross-entropy of ``x @ head_w`` over the rows, or over the
            rows' weights where they are given."""
            B, T, D = x.shape
            interpret = pallas.note("softmax_xent", fused)
            if fused:
                # fused pallas head (softmax_xent.py): no (N, V) logits in
                # the forward at all — the kernel streams W tiles through
                # VMEM with online stats (the chunked path below writes +
                # re-reads 500 MB of f32 logits per chunk; measured r5:
                # fused fwd 23.5 ms vs 28.5, and the saved-lse backward
                # skips the stat recompute).  One device only: the kernel
                # has no cross-shard lse combine for a vocab-sharded head.
                # Like attention, outside every scope: its forward is a
                # Mosaic call the benchmark finds by its compiler-made
                # name.
                from ..ops.pallas.softmax_xent import softmax_xent_loss
                weight = None if row_weight is None \
                    else row_weight.reshape(B * T)
                return softmax_xent_loss(x.reshape(B * T, D), head_w,
                                         labels.reshape(B * T), interpret,
                                         weight)
            with jax.named_scope("loss_head"):
                return chunked_ce(x, head_w, labels, row_weight)

        loss = head(x, labels)
        if further:
            # a model that predicts further ahead (multi-token prediction)
            # hands back, a depth: its hidden states, their labels, a
            # weight a row (a row's last positions have no target that far
            # on) and the depth's weight in the loss.  Each term is a
            # counter of the step, so that a run can tell them apart
            counters = dict(counters, loss_main=loss)
            for depth in further:
                term = head(depth["hidden"], depth["labels"],
                            depth["row_weight"])
                counters["loss_" + depth["name"]] = term
                loss = loss + depth["loss_weight"] * term
        return loss, counters

    def adamw_update(params, grads, opt_state):
        old_params = params
        step = opt_state["step"] + 1
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                         opt_state["m"], grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                         opt_state["v"], grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, mm, vv: (1 - learning_rate * weight_decay) * p
            - learning_rate * (mm / c1) / (jnp.sqrt(vv / c2) + eps),
            params, m, v)
        if model.frozen is not None:
            params = jax.tree_util.tree_map_with_path(
                lambda path, new, old: old if model.frozen(path) else new,
                params, old_params)
        return params, {"m": m, "v": v, "step": step}

    def loss_and_grads_1f1b(params, ids, labels):
        """Fused loss+grad via the interleaved 1F1B pipeline (no outer
        jax.grad: the pipeline carries its own backward)."""
        B, T = ids.shape
        D = cfg.hidden_size

        def emb_fn(wte, wpe):
            x = wte[ids] + wpe[:T][None]
            return x.reshape(M, B // M, T, D)

        with jax.named_scope("embed"):
            cp = _cast(params)
            x, emb_vjp = jax.vjp(emb_fn, cp["wte"], cp["wpe"])
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, batch_axes, sp_axis)))
        labels_m = labels.reshape(M, B // M, T)
        x_spec = P(None, None, "sp") if use_sp else P(None)
        head = {"g": cp["ln_f_g"], "b": cp["ln_f_b"], "w": cp["head_w"]}
        inv_tokens = 1.0 / float(B * T)

        def run(bp, xi, lab, hp):
            def last_fn(out_mb, lab_mb):
                def head_loss(h, o):
                    with jax.named_scope("final_ln"):
                        z = _layernorm(o, h["g"], h["b"])
                    with jax.named_scope("loss_head"):
                        logits = (z @ h["w"]).astype(jnp.float32)
                        logp = jax.nn.log_softmax(logits, axis=-1)
                        # one-hot contraction, not take_along_axis: a
                        # gather on mp-sharded logits inside the manual-pp
                        # region trips XLA's SPMD partitioner (CHECK
                        # failure in PartitionGather); the contraction
                        # partitions clean
                        onehot = jax.nn.one_hot(lab_mb, logits.shape[-1],
                                                dtype=logp.dtype)
                        nll = -jnp.sum(logp * onehot, axis=-1)
                        return jnp.sum(nll) * inv_tokens
                loss, (dh, dout) = jax.value_and_grad(
                    head_loss, argnums=(0, 1))(hp, out_mb)
                return loss, dout, dh
            loss, dbp, dxi, dhp = spmd_pipeline_1f1b(
                maybe_remat(block_fn), bp, xi, lab, last_fn,
                axis="pp", num_stages=pp, num_microbatches=M)
            if use_sp:
                # each sp shard saw only its sequence slice: loss and the
                # (replicated-per-shard) block/head grads are partials —
                # reduce over sp (dxi stays sharded: it IS per-slice)
                loss = lax.psum(loss, "sp")
                dbp = jax.tree.map(lambda a: lax.psum(a, "sp"), dbp)
                dhp = jax.tree.map(lambda a: lax.psum(a, "sp"), dhp)
            return loss, dbp, dxi, dhp

        lab_spec = P(None, None, "sp") if use_sp else P(None)
        loss, dblocks, dx, dhead = jax.shard_map(
            run, mesh=mesh,
            in_specs=(P("pp"), x_spec, lab_spec, P()),
            out_specs=(P(), P("pp"), x_spec, P()),
            axis_names={"pp"} | ({"sp"} if use_sp else set()),
            check_vma=False)(cp["blocks"], x, labels_m, head)
        with jax.named_scope("embed"):
            dwte, dwpe = emb_vjp(dx)
        grads = {"wte": dwte, "wpe": dwpe, "blocks": dblocks,
                 "ln_f_g": dhead["g"], "ln_f_b": dhead["b"],
                 "head_w": dhead["w"]}
        # master-weight update path expects f32 grads
        grads = jax.tree.map(
            lambda g, p: g.astype(p.dtype), grads, params)
        return loss, grads

    base_shardings = model.shardings
    shapes = jax.tree.map(
        lambda a: a.shape,
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if use_zero:
        shardings, state_shardings = zero_state_shardings(
            base_shardings, shapes, stage=sharding_stage, offload=offload)
        grad_shardings = shard_tree(base_shardings, shapes) \
            if sharding_stage >= 2 else None
        state_dev = shard_tree(base_shardings, shapes) if offload else None
    else:
        shardings, state_shardings = base_shardings, base_shardings
        grad_shardings, state_dev = None, None

    def train_step(params, opt_state, ids, labels):
        counters = {}
        if use_pp and schedule_mode == "1F1B":
            loss, grads = loss_and_grads_1f1b(params, ids, labels)
        else:
            (loss, counters), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, ids, labels)
        if grad_shardings is not None:
            # ZeRO-2: constrain grads to the sharded layout — GSPMD turns
            # the data-parallel gradient all-reduce into a reduce-scatter
            grads = jax.tree.map(lax.with_sharding_constraint, grads,
                                 grad_shardings)
        if offload:
            # ZeRO offload: state lives in pinned host RAM between steps
            mv = jax.device_put({"m": opt_state["m"], "v": opt_state["v"]},
                                {"m": state_dev, "v": state_dev})
            opt_state = {**opt_state, **mv}
        with jax.named_scope("optimizer"):
            params, opt_state = adamw_update(params, grads, opt_state)
        if use_zero and sharding_stage < 3:
            params = jax.tree.map(lax.with_sharding_constraint, params,
                                  shardings)
        if offload:
            mv = jax.device_put({"m": opt_state["m"], "v": opt_state["v"]},
                                {"m": state_shardings,
                                 "v": state_shardings})
            opt_state = {**opt_state, **mv}
        if counters:
            return loss, params, opt_state, counters
        return loss, params, opt_state

    # the jitted function's name is a symbol: the compile cache, whose
    # key ignores scopes, tells one model's step from another's by it
    train_step.__name__ = train_step.__qualname__ = model.step_name

    def init_fn(seed: int = 0):
        params = model.init(jax.random.PRNGKey(seed))
        params = jax.tree.map(jax.device_put, params, shardings)
        opt_state = {
            "m": jax.tree.map(
                lambda a, ns: jax.device_put(jnp.zeros_like(a), ns),
                params, state_shardings),
            "v": jax.tree.map(
                lambda a, ns: jax.device_put(jnp.zeros_like(a), ns),
                params, state_shardings),
            "step": jnp.zeros((), jnp.int32)}
        return params, opt_state

    # offload: opt_state lives in pinned host memory — XLA cannot alias
    # host-memory inputs onto device-memory outputs, so skip its donation
    donate = (0,) if offload else (0, 1)
    return jax.jit(train_step, donate_argnums=donate), init_fn


def _with_build_span(build):
    """``build`` with its call, entry to return, in the launch record
    (``profiler/tracer.py``) as the ``build`` span of the step it
    returns.  Below the builder and with its imports inside, so that no
    line above moves: the Mosaic kernel bodies carry the line numbers of
    their call sites in this file, and the persistent compile cache's key
    hashes those bodies."""
    import functools
    import time

    from ..profiler import tracer

    @functools.wraps(build)
    def build_and_record(*args, **kwargs):
        start_ns = time.time_ns()
        step, init_fn = build(*args, **kwargs)
        tracer.record_launch("build", start_ns, time.time_ns(),
                             fun=step.__name__)
        return step, init_fn

    return build_and_record


build_spmd_train_step = _with_build_span(build_spmd_train_step)
