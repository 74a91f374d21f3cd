"""Mixture-of-Experts: gating, capacity dispatch, expert-parallel
all-to-all.

Reference parity: ``operators/collective/global_scatter_op.*`` /
``global_gather_op.*`` — the MoE token-dispatch plumbing (all-to-all by
per-expert counts; capacity-style routing left to user code).

TPU-first: XLA needs static shapes, so dispatch is capacity-based
(Switch-Transformer style): each expert receives a fixed-capacity buffer,
overflow tokens are dropped (their combine weight is 0), and the
token→expert routing is expressed as one-hot matmuls that ride the MXU.
Expert weights are stacked on a leading E dim — batched einsum applies
all experts at once, and sharding that dim over the ``ep`` mesh axis
(Parameter.placements) is expert parallelism; the two ``lax.all_to_all``
calls are the reference's global_scatter/global_gather collapsed into
compiler collectives.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ....core.dispatch import dispatch
from ....core.tensor import Tensor
from ....nn.layer_base import Layer
from ....nn import initializer as I
from .... import nn
from ....ops import moe_rows, pallas
from ....ops.pallas import moe_rows as _row_kernels

__all__ = ["top1_gating", "moe_dispatch", "moe_combine", "moe_alltoall",
           "moe_alltoall_inverse", "MoELayer", "sigmoid_topk_routing",
           "softmax_topk_routing", "routed_rows", "held_expert_shardings",
           "routed_experts"]


def top1_gating(logits, capacity: int):
    """Switch top-1 gating with capacity.

    logits: (tokens, E).  Returns (dispatch (T, E, C), combine (T, E, C),
    aux_loss scalar)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                    # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T, E)
    # 0-based arrival rank of each token within its expert's buffer
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot      # (T, E)
    pos_in_expert = jnp.sum(pos, axis=-1)                  # (T,)
    keep = pos_in_expert < capacity
    gate = jnp.sum(probs * onehot, axis=-1) * keep         # (T,)
    pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                            dtype=jnp.float32)             # (T, C)
    dispatch_t = onehot[:, :, None] * pos_oh[:, None, :] \
        * keep[:, None, None]
    combine = dispatch_t * gate[:, None, None]
    # load-balancing aux loss (Switch eq. 4): E * sum(f_e * p_e)
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return dispatch_t, combine, aux


def moe_dispatch(x, dispatch_t):
    """x: (T, D), dispatch: (T, E, C) -> (E, C, D) expert buffers."""
    return jnp.einsum("td,tec->ecd", x, dispatch_t.astype(x.dtype))


def moe_combine(expert_out, combine):
    """expert_out: (E, C, D), combine: (T, E, C) -> (T, D)."""
    return jnp.einsum("ecd,tec->td", expert_out,
                      combine.astype(expert_out.dtype))


def moe_alltoall(buffers, axis_name: str = "ep"):
    """global_scatter: exchange expert buffers so each rank holds the
    full token set for its local experts.

    buffers: (E, C, D) with E = global expert count, E % ep == 0.
    Returns (E/ep, ep*C, D).  In-trace (shard_map) only."""
    return lax.all_to_all(buffers, axis_name, split_axis=0, concat_axis=1,
                          tiled=True)


def moe_alltoall_inverse(buffers, axis_name: str = "ep"):
    """global_gather: route expert outputs back to token owners."""
    return lax.all_to_all(buffers, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)


def _moe_ffn(tokens, gate_w, up_w, up_b, down_w, down_b, *,
             capacity: int):
    """Pure MoE FFN: gating + capacity dispatch + batched experts +
    combine.  tokens: (T, D); expert weights stacked on leading E dim."""
    logits = tokens @ gate_w                                 # (T, E)
    dispatch_t, combine, _ = top1_gating(logits, capacity)
    buf = moe_dispatch(tokens, dispatch_t)                   # (E, C, D)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", buf, up_w)
                    + up_b[:, None, :])
    out = jnp.einsum("ech,ehd->ecd", h, down_w) + down_b[:, None, :]
    return moe_combine(out, combine)


def _moe_aux(tokens, gate_w):
    logits = tokens @ gate_w
    _, _, aux = top1_gating(logits, logits.shape[0])
    return aux


class MoELayer(Layer):
    """MoE FFN layer (top-1, capacity-based).

    Expert weights are stacked (E, ...) Parameters with ``placements``
    P('ep', ...) so expert parallelism is a placement decision, exactly
    like mp in mp_layers.py.  Forward goes through the op dispatcher, so
    both the eager tape and the compiled jax.grad paths differentiate
    through gating, experts, and the aux loss.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 capacity_factor: float = 1.25, gate_weight_attr=None):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.gate = nn.Linear(d_model, num_experts, bias_attr=False)
        init = I.XavierNormal()
        self.up_w = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=init)
        self.up_b = self.create_parameter(
            [num_experts, d_hidden], is_bias=True)
        self.down_w = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=init)
        self.down_b = self.create_parameter(
            [num_experts, d_model], is_bias=True)
        for p in (self.up_w, self.up_b, self.down_w, self.down_b):
            p.placements = P("ep")
        self.aux_loss = None

    def forward(self, x):
        B, T, D = x.shape
        tokens = x.reshape([B * T, D])
        capacity = int(np.ceil(B * T / self.num_experts
                               * self.capacity_factor))
        out = dispatch(
            "moe_ffn",
            lambda t, gw, uw, ub, dw, db: _moe_ffn(
                t, gw, uw, ub, dw, db, capacity=capacity),
            [tokens, self.gate.weight, self.up_w, self.up_b,
             self.down_w, self.down_b], {})
        self.aux_loss = dispatch("moe_aux", _moe_aux,
                                 [tokens, self.gate.weight], {})
        return out.reshape([B, T, D])


# ---------------------------------------------------------------------------
# Routed experts with no dropped assignment: top-k routing over the router's
# whole width (the caller's: a ``(z, router_w, bias) -> (idx, w)`` function,
# two of which live here), dispatch by sort and segment offsets, grouped
# matmuls (``lax.ragged_dot``) over the experts held here; the rows move
# into expert order and back by the Pallas pair of ``ops/moe_rows.py`` on
# a TPU, by XLA's gathers elsewhere (and between ranks, ``_exchange``,
# everywhere).  Functional:
# the SPMD model blocks call it (models/lfm2_moe.py, models/qwen3_next.py);
# ``MoELayer`` above stays the Switch top-1 capacity layer of the Layer API.
# ---------------------------------------------------------------------------
def _chosen(s, idx):
    """The scores at the chosen experts by a one-hot product, not a
    gather: its transpose is elementwise too (a gather's is a
    scatter-add)."""
    onehot = jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype)
    return jnp.sum(s[:, None, :] * onehot, axis=-1)


def _router_logits(z, router_w):
    return jnp.dot(z.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)


def sigmoid_topk_routing(z, router_w, bias, top_k: int,
                         scaling: float = 1.0, eps: float = 1e-6):
    """One of the routings a caller may hand ``routed_experts`` (bound
    to its ``top_k`` and ``scaling``; the default there).  Scores ``s =
    sigmoid(z W_g)`` in float32; the ``top_k`` experts of ``s + bias`` are
    chosen (the bias only selects: it takes no gradient
    and is not in the weights), weighted ``s_e / (sum of the chosen s +
    eps) * scaling``.  ``eps`` is the published family's: 1e-6 in the
    ``lfm2_moe`` modelling code (the default), 1e-20 in the DeepSeek-V3
    family's ``noaux_tc`` gate (``joyai_llm_flash``: with ``n_group`` =
    ``topk_group`` = 1 that gate is this routing, the group limit
    selecting nothing).  -> (idx (N, k) int32, w (N, k) float32)."""
    s = jax.nn.sigmoid(_router_logits(z, router_w))
    _, idx = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)),
                       top_k)
    chosen = _chosen(s, idx)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * scaling


def softmax_topk_routing(z, router_w, bias=None, *, top_k: int,
                         renormalize: bool = True):
    """The other routing: ``p = softmax(z W_g)`` over the router's whole
    width in float32, the ``top_k`` largest chosen, weighted ``p_e`` or,
    with ``renormalize``, ``p_e / (sum of the chosen p)``.  No selection
    bias (``bias`` is there for the signature and must be None).
    -> (idx (N, k) int32, w (N, k) float32)."""
    assert bias is None, "softmax routing has no selection bias"
    p = jax.nn.softmax(_router_logits(z, router_w), axis=-1)
    _, idx = lax.top_k(p, top_k)
    w = _chosen(p, idx)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def routed_rows(tokens: int, top_k: int, held: int, width: int,
                factor: Optional[float]) -> Optional[int]:
    """Rows of a static routed-row buffer (``routed_experts``' ``rows``)
    for ``tokens`` tokens: ``factor`` x the rows a uniform router over
    ``width`` experts sends to the ``held`` ones, in whole 128s, at most
    every row a router could send; None (nothing can overflow) where
    ``factor`` is None."""
    if factor is None:
        return None
    even = tokens * top_k * held / width
    worst = tokens * min(top_k, held)
    return min(worst, 128 * math.ceil(factor * even / 128))


def held_expert_shardings(mesh, shapes):
    """Shardings of a model's parameter tree (``shapes``: its
    ``jax.eval_shape``) whose expert layers hold ``w1``/``w3`` (held, D,
    F) and ``w2`` (held, F, D): everything whole on every device, but
    the experts' leading axis over ``ep`` where the mesh has one."""
    ep = "ep" if mesh.shape.get("ep", 1) > 1 else None

    def spec(path, leaf):
        expert = path[-1].key in ("w1", "w3", "w2") and leaf.ndim == 3
        return NamedSharding(mesh, P(ep) if expert else P())

    return jax.tree_util.tree_map_with_path(spec, shapes)


def _routing_plan(idx, first: int, held: int, ranks: int, cap: int):
    """Where every assignment goes.  Experts ``first .. first + ranks *
    held`` live on ``ranks`` ranks, ``held`` each; the routed-row buffer
    has ``ranks * cap`` rows, rank p's at ``p * cap ..``, each rank's
    rows in expert order.  An assignment to an expert outside that range
    is nobody's here and gets no row (the chip's share of a deployment);
    one that finds its rank's ``cap`` rows taken is counted in
    ``overflow`` — with ``cap = N * min(k, held)`` that cannot happen.

    -> dict: ``slot`` (R,) the flat assignment ``n * k + j`` each row
    serves, ``row_valid`` (R,), ``kept`` (ranks,) the live rows of each
    rank (a prefix of its ``cap``), ``pos`` / ``valid`` / ``group`` (N, k)
    each assignment's row and expert (``ranks * held`` where it is not
    held here), ``sizes`` (ranks, held) rows per expert, ``counts``
    (ranks, held) assignments per expert, ``overflow`` ()."""
    N, k = idx.shape
    G = held * ranks
    g = idx.reshape(-1) - first
    key = jnp.where((g >= 0) & (g < G), g, G).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sorted_at = jnp.argsort(order)           # inverse permutation
    counts = jnp.sum(key[:, None] == jnp.arange(G, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32).reshape(ranks, held)
    per_rank = jnp.sum(counts, axis=1)
    start = jnp.cumsum(per_rank) - per_rank
    kept = jnp.minimum(per_rank, cap)
    ends = jnp.minimum(jnp.cumsum(counts, axis=1), cap)
    sizes = jnp.diff(ends, axis=1, prepend=0)
    c = jnp.arange(cap, dtype=jnp.int32)
    row_valid = (c[None, :] < kept[:, None]).reshape(-1)
    slot = order[jnp.minimum(start[:, None] + c[None, :],
                             N * k - 1).reshape(-1)]
    rank_of = jnp.minimum(key // held, ranks - 1)
    within = sorted_at - start[rank_of]
    valid = (key < G) & (within < cap)
    pos = jnp.where(valid, rank_of * cap + within, 0)
    return {"slot": slot, "row_valid": row_valid, "kept": kept,
            "pos": pos.reshape(N, k), "valid": valid.reshape(N, k),
            "group": key.reshape(N, k), "sizes": sizes, "counts": counts,
            "overflow": jnp.sum(per_rank - kept)}


# Rows into buffer order and back.  ``_take_rows`` and ``_spread_rows``
# are each other's transpose — every valid row serves exactly one valid
# (n, j) — so both directions are gathers: autodiff alone would turn the
# transpose of a gather into a scatter-add.
@jax.custom_vjp
def _take_rows(x, src, row_valid, pos, valid):
    """x (M, D) -> (R, D): row r is x[src[r]], nought where not valid."""
    return jnp.where(row_valid[:, None], x[src], 0).astype(x.dtype)


@jax.custom_vjp
def _spread_rows(y, src, row_valid, pos, valid):
    """y (R, D) -> (M, D): row m is the sum over j of y[pos[m, j]] where
    valid, accumulated in float32."""
    acc = jnp.zeros((pos.shape[0], y.shape[1]), jnp.float32)
    for j in range(pos.shape[1]):
        acc = acc + jnp.where(valid[:, j, None], y[pos[:, j]], 0)
    return acc.astype(y.dtype)


_take_rows.defvjp(
    lambda x, *plan: (_take_rows(x, *plan), plan),
    lambda plan, g: (_spread_rows(g, *plan), None, None, None, None))
_spread_rows.defvjp(
    lambda y, *plan: (_spread_rows(y, *plan), plan),
    lambda plan, g: (_take_rows(g, *plan), None, None, None, None))


def _rows_plan(N: int, k: int, D: int, cap: int, groups: int, dtype):
    """The Pallas pair's tiles (``ops/pallas/moe_rows.py`` ``plan``) where
    the backend takes kernels and the shapes tile, else None: the XLA
    ``_take_rows`` / ``_spread_rows`` above.  Counted ``moe_rows.mosaic``
    / ``.interpret`` / ``.xla``."""
    plan = _row_kernels.plan(N, k, D, cap, groups, dtype,
                             interpret=not pallas.on_tpu()) \
        if pallas.enabled() else None
    pallas.note("moe_rows", plan is not None)
    return plan


def _grouped_ffn(xs, w1, w3, w2, sizes):
    """SwiGLU of every row by its own expert: rows in expert order,
    ``sizes`` rows each; rows past the last group come out as nought."""
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(lax.ragged_dot(xs, w1, sizes)) \
            * lax.ragged_dot(xs, w3, sizes)
        return lax.ragged_dot(h, w2, sizes)


def _exchange(xs, sizes, ep_axis: str, ep: int, cap: int):
    """The rows to the ranks that hold their experts, and the way back.

    xs: (ep * cap, D), rank p's rows at ``p * cap ..`` in expert order;
    sizes: (ep, held) rows per expert of each rank.  After the
    ``all_to_all`` a rank holds (source, cap) rows, each source's in
    expert order; they are regrouped by expert across the sources — a
    permutation, by the same pair of gathers.
    -> (rows in expert order, (held,) group sizes, back: their results
    -> this rank's buffer order)."""
    D = xs.shape[-1]
    held = sizes.shape[1]
    recv = lax.all_to_all(xs.reshape(ep, cap, D), ep_axis, 0, 0)
    sizes_from = lax.all_to_all(sizes, ep_axis, 0, 0)      # (source, held)
    ends = jnp.cumsum(sizes_from, axis=1)
    c = jnp.arange(cap, dtype=jnp.int32)
    expert = jnp.sum(c[None, :, None] >= ends[:, None, :], axis=-1,
                     dtype=jnp.int32).reshape(-1)          # held: no row
    order = jnp.argsort(expert, stable=True)
    regroup = (order, jnp.arange(ep * cap) < jnp.sum(sizes_from),
               jnp.argsort(order)[:, None], (expert < held)[:, None])

    def back(out):
        out = _spread_rows(out, *regroup).reshape(ep, cap, D)
        return lax.all_to_all(out, ep_axis, 0, 0).reshape(ep * cap, D)

    return (_take_rows(recv.reshape(ep * cap, D), *regroup),
            jnp.sum(sizes_from, axis=0), back)


def routed_experts(x, router_w, bias, w1, w3, w2, *, top_k: int,
                   first_expert: int = 0, scaling: float = 1.0,
                   rows: Optional[int] = None, mesh=None,
                   token_axes=(), ep_axis: Optional[str] = None,
                   routing=None):
    """The routed-experts FFN ``sum_e w_e (silu(z W1_e) * z W3_e) W2_e``
    over the chosen experts that are HELD here, no assignment dropped.

    x: (B, T, D).  ``router_w`` (D, E) and ``bias`` (E,) span the
    router's whole width E; ``w1``/``w3`` (held, D, F) and ``w2`` (held,
    F, D) are experts ``first_expert .. first_expert + held``.  The
    routing is the caller's: ``routing(z (N, D), router_w, bias) -> (idx
    (N, top_k) int32, w (N, top_k) float32)``, for instance
    ``functools.partial(softmax_topk_routing, top_k=...)`` with ``bias``
    None; left out, it is ``sigmoid_topk_routing`` with this call's
    ``top_k`` and ``scaling``.  The plan, the grouped matmuls and the
    exchange are one copy under every routing.  Routing,
    top-k and the weights' normalisation run over all E; what the experts
    that are not held would add is left out (one chip's share of a
    deployment: run once per share, the results add up to the whole
    layer).

    ``rows`` is the size of the routed-row buffer per shard of tokens;
    None takes ``N * min(k, held)``, which no routing overflows.  A
    smaller buffer costs fewer padded rows; assignments that then find no
    room are left out and counted.

    Under a ``mesh`` of more than one device the layer runs per shard of
    the batch (``token_axes``); with ``ep_axis`` the experts' leading
    axis is sharded over it as well and the rows travel by two
    ``all_to_all``s (``first_expert`` then counts from rank 0).

    -> (y (B, T, D), counts (held over all ranks,) int32 assignments per
    held expert, overflow () int32)."""
    B, T, D = x.shape
    k = top_k
    ep = mesh.shape[ep_axis] if (mesh is not None and ep_axis) else 1
    axes = tuple(a for a in token_axes
                 if mesh is not None and mesh.shape.get(a, 1) > 1)
    if ep > 1 and ep_axis not in axes:
        raise ValueError(f"tokens must be sharded over {ep_axis!r} too: "
                         f"token_axes {token_axes}")
    if routing is None:
        def routing(z, router_w, bias):
            return sigmoid_topk_routing(z, router_w, bias, k, scaling)

    def local(x, router_w, bias, w1, w3, w2):
        held = w1.shape[0]
        z = x.reshape(-1, D)
        N = z.shape[0]
        cap = rows if rows is not None else N * min(k, held)
        with jax.named_scope("moe_route"):
            idx, w = routing(z, router_w, bias)
        rows_plan = _rows_plan(N, k, D, cap, ep * held, z.dtype)
        with jax.named_scope("moe_dispatch"):
            plan = _routing_plan(idx, first_expert, held, ep, cap)
            if rows_plan is None:
                to_rows = (plan["slot"] // k, plan["row_valid"],
                           plan["pos"], plan["valid"])
                xs = _take_rows(z, *to_rows)
            else:
                tiles = moe_rows.runs(plan["pos"], plan["valid"],
                                      plan["group"], ep * held, rows_plan)
                xs = moe_rows.dispatch(z, tiles, plan["kept"], R=ep * cap,
                                       cap=cap, groups=ep * held,
                                       plan=rows_plan)
            sizes = plan["sizes"]
            if ep > 1:
                xs, sizes, back = _exchange(xs, sizes, ep_axis, ep, cap)
        out = _grouped_ffn(xs, w1, w3, w2, sizes.reshape(-1))
        with jax.named_scope("moe_combine"):
            if ep > 1:
                out = back(out)
            if rows_plan is None:
                # each row's weight, by the same pair of gathers over the
                # flat (N k, 1) weights
                w_row = _take_rows(
                    w.reshape(-1, 1), plan["slot"], plan["row_valid"],
                    plan["pos"].reshape(-1, 1),
                    plan["valid"].reshape(-1, 1))
                y = _spread_rows(out * w_row.astype(out.dtype), *to_rows)
            else:
                y = moe_rows.combine(out, w, tiles, plan["kept"], cap=cap,
                                     groups=ep * held, plan=rows_plan)
        counts, overflow = plan["counts"], plan["overflow"]
        if axes:
            counts, overflow = lax.psum((counts, overflow), axes)
        counts = counts[lax.axis_index(ep_axis) if ep > 1 else 0]
        return y.reshape(x.shape), counts, overflow

    if not axes:
        return local(x, router_w, bias, w1, w3, w2)
    e_spec = P(ep_axis) if ep > 1 else P()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes), P(), None if bias is None else P(),
                  e_spec, e_spec, e_spec),
        out_specs=(P(axes), e_spec, P()), check_vma=False)(
            x, router_w, bias, w1, w3, w2)
