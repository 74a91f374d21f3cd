"""Auto-parallel planner: completion + comm-volume cost model.

Reference parity: ``python/paddle/distributed/auto_parallel/completion.py:429``
(complete_annotation — fill dims_mappings the user didn't write) and
``cost_model.py:720`` (estimate_cost — pick among strategies by modeled
runtime).  The reference completes a serial *program* op by op and
evaluates whole distributed programs; the TPU translation plans at the
*layer graph* level and emits ``PartitionSpec`` per parameter, because
intra-program propagation is GSPMD's job — the part XLA does NOT do is
choosing WHICH mesh axis shards WHICH parameter dim.  That choice is
this module.

Mechanism
---------
``plan_model(model, mesh)`` walks the model's Linear/Embedding sublayers
in registration order (== call order for standard sequential models) and
runs a dynamic program over per-layer strategies:

- Linear: ``col`` (shard out-features; Megatron column-parallel — the
  backward all-reduces dx), ``row`` (shard in-features; the forward
  all-reduces y), or ``rep`` (replicate; full FLOPs on every shard).
- Embedding: ``vocab`` (shard rows; forward psums the masked lookup) or
  ``rep``.
- Everything else is a passthrough for the DP state (GSPMD will still
  execute it correctly whatever we choose — mis-modeling can only cost
  estimate accuracy, never numerics).

The DP state tracks whether the activation's feature dim is currently
sharded over the mp axis, so the planner discovers the classic
col->row pairing (qkv/up column, out/down row) with exactly one
all-reduce per direction per pair.

Cost model (``estimate_cost`` analog): per-training-step seconds,
``t = flops/peak/shard + mp collective bytes/ici_bw + dp grad-allreduce
bytes/ici_bw`` — the same compute+communication decomposition the
reference's CostModel uses (op graph costs + comm costs), with TPU
constants instead of profiled op tables.

Consume the plan through the COMPILED engines (``paddle.Model``'s
jitted step, ``fleet.build_sharded_trainer``, or any whole-step
``jax.jit``): one XLA program per step keeps the mp collectives
correctly sequenced.  Eager per-op dispatch over mp-sharded parameters
is not a supported execution mode.

Pinned specs (the "partial annotation" input of complete_annotation):
pass ``pinned={"blocks.0.attn.qkv.weight": P(None, "mp")}`` and the
planner keeps them fixed, completing only the rest.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["plan_model", "shard", "Plan", "CostReport"]

# v5e-class constants; only RATIOS matter for the argmin
_PEAK_FLOPS = 197e12          # bf16 MXU
# Achieved-rate derate, set from the flagship's pre-round figure on a
# runtime that no longer exists (BERT-base at ~0.51-0.55 MFU incl. remat
# recompute and the attention/loss ops this layer-level model does not
# enumerate) — history, to be re-set from PERF_LEDGER.jsonl once the
# benchmark exists.  Applied to BOTH compute and ICI so every strategy
# RATIO — and therefore the argmin the golden tests pin — is unchanged.
_EFF = 0.55
_EFF_FLOPS = _PEAK_FLOPS * _EFF
_ICI_BW = 4.5e10 * _EFF       # achieved bytes/s per link
_ACT_BYTES = 2                # bf16 activations
_GRAD_BYTES = 4               # f32 master grads
# fixed per-collective launch/hop latency, derated like the rest so
# EVERY term of a strategy time scales by the same 1/_EFF factor (the
# argmin the golden tests pin is scale-invariant only if so)
_COLL_LATENCY = 1e-5 / _EFF


def _allreduce_time(bytes_, axis_size):
    if axis_size <= 1 or bytes_ == 0:
        return 0.0
    return _COLL_LATENCY + \
        2.0 * bytes_ * (axis_size - 1) / axis_size / _ICI_BW


@dataclass
class _Choice:
    name: str                 # col | row | rep | vocab
    weight_spec: Tuple       # PartitionSpec dims for the weight
    bias_spec: Optional[Tuple]
    in_state: str             # required activation state: r | s | any
    out_state: str
    time: float               # modeled seconds for this layer's step


@dataclass
class CostReport:
    """estimate_cost parity: modeled per-step cost of the chosen plan.
    Collective times use the plan's REAL axis degrees (r4 hardcoded 2
    here; the argmin was right but the reported number was garbage at
    mp=4/8)."""
    compute_s: float = 0.0
    mp_comm_bytes: int = 0
    dp_comm_bytes: int = 0
    sp_comm_bytes: int = 0
    param_bytes_per_device: int = 0
    mp: int = 1
    dp: int = 1
    pp: int = 1
    sp: int = 1
    num_microbatches: int = 1
    # per-stage modeled seconds when pp > 1 (balanced partition result)
    stage_times: Tuple[float, ...] = ()

    @property
    def grad_sync_degree(self):
        # parameters replicate over BOTH dp and sp: the gradient
        # all-reduce spans their product
        return max(1, self.dp) * max(1, self.sp)

    @property
    def total_s(self):
        grad_t = _allreduce_time(self.dp_comm_bytes,
                                 self.grad_sync_degree)
        sp_t = _allreduce_time(self.sp_comm_bytes, self.sp)
        if self.pp <= 1 or not self.stage_times:
            return (self.compute_s
                    + _allreduce_time(self.mp_comm_bytes, self.mp)
                    + grad_t + sp_t)
        # fill-drain pipeline: per-microbatch bottleneck stage paces the
        # steady state, one bubble slot per ACTUAL stage boundary (the
        # partition may produce fewer stages than the mesh's pp degree)
        M = max(1, self.num_microbatches)
        n_stages = len(self.stage_times)
        return max(self.stage_times) * (M + n_stages - 1) / M \
            + grad_t + sp_t


@dataclass
class Plan:
    mesh: Mesh
    param_specs: Dict[str, P]
    choices: Dict[str, str]
    report: CostReport = field(default_factory=CostReport)
    # planned layer -> pipeline stage (empty when the mesh has no pp
    # axis); contiguous by construction, balanced on modeled time
    stage_of: Dict[str, int] = field(default_factory=dict)

    def named_shardings(self) -> Dict[str, NamedSharding]:
        return {n: NamedSharding(self.mesh, s)
                for n, s in self.param_specs.items()}

    def apply(self, model):
        """Place the model's parameters onto the mesh per the plan."""
        shardings = self.named_shardings()
        for name, p in model.named_parameters():
            ns = shardings.get(name)
            if ns is not None:
                p._data = jax.device_put(p._data, ns)
        return model


def _linear_choices(in_f, out_f, tokens, mp, dp, mp_axis):
    """Strategy menu for one Linear (reference dist-op impls for matmul:
    column/row/replicate — operators/dist_matmul.py).

    ``tokens`` here is PER-DP-REPLICA: each dp replica runs its own mp
    collectives concurrently over disjoint mesh rows, and computes only
    its batch shard — only the dp gradient all-reduce moves whole-param
    bytes."""
    flops = 3 * 2 * tokens * in_f * out_f          # fwd + ~2x bwd
    wbytes = in_f * out_f * _GRAD_BYTES
    out = []
    # column-parallel: weight (in, out/mp); bwd all-reduces dx
    t = (flops / mp) / _EFF_FLOPS \
        + _allreduce_time(tokens * in_f * _ACT_BYTES, mp) \
        + _allreduce_time(wbytes / mp, dp)
    out.append(_Choice("col", (None, mp_axis), (mp_axis,), "r", "s", t))
    # row-parallel: weight (in/mp, out); fwd all-reduces y
    t = (flops / mp) / _EFF_FLOPS \
        + _allreduce_time(tokens * out_f * _ACT_BYTES, mp) \
        + _allreduce_time(wbytes / mp, dp)
    out.append(_Choice("row", (mp_axis, None), (None,), "s", "r", t))
    # replicated: full flops everywhere, full dp grad sync
    t = flops / _EFF_FLOPS + _allreduce_time(wbytes, dp)
    out.append(_Choice("rep", (None, None), (None,), "r", "r", t))
    return out


def _embedding_choices(rows, dim, tokens, mp, dp, mp_axis):
    wbytes = rows * dim * _GRAD_BYTES
    out = []
    # vocab-parallel: rows sharded; fwd psums the masked gather
    t = _allreduce_time(tokens * dim * _ACT_BYTES, mp) \
        + _allreduce_time(wbytes / mp, dp)
    # embeddings consume ids, not the activation stream: no state
    # requirement on entry ("any"), fresh replicated stream on exit
    out.append(_Choice("vocab", (mp_axis, None), None, "any", "r", t))
    t = _allreduce_time(wbytes, dp)
    out.append(_Choice("rep", (None, None), None, "any", "r", t))
    return out


def _classify(layer):
    from ...nn import Linear, Embedding
    if isinstance(layer, Linear):
        return "linear"
    if isinstance(layer, Embedding):
        return "embedding"
    return "other"


def _call_order(model, sample_input, units):
    """Execution order of the plannable leaves, from one traced forward
    (registration order can diverge from call order — e.g. a tied/LM
    head registered before the blocks it follows)."""
    order: List[str] = []
    originals = {}   # id(layer) -> (layer, original forward)
    try:
        for name, layer, _ in units:
            if id(layer) in originals:
                continue   # tied module registered under two names
            orig = layer.forward

            def rec(*a, _n=name, _f=orig, **k):
                order.append(_n)
                return _f(*a, **k)
            originals[id(layer)] = (layer, orig)
            layer.forward = rec
        model(sample_input)
    finally:
        for layer, orig in originals.values():
            layer.forward = orig
    seen = set()
    uniq_order = [n for n in order
                  if not (n in seen or seen.add(n))]
    by_name = {u[0]: u for u in units}
    ordered = [by_name[n] for n in uniq_order if n in by_name]
    missing = [u for u in units if u[0] not in seen]
    return ordered + missing


def plan_model(model, mesh: Mesh, tokens: int = 4096,
               mp_axis: str = "mp", dp_axis: str = "dp",
               pp_axis: str = "pp", sp_axis: str = "sp",
               num_microbatches: int = 4,
               pinned: Optional[Dict[str, P]] = None,
               sample_input=None) -> Plan:
    """Complete parameter shardings for ``model`` over ``mesh``.

    tokens: nominal batch*seq per step — sets the activation/parameter
    comm ratio the cost model trades off (reference estimate_cost takes
    ``batch_size`` the same way).  sample_input: optional tiny input used
    to recover true call order of the layers (falls back to registration
    order).

    Axis participation (full 4-axis planning):
    - ``mp``: per-layer col/row/vocab strategy choice (the DP below);
    - ``dp``: divides tokens, adds the gradient all-reduce;
    - ``sp``: divides tokens again (sequence shards), adds the ring
      attention K/V rotation bytes per col->row strategy pair (the pairs
      bracket an attention/FFN block — the part of ``cost_model.py:720``
      that costs comm per transformer block);
    - ``pp``: after strategies are chosen, the layer chain is
      partitioned into ``pp`` contiguous stages balancing modeled
      per-stage time (the stage-costing half of the reference's
      planner); ``Plan.stage_of`` maps each planned layer to its stage
      and ``report.total_s`` applies the fill-drain bubble factor.
    """
    pinned = dict(pinned or {})
    mp = int(mesh.shape.get(mp_axis, 1))
    dp = int(mesh.shape.get(dp_axis, 1))
    pp = int(mesh.shape.get(pp_axis, 1))
    sp = int(mesh.shape.get(sp_axis, 1))
    # per-shard tokens: dp and sp both divide the token stream
    tokens = max(1, tokens // (dp * max(1, sp)))

    units = []   # (prefix, layer, kind) for plannable leaves, in order
    for name, layer in model.named_sublayers():
        kind = _classify(layer)
        if kind in ("linear", "embedding") and \
                not any(name.startswith(u[0] + ".") for u in units):
            units.append((name, layer, kind))
    if sample_input is not None:
        units = _call_order(model, sample_input, units)

    # DP over the chain: state = activation feature dim sharded ('s')
    # over mp or replicated ('r'); resharding 's'->'r' costs an
    # all-gather of the activation at its CURRENT feature width
    INF = float("inf")

    def gather_t(width):
        if mp <= 1 or not width:
            return 0.0
        return _COLL_LATENCY + \
            tokens * width * _ACT_BYTES * (mp - 1) / mp / _ICI_BW

    # state -> (cost, choice history, activation feature width)
    best = {"r": (0.0, [], 0), "s": (INF, [], 0)}
    for name, layer, kind in units:
        w = layer.weight
        if kind == "linear":
            in_f, out_f = int(w.shape[0]), int(w.shape[1])
            menu = _linear_choices(in_f, out_f, tokens, mp, dp, mp_axis)
        else:
            out_f = int(w.shape[1])
            menu = _embedding_choices(int(w.shape[0]), out_f,
                                      tokens, mp, dp, mp_axis)
        if mp <= 1:
            # no mp axis on this mesh: only replicated strategies are
            # expressible (a 'mp'-naming spec would not resolve)
            menu = [c for c in menu if c.name == "rep"]
        pin = pinned.get(f"{name}.weight")
        if pin is not None:
            menu = [c for c in menu if P(*c.weight_spec) == pin]
            if not menu:
                raise ValueError(
                    f"pinned spec {pin} for '{name}.weight' matches no "
                    "strategy (expected one of col/row/rep/vocab specs)")
        nxt = {"r": (INF, [], 0), "s": (INF, [], 0)}
        for state, (cost, hist, width) in best.items():
            if cost == INF:
                continue
            for c in menu:
                # entering cost: 's' activations must gather to feed an
                # 'r'-input strategy; an 's'-input strategy needs 's'
                if c.in_state == "r":
                    enter = gather_t(width) if state == "s" else 0.0
                elif c.in_state == "s":
                    if state != "s":
                        continue
                    enter = 0.0
                else:
                    enter = 0.0
                total = cost + enter + c.time
                if total < nxt[c.out_state][0]:
                    nxt[c.out_state] = (total, hist + [c], out_f)
        best = nxt

    end_state = min(best, key=lambda s: best[s][0]
                    + (gather_t(best[s][2]) if s == "s" else 0.0))
    chosen = best[end_state][1]

    specs: Dict[str, P] = {}
    choices: Dict[str, str] = {}
    report = CostReport(mp=mp, dp=dp, pp=pp, sp=sp,
                        num_microbatches=num_microbatches)
    unit_times: List[float] = []   # per planned layer: compute + mp comm
    for (name, layer, kind), c in zip(units, chosen):
        specs[f"{name}.weight"] = P(*c.weight_spec)
        choices[name] = c.name
        if c.bias_spec is not None and getattr(layer, "bias", None) \
                is not None:
            specs[f"{name}.bias"] = P(*c.bias_spec)
        w = layer.weight
        wbytes = int(np.prod(w.shape)) * _GRAD_BYTES
        shard_f = mp if c.name in ("col", "row", "vocab") else 1
        report.param_bytes_per_device += wbytes // shard_f
        t_compute = t_comm = 0.0
        if kind == "linear":
            in_f, out_f = int(w.shape[0]), int(w.shape[1])
            t_compute = (3 * 2 * tokens * in_f * out_f
                         / shard_f) / _EFF_FLOPS
            if c.name == "col":
                report.mp_comm_bytes += tokens * in_f * _ACT_BYTES
                t_comm = _allreduce_time(tokens * in_f * _ACT_BYTES, mp)
                if sp > 1:
                    # ring attention rotates K/V shards around the sp
                    # axis once per attention block; a col strategy
                    # opens such a block
                    report.sp_comm_bytes += \
                        2 * tokens * in_f * _ACT_BYTES * (sp - 1)
            elif c.name == "row":
                report.mp_comm_bytes += tokens * out_f * _ACT_BYTES
                t_comm = _allreduce_time(tokens * out_f * _ACT_BYTES, mp)
        elif c.name == "vocab":
            report.mp_comm_bytes += tokens * int(w.shape[1]) * _ACT_BYTES
            t_comm = _allreduce_time(
                tokens * int(w.shape[1]) * _ACT_BYTES, mp)
        report.compute_s += t_compute
        report.dp_comm_bytes += \
            wbytes // shard_f if dp * sp > 1 else 0
        unit_times.append(t_compute + t_comm)

    stage_of: Dict[str, int] = {}
    if pp > 1 and units:
        # group units into atomic pipeline cells: every layer inside one
        # repeated block ("blocks.3.…") moves as a unit — a stage cut
        # inside a block would sever its residual stream, which the
        # hand-built spmd_pipeline never does (it shards the stacked
        # layer dim)
        import re as _re
        groups: List[List[int]] = []
        gid_of = {}
        solo: List[int] = []      # embedding/head-style one-off layers
        for ui, (name, _, _) in enumerate(units):
            m = _re.match(r"^(.*?\.\d+)(?:\.|$)", name)
            if m is None:
                # not part of a repeated block: lives OUTSIDE the
                # pipeline, exactly like gpt_spmd computes wte/head
                # before/after the pp shard_map
                solo.append(ui)
                continue
            gkey = m.group(1)
            if gkey not in gid_of:
                gid_of[gkey] = len(groups)
                groups.append([])
            groups[gid_of[gkey]].append(ui)
        if groups:
            gtimes = [sum(unit_times[ui] for ui in g) for g in groups]
            bounds = _balance_stages(gtimes, min(pp, len(groups)))
            npart = len(bounds) - 1
            for si in range(npart):
                for gi in range(bounds[si], bounds[si + 1]):
                    for ui in groups[gi]:
                        stage_of[units[ui][0]] = si
            stage_times = [sum(gtimes[bounds[si]:bounds[si + 1]])
                           for si in range(npart)]
            # outside-the-pipeline layers pace the boundary stages:
            # embedding-side solos onto stage 0, head-side onto the last
            mid = groups[0][0] if groups else 0
            for ui in solo:
                stage_times[0 if ui < mid else -1] += unit_times[ui]
            report.stage_times = tuple(stage_times)

    # remaining params (norms, convs, anything unplanned): replicated
    # over every axis — GSPMD propagates activation shardings around them
    for pname, p in model.named_parameters():
        if pname not in specs:
            spec = pinned.get(pname, P(*([None] * len(p.shape))))
            specs[pname] = spec
            report.param_bytes_per_device += \
                int(np.prod(p.shape)) * _GRAD_BYTES
    plan = Plan(mesh=mesh, param_specs=specs, choices=choices,
                report=report, stage_of=stage_of)
    return plan


def _balance_stages(times: Sequence[float], pp: int) -> List[int]:
    """Partition the layer chain into ``pp`` contiguous stages minimizing
    the max stage time (the pipeline-stage costing of the reference's
    ``cost_model.py:720``).  Returns pp+1 boundary indices.  Exact DP,
    O(n^2 * pp) — n is the number of plannable layers, tiny."""
    n = len(times)
    prefix = [0.0]
    for t in times:
        prefix.append(prefix[-1] + t)

    def seg(i, j):
        return prefix[j] - prefix[i]

    INF = float("inf")
    # dp[s][i] = minimal max-stage-time splitting times[:i] into s stages
    dp = [[INF] * (n + 1) for _ in range(pp + 1)]
    cut = [[0] * (n + 1) for _ in range(pp + 1)]
    dp[0][0] = 0.0
    for s in range(1, pp + 1):
        for i in range(1, n + 1):
            for j in range(s - 1, i):
                cand = max(dp[s - 1][j], seg(j, i))
                if cand < dp[s][i]:
                    dp[s][i] = cand
                    cut[s][i] = j
    bounds = [n]
    for s in range(pp, 0, -1):
        bounds.append(cut[s][bounds[-1]])
    return bounds[::-1]


def shard(model, mesh: Mesh, tokens: int = 4096,
          pinned: Optional[Dict[str, P]] = None, **kw) -> Plan:
    """``fleet.auto.shard(model, mesh)``: complete the model's parameter
    shardings with the cost model and place the parameters."""
    plan = plan_model(model, mesh, tokens=tokens, pinned=pinned, **kw)
    plan.apply(model)
    return plan
