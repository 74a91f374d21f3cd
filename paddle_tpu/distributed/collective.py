"""Collective communication API over named mesh axes.

Reference parity: ``python/paddle/distributed/collective.py`` (all_reduce /
all_gather / broadcast / reduce / scatter / alltoall / send / recv /
barrier / new_group) and the ``c_*`` collective op layer
(``paddle/fluid/operators/collective/`` — c_allreduce_op.h:74,341, etc.).

TPU-first: there is no ring-id→communicator registry here.  A ``Group`` is
a *named mesh axis* plus rank bookkeeping.  Inside traced code
(jit/shard_map), a collective IS the corresponding XLA HLO —
``lax.psum`` / ``lax.all_gather`` / ``lax.ppermute`` / ``lax.all_to_all``
over the axis name, compiled onto ICI.  Outside a trace (eager dygraph
emulation), the same collective is executed by wrapping it in a one-shot
``jax.shard_map`` over the group's device mesh with the *leading dimension
as the rank dimension* — i.e. the single-process stand-in for N ranks is a
rank-stacked array, exactly how the reference's multi-process tests
stack per-rank state on one host (test_dist_base.py:778).
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..profiler import tracer as _obs

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "destroy_process_group",
    "all_reduce", "all_gather", "broadcast", "reduce", "scatter",
    "alltoall", "all_to_all", "reduce_scatter", "send", "recv", "barrier",
    "wait", "stream_wait",
]


class ReduceOp:
    """reference collective.py ReduceOp."""
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_LAX_REDUCE = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
               ReduceOp.MIN: lax.pmin}


@dataclass
class Group:
    """A communication group = mesh axis + member ranks.

    reference collective.py Group(id, rank, ranks); the NCCL communicator
    it would key (collective_helper.h:68) is replaced by `axis_name`.
    """
    rank: int
    ranks: List[int]
    axis_name: str = "world"
    nranks: int = 0
    id: int = 0
    devices: Optional[list] = field(default=None, repr=False)

    def __post_init__(self):
        if not self.nranks:
            self.nranks = len(self.ranks)
        if self.devices is None:
            devs = jax.devices()
            if all(r < len(devs) for r in self.ranks):
                self.devices = [devs[r] for r in self.ranks]

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, global_rank: int) -> int:
        return self.ranks.index(global_rank)

    def mesh(self) -> Mesh:
        devs = self.devices or jax.devices()[: self.nranks]
        if len(devs) < self.nranks:
            raise RuntimeError(
                f"group of {self.nranks} ranks needs {self.nranks} local "
                f"devices for single-process emulation, have {len(devs)}")
        return Mesh(np.asarray(devs), (self.axis_name,))


_lock = threading.Lock()
_group_map = {}
_default_group: Optional[Group] = None
_group_counter = [0]


def _world_group() -> Group:
    global _default_group
    with _lock:
        if _default_group is None:
            n = jax.device_count()
            _default_group = Group(rank=0, ranks=list(range(n)),
                                   axis_name="world", nranks=n, id=0)
            _group_map[0] = _default_group
    return _default_group


def get_group(gid: int = 0) -> Optional[Group]:
    if gid == 0:
        return _world_group()
    return _group_map.get(gid)


def new_group(ranks: Optional[Sequence[int]] = None, backend=None,
              axis_name: Optional[str] = None) -> Group:
    """reference collective.py new_group — here: register axis + ranks."""
    world = _world_group()
    if ranks is None:
        ranks = list(world.ranks)
    ranks = sorted(int(r) for r in ranks)
    with _lock:
        _group_counter[0] += 1
        gid = _group_counter[0]
    from .env import get_rank
    me = get_rank()
    g = Group(rank=(ranks.index(me) if me in ranks else -1), ranks=ranks,
              axis_name=axis_name or f"group_{gid}", nranks=len(ranks),
              id=gid)
    _group_map[gid] = g
    return g


def destroy_process_group(group: Optional[Group] = None):
    global _default_group
    with _lock:
        if group is None:
            _group_map.clear()
            _default_group = None
        else:
            _group_map.pop(group.id, None)


def _resolve(group: Optional[Group]) -> Group:
    return group if group is not None else _world_group()


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _raw(x):
    # accept framework Tensor or jax array
    return getattr(x, "_data", x)


def _wrap_like(template, arr):
    if hasattr(template, "_data"):
        from ..core.tensor import Tensor
        return Tensor(arr, stop_gradient=True)
    return arr


def _eager_collective(fn, group: Group, x, out_specs=None, extra=()):
    """Run `fn` (written against the group's axis name) as a one-shot
    shard_map over the group's devices, with dim0 = rank dim."""
    ax = group.axis_name
    n = group.nranks
    assert x.shape[0] % n == 0, (
        f"eager collective expects leading dim divisible by group size "
        f"{n}, got shape {x.shape}")
    mesh = group.mesh()
    in_specs = (P(ax),) + tuple(P() for _ in extra)
    out_specs = P(ax) if out_specs is None else out_specs
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(x, *extra)


# ---------------------------------------------------------------------------
# observability: per-collective op count + payload bytes + host span
# (reference platform profiler's comm-op event rows).  Zero overhead
# when tracing is off: one predicate read per call.
# ---------------------------------------------------------------------------

def _payload_nbytes(x) -> int:
    x = getattr(x, "_data", x)
    if isinstance(x, (list, tuple)):
        return sum(_payload_nbytes(e) for e in x)
    try:
        return int(x.size) * x.dtype.itemsize
    except Exception:
        return 0


def _instrumented(fn):
    name = fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not _obs.active:
            return fn(*args, **kwargs)
        # payload = largest tensor-ish argument: handles both call
        # shapes of all_gather/scatter (payload may be the 2nd arg or a
        # tensor list) and group passed positionally or by keyword.
        # Measured BEFORE the call so output lists fn mutates in place
        # (paddle-signature all_gather(out_list, tensor)) don't count.
        g = kwargs.get("group")
        nbytes = 0
        for v in list(args) + [v for k, v in kwargs.items()
                               if k != "group"]:
            if isinstance(v, Group):
                if g is None:
                    g = v
                continue
            n = _payload_nbytes(v)
            if n > nbytes:
                nbytes = n
        t0 = _obs.now_ns()
        out = fn(*args, **kwargs)
        _obs.on_collective(name, t0, nbytes,
                           world=g.nranks if g is not None else 0)
        return out

    return wrapper


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

@_instrumented
def all_reduce(tensor, op: int = ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True, use_calc_stream: bool = True):
    """reference collective.py all_reduce / c_allreduce_op.h:341.

    In-trace: psum/pmax/pmin/product over the group's mesh axis.
    Eager: rank-stacked emulation (dim0 = rank)."""
    g = _resolve(group)
    x = _raw(tensor)

    def _fn(v):
        if op == ReduceOp.PROD:
            # no lax primitive for product-reduce: all_gather then prod
            return jnp.prod(lax.all_gather(v, g.axis_name), axis=0)
        if op == ReduceOp.AVG:
            return lax.pmean(v, g.axis_name)
        return _LAX_REDUCE[op](v, g.axis_name)

    if _is_traced(x):
        out = _fn(x)
    else:
        out = _eager_collective(
            lambda v: jnp.broadcast_to(_fn(v), v.shape), g, x)
    return _wrap_like(tensor, out)


@_instrumented
def all_gather(tensor_or_list, tensor=None, group: Optional[Group] = None,
               sync_op: bool = True):
    """reference collective.py all_gather(tensor_list, tensor).

    Also callable TPU-style as ``all_gather(tensor)`` → stacked array with
    a new leading group dim (in-trace) / full rank-stacked array (eager).
    """
    g = _resolve(group)
    out_list = None
    if tensor is None:
        src = tensor_or_list
    else:
        out_list, src = tensor_or_list, tensor
    x = _raw(src)

    if _is_traced(x):
        gathered = lax.all_gather(x, g.axis_name, axis=0)
    else:
        n = g.nranks

        def _fn(v):
            return lax.all_gather(v, g.axis_name, axis=0, tiled=False)
        gathered = _eager_collective(_fn, g, x, out_specs=P(None))
        # eager path: each rank's shard was x[rank]; gathered is (n, *shard)
        gathered = gathered.reshape((n,) + x.shape[1:] if x.shape[0] == n
                                    else gathered.shape)
    if out_list is not None:
        for i in range(g.nranks):
            out_list.append(_wrap_like(src, gathered[i]))
        return out_list
    return _wrap_like(src, gathered)


@_instrumented
def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True):
    """reference collective.py broadcast / c_broadcast_op."""
    g = _resolve(group)
    x = _raw(tensor)
    if src not in g.ranks:
        raise ValueError(f"broadcast src rank {src} not in group {g.ranks}")
    src_local = g.ranks.index(src)

    if _is_traced(x):
        gathered = lax.all_gather(x, g.axis_name, axis=0)
        out = gathered[src_local]
    else:
        def _fn(v):
            gath = lax.all_gather(v, g.axis_name, axis=0)
            return gath[src_local]
        out = _eager_collective(
            lambda v: jnp.broadcast_to(_fn(v), v.shape), g, x)
    return _wrap_like(tensor, out)


@_instrumented
def reduce(tensor, dst: int = 0, op: int = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True):
    """reference c_reduce_op: reduce to dst rank; other ranks keep input."""
    g = _resolve(group)
    x = _raw(tensor)
    if dst not in g.ranks:
        raise ValueError(f"reduce dst rank {dst} not in group {g.ranks}")
    dst_local = g.ranks.index(dst)

    def _fn(v):
        if op == ReduceOp.PROD:
            red = jnp.prod(lax.all_gather(v, g.axis_name), axis=0)
        elif op == ReduceOp.AVG:
            red = lax.pmean(v, g.axis_name)
        else:
            red = _LAX_REDUCE[op](v, g.axis_name)
        idx = lax.axis_index(g.axis_name)
        return jnp.where(idx == dst_local, red, v)

    if _is_traced(x):
        out = _fn(x)
    else:
        out = _eager_collective(_fn, g, x)
    return _wrap_like(tensor, out)


@_instrumented
def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    """reference collective.py scatter: src rank's list → one per rank."""
    g = _resolve(group)
    if tensor_list is not None:
        stacked = jnp.stack([_raw(t) for t in tensor_list])
    else:
        stacked = _raw(tensor)

    if _is_traced(stacked):
        idx = lax.axis_index(g.axis_name)
        return _wrap_like(tensor, stacked[idx])
    # eager: row r of the stacked src tensor goes to rank r
    return _wrap_like(tensor, stacked)


@_instrumented
def alltoall(in_tensor_list, out_tensor_list=None,
             group: Optional[Group] = None, sync_op: bool = True):
    """reference collective.py alltoall / alltoall op.

    In-trace: pass one array whose dim0 is split across ranks →
    lax.all_to_all.  Eager: list-of-lists semantics like the reference.
    """
    g = _resolve(group)
    if not isinstance(in_tensor_list, (list, tuple)):
        x = _raw(in_tensor_list)
        if _is_traced(x):
            out = lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0,
                                 tiled=True)
            return _wrap_like(in_tensor_list, out)
        if x.shape[0] != g.nranks:
            raise ValueError(
                f"eager alltoall expects rank-stacked input with dim0 "
                f"== group size {g.nranks}, got shape {x.shape}")

        # eager: rank-stacked (n, n*chunk, ...): dim0=rank, each row's
        # dim0 is split across ranks.
        def _fn(v):
            return lax.all_to_all(v[0], g.axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)[None]
        out = _eager_collective(_fn, g, x)
        return _wrap_like(in_tensor_list, out)
    # list form: in_tensor_list[i] goes to rank i; needs eager arrays
    n = g.nranks
    assert len(in_tensor_list) == n
    stacked = jnp.stack([_raw(t) for t in in_tensor_list])  # (n, ...)
    # single-controller emulation: every rank holds this same list, so
    # rank r receives in_tensor_list[r] from each of the n peers.
    r = max(g.rank, 0)
    outs = [stacked[r] for _ in range(n)]
    if out_tensor_list is not None:
        out_tensor_list.extend(
            _wrap_like(in_tensor_list[0], o) for o in outs)
        return out_tensor_list
    return [_wrap_like(in_tensor_list[0], o) for o in outs]


all_to_all = alltoall


@_instrumented
def reduce_scatter(tensor, tensor_list=None, op: int = ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True):
    """reference c_reducescatter_op: reduce then scatter chunks."""
    g = _resolve(group)
    if op != ReduceOp.SUM:
        raise NotImplementedError(
            "reduce_scatter supports ReduceOp.SUM only (XLA "
            "reduce-scatter is a sum; compose all_reduce+slice otherwise)")
    if tensor_list is not None:
        x = jnp.concatenate([_raw(t) for t in tensor_list], axis=0)
    else:
        x = _raw(tensor)

    if _is_traced(x):
        out = lax.psum_scatter(x, g.axis_name, scatter_dimension=0,
                               tiled=True)
        return _wrap_like(tensor, out)

    # eager rank-stacked: input (n, n*chunk, ...) with dim0=rank; each
    # rank's row is its full contribution, it gets back its reduced chunk.
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager reduce_scatter expects rank-stacked input with dim0 "
            f"== group size {g.nranks}, got shape {x.shape}")

    def _fn2(v):
        # v: (1, n*chunk, ...) local row
        return lax.psum_scatter(v[0], g.axis_name, scatter_dimension=0,
                                tiled=True)[None]
    out = _eager_collective(_fn2, g, x)
    return _wrap_like(tensor, out)


@_instrumented
def send(tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    """reference send_v2 (collective/send_v2_op.cu.cc).

    In-trace there is no one-sided send on TPU — use
    :func:`paddle_tpu.distributed.p2p.ppermute_send_recv` (send+recv fuse
    to one collective_permute).  Eager: device_put onto dst's device.
    """
    g = _resolve(group)
    x = _raw(tensor)
    if _is_traced(x):
        raise RuntimeError(
            "send() inside jit: use distributed.ppermute/p2p helpers "
            "(send/recv fuse to lax.ppermute on TPU)")
    if g.devices is not None and dst < len(g.devices):
        _P2P_BOX[(g.id, dst)] = jax.device_put(x, g.devices[dst])
    else:
        _P2P_BOX[(g.id, dst)] = x
    return tensor


@_instrumented
def recv(tensor, src: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    """reference recv_v2. Eager pair of send(); see send() for in-trace."""
    g = _resolve(group)
    x = _raw(tensor)
    if _is_traced(x):
        raise RuntimeError(
            "recv() inside jit: use distributed.ppermute/p2p helpers")
    # single-process emulation: the value sent to *this* rank
    key = (g.id, g.rank if g.rank >= 0 else 0)
    val = _P2P_BOX.pop(key, None)
    if val is None:
        raise RuntimeError("recv() without a matching send()")
    out = _wrap_like(tensor, val)
    if hasattr(tensor, "_data"):
        tensor._data = _raw(out)
    return out


_P2P_BOX = {}


@_instrumented
def barrier(group: Optional[Group] = None):
    """reference barrier op — on TPU a device sync is enough in-process."""
    g = _resolve(group)
    tok = jnp.zeros((g.nranks,), jnp.int32)
    out = all_reduce(tok, ReduceOp.SUM, g)
    jax.block_until_ready(_raw(out))


def wait(tensor, group: Optional[Group] = None, use_calc_stream: bool = True):
    """reference c_wait_compute/c_wait_comm — stream ordering is XLA's job;
    eager wait = block_until_ready."""
    jax.block_until_ready(_raw(tensor))
    return tensor


stream_wait = wait


# ---------------------------------------------------------------------------
# in-trace functional face (TPU-native; used by meta_parallel layers)
# ---------------------------------------------------------------------------

def psum(x, group: Optional[Group] = None):
    g = _resolve(group)
    return lax.psum(_raw(x), g.axis_name)


def pmean(x, group: Optional[Group] = None):
    g = _resolve(group)
    return lax.pmean(_raw(x), g.axis_name)


def ppermute(x, perm, group: Optional[Group] = None):
    g = _resolve(group)
    return lax.ppermute(_raw(x), g.axis_name, perm)


def axis_index(group: Optional[Group] = None):
    g = _resolve(group)
    return lax.axis_index(g.axis_name)


def global_scatter(x, local_count=None, global_count=None,
                   group: Optional[Group] = None):
    """reference collective/global_scatter_op.cu.cc — MoE token dispatch.

    TPU-native: variable-count send lists don't fit XLA's static shapes;
    tokens travel in fixed-capacity expert buffers (E, C, D) and the
    exchange is one all_to_all over the expert-parallel axis.  See
    fleet.meta_parallel.moe for gating/capacity. In-trace only."""
    g = _resolve(group)
    x = _raw(x)
    if not _is_traced(x):
        raise RuntimeError("global_scatter is an in-trace (shard_map) op; "
                           "eager MoE uses fleet.meta_parallel.MoELayer")
    from .fleet.meta_parallel.moe import moe_alltoall
    return moe_alltoall(x, g.axis_name)


def global_gather(x, local_count=None, global_count=None,
                  group: Optional[Group] = None):
    """reference collective/global_gather_op.cu.cc — inverse dispatch."""
    g = _resolve(group)
    x = _raw(x)
    if not _is_traced(x):
        raise RuntimeError("global_gather is an in-trace (shard_map) op; "
                           "eager MoE uses fleet.meta_parallel.MoELayer")
    from .fleet.meta_parallel.moe import moe_alltoall_inverse
    return moe_alltoall_inverse(x, g.axis_name)
