"""Data-parallel training over the device mesh.

Reference parity: ``python/paddle/fluid/dygraph/parallel.py:389``
(DataParallel) + C++ ``imperative/reducer.cc`` (bucketed fused allreduce
overlapping backward).

TPU-first — and an intentional non-port: the reference needs a Reducer
because each process owns its own gradient tensors and must fuse/schedule
NCCL allreduces by hand.  Under XLA SPMD there is nothing to schedule by
hand: the batch is sharded over the ``dp`` mesh axis, parameters are
replicated, and the gradient cross-replica sum is a compiler-inserted
``all-reduce`` that XLA's latency-hiding scheduler already overlaps with
the backward pass.  DataParallel therefore reduces to (a) holding the
mesh, (b) sharding inputs, (c) placing parameters by their
``PartitionSpec`` placements (replicated by default; TP layers set theirs
— see meta_parallel/mp_layers.py), so the same wrapper drives pure-DP and
hybrid DP×TP without a code change.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn.layer_base import Layer
from ..core.tensor import Tensor

__all__ = ["DataParallel", "shard_batch", "input_sharding_fn",
           "param_shardings", "apply_param_shardings", "scale_loss",
           "mesh_for_world", "clean_partition_spec"]


def _default_dp_mesh(axis: str = "dp") -> Mesh:
    devs = jax.devices()
    return Mesh(np.asarray(devs), (axis,))


def mesh_for_world(world: int, axis: str = "dp", devices=None) -> Mesh:
    """A 1-D mesh over the first ``world`` visible devices — the
    target-mesh constructor for cross-world checkpoint resharding: a
    tree saved at world N restores onto ``mesh_for_world(M)`` via
    ``checkpoint.load_state(..., reshard_mesh=...)`` after an elastic
    shrink or grow."""
    devs = list(devices if devices is not None else jax.devices())
    world = int(world)
    if world < 1 or world > len(devs):
        raise ValueError(f"world {world} out of range: {len(devs)} "
                         f"devices visible")
    return Mesh(np.asarray(devs[:world]), (axis,))


def clean_partition_spec(spec, mesh: Mesh, shape=None) -> P:
    """A PartitionSpec with entries the mesh can't honor dropped to
    replicated: axis names the mesh doesn't have (e.g. an mp spec on a
    pure-dp mesh), and — when ``shape`` is given — axes whose size no
    longer divides the dim (a world change can leave a DP-sharded dim
    indivisible; degrading that dim to replicated beats failing the
    restore)."""
    entries = tuple(spec) if not isinstance(spec, (list, tuple)) else spec
    cleaned = []
    for i, entry in enumerate(entries):
        keep = entry
        if entry is None:
            cleaned.append(None)
            continue
        if isinstance(entry, (list, tuple)):
            if not all(e in mesh.axis_names for e in entry):
                keep = None
            else:
                keep = tuple(entry)
        elif entry not in mesh.axis_names:
            keep = None
        if keep is not None and shape is not None and i < len(shape):
            axes = keep if isinstance(keep, tuple) else (keep,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if size and int(shape[i]) % size != 0:
                keep = None
        cleaned.append(keep)
    return P(*cleaned)


def shard_batch(arrays, mesh: Mesh, axis: str = "dp"):
    """Place arrays so dim0 is split across the `axis` mesh axis."""
    if axis not in mesh.axis_names:
        return arrays
    spec = NamedSharding(mesh, P(axis))
    out = []
    for a in arrays:
        arr = getattr(a, "_data", a)
        n = mesh.shape[axis]
        if arr.ndim == 0 or arr.shape[0] % n != 0:
            out.append(jax.device_put(arr, NamedSharding(mesh, P())))
        else:
            out.append(jax.device_put(arr, spec))
    return out


def input_sharding_fn(mesh: Mesh, axis: str = "dp"):
    """Per-leaf sharding chooser for the io DevicePrefetcher: the same
    rules as :func:`shard_batch` (dim0 split over ``axis`` when
    divisible, replicated otherwise), as a callable the prefetch thread
    applies inside its ``device_put``.  Batches then land on the mesh
    pre-sharded — no host gather and no re-placement inside the train
    step (``shard_batch`` becomes a no-op on already-committed arrays).

    Returns None when the mesh is not fully addressable from this
    process (multi-host): per-process shards can't be globally placed
    with a plain ``device_put``; those pipelines keep host batches and
    shard in-step."""
    if axis not in mesh.axis_names:
        return None
    if any(d.process_index != jax.process_index() for d in
           mesh.devices.flat):
        return None
    split = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    n = mesh.shape[axis]

    def leaf_sharding(arr):
        if getattr(arr, "ndim", 0) == 0 or arr.shape[0] % n != 0:
            return repl
        return split

    return leaf_sharding


def param_shardings(layer: Layer, mesh: Mesh) -> Dict[str, NamedSharding]:
    """name -> NamedSharding from each Parameter's `placements` dist attr
    (replicated when unset).  The TPU-native analog of the reference's
    auto_parallel completion step (distributed/auto_parallel/completion.py):
    annotations on params, propagation left to GSPMD."""
    out = {}
    for name, p in layer.named_parameters():
        spec = p.placements if p.placements is not None else P()
        out[name] = NamedSharding(mesh, clean_partition_spec(spec, mesh))
    return out


def apply_param_shardings(layer: Layer, mesh: Mesh):
    """device_put every parameter/buffer onto the mesh per its placements."""
    shardings = param_shardings(layer, mesh)
    lookup = dict(layer.named_parameters())
    for name, sh in shardings.items():
        p = lookup[name]
        p._data = jax.device_put(p._data, sh)
    rep = NamedSharding(mesh, P())
    for name, b in layer.named_buffers():
        b._data = jax.device_put(b._data, rep)


def scale_loss(loss, dp_world_size: Optional[int] = None):
    """reference dygraph/parallel.py scale_loss — divide by dp degree.
    Under pmean-style grad sync this is a no-op; kept for API parity."""
    n = dp_world_size or jax.device_count()
    arr = getattr(loss, "_data", loss)
    out = arr / n
    return Tensor(out) if isinstance(loss, Tensor) else out


class DataParallel(Layer):
    """reference dygraph/parallel.py:389.

    Wraps a Layer for mesh-parallel execution.  `forward` delegates to the
    wrapped layer (eager single-device semantics are unchanged); the jit
    path (hapi Model / fleet train loops) queries `.mesh` and
    `.shard_inputs` to lay the batch and parameters onto the mesh, after
    which XLA inserts the gradient all-reduce the reference's Reducer
    performed by hand.
    """

    def __init__(self, layers: Layer, strategy=None,
                 comm_buffer_size: int = 25,
                 last_comm_buffer_size: int = 1,
                 find_unused_parameters: bool = False,
                 group=None, mesh: Optional[Mesh] = None,
                 dp_axis: str = "dp"):
        super().__init__()
        self._layers = layers
        self._dp_axis = dp_axis
        # comm_buffer_size / find_unused_parameters are accepted for API
        # parity; XLA's scheduler owns fusion & overlap (see module doc).
        self.find_unused_parameters = find_unused_parameters
        if mesh is None:
            if group is not None and getattr(group, "devices", None):
                mesh = Mesh(np.asarray(group.devices), (dp_axis,))
            else:
                mesh = _default_dp_mesh(dp_axis)
        self.mesh = mesh
        apply_param_shardings(layers, mesh)

    def forward(self, *inputs, **kwargs):
        # ops with a Mosaic kernel (attention) must know the mesh: GSPMD
        # cannot partition the kernel, so it runs per shard — batch over
        # the dp axis, heads over mp where TP layers shard them
        from ..ops import pallas
        with pallas.kernel_mesh(self.mesh, batch_axes=(self._dp_axis,),
                                head_axes=("mp",)):
            return self._layers(*inputs, **kwargs)

    def shard_inputs(self, arrays):
        return shard_batch(arrays, self.mesh, self._dp_axis)

    def scale_loss(self, loss):
        return loss  # grads are mean-reduced by sharded-batch jit math

    # reference API parity ------------------------------------------------
    def no_sync(self):
        import contextlib
        return contextlib.nullcontext()

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)
