"""Tensor: the user-facing eager ndarray.

Reference parity: ``paddle/fluid/framework/tensor.h:89`` (typed ndarray with
Place-tagged allocation) + ``imperative`` VarBase semantics (stop_gradient,
.grad, hooks).  TPU-first: the storage IS a jax.Array living on a PJRT
buffer; device placement, layout, and streams are XLA/PJRT concerns.  LoD
(ragged sequences) is represented with dense tensors + explicit
lengths/segment-ids (see ops/sequence.py) rather than LoDTensor metadata.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from .dtype import dtype_to_jnp, canonical_dtype
from .place import Place, CPUPlace, TPUPlace, _current_place

__all__ = ["Tensor", "Parameter", "to_tensor"]

_name_counter = threading.local()


def _next_name(prefix="tensor"):
    c = getattr(_name_counter, "c", 0)
    _name_counter.c = c + 1
    return f"{prefix}_{c}"


def _place_of(arr) -> Place:
    try:
        dev = list(arr.devices())[0]
    except Exception:
        return CPUPlace(0)
    if dev.platform == "tpu":
        return TPUPlace(dev.id)
    return CPUPlace(dev.id)


class Tensor:
    __slots__ = ("_data", "stop_gradient", "grad", "_grad_node",
                 "_output_index", "_hooks", "name", "persistable",
                 "trainable", "__weakref__")

    def __init__(self, data, stop_gradient: bool = True, name: Optional[str] = None):
        if isinstance(data, Tensor):
            data = data._data
        self._data = data
        self.stop_gradient = stop_gradient
        self.grad: Optional[Tensor] = None
        self._grad_node = None
        self._output_index = 0
        self._hooks = []
        self.name = name or _next_name()
        self.persistable = False
        self.trainable = not stop_gradient

    # ------------------------------------------------------------------
    # metadata
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return self._data.dtype

    @property
    def ndim(self):
        return self._data.ndim

    # paddle alias
    @property
    def dim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def place(self) -> Place:
        return _place_of(self._data)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def T(self):
        from .. import ops
        return ops.transpose(self, perm=list(range(self.ndim))[::-1])

    def numel(self) -> int:
        return int(self._data.size)

    def element_size(self) -> int:
        return self._data.dtype.itemsize

    # ------------------------------------------------------------------
    # host interop
    # ------------------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        return np.asarray(self._data).item(*args)

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __float__(self):
        return float(np.asarray(self._data))

    def __int__(self):
        return int(np.asarray(self._data))

    def __bool__(self):
        return bool(np.asarray(self._data))

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __repr__(self):
        grad_txt = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={canonical_dtype(self.dtype)}, "
                f"place={self.place}{grad_txt},\n       {np.asarray(self._data)!r})")

    # ------------------------------------------------------------------
    # autograd surface
    # ------------------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False):
        autograd.backward(self, grad_tensor, retain_graph)

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self.grad is not None:
            # works for Tensor and SelectedRows grads alike
            self.grad = Tensor(jnp.zeros(tuple(self.grad.shape),
                                         self.grad.dtype),
                               stop_gradient=True)
        else:
            self.grad = None

    clear_grad = clear_gradient

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(_self):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass
        return _Handle()

    def _accumulate_grad(self, g):
        from .selected_rows import SelectedRows
        if isinstance(g, SelectedRows):
            # row-sparse grad (reference SelectedRows accumulation)
            if self.grad is None:
                self.grad = g
            elif isinstance(self.grad, SelectedRows):
                self.grad = self.grad.merge(g)
            else:
                self.grad = Tensor(self.grad._data + g.to_dense(),
                                   stop_gradient=True)
            return
        g = jnp.asarray(g)
        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True)
        elif isinstance(self.grad, SelectedRows):
            self.grad = Tensor(self.grad.to_dense() + g, stop_gradient=True)
        else:
            self.grad = Tensor(self.grad._data + g, stop_gradient=True)

    def detach(self) -> "Tensor":
        t = Tensor(self._data, stop_gradient=True, name=self.name + "_detached")
        return t

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.assign(self)

    # ------------------------------------------------------------------
    # mutation (in-place rebind; eager only)
    # ------------------------------------------------------------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        self._data = jnp.asarray(value, dtype=self._data.dtype)
        return self

    def copy_(self, other, blocking: bool = True):
        return self.set_value(other)

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    def scale_(self, scale: float):
        self._data = self._data * scale
        return self

    # ------------------------------------------------------------------
    # dtype / device movement
    # ------------------------------------------------------------------
    def astype(self, dtype) -> "Tensor":
        from .. import ops
        return ops.cast(self, dtype=canonical_dtype(dtype))

    cast = astype

    def to(self, *args, **kwargs):
        dtype = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str):
                if a in ("cpu", "tpu") or ":" in a:
                    device = a
                else:
                    dtype = a
            elif isinstance(a, Place):
                device = a
        out = self
        if device is not None:
            if isinstance(device, str):
                kind, _, idx = device.partition(":")
                place = (TPUPlace if kind in ("tpu", "xla") else CPUPlace)(
                    int(idx) if idx else 0)
            else:
                place = device
            out = Tensor(jax.device_put(out._data, place.jax_device()),
                         stop_gradient=out.stop_gradient)
        if dtype is not None:
            out = out.astype(dtype)
        return out

    def cpu(self):
        return self.to("cpu")

    def pin_memory(self):
        return self.cpu()

    # ------------------------------------------------------------------
    # indexing (method bodies attached by ops package for the rest)
    # ------------------------------------------------------------------
    @staticmethod
    def _unwrap_index(idx):
        # Tensor indices (incl. bool masks and int arrays) unwrap to
        # their arrays; tuples recurse
        if isinstance(idx, Tensor):
            return idx._data
        if isinstance(idx, tuple):
            return tuple(Tensor._unwrap_index(i) for i in idx)
        return idx

    def __getitem__(self, idx):
        from .dispatch import dispatch
        idx = Tensor._unwrap_index(idx)

        def _index(x, *, idx=idx):
            return x[idx]
        return dispatch("getitem", _index, (self,), {})

    def __setitem__(self, idx, value):
        if isinstance(value, Tensor):
            value = value._data
        self._data = self._data.at[Tensor._unwrap_index(idx)].set(value)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # block until device work for this tensor is done (profiling/benchmark)
    def _sync(self):
        jax.block_until_ready(self._data)
        return self


class Parameter(Tensor):
    """Trainable tensor owned by an nn.Layer (reference:
    python/paddle/fluid/framework.py Parameter)."""

    __slots__ = ("optimize_attr", "regularizer", "need_clip",
                 "is_distributed", "placements")

    def __init__(self, data, trainable: bool = True, name: Optional[str] = None):
        super().__init__(data, stop_gradient=not trainable,
                         name=name or _next_name("param"))
        self.persistable = True
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False
        # TPU-native dist attr: jax PartitionSpec over named mesh axes
        # (reference auto_parallel interface.py:34 shard_tensor dist_attr).
        self.placements = None

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor parity."""
    if isinstance(data, Tensor):
        out = data
        if dtype is not None and canonical_dtype(dtype) != canonical_dtype(out.dtype):
            out = out.astype(dtype)
        if not stop_gradient:
            out = Tensor(out._data, stop_gradient=False)
        return out
    jdtype = dtype_to_jnp(dtype) if dtype is not None else None
    if jdtype is None and isinstance(data, (bool, int, float, list, tuple)):
        probe = np.asarray(data)
        if probe.dtype == np.float64:
            jdtype = jnp.float32  # paddle default float is fp32
        elif probe.dtype == np.int64:
            jdtype = dtype_to_jnp("int64")
    elif jdtype is None and isinstance(data, np.ndarray) and \
            data.dtype in (np.int64, np.float64):
        jdtype = dtype_to_jnp(str(data.dtype))
    arr = jnp.asarray(data, dtype=jdtype)
    if isinstance(place, Place):
        arr = jax.device_put(arr, place.jax_device())
    return Tensor(arr, stop_gradient=stop_gradient)
