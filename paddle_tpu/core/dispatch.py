"""Op dispatch + kernel registry.

Reference parity: ``paddle/pten/core/kernel_factory.h:108,225,255`` (kernel
registry keyed by backend/layout/dtype) and ``imperative/prepared_operator.cc``
(kernel selection + launch).  On TPU, "kernels" are jax-traceable callables;
the registry keys (op, backend) where backend is 'xla' (default lowering) or
'pallas' (hand-written TPU kernel).  Dispatch records autograd via jax.vjp —
see core/autograd.py.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import autograd
from ..utils import flags as _flags_mod
from ..profiler import tracer as _tracer

__all__ = ["register_kernel", "get_kernel", "dispatch", "KernelKey"]


def _debug_check_outputs(op_name, outs):
    import numpy as _np
    if _flags_mod.get_flag("FLAGS_check_nan_inf"):
        for i, o in enumerate(outs):
            if hasattr(o, "dtype") and jax.numpy.issubdtype(
                    o.dtype, jax.numpy.floating) and not isinstance(
                    o, jax.core.Tracer):
                a = _np.asarray(o)
                if not _np.isfinite(a).all():
                    raise FloatingPointError(
                        f"op '{op_name}' output {i} contains "
                        f"{'NaN' if _np.isnan(a).any() else 'Inf'} "
                        f"(FLAGS_check_nan_inf enabled)")
    elif _flags_mod.get_flag("FLAGS_benchmark"):
        for o in outs:
            if hasattr(o, "block_until_ready") and not isinstance(
                    o, jax.core.Tracer):
                o.block_until_ready()


class KernelKey(Tuple):
    """(op_name, backend)."""


_REGISTRY: Dict[Tuple[str, str], Callable] = {}
_preferred_backend = threading.local()
_pallas = None


def register_kernel(op_name: str, backend: str = "xla"):
    """Decorator: register an implementation for (op_name, backend)."""
    def deco(fn):
        _REGISTRY[(op_name, backend)] = fn
        return fn
    return deco


def get_kernel(op_name: str, backend: Optional[str] = None) -> Callable:
    backend = backend or preferred_backend()
    fn = _REGISTRY.get((op_name, backend))
    if fn is None:
        fn = _REGISTRY.get((op_name, "xla"))
    if fn is None:
        raise KeyError(f"no kernel registered for op '{op_name}'")
    return fn


def preferred_backend() -> str:
    """'pallas' on a TPU unless disabled via FLAGS_use_pallas=0.

    The flag is re-read every call so
    ``set_flags({'FLAGS_use_pallas': 0/1})`` flips the dispatch path at
    runtime (the reference flips kernels per-op the same way via
    FLAGS_run_pten_kernel).  PADDLE_PALLAS_FORCE=1 takes 'pallas' on any
    platform (kernels run in interpret mode off-TPU) — the test hook.
    Which implementation each op then traced is counted by
    ``ops.pallas.note``.
    """
    val = getattr(_preferred_backend, "value", None)
    if val is not None:
        return val
    from ..utils import flags
    if not flags.get_flag("FLAGS_use_pallas"):
        return "xla"
    global _pallas
    if _pallas is None:     # ops imports this module: bind on first use
        from ..ops import pallas as _pallas
    return "pallas" if _pallas.enabled() else "xla"


def _tensors_of(args):
    from .tensor import Tensor
    return [a for a in args if isinstance(a, Tensor)]




# ---------------------------------------------------------------------------
# eager jit/vjp cache (SURVEY §7 hard part (a): dygraph speed without
# per-op C++ dispatch).  jax.vjp re-traces its function on every call —
# ~1.8ms per tracked op eagerly.  For impls whose closure captures only
# hashable primitives, the traced forward and backward are cached as
# jitted functions keyed by (code, captured values, avals, attrs):
# the backward re-derives grads from primals inside jit (XLA dead-code
# eliminates the unused primal recompute for linear ops — remat posture
# for the rest), so a cache hit costs two jitted dispatches (~40x less).
# Ops capturing arrays/PRNG keys (dropout) are uncacheable and keep the
# exact per-call path.  FLAGS_eager_jit_cache=0 disables.
# ---------------------------------------------------------------------------
_EAGER_CACHE: Dict[tuple, tuple] = {}
_SCALARS = (int, float, bool, str, bytes, type(None), type(Ellipsis))


class _HashableMeta(type):
    """isinstance(v, _HASHABLE) — scalars, plus slices whose components
    are themselves scalars.  A slice built from device arrays
    (t[i0:i0+k]) must NOT be cache-keyed: jax arrays are unhashable and
    would make the whole cache key blow up with TypeError at lookup."""
    def __instancecheck__(cls, v):
        if isinstance(v, _SCALARS):
            return True
        if isinstance(v, slice):
            return all(isinstance(c, _SCALARS)
                       for c in (v.start, v.stop, v.step))
        return False


class _HASHABLE(metaclass=_HashableMeta):
    pass


def _closure_key(fn):
    """Hashable identity for fn incl. captured values, or None."""
    if isinstance(fn, functools.partial):
        inner = _closure_key(fn.func)
        if inner is None:
            return None
        parts = [inner]
        for a in fn.args:
            if not isinstance(a, _HASHABLE):
                return None
            parts.append(_freeze(a))
        for k, v in sorted(fn.keywords.items()):
            if not _attr_hashable(v):
                return None
            parts.append((k, _freeze(v)))
        return ("partial",) + tuple(parts)
    code = getattr(fn, "__code__", None)
    if code is None:
        # jnp/numpy ufuncs and library callables are stateless: behavior
        # IS their identity (the cache entry pins a strong ref so the id
        # stays valid).  Arbitrary callable objects may carry mutable
        # state -> never identity-keyed.
        mod = getattr(fn, "__module__", "") or ""
        if callable(fn) and mod.split(".")[0] in ("jax", "numpy", "jnp"):
            return ("obj", id(fn))
        return None
    parts = [id(code)]
    # default args carry per-call payloads too (e.g. getitem's idx=idx)
    for v in (fn.__defaults__ or ()):
        if not _attr_hashable(v):
            return None
        parts.append(("d", _freeze(v)))
    for k, v in sorted((fn.__kwdefaults__ or {}).items()):
        if not _attr_hashable(v):
            return None
        parts.append((k, _freeze(v)))
    for cell in fn.__closure__ or ():
        try:
            v = cell.cell_contents
        except ValueError:
            return None
        if isinstance(v, _HASHABLE):
            parts.append(_freeze(v))
        elif isinstance(v, type) or isinstance(v, jnp.dtype):
            parts.append(repr(v))          # jnp.float32 / np.dtype refs
        elif isinstance(v, (tuple, list)) and all(
                isinstance(x, _HASHABLE) for x in v):
            parts.append(tuple(v))
        else:
            inner = _closure_key(v) if callable(v) else None
            if inner is None:
                return None
            parts.append(inner)
    return tuple(parts)


def _attr_hashable(v):
    if isinstance(v, _HASHABLE):
        return True
    if isinstance(v, (tuple, list)):
        return all(_attr_hashable(x) for x in v)
    return False


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, slice):  # version-portable (slices hash only >=3.12)
        return ("slice", v.start, v.stop, v.step)
    return v


def _cached_pair(op_name, fn, kwargs, arrays):
    """(fwd_jit, bwd_jit) for a cacheable dispatch, else None."""
    if not _flags_mod.get_flag("FLAGS_eager_jit_cache"):
        return None
    trace = _tracer.active
    fkey = _closure_key(fn)
    if fkey is None:
        if trace:
            _tracer.on_cache_event("uncacheable")
        return None
    if kwargs and not all(_attr_hashable(v) for v in kwargs.values()):
        if trace:
            _tracer.on_cache_event("uncacheable")
        return None
    avals = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
    akey = tuple(sorted((k, _freeze(v)) for k, v in kwargs.items()))
    key = (op_name, fkey, akey, avals)
    try:
        entry = _EAGER_CACHE.get(key)
    except TypeError:        # unhashable payload slipped past the checks
        if trace:
            _tracer.on_cache_event("uncacheable")
        return None          # -> uncached per-call path, not a crash

    if trace:
        _tracer.on_cache_event("hit" if entry is not None else "miss")
    if entry is None:
        closed = functools.partial(fn, **kwargs) if kwargs else fn
        fwd = jax.jit(closed)

        def bwd(primals, cot):
            _, vjp_fn = jax.vjp(closed, *primals)
            gs = vjp_fn(cot)
            # float0 (int-input) grads aren't valid jit outputs -> None
            return tuple(
                None if (hasattr(g, "dtype")
                         and g.dtype == jax.dtypes.float0) else g
                for g in gs)
        # fn pinned in the entry: keeps id()-based keys valid
        entry = (fwd, jax.jit(bwd), fn)
        _EAGER_CACHE[key] = entry
    return entry


def dispatch(op_name: str, fn: Callable, tensor_args: Sequence, kwargs: dict):
    """Run ``fn(*arrays, **kwargs)`` eagerly, recording a GradNode when any
    input requires grad.  ``tensor_args`` are Tensors (positionally matching
    fn's array params); kwargs are static non-tensor attrs."""
    from .tensor import Tensor

    # static-graph capture: under paddle.enable_static() ops append to the
    # active Program instead of executing (reference: OpProtoHolder append
    # path, framework.py:2147; see static/program.py capture_op)
    from ..static import mode as _static_mode
    if not _static_mode.in_dynamic_mode():
        from ..static import program as _static_program
        prog = _static_program.capturing_program()
        if prog is not None:
            return _static_program.capture_op(prog, op_name, fn,
                                              tensor_args, kwargs)

    # host-span + metrics instrumentation (profiler v2): one predicate
    # read when tracing is off, span + counters when on
    _t0 = time.perf_counter_ns() if _tracer.active else 0

    # kernel-registry consultation (reference operator.cc:1296 ChooseKernel
    # / pten kernel_factory.h:255): when the caller passed the registered
    # 'xla' kernel and a better backend (pallas) has a registration for
    # this op, dispatch swaps it in.  FLAGS_use_pallas=0 forces 'xla'.
    backend = preferred_backend()
    if backend != "xla" and _REGISTRY.get((op_name, "xla")) is fn:
        fn = _REGISTRY.get((op_name, backend), fn)

    arrays = [t._data for t in tensor_args]
    # AMP autocast rewrite (reference imperative/tracer.cc:179-185)
    from ..amp import amp_cast_inputs, _amp_state
    if _amp_state() is not None:
        arrays = amp_cast_inputs(op_name, arrays)
    needs_grad = autograd.is_grad_enabled() and any(
        not t.stop_gradient for t in tensor_args)

    if kwargs:
        closed = functools.partial(fn, **kwargs)
    else:
        closed = fn

    pair = None
    if not any(isinstance(a, jax.core.Tracer) for a in arrays):
        pair = _cached_pair(op_name, fn, kwargs, arrays)

    try:
        if needs_grad:
            if pair is not None:
                fwd_jit, bwd_jit = pair[0], pair[1]
                out = fwd_jit(*arrays)
                outs_t = out if isinstance(out, tuple) else (out,)
                if all(jax.numpy.issubdtype(o.dtype, jax.numpy.inexact)
                       for o in outs_t):
                    vjp_fn = functools.partial(bwd_jit, tuple(arrays))
                else:
                    # int outputs take float0 cotangents, which cannot
                    # cross a jit boundary — rare; pay the retrace
                    out, vjp_fn = jax.vjp(closed, *arrays)
            elif _t0:
                _tt = time.perf_counter_ns()
                out, vjp_fn = jax.vjp(closed, *arrays)
                _tracer.on_trace_time(time.perf_counter_ns() - _tt)
            else:
                out, vjp_fn = jax.vjp(closed, *arrays)
            node = autograd.record(op_name, closed, tensor_args, arrays,
                                   (out, vjp_fn))
        else:
            out = pair[0](*arrays) if pair is not None \
                else closed(*arrays)
            node = None
    except Exception as e:  # enforce-style op context (enforce.h:422)
        from ..profiler import memscope as _memscope
        if _memscope.active and _memscope.is_oom(e):
            _memscope.oom_dump(e, context=f"dispatch:{op_name}")
        from .errors import tag_op_error
        tag_op_error(op_name, e)

    tuple_output = isinstance(out, tuple)
    outs = out if tuple_output else (out,)

    # FLAGS_check_nan_inf: per-op numeric guard (reference
    # framework/details/nan_inf_utils_detail.cc:559 CheckOpHasNanOrInf);
    # FLAGS_benchmark: per-op device sync (reference operator.cc:1210).
    # `debug_ops_active` is a cached module attribute so the common
    # all-off case costs one attribute read on the hot path.
    if _flags_mod.debug_ops_active:
        _debug_check_outputs(op_name, outs)
    wrapped = []
    for i, o in enumerate(outs):
        t = Tensor(o, stop_gradient=(node is None))
        if node is not None:
            t._grad_node = node
            t._output_index = i
        wrapped.append(t)
    if _t0:
        _tracer.on_dispatch(op_name, _t0)
    return tuple(wrapped) if tuple_output else wrapped[0]


def defop(op_name: str, n_tensor_args: Optional[int] = None):
    """Build a user-facing op from an array-level implementation.

    The produced wrapper accepts Tensors (or array-likes) for its first
    ``n_tensor_args`` positional parameters and static attrs as kwargs.
    """
    def deco(fn):
        register_kernel(op_name, "xla")(fn)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            from .tensor import Tensor, to_tensor
            kwargs.pop("name", None)
            n = n_tensor_args if n_tensor_args is not None else len(args)
            tensors = []
            for a in args[:n]:
                tensors.append(a if isinstance(a, Tensor) else to_tensor(a))
            static = kwargs
            extra = args[n:]
            if extra:
                raise TypeError(
                    f"{op_name}: positional static attrs not supported; "
                    "pass them as keywords")
            impl = get_kernel(op_name)
            return dispatch(op_name, impl, tensors, static)
        return wrapper
    return deco
