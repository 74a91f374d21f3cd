"""paddle.jit: dygraph-to-static == trace-and-compile with XLA.

Reference parity: ``python/paddle/fluid/dygraph/jit.py:161`` @to_static
(declarative), ``:529`` save, ``:901`` load, TracedLayer.  Python control
flow over *concrete* values resolves during jax tracing; tensor-dependent
``if``/``while``/``for range``/bool ops are AST-converted by
``jit.dy2static`` into ``lax.cond``/``lax.while_loop`` (the reference's
``dygraph_to_static/`` suite re-targeted at XLA structured control flow).

Input-spec caching mirrors ``program_translator.py:144`` CacheKey: one
compiled executable per (shapes, dtypes, training-mode) signature.
"""
from __future__ import annotations

import functools
import os
import pickle
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd
from ..core.random import default_generator, rng_scope
from ..core.tensor import Tensor, to_tensor
from ..nn.layer_base import Layer

__all__ = ["to_static", "not_to_static", "save", "load", "TracedLayer",
           "InputSpec", "StaticFunction", "TranslatedLayer"]


class InputSpec:
    """Shape/dtype declaration (reference paddle.static.InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name

    def to_aval(self):
        from ..core.dtype import dtype_to_jnp
        shape = [1 if s in (None, -1) else int(s) for s in self.shape]
        return jax.ShapeDtypeStruct(tuple(shape), dtype_to_jnp(self.dtype))

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _tree_to_arrays(obj):
    """Tensors -> arrays, leave everything else (pytree-compatible)."""
    return jax.tree_util.tree_map(
        lambda x: x._data if isinstance(x, Tensor) else x, obj,
        is_leaf=lambda x: isinstance(x, Tensor))


def _tree_to_tensors(obj):
    return jax.tree_util.tree_map(
        lambda x: Tensor(x) if isinstance(x, jnp.ndarray) else x, obj)


class StaticFunction:
    """Compiled wrapper around a Layer's forward (or a bound method).

    The layer's (params, buffers) are threaded through jax.jit explicitly,
    so parameter updates never invalidate the compiled executable — only
    shape/dtype changes retrace.
    """

    def __init__(self, fn, layer: Optional[Layer] = None, input_spec=None):
        from .dy2static import convert_to_static
        self._fn = convert_to_static(fn)
        self._layer = layer
        self._input_spec = input_spec
        self._cache: Dict[Any, Any] = {}
        functools.update_wrapper(self, fn)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = StaticFunction(self._fn.__get__(instance, owner), instance,
                               self._input_spec)
        # cache the bound wrapper on the instance so the compile cache lives
        object.__setattr__(instance, self._fn.__name__ + "__static", bound)
        return bound

    def _resolve_layer(self, args):
        if self._layer is not None:
            return self._layer, args
        if args and isinstance(args[0], Layer):
            return args[0], args[1:]
        return None, args

    def _make_compiled(self, layer, n_args, training, static_kwargs):
        fn = self._fn

        def compiled(params, buffers, key, *arrays):
            tensors = [Tensor(a) for a in arrays]
            with rng_scope(key):
                with autograd.no_grad():
                    if layer is not None:
                        layer.load_functional_state(params, buffers)
                        out = fn(*tensors, **static_kwargs)
                        new_buffers = {n: b._data for n, b in
                                       layer.named_buffers()}
                    else:
                        out = fn(*tensors, **static_kwargs)
                        new_buffers = {}
            return _tree_to_arrays(out), new_buffers
        return jax.jit(compiled)

    def __call__(self, *args, **kwargs):
        layer, call_args = (self._layer, args)
        tensor_args = [to_tensor(a) if not isinstance(a, Tensor) else a
                       for a in call_args]
        arrays = [t._data for t in tensor_args]
        training = layer.training if layer is not None else False
        key = (tuple((a.shape, str(a.dtype)) for a in arrays), training,
               tuple(sorted(kwargs.items())))
        if key not in self._cache:
            self._cache[key] = self._make_compiled(layer, len(arrays),
                                                   training, kwargs)
        compiled = self._cache[key]
        if layer is not None:
            params, buffers = layer.functional_state()
        else:
            params, buffers = {}, {}
        rng_key = default_generator.next_key()
        out_arrays, new_buffers = compiled(params, buffers, rng_key, *arrays)
        if layer is not None:
            layer.load_functional_state(params, new_buffers)
        return _tree_to_tensors(out_arrays)

    @property
    def concrete_program(self):
        return self._cache


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator: compile a Layer / function with XLA (== @declarative)."""
    def wrap(fn):
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, layer, input_spec)
            layer.forward = sf
            layer._static_function = sf
            return layer
        return StaticFunction(fn, None, input_spec)
    if function is not None:
        return wrap(function)
    return wrap


declarative = to_static


def not_to_static(fn):
    fn._not_to_static = True
    return fn


# ---------------------------------------------------------------------------
# save / load: inference artifact via jax.export (StableHLO) — the
# save_inference_model equivalent (reference fluid/io.py:1246)
# ---------------------------------------------------------------------------
def avals_for_export(shapes_dtypes):
    """ShapeDtypeStructs for export, preserving dynamic dims (None/-1) as
    jax.export symbolic dimensions in one shared scope so the artifact
    accepts any batch size (reference: dynamic-batch save_inference_model).

    Single source of truth for dim concretization — also used by
    static/io.py; returns (symbolic_avals_or_None, concrete_avals)."""
    from jax import export as jax_export
    concrete = [jax.ShapeDtypeStruct(
        tuple(1 if s in (None, -1) else int(s) for s in shape), dt)
        for shape, dt in shapes_dtypes]
    if not any(s in (None, -1) for shape, _ in shapes_dtypes for s in shape):
        return None, concrete
    scope = jax_export.SymbolicScope()
    symbolic, k = [], 0
    for shape, dt in shapes_dtypes:
        if any(s in (None, -1) for s in shape):
            parts = []
            for s in shape:
                if s in (None, -1):
                    parts.append(f"dyn{k}")
                    k += 1
                else:
                    parts.append(str(int(s)))
            shp = jax_export.symbolic_shape(", ".join(parts), scope=scope)
        else:
            shp = tuple(int(s) for s in shape)
        symbolic.append(jax.ShapeDtypeStruct(tuple(shp), dt))
    return symbolic, concrete


def export_with_dynamic_dims(jitted, shapes_dtypes, *leading_args):
    """jax.export `jitted`, trying symbolic (dynamic-dim) avals first and
    falling back to concretized dims with a loud warning."""
    import warnings
    from jax import export as jax_export
    symbolic, concrete = avals_for_export(shapes_dtypes)
    if symbolic is not None:
        try:
            return jax_export.export(jitted)(*leading_args, *symbolic)
        except Exception as e:
            warnings.warn(
                "dynamic-dim (symbolic shape) export failed "
                f"({type(e).__name__}: {e}); falling back to concrete "
                "dims — the artifact will only accept the concretized "
                "shapes", UserWarning)
    return jax_export.export(jitted)(*leading_args, *concrete)


def save(layer, path, input_spec=None, **configs):
    """Serialize layer forward as StableHLO + params + pickle fallback."""
    if input_spec is None:
        raise ValueError("jit.save requires input_spec on the TPU path")
    shapes_dtypes = []
    from ..core.dtype import dtype_to_jnp
    for s in input_spec:
        if isinstance(s, InputSpec):
            shapes_dtypes.append((list(s.shape), dtype_to_jnp(s.dtype)))
        else:
            shapes_dtypes.append((list(s.shape), s._data.dtype))
    layer.eval()
    params, buffers = layer.functional_state()

    def infer(params, buffers, *arrays):
        tensors = [Tensor(a) for a in arrays]
        with autograd.no_grad():
            layer.load_functional_state(params, buffers)
            out = layer.forward(*tensors) if not isinstance(
                layer.forward, StaticFunction) else \
                layer._static_function._fn(*tensors)
        return _tree_to_arrays(out)

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    meta = {"kind": "layer",
            "params": {k: np.asarray(v) for k, v in params.items()},
            "buffers": {k: np.asarray(v) for k, v in buffers.items()},
            "feed_names": [getattr(s, "name", None) or f"input_{i}"
                           for i, s in enumerate(input_spec)],
            # record the *declared* dims (dynamic stays -1) so artifact
            # consumers see the true accepted shapes, not the fallback
            # concretization (which avals_for_export owns)
            "input_avals": [([-1 if d in (None, -1) else int(d)
                              for d in shape], str(np.dtype(dt)))
                            for shape, dt in shapes_dtypes]}
    p_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for k, v in params.items()}
    b_avals = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for k, v in buffers.items()}
    exported_bytes = None
    try:
        exp = export_with_dynamic_dims(jax.jit(infer), shapes_dtypes,
                                       p_avals, b_avals)
        exported_bytes = exp.serialize()
    except Exception as e:  # pragma: no cover - export unsupported path
        meta["export_error"] = str(e)
    finally:
        # tracing rebinds the live layer's tensors to tracers; restore
        layer.load_functional_state(params, buffers)

    # Reduced-precision program variants (reference parity: the
    # inference precision passes swap the *executed kernels* —
    # paddle_pass_builder.cc:132; the TPU translation re-traces the
    # layer so matmuls/convs run in the target dtype on the MXU).  The
    # Predictor picks the variant matching Config.set_precision; weights
    # then live on device in the reduced dtype (real HBM saving) and
    # every dot executes reduced.  Inputs keep the declared (f32)
    # signature and are cast at program entry.
    if exported_bytes is not None:
        meta["programs"] = {}
        for prec_name, tgt in (("Bfloat16", jnp.bfloat16),
                               ("Half", jnp.float16)):
            def infer_reduced(params, buffers, *arrays, _t=tgt):
                arrays = [a.astype(_t) if a.dtype == jnp.float32 else a
                          for a in arrays]
                return infer(params, buffers, *arrays)

            def red(avals, _t=tgt):
                return {k: jax.ShapeDtypeStruct(
                    a.shape, _t if a.dtype == jnp.float32 else a.dtype)
                    for k, a in avals.items()}
            try:
                exp_r = export_with_dynamic_dims(
                    jax.jit(infer_reduced), shapes_dtypes,
                    red(p_avals), red(b_avals))
                meta["programs"][prec_name] = exp_r.serialize()
            except Exception as e:  # pragma: no cover
                meta.setdefault("precision_export_errors",
                                {})[prec_name] = str(e)
            finally:
                layer.load_functional_state(params, buffers)
        # Int8: weight-only quantized execution — int8 rows + per-channel
        # scales are the *resident* form (4x HBM), dequantized to bf16
        # in-program right at each weight's use so the dots ride the MXU
        # in bf16 (mkldnn_quantizer.cc:1 is the reference's calibrated
        # analog; weight-only is the TPU-profitable scheme).
        # matmul/conv weights only (ndim >= 2): a 1-D bias "quantized"
        # with per-channel (== per-element) scales would be BIGGER than
        # its f32 original
        from ..quantization import default_int8_axis
        int8_keys = sorted(k for k, v in params.items()
                           if v.dtype == jnp.float32 and v.ndim >= 2
                           and v.size > 16)
        # per-key quantization axis: conv kernels (rank>=3) scale per
        # OUTPUT channel (axis 0), matmul weights per column — recorded
        # in the meta so every loader dequantizes on the right axis
        int8_axes = {k: default_int8_axis(params[k].ndim)
                     for k in int8_keys}

        def infer_int8(qparams, buffers, *arrays):
            dq = {}
            for k, v in qparams.items():
                if k in set(int8_keys):
                    q, scales = v
                    shape = [1] * q.ndim
                    shape[int8_axes[k]] = -1
                    dq[k] = q.astype(jnp.bfloat16) * \
                        scales.astype(jnp.bfloat16).reshape(shape)
                else:
                    # below-threshold f32 params (biases, norms) cast to
                    # the compute dtype too, or they'd re-promote every
                    # downstream op back to f32
                    dq[k] = v.astype(jnp.bfloat16) \
                        if v.dtype == jnp.float32 else v
            buffers = {k: v.astype(jnp.bfloat16)
                       if v.dtype == jnp.float32 else v
                       for k, v in buffers.items()}
            arrays = [a.astype(jnp.bfloat16)
                      if a.dtype == jnp.float32 else a for a in arrays]
            return infer(dq, buffers, *arrays)

        q_avals = {}
        for k, a in p_avals.items():
            if k in int8_keys:
                q_avals[k] = (jax.ShapeDtypeStruct(a.shape, jnp.int8),
                              jax.ShapeDtypeStruct(
                                  (a.shape[int8_axes[k]],), jnp.float32))
            else:
                q_avals[k] = a
        try:
            exp_q = export_with_dynamic_dims(
                jax.jit(infer_int8), shapes_dtypes, q_avals, b_avals)
            meta["programs"]["Int8"] = exp_q.serialize()
            meta["int8_keys"] = int8_keys
            meta["int8_axes"] = int8_axes
        except Exception as e:  # pragma: no cover
            meta.setdefault("precision_export_errors", {})["Int8"] = str(e)
        finally:
            layer.load_functional_state(params, buffers)

    with open(path + ".pdmodel", "wb") as f:
        f.write(exported_bytes or b"")
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump(meta, f, protocol=4)


class TranslatedLayer(Layer):
    """Inference layer reloaded from a jit.save artifact (reference
    fluid/dygraph/io.py TranslatedLayer)."""

    def __init__(self, exported, meta):
        super().__init__()
        self._exported = exported
        self._params = {k: jnp.asarray(v) for k, v in meta["params"].items()}
        self._buffers_arrs = {k: jnp.asarray(v) for k, v in
                              meta["buffers"].items()}

    def forward(self, *inputs):
        arrays = [to_tensor(i)._data for i in inputs]
        out = self._exported.call(self._params, self._buffers_arrs, *arrays)
        return _tree_to_tensors(out)


def load(path, **configs):
    with open(path + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    with open(path + ".pdmodel", "rb") as f:
        blob = f.read()
    if not blob:
        raise RuntimeError(
            f"artifact at {path} has no serialized StableHLO "
            f"(export error: {meta.get('export_error')})")
    from jax import export as jax_export
    exported = jax_export.deserialize(blob)
    return TranslatedLayer(exported, meta)


class TracedLayer:
    """Minimal TracedLayer parity (reference jit.py:1162): wraps a layer
    with a jitted forward traced from example inputs."""

    def __init__(self, layer, inputs):
        self._sf = StaticFunction(layer.forward, layer)
        self._layer = layer
        self._last_inputs = [to_tensor(i) for i in inputs]
        self._sf(*inputs)

    @staticmethod
    def trace(layer, inputs):
        tl = TracedLayer(layer, inputs)
        return tl._sf(*inputs), tl

    def __call__(self, *inputs):
        return self._sf(*inputs)

    def save_inference_model(self, path, feed=None, fetch=None):
        specs = [InputSpec(t.shape, str(t.dtype)) for t in self._last_inputs]
        save(self._layer, path, input_spec=specs)


# dy2static surface re-exports (reference paddle.jit namespace)
from . import dy2static  # noqa: E402,F401
from .dy2static import ProgramTranslator  # noqa: E402,F401


def set_code_level(level=100):
    """reference jit.set_code_level: print the converted source of
    subsequently-converted functions when level > 0."""
    from .dy2static import program_translator as _pt
    _pt.CODE_LEVEL = level


_verbosity = 0


def set_verbosity(level=0, also_to_stdout=False):
    """reference jit.set_verbosity: transform-log verbosity only (does
    not toggle converted-source printing — that is set_code_level)."""
    global _verbosity
    _verbosity = level
