"""paddle.Model — the high-level train/eval/predict API.

Reference parity: ``python/paddle/hapi/model.py:906`` (Model, fit:1556,
train_batch:1044, Dynamic/StaticGraphAdapter).  TPU-first: instead of two
adapters, Model has two execution engines:

- **eager**: per-op dispatch with tape autograd (debuggable), and
- **compiled** (default): ONE jitted XLA train-step threading
  (params, buffers, opt-state, rng) functionally — this is where MXU
  utilization comes from.  The compiled step is built once per input
  signature, mirrorring StaticGraphAdapter's lazily-built Program.
"""
from __future__ import annotations

import os
import pickle
import time
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd
from ..core.random import default_generator, rng_scope
from ..core.tensor import Tensor, to_tensor
from ..metric import Metric
from ..profiler import memscope as _memscope
from ..profiler import metrics as _metrics
from ..profiler import tracer as _obs
from ..utils import chaos as _chaos
from .callbacks import config_callbacks

__all__ = ["Model"]


import numbers


class _LazyScalar(numbers.Real):
    """Float-like view of a device scalar that materialises on first use.

    The reference's DygraphAdapter.train_batch calls ``loss.numpy()``
    eagerly — a ~µs sync on a locally attached GPU.  On TPU (and
    especially through a remote runtime) an eager per-step fetch stalls
    the whole async dispatch pipeline: profiled r4, ResNet50
    ``Model.train_batch`` spent ~100 ms/step blocked on the loss fetch
    against ~112 ms of device compute.  Keeping the scalar lazy lets
    consecutive steps pipeline; printing/comparing/formatting the loss
    coerces it via ``__float__`` exactly like a float.  (For JSON
    serialization, coerce explicitly: ``float(logs["loss"])``.)

    **Deferred-error contract**: a device fault in the step (or an
    XLA runtime error) surfaces at the first coercion of this scalar —
    potentially lines away from the ``train_batch`` call that queued the
    step.  Every coercion failure is re-raised annotated with the step
    index that produced the value, so the failing batch is always
    attributable.  For eager per-step surfacing (and NaN/Inf loss
    detection at the producing step), enable ``FLAGS_check_nan_inf`` —
    ``train_batch`` then materialises the loss before returning, at the
    documented pipeline cost.
    """

    __slots__ = ("_arr", "_val", "_origin")

    def __init__(self, arr, origin: str = None):
        self._arr = arr
        self._val = None
        self._origin = origin

    def __float__(self):
        if self._val is None:
            # each materialization is one host<->device round trip that
            # drains the async pipeline; counted so CI can assert the
            # steady-state loop blocks at most once per log_freq window
            _metrics.counter(
                "train.loss_fetch",
                "lazy-loss device scalars materialized on the host "
                "(each one is a pipeline sync point)").inc()
            try:
                self._val = float(self._arr)
            except Exception as e:
                raise RuntimeError(
                    f"device computation for {self._origin or 'this value'}"
                    f" failed; the error belongs to that step, not the "
                    f"line coercing the value (lazy-loss contract — see "
                    f"Model.train_batch)") from e
            self._arr = None
        return self._val

    def __repr__(self):
        return repr(float(self))

    def __format__(self, spec):
        return format(float(self), spec)

    def __bool__(self):
        return bool(float(self))

    def __int__(self):
        return int(float(self))

    def __index__(self):
        return int(float(self))

    def __array__(self, dtype=None, copy=None):
        return np.asarray(float(self), dtype=dtype or np.float64)

    def __hash__(self):
        return hash(float(self))

    # numbers.Real protocol — everything coerces through float()
    def __abs__(self): return abs(float(self))
    def __neg__(self): return -float(self)
    def __pos__(self): return float(self)
    def __trunc__(self): return float(self).__trunc__()
    def __floor__(self): return float(self).__floor__()
    def __ceil__(self): return float(self).__ceil__()
    def __round__(self, n=None): return round(float(self), n)
    def __add__(self, o): return float(self) + o
    def __radd__(self, o): return o + float(self)
    def __sub__(self, o): return float(self) - o
    def __rsub__(self, o): return o - float(self)
    def __mul__(self, o): return float(self) * o
    def __rmul__(self, o): return o * float(self)
    def __truediv__(self, o): return float(self) / o
    def __rtruediv__(self, o): return o / float(self)
    def __floordiv__(self, o): return float(self) // o
    def __rfloordiv__(self, o): return o // float(self)
    def __mod__(self, o): return float(self) % o
    def __rmod__(self, o): return o % float(self)
    def __pow__(self, o): return float(self) ** o
    def __rpow__(self, o): return o ** float(self)
    def __eq__(self, o): return float(self) == self._c(o)
    def __lt__(self, o): return float(self) < self._c(o)
    def __le__(self, o): return float(self) <= self._c(o)
    def __gt__(self, o): return float(self) > self._c(o)
    def __ge__(self, o): return float(self) >= self._c(o)

    @staticmethod
    def _c(o):
        return float(o) if isinstance(o, _LazyScalar) else o


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _batch_len(ins) -> int:
    """Samples in one batch (leading dim of the first input), 0 if moot."""
    try:
        return int(ins[0].shape[0])
    except Exception:
        return 0


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._use_jit = True
        self._jit_cache = {}
        self.stop_training = False
        self._save_dir = None

    # ------------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit=True, offload=False):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle Metric")
        self._use_jit = jit
        self._amp_level = None
        self._amp_dtype = "bfloat16"
        self._amp_custom_white = None
        self._amp_custom_black = None
        self._amp_scaler_cfg = None
        self._amp_scaler_state = None
        if amp_configs:
            cfg = {"level": amp_configs} if isinstance(amp_configs, str) \
                else dict(amp_configs)
            self._amp_level = cfg.get("level", "O1")
            if self._amp_level not in ("O1", "O2"):
                raise ValueError(
                    f"amp_configs level must be 'O1' or 'O2', got "
                    f"{self._amp_level!r}")
            self._amp_dtype = cfg.get("dtype", "bfloat16")
            self._amp_custom_white = cfg.get("custom_white_list")
            self._amp_custom_black = cfg.get("custom_black_list")
            if self._amp_dtype in ("float16", "fp16"):
                # fp16's 5-bit exponent needs dynamic loss scaling; the
                # whole state machine rides INSIDE the jitted step (no
                # per-step host sync on found_inf).  bf16 shares fp32's
                # exponent range, so it never engages the scaler.
                self._amp_scaler_cfg = {
                    "init_loss_scaling": float(
                        cfg.get("init_loss_scaling", 2.0 ** 15)),
                    "incr_ratio": float(cfg.get("incr_ratio", 2.0)),
                    "decr_ratio": float(cfg.get("decr_ratio", 0.5)),
                    "incr_every_n_steps": int(
                        cfg.get("incr_every_n_steps", 1000)),
                    "decr_every_n_nan_or_inf": int(
                        cfg.get("decr_every_n_nan_or_inf", 2)),
                    "use_dynamic_loss_scaling": bool(
                        cfg.get("use_dynamic_loss_scaling", True)),
                }
        # opt-in optimizer-state offload to pinned host memory (the
        # single-device sibling of the ZeRO offload knob in
        # distributed/fleet/sharded_trainer.py) — trades one opt-state
        # round-trip of PCIe/host bandwidth per step for its HBM
        self._offload = bool(offload)
        return self

    # ------------------------------------------------------------------
    # compiled train step
    # ------------------------------------------------------------------
    def _build_jit_train_step(self, n_inputs, n_labels, remat=False):
        net, opt, loss_fn = self.network, self._optimizer, self._loss
        amp_level = self._amp_level
        amp_dtype = getattr(self, "_amp_dtype", "bfloat16")
        amp_white = getattr(self, "_amp_custom_white", None)
        amp_black = getattr(self, "_amp_custom_black", None)
        scaler_cfg = getattr(self, "_amp_scaler_cfg", None)
        low = None
        if amp_level:
            from ..core.dtype import dtype_to_jnp
            low = dtype_to_jnp(amp_dtype)

        def step(params, buffers, opt_state, scaler_state, key_base,
                 rng_ctr, lr, *data):
            # rng key derived IN-JIT from a device-resident counter
            # (same (seed, counter) stream as Generator.next_key): a
            # host-built key per step is a tiny host->device transfer
            # ahead of every execute
            rng_ctr = rng_ctr + jnp.uint32(1)
            key = jnp.stack([key_base[0], key_base[1] ^ rng_ctr])
            inputs = [Tensor(a) for a in data[:n_inputs]]
            labels = [Tensor(a) for a in data[n_inputs:]]

            def loss_of(params):
                with rng_scope(key), autograd.no_grad():
                    if low is not None and amp_level == "O2":
                        # O2 master-weight contract: the fp32 params in
                        # `params` ARE the masters (the grad/update
                        # domain); the network sees a low-dtype view.
                        # The cast is inside the differentiated function,
                        # so grads land back on the fp32 leaves.
                        net_params = {
                            n: (p.astype(low) if p.dtype == jnp.float32
                                else p) for n, p in params.items()}
                    else:
                        net_params = params
                    net.load_functional_state(net_params, buffers)
                    if amp_level:
                        from ..amp import auto_cast
                        with auto_cast(level=amp_level, dtype=amp_dtype,
                                       custom_white_list=amp_white,
                                       custom_black_list=amp_black):
                            outs = net.forward(*inputs)
                    else:
                        outs = net.forward(*inputs)
                    outs_l = _to_list(outs)
                    loss = loss_fn(*(outs_l + labels))
                    new_buffers = {n: b._data for n, b in net.named_buffers()}
                loss_arr = loss._data if isinstance(loss, Tensor) else loss
                loss_arr = loss_arr.astype(jnp.float32)
                if scaler_state is not None:
                    loss_arr = loss_arr * scaler_state["scale"]
                return loss_arr, \
                    ([o._data for o in outs_l], new_buffers)

            if remat:
                # budget-driven rematerialization (FLAGS_remat_budget_mb
                # below the planner's peak estimate): keep matmul
                # outputs, recompute the cheap elementwise tail in the
                # backward — the same save-dots selection RematPass
                # makes over captured Programs
                loss_of = jax.checkpoint(
                    loss_of, policy=jax.checkpoint_policies.dots_saveable)

            (loss, (outs, new_buffers)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)

            new_scaler = None
            if scaler_state is not None:
                # fp16 path: unscale, detect non-finite grads, and make
                # the whole update a per-leaf no-op on overflow — the
                # check_finite_and_unscale / update_loss_scaling state
                # machine fused into the step (zero host syncs)
                inv = jnp.float32(1.0) / scaler_state["scale"]
                loss = loss * inv
                found_inf = jnp.zeros((), jnp.bool_)
                for g in jax.tree_util.tree_leaves(grads):
                    found_inf = jnp.logical_or(
                        found_inf,
                        jnp.logical_not(jnp.all(jnp.isfinite(g))))
                grads = jax.tree_util.tree_map(
                    lambda g: (g * inv.astype(g.dtype)), grads)
                new_params, new_opt_state = opt.functional_apply(
                    params, grads, opt_state, lr)
                skip = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(found_inf, old, new),
                    (new_params, new_opt_state), (params, opt_state))
                new_params, new_opt_state = skip
                if scaler_cfg["use_dynamic_loss_scaling"]:
                    from ..ops.amp_ops import update_loss_scaling
                    ns, ng, nb = update_loss_scaling(
                        Tensor(found_inf), Tensor(scaler_state["scale"]),
                        Tensor(scaler_state["good"]),
                        Tensor(scaler_state["bad"]),
                        scaler_cfg["incr_every_n_steps"],
                        scaler_cfg["decr_every_n_nan_or_inf"],
                        scaler_cfg["incr_ratio"], scaler_cfg["decr_ratio"])
                    new_scaler = {"scale": ns._data, "good": ng._data,
                                  "bad": nb._data, "found_inf": found_inf}
                else:
                    new_scaler = {"scale": scaler_state["scale"],
                                  "good": scaler_state["good"],
                                  "bad": scaler_state["bad"],
                                  "found_inf": found_inf}
            else:
                new_params, new_opt_state = opt.functional_apply(
                    params, grads, opt_state, lr)
            return loss, outs, new_buffers, new_params, new_opt_state, \
                new_scaler, rng_ctr

        return jax.jit(step, donate_argnums=(0, 2))

    def _remat_decision(self, batch_size: int = 1) -> bool:
        """True when ``FLAGS_program_remat`` + ``FLAGS_remat_budget_mb``
        are set and the static memory planner's train-peak estimate for
        this model exceeds the budget — the same flag pair that rewrites
        captured Programs (static/program.py pass pipeline), here
        deciding whether the jitted hapi step wraps its loss in
        :func:`jax.checkpoint`.  An un-plannable model (no input/label
        specs, capture failure) under an explicit budget remats
        conservatively.  The verdict is cached per budget value."""
        from ..utils import flags as _flags
        if not _flags.get_flag("FLAGS_program_remat"):
            return False
        budget_mb = int(_flags.get_flag("FLAGS_remat_budget_mb") or 0)
        if budget_mb <= 0:
            return False
        cached = getattr(self, "_remat_cache", None)
        if cached is not None and cached[0] == (budget_mb, batch_size):
            return cached[1]
        peak = None
        try:
            if self._inputs and self._labels:
                peak = int(self.static_memory_plan(
                    "train", batch_size=max(1, batch_size)).peak_bytes)
        except Exception:   # noqa: BLE001 — unplannable nets still remat
            peak = None
        on = peak is None or peak > budget_mb * (1 << 20)
        if on:
            warnings.warn(
                f"fit: rematerialization engaged — planner peak "
                f"{'unknown' if peak is None else f'{peak}B'} vs budget "
                f"{budget_mb}MB (FLAGS_remat_budget_mb); the train step "
                f"recomputes non-matmul activations in the backward")
        self._remat_active = on
        self._remat_planned_peak = peak
        self._remat_cache = ((budget_mb, batch_size), on)
        return on

    def _offload_shardings(self):
        """(host, device) shardings for the ``prepare(offload=True)``
        opt-state knob, or None when it cannot apply: data-parallel
        wrappers keep their ZeRO offload (sharded_trainer), and a host
        backend without a ``pinned_host`` memory space warns once and
        trains un-offloaded.  A TPU without the space raises
        (``core.place.pinned_host_kind``)."""
        cached = getattr(self, "_offload_sh_cache", "unset")
        if cached != "unset":
            return cached
        result = None
        if not hasattr(self.network, "shard_inputs"):
            from ..core.place import pinned_host_kind
            dev = jax.devices()[0]
            kind = pinned_host_kind(dev)
            if kind:
                from jax.sharding import SingleDeviceSharding
                result = (SingleDeviceSharding(dev, memory_kind=kind),
                          SingleDeviceSharding(
                              dev, memory_kind="device"))
            else:
                warnings.warn(
                    "prepare(offload=True): this host backend exposes "
                    "no pinned_host memory space — optimizer-state "
                    "offload is a no-op here (training proceeds "
                    "un-offloaded)")
        else:
            warnings.warn(
                "prepare(offload=True): data-parallel models offload "
                "through the fleet ZeRO path (sharded_trainer offload=) "
                "— the hapi knob is a no-op under shard_inputs")
        self._offload_sh_cache = result
        return result

    def _device_rng_state(self):
        """(key_base, rng_ctr) device scalars for the jitted step,
        cached so the steady-state training loop does ZERO per-step
        host->device transfers.  Mirrors Generator.next_key's
        (splitmix64(seed), counter) stream exactly; resyncs whenever
        the host generator moved independently (reseed, eager draws,
        set_rng_state) and falls back to None in split-chain mode."""
        from ..core.random import counter_stream_key_words, _state
        gen = default_generator
        if gen._key is not None or getattr(_state, "scope", None) \
                is not None:
            # explicit-key mode, or an active rng_scope (which must
            # keep routing every draw): legacy per-step key path
            return None, None
        hi, lo = counter_stream_key_words(gen._seed)
        cache = getattr(self, "_rng_dev_cache", None)
        if cache is not None and cache[0] == (gen._seed, gen._counter):
            base, ctr = cache[1], cache[2]
        else:                          # first step / host moved: resync
            base = jnp.asarray(np.array([hi, lo], np.uint32))
            ctr = jnp.asarray(np.uint32(gen._counter))
        return base, ctr

    def _lr_device(self):
        lr_val = float(self._optimizer.get_lr())
        cache = getattr(self, "_lr_dev_cache", None)
        if cache is not None and cache[0] == lr_val:
            return cache[1]
        arr = jnp.asarray(lr_val, jnp.float32)
        self._lr_dev_cache = (lr_val, arr)
        return arr

    def _build_jit_eval_step(self, n_inputs, n_labels, with_loss):
        net, loss_fn = self.network, self._loss

        def step(params, buffers, *data):
            inputs = [Tensor(a) for a in data[:n_inputs]]
            labels = [Tensor(a) for a in data[n_inputs:]]
            with autograd.no_grad():
                net.load_functional_state(params, buffers)
                outs = _to_list(net.forward(*inputs))
                loss = None
                if with_loss and loss_fn is not None and labels:
                    l = loss_fn(*(outs + labels))
                    loss = (l._data if isinstance(l, Tensor) else l)
            return [o._data for o in outs], loss
        return jax.jit(step)

    # ------------------------------------------------------------------
    # batch-level API
    # ------------------------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        inputs = _to_list(inputs)
        labels = _to_list(labels)
        self.network.train()
        if self._use_jit and update:
            return self._train_batch_jit(inputs, labels)
        return self._train_batch_eager(inputs, labels, update)

    def _train_batch_jit(self, inputs, labels):
        arrays = [to_tensor(t)._data for t in inputs + labels]
        if hasattr(self.network, "shard_inputs"):
            # DataParallel/hybrid wrapper: lay batch onto the mesh; XLA
            # then emits the cross-replica grad all-reduce (reducer.cc's
            # job in the reference) during compilation.
            arrays = self.network.shard_inputs(arrays)
        remat_on = self._remat_decision(batch_size=_batch_len(inputs))
        sig = ("train", remat_on,
               tuple((a.shape, str(a.dtype)) for a in arrays))
        step = self._jit_cache.get(sig)
        net, opt = self.network, self._optimizer
        params, buffers = net.functional_state()
        if not hasattr(opt, "_fn_state") or opt._fn_state is None:
            opt._fn_state = opt.functional_init(params)
        offload_sh = self._offload_shardings() \
            if getattr(self, "_offload", False) else None
        if offload_sh is not None and getattr(self, "_opt_on_host", False):
            # opt state parked in pinned host memory since last step:
            # stage it back into HBM for the (donating) jit step
            opt._fn_state = jax.device_put(opt._fn_state, offload_sh[1])
        scaler_state = None
        if getattr(self, "_amp_scaler_cfg", None) is not None:
            scaler_state = self._amp_scaler_state
            if scaler_state is None:
                c = self._amp_scaler_cfg
                scaler_state = {
                    "scale": jnp.asarray(c["init_loss_scaling"],
                                         jnp.float32),
                    "good": jnp.zeros((), jnp.int32),
                    "bad": jnp.zeros((), jnp.int32)}
        key_base, rng_ctr = self._device_rng_state()
        if key_base is None:
            # split-chain mode: a per-step host-built key (transfer) —
            # correctness over the zero-transfer fast path.  The step
            # derives key = [base0, base1 ^ (ctr+1)]; pre-XOR base1 so
            # the derived key equals the generator's key exactly
            key = default_generator.next_key()
            key_base = jnp.stack([key[0], key[1] ^ jnp.uint32(1)])
            rng_ctr = jnp.uint32(0)
            split_chain = True
        else:
            split_chain = False
        lr = self._lr_device()
        fresh_step = step is None
        aot_hit = False
        if step is None:
            step = self._build_jit_train_step(len(inputs), len(labels),
                                              remat=remat_on)
            from ..utils import artifact_store as _aot
            if _aot.active() is not None and \
                    not hasattr(self.network, "shard_inputs"):
                # single-device only: AOT executables are sharding-
                # strict, and the DP wrapper's param shardings evolve
                # between the first and later steps
                # AOT path through the artifact store: a relaunched
                # trainer (PR 3 supervisor) deserializes the persisted
                # executable instead of paying the XLA compile.  A
                # lowering or store defect raises — an armed store that
                # silently compiled every time would look like a cache.
                step = _aot.aot_compile(
                    step.lower(params, buffers, opt._fn_state,
                               scaler_state, key_base, rng_ctr,
                               *([lr] + arrays)),
                    label="hapi.train_step")
                aot_hit = True   # ledger entry recorded by the store
            self._jit_cache[sig] = step
        # step-phase attribution: the dispatch call is where device
        # backpressure surfaces in a sync-free loop (XLA bounds the
        # in-flight queue), so its duration is the per-step "device"
        # phase; fit subtracts it from the body time to get "host"
        _d0 = _obs.now_ns() if _obs.active else 0
        # compile ledger: a fresh jit step compiles inside its first
        # dispatch; time that call so the ledger carries the wall cost
        # (the AOT path records its own entry through the store)
        _m0 = _obs.now_ns() if (_memscope.active and fresh_step
                                and not aot_hit) else 0
        try:
            (loss, outs, new_buffers, new_params, new_state, new_scaler,
             new_ctr) = step(params, buffers, opt._fn_state, scaler_state,
                             key_base, rng_ctr, *([lr] + arrays))
        except Exception as e:
            net.load_functional_state(params, buffers)  # drop leaked tracers
            if _memscope.active and _memscope.is_oom(e):
                # OOM forensics: census + flight ring land in
                # PADDLE_FLIGHT_DIR before the error re-raises
                _memscope.oom_dump(e, context="hapi.train_step")
            raise
        if _m0:
            _memscope.compile_record(
                "hapi.train_step", sig,
                (_obs.now_ns() - _m0) / 1e9, provenance="jit")
        if _d0:
            self._last_dispatch_ns = _obs.on_step_phase("device", _d0)
        if not split_chain:
            # mirror the in-jit counter bump on the host generator so
            # get_rng_state()/eager draws stay consistent, and keep the
            # device counter for the next step (zero transfers)
            default_generator._counter += 1
            self._rng_dev_cache = ((default_generator._seed,
                                    default_generator._counter),
                                   key_base, new_ctr)
        if new_scaler is not None:
            # device arrays, never synced here: found_inf is only
            # materialized if someone (tests, the nan guard) reads it
            self._amp_found_inf = new_scaler["found_inf"]
            self._amp_scaler_state = {k: new_scaler[k]
                                      for k in ("scale", "good", "bad")}
        if offload_sh is not None:
            new_state = jax.device_put(new_state, offload_sh[0])
            self._opt_on_host = True
            if _memscope.active:
                try:
                    _memscope.set_tag_bytes(
                        "host_offload", _memscope.tree_nbytes(new_state))
                except Exception:   # noqa: BLE001 — accounting never throws
                    pass
        opt._fn_state = new_state
        net.load_functional_state(new_params, new_buffers)
        if opt._lr_scheduler is None and hasattr(opt, "_global_step"):
            opt._global_step += 1
        metrics = self._update_metrics(outs, labels)
        self._train_step_count = getattr(self, "_train_step_count", 0) + 1
        lazy = _LazyScalar(loss,
                           origin=f"train step {self._train_step_count}")
        if _chaos.active and _chaos.hit("step.loss") == "nan":
            # chaos layer: poison this step's loss so the anomaly guard
            # / nan-check paths can be exercised deterministically
            lazy = float("nan")
        from ..utils import flags as _flags
        if _flags.get_flag("FLAGS_check_nan_inf"):
            # numeric-guard mode: surface device faults and NaN/Inf loss
            # AT the producing step (trades away the async pipeline)
            v = float(lazy)
            if not np.isfinite(v):
                raise FloatingPointError(
                    f"loss is {v} at train step {self._train_step_count} "
                    f"(FLAGS_check_nan_inf enabled)")
        return self._pack_logs(lazy, metrics)

    def _train_batch_eager(self, inputs, labels, update=True):
        net, opt = self.network, self._optimizer
        if self._amp_level:
            from ..amp import auto_cast
            with auto_cast(level=self._amp_level,
                           dtype=getattr(self, "_amp_dtype", "bfloat16"),
                           custom_white_list=getattr(
                               self, "_amp_custom_white", None),
                           custom_black_list=getattr(
                               self, "_amp_custom_black", None)):
                outs = _to_list(net(*[to_tensor(i) for i in inputs]))
        else:
            outs = _to_list(net(*[to_tensor(i) for i in inputs]))
        losses = self._loss(*(outs + [to_tensor(l) for l in labels]))
        losses.backward()
        if update:
            opt.step()
            opt.clear_grad()
        metrics = self._update_metrics([o._data for o in outs], labels)
        return self._pack_logs(float(losses), metrics)

    def eval_batch(self, inputs, labels=None):
        inputs = _to_list(inputs)
        labels = _to_list(labels)
        self.network.eval()
        arrays = [to_tensor(t)._data for t in inputs + labels]
        if hasattr(self.network, "shard_inputs"):
            arrays = self.network.shard_inputs(arrays)
        sig = ("eval", tuple((a.shape, str(a.dtype)) for a in arrays))
        if sig not in self._jit_cache:
            self._jit_cache[sig] = self._build_jit_eval_step(
                len(inputs), len(labels), True)
        params, buffers = self.network.functional_state()
        try:
            outs, loss = self._jit_cache[sig](params, buffers, *arrays)
        finally:
            # tracing rebinds layer tensors to tracers; restore concrete
            self.network.load_functional_state(params, buffers)
        metrics = self._update_metrics(outs, labels)
        loss_val = float(loss) if loss is not None else None
        return self._pack_logs(loss_val, metrics)

    def predict_batch(self, inputs):
        inputs = _to_list(inputs)
        self.network.eval()
        arrays = [to_tensor(t)._data for t in inputs]
        if hasattr(self.network, "shard_inputs"):
            arrays = self.network.shard_inputs(arrays)
        sig = ("pred", tuple((a.shape, str(a.dtype)) for a in arrays))
        if sig not in self._jit_cache:
            self._jit_cache[sig] = self._build_jit_eval_step(
                len(inputs), 0, False)
        params, buffers = self.network.functional_state()
        try:
            outs, _ = self._jit_cache[sig](params, buffers, *arrays)
        finally:
            self.network.load_functional_state(params, buffers)
        return [np.asarray(o) for o in outs]

    def _update_metrics(self, out_arrays, labels):
        if not self._metrics:
            return {}
        results = {}
        for metric in self._metrics:
            computed = metric.compute(
                Tensor(out_arrays[0]), *[to_tensor(l) for l in labels])
            if isinstance(computed, (list, tuple)):
                res = metric.update(*[c for c in computed])
            else:
                res = metric.update(computed)
            names = metric.name()
            results[names[0] if isinstance(names, list) else names] = res
        return results

    def _pack_logs(self, loss, metrics):
        logs = {}
        if loss is not None:
            logs["loss"] = loss
        logs.update(metrics)
        return logs

    # ------------------------------------------------------------------
    # fault tolerance: checkpoint resume, heartbeats, anomaly guard
    # ------------------------------------------------------------------
    def _ckpt_tree(self, step_count: int):
        """(params, buffers, opt, rng, step) as one checkpointable tree —
        everything a relaunched worker needs to continue bit-exactly.
        The meta leaf also records the data-parallel world and the
        exact global sample count consumed, so a relaunch at a
        DIFFERENT world size (elastic shrink/grow) can recompute its
        replay offset by samples instead of by now-meaningless step
        indices."""
        params, buffers = self.network.functional_state()
        opt = self._optimizer
        if getattr(opt, "_fn_state", None) is None:
            opt._fn_state = opt.functional_init(params)
        gen = default_generator
        return {"params": params, "buffers": buffers,
                "opt": opt._fn_state,
                "meta": {"step": np.int64(step_count),
                         "rng_seed": np.uint64(gen._seed),
                         "rng_counter": np.uint64(gen._counter),
                         "world": np.int64(
                             getattr(self, "_fit_data_world", 1)),
                         "samples": np.int64(
                             getattr(self, "_fit_samples_seen", 0)),
                         # epoch-scoped counters: cross-world replay
                         # must not compare sample totals ACROSS epochs
                         # (DistributedBatchSampler ceil-pads each
                         # epoch to a world-dependent total)
                         "epoch": np.int64(
                             getattr(self, "_fit_epoch", 0)),
                         "samples_epoch": np.int64(
                             getattr(self, "_fit_samples_epoch", 0))}}

    def _fit_resume(self, checkpointer, data_world: Optional[int] = None):
        """Restore the newest intact checkpoint (corrupt steps are
        quarantined by the checkpointer); returns a dict of
        ``{"step", "world", "samples"}`` describing the restored state,
        or None when nothing intact exists (cold start — the live state
        is left untouched).  ``world``/``samples`` are None for trees
        written before manifest v2 (which still load via the legacy
        meta template)."""
        from ..distributed.checkpoint import (CheckpointCorruptError,
                                              derive_rank_seed)
        if data_world is None:
            data_world = getattr(self, "_fit_data_world", 1)
        template = self._ckpt_tree(0)
        legacy = dict(template)
        legacy["meta"] = {k: template["meta"][k]
                          for k in ("step", "rng_seed", "rng_counter")}
        try:
            restored = checkpointer.restore(template=template)
        except CheckpointCorruptError:
            if checkpointer.all_steps():
                warnings.warn(
                    "fit: no intact checkpoint survived verification; "
                    "starting from scratch")
            return None
        except Exception:
            # the manifest format decides whether this is a pre-v2 tree
            # (whose meta lacks the new keys, so the full template
            # mismatches the stored structure) or a v2 tree that failed
            # for a real reason — only the former gets the legacy-shape
            # retry (MIGRATION: v1 trees still load, sans cross-world
            # replay recompute); masking a genuine v2 failure behind a
            # legacy retry would bury the actual error
            seen = getattr(checkpointer, "last_restored_meta", None) or {}
            if int(seen.get("format") or 1) >= 2:
                raise
            try:
                restored = checkpointer.restore(template=legacy)
            except CheckpointCorruptError:
                if checkpointer.all_steps():
                    warnings.warn(
                        "fit: no intact checkpoint survived "
                        "verification; starting from scratch")
                return None
        self.network.load_functional_state(restored["params"],
                                           restored["buffers"])
        self._optimizer._fn_state = restored["opt"]
        meta = restored["meta"]
        old_world = int(meta["world"]) if "world" in meta else None
        samples = int(meta["samples"]) if "samples" in meta else None
        epoch = int(meta["epoch"]) if "epoch" in meta else 0
        samples_epoch = int(meta["samples_epoch"]) \
            if "samples_epoch" in meta else samples
        gen = default_generator
        if old_world is not None and old_world != data_world:
            # cross-world resume: the rank<->host mapping has rotated,
            # so each survivor re-derives its stream deterministically
            # from its NEW rank instead of inheriting whichever old
            # rank's stream happens to be in the restored tree
            rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
            gen._seed = derive_rank_seed(int(meta["rng_seed"]), rank)
        else:
            gen._seed = int(meta["rng_seed"])
        gen._counter = int(meta["rng_counter"])
        gen._key = None
        self._rng_dev_cache = None     # device counter resyncs next step
        step = int(meta["step"])
        warnings.warn(f"fit: resumed from checkpoint at step {step} "
                      f"(generation "
                      f"{os.environ.get('PADDLE_RESTART_GENERATION', '0')}"
                      + (f", saved at data-parallel world {old_world}"
                         if old_world is not None else "") + ")")
        # the DIRECTORY label the checkpointer restored from: may sit
        # above meta["step"] when an earlier elastic resume offset the
        # numbering (see _fit_save_offset in fit)
        seen = getattr(checkpointer, "last_restored_meta", None) or {}
        label = seen.get("step")
        label = step if label is None else int(label)
        return {"step": step, "world": old_world, "samples": samples,
                "epoch": epoch, "samples_epoch": samples_epoch,
                "label": label}

    def _make_heartbeat(self):
        """Supervised-launch heartbeat: when the launcher exported
        PADDLE_SUPERVISE_STORE, put this rank's step payload under the
        generation-prefixed supervise key so the watchdog can tell
        progress from a hang — and, since the payload carries the mean
        per-step wall time between beats, so the supervisor's straggler
        detector can median step times across the gang.  The generation
        prefix keeps a slow-dying worker from a prior generation from
        feeding the current generation's watchdog.  Returns None (zero
        per-step cost) when unsupervised."""
        spec = os.environ.get("PADDLE_SUPERVISE_STORE")
        if not spec:
            return None
        import json as _json
        # supervised workers also install the SIGUSR1 thread-dump
        # handler: before the watchdog kills a stalled gang it signals
        # each worker, so the wedged one's log ends with every thread's
        # stack and currently-held sanitizer locks (diagnosable
        # artifact instead of a silent SIGKILL)
        from ..utils import concurrency as _conc
        _conc.install_signal_dump()
        from ..distributed import fleet_metrics as _fleet
        from ..distributed.fleet.elastic.manager import store_from_spec
        from ..distributed.launch import heartbeat_key
        from ..profiler import flight as _flight
        store = store_from_spec(spec)
        job = os.environ.get("PADDLE_SUPERVISE_JOB", "default")
        gen = os.environ.get("PADDLE_RESTART_GENERATION", "0")
        rank = os.environ.get("PADDLE_TRAINER_ID", "0")
        key = heartbeat_key(job, gen, rank)
        interval = float(os.environ.get("PADDLE_HEARTBEAT_INTERVAL",
                                        "1.0"))
        if _flight.active:
            _flight.note("launch", "fit_start", generation=gen,
                         rank=rank,
                         world=os.environ.get("PADDLE_TRAINERS_NUM"))
        state = {"t": 0.0, "step": None}

        def beat(step):
            now = time.monotonic()
            if now - state["t"] < interval:
                return
            payload = {"step": step}
            prev_t, prev_step = state["t"], state["step"]
            if prev_t and isinstance(step, int) and \
                    isinstance(prev_step, int) and step > prev_step:
                # mean per-step wall time since the last beat — the
                # straggler detector's input (int steps only: eval
                # beats keep the watchdog fed but carry no timing)
                payload["dt"] = round((now - prev_t) /
                                      (step - prev_step), 6)
            state["t"], state["step"] = now, step
            try:
                store.put(key, _json.dumps(payload))
            except Exception:
                pass   # store blip: the TTL/watchdog slack absorbs it
            try:
                # fleet metrics ride the heartbeat cadence: one registry
                # snapshot per beat under a generation-prefixed key the
                # supervisor aggregates into its /metrics endpoint
                _fleet.publish(store, job, gen, rank, step=step)
            except Exception:
                pass   # same store-blip tolerance as the beat itself

        return beat

    def _state_refs(self):
        # deep copies, not refs: the jitted step DONATES params/opt
        # buffers (donate_argnums), so the pre-step arrays are dead the
        # moment the step runs — reverting must restore surviving copies
        def cp(a):
            return jnp.array(a._data if hasattr(a, "_data") else a,
                             copy=True)
        params, buffers = self.network.functional_state()
        opt_state = getattr(self._optimizer, "_fn_state", None)
        return (jax.tree.map(cp, params), jax.tree.map(cp, buffers),
                None if opt_state is None else jax.tree.map(cp, opt_state))

    def _restore_state_refs(self, snap):
        params, buffers, opt_state = snap
        self.network.load_functional_state(params, buffers)
        if opt_state is not None:
            self._optimizer._fn_state = opt_state

    def _handle_anomaly(self, action, value, step_count, snap,
                        checkpointer):
        """nan/inf loss policy (FLAGS_anomaly_action).  'skip' reverts
        this step's update; 'rollback' restores the newest intact
        checkpoint (data is not rewound — training continues with the
        next batch either way)."""
        from ..profiler import flight as _flight
        from ..profiler import metrics as _metrics
        _metrics.counter("train.anomaly",
                         "nan/inf losses caught by the fit anomaly "
                         "guard").inc()
        if _flight.active:
            _flight.note("train", "anomaly", value=str(value),
                         step=step_count, action=action)
        if action == "raise":
            raise FloatingPointError(
                f"loss is {value} at train step {step_count} "
                f"(FLAGS_anomaly_action=raise)")
        if action == "rollback" and checkpointer is not None:
            restored = self._fit_resume(checkpointer)
            if restored is not None:
                warnings.warn(f"anomalous loss {value} at step "
                              f"{step_count}: rolled back to checkpoint "
                              f"step {restored['step']}")
                return
            warnings.warn("FLAGS_anomaly_action=rollback: no intact "
                          "checkpoint yet, reverting this step instead")
        elif action == "rollback":
            warnings.warn("FLAGS_anomaly_action=rollback without a "
                          "checkpointer: reverting this step instead")
        self._restore_state_refs(snap)
        # the eager/accumulation path has already backward()ed the
        # poisoned loss into .grad — flush it or the next boundary
        # opt.step() applies the NaN update anyway (no-op on the
        # functional jit path, which carries no .grad state)
        if hasattr(self._optimizer, "clear_grad"):
            self._optimizer.clear_grad()
        warnings.warn(f"anomalous loss {value} at step {step_count}: "
                      f"step reverted, continuing")

    # ------------------------------------------------------------------
    # loop-level API
    # ------------------------------------------------------------------
    def _epoch_input(self, loader, depth):
        """(iterator, prefetcher-or-None) for one epoch over ``loader``:
        the io DevicePrefetcher stage (background collate +
        ``device_put``, ``depth`` batches resident on device) unless
        disabled or the loader runs its own.  For DataParallel/hybrid
        networks the prefetch ``device_put`` uses the step's input
        sharding, so multi-chip feeds land pre-sharded; on meshes with
        no local placement (multi-host) prefetch is bypassed entirely —
        including a loader-owned stage — because batches must stay
        host-side for the in-step global sharding."""
        from ..io import DataLoader, DevicePrefetcher
        from ..utils import flags as _flags
        if depth is None:
            depth = _flags.get_flag("FLAGS_prefetch_to_device")
        depth = int(depth or 0)
        sharding = None
        dp_net = self._use_jit and hasattr(self.network, "shard_inputs") \
            and getattr(self.network, "mesh", None) is not None
        if dp_net:
            from ..distributed.parallel import input_sharding_fn
            sharding = input_sharding_fn(
                self.network.mesh, getattr(self.network, "_dp_axis", "dp"))
            if sharding is None:
                # no local placement exists: force host batches even if
                # the loader has its own device-prefetch stage
                if getattr(loader, "prefetch_to_device", 0) > 0 and \
                        hasattr(loader, "_iter_batches"):
                    return loader._iter_batches(), None
                return iter(loader), None
        if getattr(loader, "prefetch_to_device", 0) > 0:
            # the loader's own stage runs in its __iter__; (re)hand it
            # this fit's input sharding — a loader reused across
            # models/meshes must not keep a stale closure
            loader._input_sharding = sharding
            return iter(loader), None
        if depth <= 0:
            return iter(loader), None
        if isinstance(loader, DataLoader):
            pf = DevicePrefetcher.for_loader(loader, depth=depth,
                                             sharding=sharding)
        else:
            try:
                pf = DevicePrefetcher(iter(loader), depth=depth,
                                      sharding=sharding)
            except TypeError:
                return iter(loader), None
        self._last_prefetcher = pf
        return iter(pf), pf

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, checkpointer=None,
            prefetch_to_device=None):
        from ..io import DataLoader, Dataset
        self._save_dir = save_dir
        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        if eval_data is not None and isinstance(eval_data, Dataset):
            eval_loader = DataLoader(eval_data, batch_size=batch_size,
                                     num_workers=num_workers)
        else:
            eval_loader = eval_data

        try:
            steps = len(train_loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                batch_size=batch_size, steps=steps,
                                log_freq=log_freq, verbose=verbose,
                                save_freq=save_freq, save_dir=save_dir,
                                metrics=["loss"] + [m.name() for m in
                                                    self._metrics])
        # fault-tolerance hooks: all of them cost one predicate read per
        # step when unconfigured (no supervisor env, no checkpointer, no
        # anomaly flag) — the PR-1 instrumentation discipline
        from ..utils import flags as _flags
        anomaly = _flags.get_flag("FLAGS_anomaly_action")
        heartbeat = self._heartbeat = self._make_heartbeat()
        # data-parallel world of the DATA pipeline: >1 only when the
        # loader actually shards the index space across ranks
        # (DistributedBatchSampler) — replicated-data gangs train every
        # sample on every rank, so their replay offsets are world-free
        from ..io import DistributedBatchSampler
        data_world = 1
        bs = getattr(train_loader, "batch_sampler", None)
        if isinstance(bs, DistributedBatchSampler):
            data_world = int(bs.nranks)
        self._fit_data_world = data_world
        self._fit_samples_seen = 0
        start_step = 0
        resume_samples = None
        resume_epoch = 0
        # checkpoint directory labels must stay monotonic across
        # elastic resumes: a GROW renumbers step_count DOWNWARD on the
        # new grid, and saving step 101 next to a stale old-world step
        # 200 would make every later restore pick the pre-grow tree.
        # The offset keeps labels strictly increasing while the tree's
        # meta keeps the true new-grid step count for replay math.
        self._fit_save_offset = 0
        if checkpointer is not None and self._optimizer is not None:
            info = self._fit_resume(checkpointer, data_world)
            if info is not None:
                if info["world"] is not None and \
                        info["world"] != data_world and \
                        info["samples_epoch"] is not None:
                    self._fit_save_offset = info["label"]
                    # elastic world change: step indices from the old
                    # world are meaningless here — replay completed
                    # epochs wholesale (their padded sample totals are
                    # world-dependent, so the counts don't transfer)
                    # and skip WITHIN the saved epoch by global sample
                    # count, so nothing is double-trained or silently
                    # dropped
                    resume_samples = info["samples_epoch"]
                    resume_epoch = info["epoch"]
                    warnings.warn(
                        f"fit: resharded resume — checkpoint was taken "
                        f"at data-parallel world {info['world']}, this "
                        f"run is world {data_world}; replaying "
                        f"{resume_epoch} completed epoch(s) plus "
                        f"{resume_samples} already-trained global "
                        f"samples instead of old-world step indices")
                else:
                    start_step = info["step"]
                    # same-world: continue an existing label offset
                    # (label == step means no offset ever applied)
                    self._fit_save_offset = max(
                        0, info["label"] - info["step"])

        cbks.on_train_begin()
        # memscope goodput/attribution layer: one predicate read per
        # hook when FLAGS_mem_accounting is off (`_gp is None` below)
        _gp = None
        _tagged_opt = False
        if _memscope.active:
            try:
                _memscope.set_tag_bytes(
                    "params",
                    _memscope.tree_nbytes(self.network.functional_state()))
            except Exception:   # noqa: BLE001 — accounting never throws
                pass
            _gp = _memscope.GoodputMeter("train").start()
        step_count = 0
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch)
            self._fit_epoch = epoch
            self._fit_samples_epoch = 0
            for m in self._metrics:
                m.reset()
            logs = {}
            # async input pipeline: a fresh one-shot prefetch stage per
            # epoch; falls through to the plain loader when disabled
            it, pf = self._epoch_input(train_loader, prefetch_to_device)
            step = -1
            try:
                while True:
                    # step-phase breakdown (host tracer on): data_wait
                    # is the time this loop blocked on the input
                    # pipeline; with prefetch warm it is ~queue-pop
                    trace = _obs.active
                    _tw0 = _obs.now_ns() if (trace or _gp is not None) \
                        else 0
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    if _tw0:
                        _dw = _obs.on_step_phase("data_wait", _tw0) \
                            if trace else _obs.now_ns() - _tw0
                        if _gp is not None:
                            _gp.add_ns("data_wait", _dw)
                    step += 1
                    if resume_samples is not None:
                        # cross-world resume: replay the data order,
                        # counting GLOBAL samples (this rank's batch x
                        # data world) until the checkpoint's consumed-
                        # sample mark — the step grid of the old world
                        # doesn't exist here.  Completed old-world
                        # epochs replay WHOLESALE — their ceil-padded
                        # sample totals are world-dependent, so the
                        # counts don't transfer across epochs.
                        if epoch > resume_epoch:
                            # the saved epoch is exhausted on the new
                            # grid (its old padded total can exceed the
                            # new one); training resumes here
                            resume_samples = None
                        else:
                            bl = _batch_len(self._split_batch(batch)[0]) \
                                * data_world
                            if epoch < resume_epoch or \
                                    self._fit_samples_epoch + bl <= \
                                    resume_samples:
                                self._fit_samples_seen += bl
                                self._fit_samples_epoch += bl
                                step_count += 1
                                continue
                            if self._fit_samples_epoch < resume_samples:
                                warnings.warn(
                                    f"fit: resharded-resume boundary "
                                    f"falls inside a batch — re-training "
                                    f"{resume_samples - self._fit_samples_epoch}"
                                    f" of {resume_samples} replayed "
                                    f"samples (the old step boundary is "
                                    f"not representable on the new "
                                    f"world's batch grid)")
                            resume_samples = None
                    if resume_samples is None and \
                            step_count < start_step:
                        # resumed run: this batch's update is already
                        # inside the restored state — replay the data
                        # order without re-training (shuffle must be
                        # deterministic/off for exact continuation, as
                        # in the reference resume)
                        step_count += 1
                        bl = _batch_len(
                            self._split_batch(batch)[0]) * data_world
                        self._fit_samples_seen += bl
                        self._fit_samples_epoch += bl
                        continue
                    cbks.on_train_batch_begin(step)
                    ins, lbls = self._split_batch(batch)
                    # profiler v2 hot-path hook: with the host tracer
                    # off this whole block is one predicate read per
                    # step
                    _t0 = _obs.now_ns() if trace else 0
                    self._last_dispatch_ns = 0
                    if anomaly:
                        # pre-step copies (the jit step donates its
                        # inputs); this is the guard's per-step cost
                        snap = self._state_refs()
                    _s0 = _obs.now_ns() if _gp is not None else 0
                    if accumulate_grad_batches > 1:
                        # grad accumulation rides the eager tape:
                        # backward accumulates into .grad, step fires on
                        # the boundary
                        update = (step + 1) % accumulate_grad_batches == 0
                        self.network.train()
                        logs = self._train_batch_eager(ins, lbls,
                                                       update=update)
                    else:
                        logs = self.train_batch(ins, lbls)
                    if _s0:
                        _gp.step_ns(_obs.now_ns() - _s0)
                        if not trace:
                            # tracer off: the phase hooks don't run, so
                            # sample the step watermark here
                            _memscope.on_phase("step")
                        if not _tagged_opt:
                            _tagged_opt = True
                            st = getattr(self._optimizer, "_fn_state",
                                         None)
                            if st is not None:
                                _memscope.set_tag_bytes(
                                    "opt_state",
                                    _memscope.tree_nbytes(st))
                    if _t0:
                        _obs.on_hapi_step(_t0, num_samples=_batch_len(ins),
                                          mode="train")
                    step_count += 1
                    self._fit_samples_seen += _batch_len(ins) * data_world
                    self._fit_samples_epoch += _batch_len(ins) * data_world
                    if anomaly and "loss" in logs:
                        # guard mode materialises the loss at the
                        # producing step (its documented synchronous
                        # trade against the lazy-loss pipeline)
                        v = float(logs["loss"])
                        if not np.isfinite(v):
                            _a0 = _obs.now_ns() if _gp is not None else 0
                            self._handle_anomaly(anomaly, v, step_count,
                                                 snap, checkpointer)
                            if _a0:
                                _gp.add_ns("anomaly",
                                           _obs.now_ns() - _a0)
                            logs["loss"] = v
                    if _chaos.active:
                        # host.slow: deterministic per-rank slowdown of
                        # the step loop (a 'delay' action stretches
                        # this step's wall time, which the next beat's
                        # dt payload then reports — the straggler-
                        # detection test bed)
                        _chaos.hit("host.slow")
                    if heartbeat is not None:
                        # step + per-step wall time — never the device
                        heartbeat(step_count)
                    save_label = step_count + self._fit_save_offset
                    if checkpointer is not None and (
                            not hasattr(checkpointer, "want_save")
                            or checkpointer.want_save(save_label)):
                        # tree build + host snapshot only on steps the
                        # checkpointer will actually write; interval
                        # steps stay sync-free.  The directory label
                        # carries the elastic offset; the tree's meta
                        # records the true new-grid step count
                        _c0 = _obs.now_ns() if _gp is not None else 0
                        checkpointer.save(save_label,
                                          self._ckpt_tree(step_count))
                        if _c0:
                            _gp.add_ns("checkpoint",
                                       _obs.now_ns() - _c0)
                    # reference hapi: callbacks see the ACTUAL batch
                    # size so ips stays honest on the final partial
                    # batch
                    logs["batch_size"] = _batch_len(ins)
                    cbks.on_train_batch_end(step, logs)
                    if _t0:
                        _obs.on_step_host(
                            _obs.now_ns() - _t0 - self._last_dispatch_ns)
                    if num_iters is not None and step_count >= num_iters:
                        break
            finally:
                if pf is not None:
                    pf.close()
                else:
                    # a loader-owned stage must also stop promptly on an
                    # exception — a stored traceback pins the suspended
                    # generator and would keep its producer alive
                    lpf = getattr(train_loader, "_last_prefetcher", None)
                    if lpf is not None:
                        lpf.close()
            cbks.on_epoch_end(epoch, logs)
            if eval_loader is not None and epoch % eval_freq == 0:
                self.evaluate(eval_loader, batch_size=batch_size,
                              verbose=verbose, callbacks=cbks,
                              _inner=True)
            if cbks.stop_training or self.stop_training:
                break
            if num_iters is not None and step_count >= num_iters:
                break
        cbks.on_train_end()
        if checkpointer is not None:
            # the final step's async write must land before fit returns
            # (a supervisor relaunch right after fit would otherwise
            # resume one step short) — goodput charges the drain to the
            # checkpoint bucket: a chaos-delayed ckpt.write stalls HERE
            _c0 = _obs.now_ns() if _gp is not None else 0
            checkpointer.wait_until_finished()
            if _c0:
                _gp.add_ns("checkpoint", _obs.now_ns() - _c0)
        if _gp is not None:
            # train.goodput.* gauges + the goodput.r<rank>.g<gen>.json
            # doc the PR 9 supervise report folds in
            self._last_goodput = _gp.finish(
                extra={"steps": step_count,
                       "samples": self._fit_samples_seen})

    def _split_batch(self, batch):
        if isinstance(batch, (list, tuple)):
            n_in = len(self._inputs) if self._inputs else 1
            if len(batch) <= n_in:
                return list(batch), []
            return list(batch[:n_in]), list(batch[n_in:])
        return [batch], []

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None,
                 _inner=False):
        from ..io import DataLoader, Dataset
        if isinstance(eval_data, Dataset):
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = eval_data
        cbks = callbacks if _inner else config_callbacks(
            callbacks, model=self, verbose=verbose, log_freq=log_freq,
            mode="eval")
        for m in self._metrics:
            m.reset()
        cbks.on_eval_begin()
        losses = []
        logs = {}
        heartbeat = getattr(self, "_heartbeat", None)
        for step, batch in enumerate(loader):
            cbks.on_eval_batch_begin(step)
            ins, lbls = self._split_batch(batch)
            if heartbeat is not None:
                # epoch-end evaluation advances the heartbeat too, so a
                # long eval pass isn't misread as a hung train step
                heartbeat(f"eval-{step}")
            _t0 = _obs.now_ns() if _obs.active else 0
            logs = self.eval_batch(ins, lbls)
            if _t0:
                _obs.on_hapi_step(_t0, num_samples=_batch_len(ins),
                                  mode="eval")
            if "loss" in logs:
                losses.append(logs["loss"])
            logs["batch_size"] = _batch_len(ins)
            cbks.on_eval_batch_end(step, logs)
        final = {}
        if losses:
            final["loss"] = float(np.mean(losses))
        for m in self._metrics:
            names = m.name()
            res = m.accumulate()
            final[names[0] if isinstance(names, list) else names] = res
        cbks.on_eval_end(final)
        return final

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        from ..io import DataLoader, Dataset
        if isinstance(test_data, Dataset):
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = test_data
        outputs = []
        for batch in loader:
            ins, _ = self._split_batch(batch)
            outputs.append(self.predict_batch(ins))
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # ------------------------------------------------------------------
    # persistence / introspection
    # ------------------------------------------------------------------
    def save(self, path, training=True):
        from .. import framework_io
        if training:
            framework_io.save(self.network.state_dict(), path + ".pdparams")
            if self._optimizer is not None:
                framework_io.save(self._optimizer.state_dict(),
                                  path + ".pdopt")
        else:
            from .. import jit as jit_mod
            specs = None
            if self._inputs:
                specs = self._inputs
            jit_mod.save(self.network, path, input_spec=specs)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from .. import framework_io
        state = framework_io.load(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(framework_io.load(opt_path))

    def static_memory_plan(self, mode="train", input_spec=None,
                           label_spec=None, batch_size=1):
        """Capture this model as a static Program and return its
        :class:`~paddle_tpu.static.passes.memory_plan.MemoryPlan` — a
        byte-accurate peak-HBM estimate with a per-op liveness timeline,
        without allocating a single device buffer.

        ``mode="eval"`` plans the forward pass only; ``mode="train"``
        additionally runs :func:`static.append_backward` on the captured
        loss, so activations pinned as vjp residuals and parameter
        gradients are counted.  The train view covers forward+backward;
        optimizer update state (momentum/variance slots) is not part of
        the captured program, so the estimate undershoots a measured
        training peak by roughly one extra parameter-sized buffer per
        optimizer slot.

        Specs default to the ``inputs=``/``labels=`` the Model was
        constructed with; ``None``/``-1`` spec dims resolve to
        ``batch_size``.
        """
        from ..jit.dy2static.program_translator import ProgramTranslator
        from ..static import program as _prog_mod
        from ..static.passes.memory_plan import build_memory_plan

        specs = (_to_list(input_spec) if input_spec is not None
                 else list(self._inputs or []))
        if not specs:
            raise ValueError(
                "static_memory_plan needs input specs: pass input_spec= "
                "or construct Model(net, inputs=[InputSpec(...)])")
        lspecs = []
        if mode == "train":
            if self._loss is None:
                raise ValueError(
                    "static_memory_plan(mode='train') requires "
                    "prepare(loss=...) first; use mode='eval' for a "
                    "forward-only plan")
            lspecs = (_to_list(label_spec) if label_spec is not None
                      else list(self._labels or []))
            if not lspecs:
                raise ValueError(
                    "static_memory_plan(mode='train') needs label specs: "
                    "pass label_spec= or construct "
                    "Model(net, inputs=..., labels=[InputSpec(...)])")
        elif mode != "eval":
            raise ValueError(f"mode must be 'train' or 'eval', got {mode!r}")

        net, loss_fn, n_in = self.network, self._loss, len(specs)
        if mode == "train":
            def _capture(*args):
                outs = _to_list(net.forward(*args[:n_in]))
                return loss_fn(*(outs + list(args[n_in:])))
        else:
            def _capture(*args):
                return net.forward(*args)

        prog, feeds, fetch = ProgramTranslator.get_instance().get_program(
            _capture, specs + lspecs)
        fetch_names = [v.name for v in fetch]
        if mode == "train":
            # fetch the grads too: with only the loss fetched, liveness
            # would mark every backward op dead and the plan would
            # degenerate to the forward view
            pairs = _prog_mod.append_backward(fetch[0])
            fetch_names = [fetch[0].name] + [g.name for _, g in pairs]

        feed_shapes, feed_dtypes = {}, {}
        for v, spec in zip(feeds, specs + lspecs):
            feed_shapes[v.name] = tuple(
                batch_size if d in (None, -1) else int(d)
                for d in spec.shape)
            feed_dtypes[v.name] = str(getattr(spec, "dtype", "float32"))
        return build_memory_plan(prog, feed_shapes=feed_shapes,
                                 feed_dtypes=feed_dtypes,
                                 fetch_names=fetch_names)

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        from .summary_mod import summary as _summary
        return _summary(self.network, input_size, dtypes=dtype)
