"""InferenceEngine: dynamic batching over a cloned-predictor pool.

The synchronous ``Predictor`` answers one request per dispatch; under
concurrent traffic every caller pays a full device round-trip and every
novel shape a full XLA compile.  The engine turns a saved artifact into
a servable endpoint:

- callers ``submit()`` (future) or ``infer()`` (blocking); requests pass
  admission control (bounded queue, per-request deadlines, explicit
  overload rejection — ``admission.py``);
- a batcher thread coalesces compatible requests (same non-batch dims
  and dtypes) into one padded batch per ``max_batch_size`` /
  ``batch_timeout_ms`` window — Clipper-style adaptive batching;
- batches run on a pool of ``Predictor.clone()`` workers sharing ONE
  set of device weights and one executable population;
- input shapes are bucketed (``bucketing.py``) so total compiles are
  bounded by the bucket count, not the observed-shape count;
- results fan back out per request, sliced from the batch output —
  bit-identical to an unbatched ``Predictor.run`` on the same rows.

Composition with the rest of the stack: ``serving.*`` metrics land in
the profiler registry (PR 1), the ``serve.request`` chaos site makes
fault-injected soak tests deterministic (PR 3), and program artifacts
are re-verified by the static-analysis pass bundle once at load (PR 2).
"""
from __future__ import annotations

import queue as _queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from ..profiler import flight as _flight
from ..profiler import memscope as _memscope
from ..profiler import rtrace as _rtrace
from ..profiler import tracer as _tracer
from ..utils import concurrency as _conc
from .admission import (PRIORITIES, AdmissionController, DeadlineExceeded,
                        EngineClosed, RequestRejected, TenantQuotaTable,
                        deadline_from_ms, priority_rank)
from .bucketing import BucketPolicy, ExecutableCache

__all__ = ["EngineConfig", "InferenceEngine", "RequestRejected",
           "DeadlineExceeded", "EngineClosed", "GenerationEngineConfig",
           "GenerationEngine", "GenerationStream",
           "PagedGenerationEngine"]


class EngineConfig:
    """Serving knobs (all have production-sane defaults).

    max_batch_size    rows per executed batch; also the admission cap on
                      a single request's rows
    batch_timeout_ms  how long the batcher holds an open batch waiting
                      for co-travelers before dispatching it partial
    num_workers       predictor clones executing batches concurrently
    max_queue         admission bound on waiting requests (default:
                      FLAGS_serving_queue_depth)
    deadline_ms       default per-request deadline; None = no deadline
    pad_dynamic_dims  also bucket non-batch dynamic dims (opt-in: only
                      sound for padding-invariant/masked models)
    min_batch_bucket  smallest batch bucket (e.g. 4 keeps tiny batches
                      from fragmenting the executable population)
    validate_artifact run the static-analysis verify pass over the
                      artifact's embedded program desc at load (PR 2)
    warmup            pre-populate every batch-bucket executable at
                      construction (from the AOT artifact store when
                      FLAGS_compile_cache_dir is armed — then a fresh
                      engine costs deserialization, not compiles), so
                      first-request latency equals steady state;
                      warmed-bucket count lands in /healthz.  Pass
                      ``"async"`` to warm on a background thread: the
                      engine serves immediately (cold requests compile)
                      but reports ``ready=False`` until warmup lands —
                      a fleet router treats not-ready replicas as
                      undispatchable, so traffic never lands in the
                      cold-compile window
    name              metrics prefix (default "serving"); give each
                      engine a distinct name when one process serves
                      several models, or their counters/gauges mix
    """

    def __init__(self, max_batch_size: int = 8,
                 batch_timeout_ms: float = 2.0,
                 num_workers: int = 2,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 pad_dynamic_dims: bool = False,
                 min_batch_bucket: int = 1,
                 validate_artifact: bool = True,
                 warmup: bool = False,
                 name: str = "serving"):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        self.max_batch_size = int(max_batch_size)
        self.batch_timeout_ms = float(batch_timeout_ms)
        self.num_workers = int(num_workers)
        if max_queue is None:
            from ..utils import flags as _flags
            max_queue = int(_flags.get_flag("FLAGS_serving_queue_depth"))
        self.max_queue = int(max_queue)
        self.deadline_ms = deadline_ms
        self.pad_dynamic_dims = bool(pad_dynamic_dims)
        self.min_batch_bucket = int(min_batch_bucket)
        self.validate_artifact = bool(validate_artifact)
        self.warmup = warmup if warmup == "async" else bool(warmup)
        self.name = str(name)


class _Request:
    __slots__ = ("arrays", "rows", "sig", "future", "deadline",
                 "t_submit", "ctx", "t_submit_ns")

    def __init__(self, arrays, rows, sig, deadline, ctx=None):
        self.arrays = arrays
        self.rows = rows
        self.sig = sig
        self.future: Future = Future()
        self.deadline = deadline
        self.t_submit = time.monotonic()
        # request-trace context (profiler/rtrace.py) riding the request
        # across the batcher/worker thread hops; None when untraced
        self.ctx = ctx
        self.t_submit_ns = _tracer.now_ns() if ctx is not None else 0

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) > self.deadline


def validate_artifact(predictor, name: str = "serving"
                      ) -> Optional[object]:
    """Run the prog-san verify pass (PR 2) over the artifact's embedded
    program description, once, at load.  Program-kind artifacts saved by
    ``static.save_inference_model`` carry an op table; a malformed one
    (dangling inputs, def-after-use, broken fetches) raises here —
    at endpoint construction — instead of surfacing as a cryptic
    execution error under traffic.  Layer artifacts (pure StableHLO, no
    op table) get aval/meta consistency checks only.  Returns the
    analysis report, or None when there was nothing op-level to verify.
    """
    meta = predictor._meta
    avals = meta.get("input_avals") or []
    feed_names = predictor.get_input_names()
    if len(avals) != len(feed_names):
        raise RuntimeError(
            f"artifact metadata is inconsistent: {len(feed_names)} feed "
            f"names vs {len(avals)} input avals — was the .pdiparams "
            "file truncated or hand-edited?")
    from ..profiler import metrics as _metrics
    desc = meta.get("program_desc")
    if not desc:
        _metrics.counter(f"{name}.artifact.validated",
                         "artifacts validated at engine load").inc()
        return None
    from ..static.passes import analyze
    from ..static.program import OpDesc, Program, Variable
    prog = Program()
    for n, (shape, dt) in (desc.get("placeholders") or {}).items():
        v = Variable(n, shape, dt, program=prog)
        v.is_placeholder = True
        prog._placeholders[n] = v
        prog._vars[n] = v
    for n in desc.get("parameters", ()):
        prog.parameters[n] = None
    for n in desc.get("constants", ()):
        prog.constants[n] = None
    for n in desc.get("state_vars", ()):
        prog.state_vars[n] = None
    for row in desc.get("ops", ()):
        prog._append(OpDesc(row["type"], row["kind"], None,
                            row["inputs"], row["outputs"],
                            attrs=row.get("attrs"),
                            fwd_idx=row.get("fwd_idx")))
    feed_shapes = {n: [1 if d is None or int(d) < 0 else int(d)
                       for d in shape]
                   for n, (shape, _dt) in
                   (desc.get("placeholders") or {}).items()}
    report = analyze(prog, feed_shapes=feed_shapes,
                     fetch_names=list(desc.get("fetch_names") or ()),
                     passes=("verify",))
    report.raise_on_error()
    _metrics.counter(f"{name}.artifact.validated",
                     "artifacts validated at engine load").inc()
    return report


class InferenceEngine:
    """Dynamic-batching serving endpoint over a saved artifact.

    ``model`` is a path prefix, an ``inference.Config``, or an existing
    ``Predictor``.  See :class:`EngineConfig` for the knobs, and
    ``serving.server.ServingServer`` for the HTTP frontend.

    Contract: inputs are batch-major (dim 0 is the sample dim) and the
    model is row-independent along it — the standard inference-artifact
    shape contract, and what makes batched outputs bit-identical to
    unbatched runs of the same rows.
    """

    def __init__(self, model, config: Optional[EngineConfig] = None):
        from .. import inference as _inf
        self.config = config or EngineConfig()
        if isinstance(model, str):
            model = _inf.Config(model)
        if isinstance(model, _inf.Config):
            model = _inf.Predictor(model)
        self._base = model
        self._precision = model._config._precision.name
        self.metrics_prefix = self.config.name
        if self.config.validate_artifact:
            self.report = validate_artifact(model, name=self.config.name)
        else:
            self.report = None
        # materialize shared state BEFORE cloning so every worker holds
        # the same device weights (identity, not copies)
        if model._kind == "layer":
            model._materialize_params()
        self.input_names = model.get_input_names()
        self._policy = BucketPolicy(
            model._meta.get("input_avals") or [],
            max_batch_size=self.config.max_batch_size,
            min_batch_bucket=self.config.min_batch_bucket,
            pad_dynamic_dims=self.config.pad_dynamic_dims)
        self._cache = ExecutableCache(name=self.config.name)
        self._admission = AdmissionController(
            self.config.max_queue, max_rows=self.config.max_batch_size,
            name=self.config.name)

        from ..profiler import metrics as _metrics
        prefix = self.metrics_prefix
        self._m_latency = _metrics.histogram(
            f"{prefix}.request.latency_ms",
            "end-to-end request latency (submit -> result)")
        self._m_qwait = _metrics.histogram(
            f"{prefix}.queue_wait_ms",
            "time a request waited before entering an executed batch")
        self._m_occupancy = _metrics.histogram(
            f"{prefix}.batch.occupancy",
            "real request rows per executed batch (before padding)")
        self._m_fill = _metrics.histogram(
            f"{prefix}.batch.fill",
            "rows / bucket-size ratio of executed batches")
        self._m_pad_waste = _metrics.histogram(
            f"{prefix}.pad_waste",
            "fraction of each executed bucket that was padding")
        self._m_batches = _metrics.counter(
            f"{prefix}.batch.executed", "batches dispatched to workers")
        self._m_done = _metrics.counter(
            f"{prefix}.request.completed", "requests answered successfully")
        self._m_failed = _metrics.counter(
            f"{prefix}.request.failed", "requests completed exceptionally "
            "(model error or injected fault)")
        _metrics.gauge(f"{prefix}.workers", "predictor clones in the "
                       "pool").set(self.config.num_workers)

        # readiness: alive != dispatchable.  False while warmup is still
        # compiling buckets — /healthz reports it and the fleet router
        # treats not-ready replicas as undispatchable
        self.ready = False
        self.warmed_buckets = 0
        if self.config.warmup and self.config.warmup != "async":
            self._warmup()
            self.ready = True
        elif not self.config.warmup:
            self.ready = True        # nothing to wait for

        self._pending: deque = deque()
        # sanitizer factories (utils/concurrency.py): plain threading
        # primitives when FLAGS_lock_san=0, instrumented (order graph +
        # contention histograms) when on
        self._cond = _conc.Condition(name=f"{self.config.name}"
                                     ".engine.cond")
        # serializes metric updates issued from concurrent workers: the
        # registry's Counter.inc is deliberately lock-free (PR-1 hot
        # path), but the serving gate asserts EXACT counts, so the
        # engine's own increments must not lose races
        self._mlock = _conc.Lock(name=f"{self.config.name}"
                                 ".engine.metrics")
        self._batch_q: "_queue.Queue" = _queue.Queue(
            maxsize=max(2, 2 * self.config.num_workers))
        self._stop = False
        self._paused = False
        self._closed = False
        # quiesce bookkeeping for weight hot-swap: batches queued or
        # executing (incremented by the batcher BEFORE the queue put so
        # there is no counted-nowhere window) + whether the batcher is
        # mid-assembly of a batch
        self._inflight = 0
        self._batcher_busy = False
        self._workers: List[threading.Thread] = []
        self._predictors = [model.clone()
                            for _ in range(self.config.num_workers)]
        self._batcher = _conc.spawn(self._batcher_loop,
                                    name="serving-batcher")
        for i, p in enumerate(self._predictors):
            self._workers.append(_conc.spawn(
                self._worker_loop, args=(p,),
                name=f"serving-worker-{i}"))
        if self.config.warmup == "async":
            _conc.spawn(self._warmup_async, name="serving-warmup")

    def _warmup_async(self):
        try:
            self._warmup()
        finally:
            # an engine closed mid-warmup must stay not-ready: flipping
            # it back would make routers dispatch into EngineClosed
            if not self._closed:
                self.ready = True

    # -- warmup --------------------------------------------------------
    def _warmup(self):
        """Compile (or, with the AOT artifact store armed, deserialize)
        every batch-bucket executable before the first request, so
        first-request latency equals steady state.  Skipped per input
        with dynamic non-batch dims (the bucket set is unbounded there
        unless pad_dynamic_dims bounds it — and then only the batch
        buckets are enumerable anyway)."""
        avals = self._base._meta.get("input_avals") or []
        if len(avals) != len(self.input_names):
            import warnings
            warnings.warn(
                "EngineConfig.warmup: artifact metadata has no usable "
                "input_avals (legacy/storage-reduced artifact?) — "
                "warmup skipped; buckets will compile on first use",
                UserWarning, stacklevel=3)
            return
        shapes = []
        for shape, dt in avals:
            tail = [int(d) if d is not None else -1 for d in shape[1:]]
            if any(d < 0 for d in tail):
                import warnings
                warnings.warn(
                    f"EngineConfig.warmup: input has dynamic non-batch "
                    f"dims {list(shape)}; bucket set is unbounded — "
                    "warmup skipped for this engine", UserWarning,
                    stacklevel=3)
                return
            shapes.append((tail, str(dt)))
        # derive the bucket set from the SAME policy the batcher uses —
        # a second copy of the bucketing rule would silently warm the
        # wrong keys if the rule ever changed
        buckets, rows = [], 1
        while True:
            b = self._policy.batch_bucket(rows)
            buckets.append(b)
            if b >= self.config.max_batch_size:
                break
            rows = b + 1
        errors = []
        for rows in buckets:
            padded = [np.zeros((rows,) + tuple(tail), dt)
                      for tail, dt in shapes]
            try:
                self._run_bucketed(self._base, padded)
            except Exception as e:  # noqa: BLE001 — best-effort, but loud
                errors.append((rows, e))
        if errors:
            import warnings
            warnings.warn(
                f"engine warmup failed for {len(errors)} bucket(s) "
                f"(first: rows={errors[0][0]}: {errors[0][1]!r}); "
                "those buckets will compile on first use",
                RuntimeWarning, stacklevel=3)
        self.warmed_buckets = len(self._cache)
        from ..profiler import metrics as _metrics
        _metrics.gauge(
            f"{self.metrics_prefix}.warmed_buckets",
            "bucket executables pre-populated at engine construction"
        ).set(self.warmed_buckets)

    def memory_breakdown(self) -> Dict[str, int]:
        """The ``/healthz`` memory fields for the batch engine:
        resident parameter bytes plus the process peak census."""
        try:
            pb = _memscope.tree_nbytes(
                getattr(self._base, "_params", None) or {})
        except Exception:   # noqa: BLE001 — health must never raise
            pb = 0
        return {
            "mem_params_bytes": int(pb),
            "mem_peak_step_bytes":
                int(_memscope.peak_bytes()) if _memscope.active else 0,
        }

    # -- client surface ------------------------------------------------
    def submit(self, inputs, deadline_ms: Optional[float] = "default",
               trace_ctx=None) -> Future:
        """Enqueue one request; returns a Future resolving to the list
        of output arrays (np.ndarray, one per model output, sliced to
        this request's rows).  Raises RequestRejected/EngineClosed at
        admission; chaos site ``serve.request`` can fail or delay here.
        ``trace_ctx`` (an rtrace TraceContext, usually built by the
        HTTP layer from the ``traceparent`` header) makes the request's
        admission/queue/execute hops emit request-scoped spans.
        """
        arrays = self._normalize(inputs)
        rows = int(arrays[0].shape[0])
        traced = trace_ctx is not None and _rtrace.active
        t_adm = _tracer.now_ns() if traced else 0
        try:
            from ..utils import chaos as _chaos
            if _chaos.active:
                _chaos.hit("serve.request")
            self._admission.acquire(rows)
        except RequestRejected as e:
            if traced:
                trace_ctx.record("admission", t_adm, outcome=e.reason,
                                 terminated=True)
            raise
        except Exception as e:
            if traced:
                trace_ctx.record("admission", t_adm,
                                 outcome=type(e).__name__,
                                 terminated=True)
            raise
        if traced:
            trace_ctx.record("admission", t_adm, outcome="admitted")
        if deadline_ms == "default":
            deadline_ms = self.config.deadline_ms
        req = _Request(arrays, rows, self._signature(arrays),
                       deadline_from_ms(deadline_ms), ctx=trace_ctx)
        with self._cond:
            if self._closed:
                self._admission.release()
                if traced:
                    trace_ctx.record("queue_wait", req.t_submit_ns,
                                     outcome="closed", terminated=True)
                raise EngineClosed()
            self._pending.append(req)
            self._cond.notify()
        return req.future

    def infer(self, inputs, deadline_ms: Optional[float] = "default",
              timeout: Optional[float] = None,
              trace_ctx=None) -> List[np.ndarray]:
        """Blocking submit; ``timeout`` (seconds) bounds the wait
        independently of the request deadline."""
        fut = self.submit(inputs, deadline_ms=deadline_ms,
                          trace_ctx=trace_ctx)
        try:
            return fut.result(timeout=timeout)
        except (TimeoutError, _FutureTimeout):
            if fut.done():
                # the request finished after all: either the worker beat
                # the wait-timeout by a hair (return its result instead
                # of discarding it) or the error is the request's OWN
                # (shed deadline, model-side timeout) and re-raises here
                return fut.result()
            raise DeadlineExceeded(
                f"no result within {timeout}s (request may still "
                "complete; use submit() for a cancellable future)")

    # -- operations ----------------------------------------------------
    def pause(self):
        """Stop draining the queue (maintenance / deterministic overload
        tests); admission keeps filling up to max_queue, then sheds."""
        with self._cond:
            self._paused = True

    def resume(self):
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    def stats(self) -> Dict[str, object]:
        from ..profiler import metrics as _metrics
        snap = _metrics.snapshot()
        return {k: v for k, v in snap.items()
                if k.startswith((self.metrics_prefix + ".",
                                 "inference."))}

    @property
    def occupancy(self) -> int:
        """Requests queued or executing — the live-load signal a fleet
        router's least-loaded dispatch reads from the registry."""
        return self._admission.depth + self._inflight

    def swap_weights(self, params, buffers=None, *,
                     timeout: float = 30.0):
        """Zero-downtime weight hot-swap: replace the pool's shared
        weight set between batches.

        Every ``Predictor.clone()`` in the pool shares ONE weight dict
        through the ``_jit_holder`` contract (identity, not copies), so
        the swap is an in-place update of that dict: quiesce the
        batcher + workers (no batch may sit between its weight read and
        its execute), write the new arrays, resume.  Batches never mix
        weight sets — each executes entirely on the old or entirely on
        the new tree — and queued requests simply wait out the
        (millisecond) quiesce window, so nothing is dropped.
        Executables are untouched: weights are call *arguments*, and
        the tree is validated shape/dtype-exact, so there is no
        recompile and no retrace."""
        base = self._base
        if base._kind != "layer":
            raise RuntimeError(
                "swap_weights needs a layer-kind artifact (program-kind "
                "artifacts carry no serving-side weight set)")
        live = base._materialize_params()
        if live is not base._params:
            raise RuntimeError(
                "swap_weights supports plain-precision artifacts only: "
                "this engine serves a reduced/quantized weight set — "
                "re-quantize offline and roll the artifact instead")
        import jax.numpy as jnp
        new_p = {k: jnp.asarray(getattr(v, "_data", v))
                 for k, v in params.items()}
        _check_swap_tree(live, new_p, "params")
        new_b = None
        if buffers is not None:
            new_b = {k: jnp.asarray(getattr(v, "_data", v))
                     for k, v in buffers.items()}
            _check_swap_tree(base._buffers, new_b, "buffers")
        deadline = time.monotonic() + (timeout or 30.0)
        with self._cond:
            prior_paused = self._paused
            self._paused = True
        try:
            self._quiesce(deadline)
            with base._jit_holder["lock"]:
                live.update(new_p)
                if new_b is not None:
                    base._buffers.update(new_b)
        finally:
            with self._cond:
                self._paused = prior_paused
                self._cond.notify_all()
        from ..profiler import metrics as _metrics
        with self._mlock:
            _metrics.counter(
                f"{self.metrics_prefix}.weight_swaps",
                "zero-downtime weight hot-swaps applied").inc()
        if _flight.active:
            _flight.note("serve", "weights_swap",
                         engine=self.metrics_prefix)

    def _quiesce(self, deadline: float):
        """Wait until no batch is queued or executing (the batcher is
        already paused by the caller).  ``_inflight`` covers a batch
        from before its queue put through the end of its execute, and
        ``_batcher_busy`` covers the assembly window, so predicate
        true == no request is anywhere between weight read and
        result."""
        with self._cond:
            while self._inflight != 0 or self._batcher_busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        "swap_weights could not quiesce the engine: "
                        "in-flight batches did not drain in time")
                self._cond.wait(timeout=remaining)

    def close(self, timeout: Optional[float] = 30.0):
        """Reject new work, drain queued requests, stop the pool."""
        self.ready = False
        self._admission.close()
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stop = True
            self._paused = False
            self._cond.notify_all()
        self._batcher.join(timeout=timeout)
        for _ in self._workers:
            try:  # a wedged worker must not turn close() into a hang
                self._batch_q.put(None, timeout=timeout)
            except _queue.Full:
                break
        for t in self._workers:
            t.join(timeout=timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- internals -----------------------------------------------------
    def _normalize(self, inputs) -> List[np.ndarray]:
        if isinstance(inputs, dict):
            missing = [n for n in self.input_names if n not in inputs]
            if missing:
                raise ValueError(f"missing inputs {missing}; model "
                                 f"expects {self.input_names}")
            inputs = [inputs[n] for n in self.input_names]
        inputs = list(inputs) if isinstance(inputs, (list, tuple)) \
            else [inputs]
        if len(inputs) != len(self.input_names):
            raise ValueError(
                f"request has {len(inputs)} inputs but the model takes "
                f"{len(self.input_names)}: {self.input_names}")
        arrays = [np.asarray(a) for a in inputs]
        rows = None
        for n, a in zip(self.input_names, arrays):
            if a.ndim == 0:
                raise ValueError(
                    f"input '{n}' is 0-d; engine inputs are batch-major "
                    "(dim 0 is the sample dim)")
            if rows is None:
                rows = a.shape[0]
            elif a.shape[0] != rows:
                raise ValueError(
                    "inputs disagree on the batch dim: "
                    f"{[tuple(x.shape) for x in arrays]}")
        if rows == 0:
            raise ValueError("empty request (0 rows)")
        return arrays

    def _signature(self, arrays) -> tuple:
        """Requests coalesce only when their padded non-batch dims and
        dtypes match — the concatenated batch must be rectangular."""
        sig = []
        for i, a in enumerate(arrays):
            tail = self._policy.bucket_shape(i, a.shape, 0)[1:]
            sig.append((tail, str(a.dtype)))
        return tuple(sig)

    @staticmethod
    def _complete(fut: Future, result=None, exc=None) -> bool:
        """Resolve a request future, tolerating client-side cancel():
        a cancelled future must never blow up the batcher/worker
        pipeline.  Returns False when the client cancelled first."""
        if not fut.set_running_or_notify_cancel():
            return False
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
        return True

    def _shed(self, req: _Request):
        with self._mlock:                # batcher AND workers shed
            self._admission.shed_deadline()
        if req.ctx is not None and _rtrace.active:
            req.ctx.record("queue_wait", req.t_submit_ns,
                           outcome="shed_deadline", terminated=True)
        self._complete(req.future, exc=DeadlineExceeded(
            "request deadline expired while queued (engine overloaded "
            "relative to the deadline)"))

    def _batcher_loop(self):
        timeout_s = self.config.batch_timeout_ms / 1e3
        while True:
            with self._cond:
                # no timeout needed: submit/resume/close all notify, so
                # an idle engine parks instead of polling at 10 Hz
                while (not self._pending or self._paused) \
                        and not self._stop:
                    self._cond.wait()
                if not self._pending and self._stop:
                    break
                if self._paused and not self._stop:
                    continue
                first = self._pending.popleft()
                self._batcher_busy = True
            self._admission.release()
            if first.expired():
                self._shed(first)
                self._batcher_idle()
                continue
            batch = [first]
            rows = first.rows
            if timeout_s <= 0:
                # batch-less mode (documented solo-exact numerics for
                # single-row requests): never coalesce, dispatch as-is
                self._dispatch_batch(batch)
                continue
            t_close = time.monotonic() + timeout_s
            while rows < self.config.max_batch_size:
                with self._cond:
                    took = []
                    for r in list(self._pending):
                        if r.sig == first.sig and \
                                rows + r.rows <= self.config.max_batch_size:
                            self._pending.remove(r)
                            took.append(r)
                            rows += r.rows
                    # a compatible request that no longer FITS means the
                    # batch is capacity-done: ship it now, don't idle out
                    # the timeout window
                    fit_limited = any(r.sig == first.sig
                                      for r in self._pending)
                for r in took:
                    self._admission.release()
                    if r.expired():
                        self._shed(r)
                        rows -= r.rows
                    else:
                        batch.append(r)
                if rows >= self.config.max_batch_size or self._stop \
                        or fit_limited:
                    break
                remaining = t_close - time.monotonic()
                if remaining <= 0:
                    break
                with self._cond:
                    # wait even when incompatible requests sit queued —
                    # they belong to the NEXT batch; new arrivals notify
                    # and re-trigger the scan (worst case one timeout
                    # window of extra latency, never a busy spin)
                    self._cond.wait(timeout=remaining)
            self._dispatch_batch(batch)

    def _dispatch_batch(self, batch):
        """Hand one assembled batch to the worker pool.  ``_inflight``
        is incremented BEFORE the queue put, so the quiesce predicate
        (``_quiesce``) can never observe an empty queue while a batch
        is between the batcher and a worker."""
        with self._cond:
            self._inflight += 1
        self._batch_q.put(batch)
        self._batcher_idle()

    def _batcher_idle(self):
        with self._cond:
            self._batcher_busy = False
            self._cond.notify_all()

    def _worker_loop(self, predictor):
        while True:
            batch = self._batch_q.get()
            if batch is None:
                break
            try:
                self._execute_batch(predictor, batch)
            except BaseException as e:  # noqa: BLE001 - fan the error out
                for r in batch:
                    if not r.future.done():
                        try:
                            r.future.set_exception(e)
                            with self._mlock:
                                self._m_failed.inc()
                        except Exception:  # cancelled concurrently
                            pass
            finally:
                with self._cond:
                    self._inflight -= 1
                    self._cond.notify_all()

    def _execute_batch(self, predictor, batch: List[_Request]):
        now = time.monotonic()
        live = []
        for r in batch:
            if r.future.cancelled():
                continue                 # client gave up; don't compute
            if r.expired(now):
                self._shed(r)
            else:
                live.append(r)
        if not live:
            return
        rows = sum(r.rows for r in live)
        bucket = self._policy.batch_bucket(rows)
        padded = []
        for i in range(len(self.input_names)):
            # one zero-filled bucket allocation per input; each request
            # writes its rows (and, under pad_dynamic_dims, its tail
            # sub-extent) straight into place — no per-request pad
            # copies, no concatenate
            tail = live[0].sig[i][0]
            buf = np.zeros((bucket,) + tail, live[0].arrays[i].dtype)
            off = 0
            for r in live:
                a = r.arrays[i]
                buf[(slice(off, off + r.rows),)
                    + tuple(slice(0, s) for s in a.shape[1:])] = a
                off += r.rows
            padded.append(buf)
        with self._mlock:
            for r in live:
                self._m_qwait.observe((now - r.t_submit) * 1e3)
            self._m_occupancy.observe(rows)
            self._m_fill.observe(rows / bucket)
            self._m_pad_waste.observe((bucket - rows) / bucket)
            self._m_batches.inc()

        traced = [r for r in live if r.ctx is not None] \
            if _rtrace.active else []
        t0 = _tracer.now_ns() if traced else 0
        for r in traced:
            r.ctx.record("queue_wait", r.t_submit_ns, t0)
        outs = self._run_bucketed(predictor, padded)
        outs = [np.asarray(o) for o in outs]
        if traced:
            # fan-in causality: ONE span for the fused batch, each
            # member's own 'execute' span pointing back at it
            t1 = _tracer.now_ns()
            bspan = _rtrace.batch_span(
                "batch::execute", t0, t1, [r.ctx for r in traced],
                rows=rows, bucket=bucket)
            for r in traced:
                r.ctx.record("execute", t0, t1, batch_span=bspan,
                             rows=r.rows)
        off = 0
        done_t = time.monotonic()
        for r in live:
            # copy strict sub-slices: a client holding its rows must not
            # pin the whole bucket-sized output array (padding included)
            result = [o if o.ndim == 0 or (off == 0 and
                                           r.rows == o.shape[0])
                      else o[off:off + r.rows].copy()
                      for o in outs]
            off += r.rows
            if self._complete(r.future, result=result):
                with self._mlock:
                    self._m_done.inc()
                    self._m_latency.observe((done_t - r.t_submit) * 1e3)

    def _run_bucketed(self, predictor, padded: List[np.ndarray]):
        import jax
        import jax.numpy as jnp
        arrays = [jnp.asarray(a) for a in padded]
        key = (tuple((a.shape, str(a.dtype)) for a in arrays),
               self._precision)
        leading = [predictor._materialize_params(),
                   predictor._buffers] if predictor._kind == "layer" \
            else []

        def compile_fn():
            jit_fn = predictor._compiled_call()
            avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in arrays]
            lead_avals = [jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
                for t in leading]
            # AOT artifact store: an engine relaunch loads the
            # persisted executable instead of re-compiling the bucket
            # (utils/artifact_store.py; armed with
            # FLAGS_compile_cache_dir)
            from ..utils.artifact_store import aot_compile
            return aot_compile(
                jit_fn.lower(*lead_avals, *avals),
                label=f"{self.config.name}.bucket")
        exe = self._cache.get_or_compile(key, compile_fn)
        out = exe(*leading, *arrays)
        return predictor._finalize_outputs(out)


def _check_swap_tree(live: Dict[str, object], new: Dict[str, object],
                     what: str):
    """A hot-swap may never half-apply: the incoming tree must match
    the live one key-for-key in shape and dtype.  Weights are
    executable *arguments* — a mismatched swap would mean silent
    retraces (new compiles mid-traffic) or shape errors inside a live
    batch, so it is rejected wholesale before anything is touched."""
    missing = sorted(set(live) - set(new))
    extra = sorted(set(new) - set(live))
    if missing or extra:
        raise ValueError(
            f"swap_weights {what} tree mismatch: missing {missing[:3]}"
            f"{'...' if len(missing) > 3 else ''}, unexpected "
            f"{extra[:3]}{'...' if len(extra) > 3 else ''} — swap "
            "trees must match the served model exactly")
    for k, v in new.items():
        cur = live[k]
        if tuple(v.shape) != tuple(cur.shape) or \
                str(v.dtype) != str(cur.dtype):
            raise ValueError(
                f"swap_weights {what}[{k!r}]: incoming "
                f"{tuple(v.shape)}/{v.dtype} vs served "
                f"{tuple(cur.shape)}/{cur.dtype} — a shape/dtype "
                "change is a new artifact, not a hot-swap")


# ---------------------------------------------------------------------------
# continuous (in-flight) batching for autoregressive generation
# ---------------------------------------------------------------------------

class GenerationEngineConfig:
    """Knobs for :class:`GenerationEngine`.

    max_slots            rows of the running decode batch (the slot
                         count); every compiled step has exactly this
                         batch shape, so empty slots cost compute but
                         never a recompile
    max_length           KV-cache capacity per slot (prompt + generated
                         tokens); defaults to the model's max_seq_len
    max_new_tokens       per-request default generation budget
    max_queue            admission bound on waiting requests (default:
                         FLAGS_serving_queue_depth)
    max_tokens_in_flight token-budget admission bound: the sum of every
                         admitted request's (prompt_len +
                         max_new_tokens) reservation; default
                         max_slots * max_length (i.e. "what the cache
                         can physically hold")
    deadline_ms          default per-request deadline (sheds while
                         queued, like the batch engine); None = none
    aging_s              priority-aging interval for the queue: a
                         waiting request's effective priority improves
                         one class per ``aging_s`` seconds, so batch
                         traffic is delayed under interactive bursts
                         but can never starve (0 disables aging —
                         strict priority order)
    tenant_quotas        per-tenant token-bucket table
                         ``{tenant: {"rate": tokens/s, "burst": max}}``
                         (``"*"`` = default for unlisted tenants);
                         exhaustion sheds typed ``tenant_quota``.
                         Hot-reloadable at runtime via
                         ``engine.set_quotas`` / admission.QuotaWatcher
    prompt_bucket_min    smallest prompt-length bucket (prefill
                         executables are one-per-bucket)
    warmup               pre-populate the decode executable and every
                         prompt-bucket prefill executable at
                         construction (from the AOT artifact store when
                         FLAGS_compile_cache_dir is armed), so
                         time-to-first-token equals steady state from
                         request one; warmed count lands in /healthz.
                         ``"async"`` warms on a background thread and
                         holds ``ready=False`` until done (routers
                         treat not-ready as undispatchable)
    name                 metrics prefix (default "serving" — gives the
                         ``serving.prefill`` / ``serving.decode`` /
                         ``serving.compile`` names the gates assert on)

    Paged-KV knobs (read by :class:`PagedGenerationEngine` only; the
    contiguous engine ignores them):

    block_size           KV block width in tokens; must divide
                         max_length (bit-parity vs contiguous needs the
                         gathered view capacity == contiguous capacity)
    num_blocks           the arena's block-pool size — THE serving HBM
                         budget.  Default max_slots * (max_length /
                         block_size), i.e. the contiguous engine's
                         worst-case footprint; provision it for the
                         expected live tokens instead and the same HBM
                         carries a multiple of the streams
    kv_cache_dtype       'float32' | 'int8' block storage; default
                         reads FLAGS_kv_cache_dtype at construction
    prefix_cache_blocks  content-addressed prefix-cache capacity in
                         blocks (0 disables); default reads
                         FLAGS_prefix_cache_blocks
    speculative_k        draft tokens per decode step from the n-gram
                         prompt-lookup drafter (0 disables); default
                         reads FLAGS_speculative_k
    spec_ngram           trailing n-gram width the drafter matches
    """

    def __init__(self, max_slots: int = 4,
                 max_length: Optional[int] = None,
                 max_new_tokens: int = 64,
                 max_queue: Optional[int] = None,
                 max_tokens_in_flight: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 aging_s: float = 2.0,
                 tenant_quotas: Optional[Dict[str, dict]] = None,
                 prompt_bucket_min: int = 8,
                 warmup: bool = False,
                 name: str = "serving",
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 kv_cache_dtype: Optional[str] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 speculative_k: Optional[int] = None,
                 spec_ngram: int = 2):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_slots = int(max_slots)
        self.max_length = max_length
        self.max_new_tokens = int(max_new_tokens)
        from ..utils import flags as _flags
        if max_queue is None:
            max_queue = int(_flags.get_flag("FLAGS_serving_queue_depth"))
        self.max_queue = int(max_queue)
        self.max_tokens_in_flight = max_tokens_in_flight
        self.deadline_ms = deadline_ms
        self.aging_s = float(aging_s)
        self.tenant_quotas = tenant_quotas
        self.prompt_bucket_min = int(prompt_bucket_min)
        self.warmup = warmup if warmup == "async" else bool(warmup)
        self.name = str(name)
        self.block_size = int(block_size)
        self.num_blocks = num_blocks
        if kv_cache_dtype is None:
            kv_cache_dtype = str(_flags.get_flag("FLAGS_kv_cache_dtype"))
        self.kv_cache_dtype = kv_cache_dtype
        if prefix_cache_blocks is None:
            prefix_cache_blocks = int(
                _flags.get_flag("FLAGS_prefix_cache_blocks"))
        self.prefix_cache_blocks = int(prefix_cache_blocks)
        if speculative_k is None:
            speculative_k = int(_flags.get_flag("FLAGS_speculative_k"))
        self.speculative_k = int(speculative_k)
        self.spec_ngram = int(spec_ngram)


class _GenRequest:
    __slots__ = ("prompt", "max_new", "temperature", "top_k", "top_p",
                 "seed", "eos", "deadline", "budget", "future", "queue",
                 "tokens", "t_submit", "t_first", "t_last", "cancelled",
                 "blocks", "cached_len", "ctx", "t_submit_ns",
                 "finish_reason", "tenant", "priority", "parked")

    def __init__(self, prompt, max_new, temperature, top_k, top_p,
                 seed, eos, deadline, budget, ctx=None, tenant=None,
                 priority=1):
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed
        self.eos = eos
        self.deadline = deadline
        self.budget = budget
        self.future: Future = Future()
        self.queue: "_queue.Queue" = _queue.Queue()
        self.tokens: List[int] = []
        self.t_submit = time.monotonic()
        self.t_first = None
        self.t_last = None
        self.cancelled = False
        self.blocks: List[int] = []    # paged mode: held KV block ids
        self.cached_len = 0            # paged mode: prefix-cache cover
        # request-trace context (profiler/rtrace.py) carried across the
        # submit -> scheduler -> stream thread hops; None when untraced
        self.ctx = ctx
        self.t_submit_ns = _tracer.now_ns() if ctx is not None else 0
        self.finish_reason: Optional[str] = None
        self.tenant: Optional[str] = tenant
        self.priority = int(priority)   # rank into admission.PRIORITIES
        # preemption park state: {"host": payload, "nblocks": n,
        # "pos": absolute position, "last": last sampled token} while
        # the request sits swapped out in host memory; None otherwise
        self.parked: Optional[dict] = None

    @property
    def request_id(self) -> Optional[str]:
        return self.ctx.request_id if self.ctx is not None else None

    @property
    def priority_name(self) -> str:
        return PRIORITIES[self.priority]

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.monotonic()) > self.deadline


class GenerationStream:
    """Handle for one in-flight generation request.

    Iterate it to consume tokens as the engine emits them (ends when
    the request finishes; raises the request's error if it failed), or
    call :meth:`result` to block for the full generated sequence.
    ``cancel()`` asks the scheduler to retire the request at the next
    token boundary — the future then resolves to the partial tokens.
    """

    def __init__(self, req: _GenRequest):
        self._req = req

    def __iter__(self):
        while True:
            item = self._req.queue.get()
            if item is None:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Generated token ids (prompt excluded; eos, when hit,
        included as the last element)."""
        try:
            return self._req.future.result(timeout=timeout)
        except (TimeoutError, _FutureTimeout):
            if self._req.future.done():
                return self._req.future.result()
            raise DeadlineExceeded(
                f"no result within {timeout}s (generation may still "
                "be running; iterate the stream for partial tokens)")

    def cancel(self):
        self._req.cancelled = True

    @property
    def done(self) -> bool:
        return self._req.future.done()


class GenerationEngine:
    """Continuous (in-flight) batching over an autoregressive model.

    The PR 4 :class:`InferenceEngine` coalesces independent one-shot
    requests; LLM chat traffic is iterative — each request is a decode
    LOOP whose length nobody knows up front.  Batching whole requests
    would make every short request wait for the longest batchmate.
    This engine batches at **token boundaries** instead (Orca-style):

    - a fixed bank of ``max_slots`` decode slots runs one fused
      fixed-shape decode step per token for every occupied slot;
    - queued requests are admitted into free slots BETWEEN decode
      steps: their prompts are prefilled (grouped per prompt-length
      bucket) directly into the shared fixed-capacity KV-cache without
      touching running neighbours (``update_mask`` merge);
    - finished rows (eos / token budget / cache full) retire
      immediately and their slot is re-admitted next boundary — the
      batch never drains to refill;
    - tokens stream out per request as they are sampled
      (:class:`GenerationStream`; the HTTP layer exposes SSE).

    Because rows never interact (see ``generation/sampling.py``) and
    every step runs at the same ``(max_slots, ...)`` shapes as a
    solo :meth:`GenerationSession.generate` call over the same session,
    each streamed sequence is **bit-identical** to the sequential
    ``generate()`` reference — chaos soak in ``tools/decode_gate.py``
    pins exactly that.

    Admission extends PR 4's queue-depth bound with a **token budget**
    (``max_tokens_in_flight``): requests reserve prompt + max_new
    tokens at submit and return them at retirement, so overload sheds
    in the unit the hardware is actually provisioned in.

    Metrics (PR 1 registry, ``<name>.`` prefix): ``prefill``/``decode``
    step histograms, ``ttft_ms``, ``inter_token_ms``,
    ``decode.occupancy``, ``tokens_out``, ``compile`` + the admission
    SLO counters.
    """

    def __init__(self, model, config: Optional[GenerationEngineConfig]
                 = None):
        self.config = config or GenerationEngineConfig()
        cfg = self.config
        self.model = model
        max_len = int(cfg.max_length or model.cfg.max_seq_len)
        self.session = self._make_session(model, cfg, max_len)
        self.max_length = self.session.max_length
        S = self.slots = self.session.batch_capacity
        self.metrics_prefix = cfg.name
        self._admission = self._make_admission(cfg)

        from ..profiler import metrics as _metrics
        p = cfg.name
        self._m_ttft = _metrics.histogram(
            f"{p}.ttft_ms", "time to first token (submit -> first "
            "sampled token)")
        self._m_itl = _metrics.histogram(
            f"{p}.inter_token_ms", "gap between consecutive streamed "
            "tokens of one request")
        self._m_occ = _metrics.histogram(
            f"{p}.decode.occupancy", "occupied slots per decode step")
        self._m_done = _metrics.counter(
            f"{p}.request.completed", "requests answered successfully")
        self._m_failed = _metrics.counter(
            f"{p}.request.failed", "requests completed exceptionally")
        self._m_cancelled = _metrics.counter(
            f"{p}.request.cancelled", "requests retired by client "
            "cancel (future resolves to the partial tokens; not "
            "counted as completed — SLO dashboards must not mistake "
            "disconnects for answers)")
        _metrics.gauge(f"{p}.slots", "decode slots").set(S)

        # warmup BEFORE the slot bank exists: the warmup cache is a
        # local that frees on return, so peak device memory stays at
        # one KV cache either way (async warmup trades that guarantee
        # for immediate liveness + an honest ready=False window)
        self.ready = False
        self.warmed_buckets = 0
        if cfg.warmup and cfg.warmup != "async":
            self._warmup()
            self.ready = True
        elif not cfg.warmup:
            self.ready = True

        # slot bank (host-side control state; caches live on device)
        self._init_slot_state()

        self._pending: deque = deque()
        # requests preempted out of their decode slots to host memory
        # (paged engine only; the base engine never parks anything)
        self._parked: List[_GenRequest] = []
        # KV chains shipped in from a prefill replica, queued for the
        # scheduler thread to swap into the arenas at a token boundary
        # (paged engine only — the arenas are loop-thread state, so an
        # HTTP thread may never write them directly)
        self._imports: List[dict] = []
        self._aging_s = float(cfg.aging_s)
        self._cond = _conc.Condition(name=f"{cfg.name}"
                                     ".genengine.cond")
        self._mlock = _conc.Lock(name=f"{cfg.name}.genengine.metrics")
        self._stop = False
        self._paused = False
        self._closed = False
        # pending weight swap, applied by the scheduler BETWEEN token
        # boundaries: (params, buffers, done_event, error_holder)
        self._swap = None
        self._scheduler = _conc.spawn(
            self._loop, name="generation-scheduler")
        if cfg.warmup == "async":
            _conc.spawn(self._warmup_async, name="generation-warmup")

    def _warmup_async(self):
        try:
            self._warmup()
        finally:
            # an engine closed mid-warmup must stay not-ready: flipping
            # it back would make routers dispatch into EngineClosed
            if not self._closed:
                self.ready = True

    # -- construction hooks (PagedGenerationEngine overrides these) ----
    def _make_session(self, model, cfg: GenerationEngineConfig,
                      max_len: int):
        from ..generation import GenerationSession
        return GenerationSession(
            model, batch_capacity=cfg.max_slots, max_length=max_len,
            prompt_bucket_min=cfg.prompt_bucket_min, name=cfg.name)

    def _make_admission(self, cfg: GenerationEngineConfig
                        ) -> AdmissionController:
        budget = cfg.max_tokens_in_flight
        if budget is None:
            budget = self.slots * self.max_length
        return AdmissionController(
            cfg.max_queue, max_rows=None, name=cfg.name,
            max_tokens=int(budget),
            quotas=self._make_quotas(cfg))

    @staticmethod
    def _make_quotas(cfg: GenerationEngineConfig):
        if not cfg.tenant_quotas:
            return None
        return TenantQuotaTable(cfg.tenant_quotas)

    def set_quotas(self, quotas) -> int:
        """Hot-swap the per-tenant quota table (dict of tenant ->
        ``{"rate": tokens/s, "burst": tokens}``, a built
        :class:`TenantQuotaTable`, or None to drop quota enforcement).
        Validated before publication; in-flight bucket levels carry
        over clamped to the new burst.  Returns the table generation.
        This is the :class:`QuotaWatcher` apply hook — throttle a
        tenant without a restart."""
        return self._admission.set_quotas(quotas)

    def _init_slot_arrays(self):
        S = self.slots
        self._slot_req: List[Optional[_GenRequest]] = [None] * S
        self._positions = np.zeros((S,), np.int32)
        self._last_tok = np.zeros((S,), np.int32)
        self._keys = np.zeros((S, 2), np.uint32)
        self._temps = np.zeros((S,), np.float32)
        self._tks = np.zeros((S,), np.int32)
        self._tps = np.ones((S,), np.float32)

    def _init_slot_state(self):
        self._caches = self.session.init_caches()
        self._init_slot_arrays()
        if _memscope.active:
            self._note_memory_tags()

    # -- memory accounting --------------------------------------------
    def _kv_arena_bytes(self) -> int:
        """Device bytes held by the KV store (the contiguous engine's
        per-slot cache bank; the paged engine overrides with the
        block-pool arena)."""
        return _memscope.tree_nbytes(getattr(self, "_caches", None))

    def _params_bytes(self) -> int:
        try:
            return _memscope.tree_nbytes(self.model.functional_state())
        except Exception:       # noqa: BLE001 — accounting never throws
            return 0

    def _note_memory_tags(self):
        """Attribute this engine's exactly-known footprints to the
        memscope tags (callers gate on the predicate)."""
        _memscope.set_tag_bytes("params", self._params_bytes())
        _memscope.set_tag_bytes("kv_arena", self._kv_arena_bytes())

    def memory_breakdown(self) -> Dict[str, int]:
        """The ``/healthz`` memory fields: where this engine's HBM
        goes, next to the ``kv_blocks_*`` capacity signals.  The peak
        field samples the census only when accounting is armed, so
        unflagged health probes stay attribute-math cheap."""
        return {
            "mem_params_bytes": self._params_bytes(),
            "mem_kv_arena_bytes": self._kv_arena_bytes(),
            "mem_prefix_cache_bytes": 0,
            "mem_peak_step_bytes":
                _memscope.peak_bytes() if _memscope.active else 0,
        }

    def _token_reservation(self, prompt, max_new: int) -> int:
        """Tokens to reserve against the admission budget at submit —
        the contiguous engine's worst case (prompt + max_new; a slot
        physically holds that many cache rows whether used or not).
        The paged engine returns 0: its admission signal is live
        block-pool occupancy, not a worst-case reservation."""
        return int(prompt.size) + int(max_new)

    def _warmup(self):
        """One masked-out prefill per prompt bucket plus one decode
        step over throwaway caches: populates the session's executable
        cache (through the AOT artifact store when armed) without
        touching any real slot state.  All-False update masks keep the
        warmup mathematically inert; ``live_rows=0`` keeps it out of
        the token metrics."""
        from .bucketing import seq_buckets
        S = self.slots
        keys = np.zeros((S, 2), np.uint32)
        temps = np.zeros((S,), np.float32)
        tks = np.zeros((S,), np.int32)
        tps = np.ones((S,), np.float32)
        errors = []
        caches = self.session.init_caches()
        for pb in seq_buckets(self.max_length,
                              self.config.prompt_bucket_min):
            try:
                _tok, caches = self.session.prefill(
                    caches, np.zeros((S, pb), np.int32),
                    np.ones((S,), np.int32), np.zeros((S,), bool),
                    keys, temps, tks, tps)
            except Exception as e:  # noqa: BLE001 — best-effort, but loud
                errors.append((f"prefill:{pb}", e))
        try:
            self.session.decode(
                caches, np.zeros((S,), np.int32),
                np.zeros((S,), np.int32), keys, temps, tks, tps,
                live_rows=0)
        except Exception as e:      # noqa: BLE001
            errors.append(("decode", e))
        self._finish_warmup(errors)

    def _finish_warmup(self, errors):
        """Shared warmup tail (both engine flavors): loud best-effort
        failure report + the ``decode_warmed_buckets`` gauge."""
        if errors:
            import warnings
            warnings.warn(
                f"{type(self).__name__} warmup failed for "
                f"{len(errors)} step(s) (first: {errors[0][0]}: "
                f"{errors[0][1]!r}); those buckets will compile on "
                "first use", RuntimeWarning, stacklevel=4)
        self.warmed_buckets = len(self.session._cache)
        from ..profiler import metrics as _metrics
        # decode_-prefixed: a dual-engine server with both configs at
        # the default name='serving' must not have the batch engine's
        # warmed_buckets gauge overwritten (mirrors the /healthz key)
        _metrics.gauge(
            f"{self.metrics_prefix}.decode_warmed_buckets",
            "prefill/decode executables pre-populated at engine "
            "construction").set(self.warmed_buckets)

    # -- client surface ------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               do_sample: bool = False, temperature: float = 1.0,
               top_k: int = 0, top_p: float = 1.0, seed: int = 0,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = "default",
               trace_ctx=None, tenant: Optional[str] = None,
               priority: Optional[str] = None) -> GenerationStream:
        """Enqueue one prompt; returns a :class:`GenerationStream`.
        Raises :class:`RequestRejected` at admission (``queue_full`` /
        ``token_budget`` / ``too_large`` / ``tenant_quota`` /
        ``closed``); the ``serve.request`` chaos site can fail or
        delay here.  ``tenant`` charges the request against that
        tenant's token bucket (when quotas are configured) and labels
        its per-tenant metrics; ``priority`` is one of
        ``admission.PRIORITIES`` ("interactive" < "standard" <
        "batch") and orders dequeue — lower classes only run when no
        higher class is waiting, subject to bounded aging
        (``aging_s``).  ``trace_ctx`` (an rtrace TraceContext, usually
        built by the HTTP layer from ``traceparent``/``X-Request-Id``)
        makes every hop of this request — admission verdict, queue
        wait, prefill, each decode boundary — emit request-scoped
        spans."""
        prompt = np.asarray(getattr(prompt, "_data", prompt))
        prompt = prompt.reshape(-1).astype(np.int32)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.config.max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        rank = priority_rank(priority)   # ValueError on unknown class
        traced = trace_ctx is not None and _rtrace.active
        t_adm = _tracer.now_ns() if traced else 0
        budget = self._token_reservation(prompt, max_new)
        # the quota charge is the request's true worst-case token cost
        # regardless of engine flavor (the paged engine's admission
        # budget is 0 — occupancy-driven — but a tenant's bucket must
        # still drain by what the request can consume)
        quota_cost = int(prompt.size) + max_new
        try:
            if prompt.size >= self.max_length:
                # route through the controller so the per-reason counter
                # and its lock discipline apply (the gates assert exact
                # counts)
                self._admission._reject(
                    "too_large",
                    f"prompt of {prompt.size} tokens leaves no room in "
                    f"the {self.max_length}-slot KV-cache")
            from ..utils import chaos as _chaos
            if _chaos.active:
                _chaos.hit("serve.request")
            self._admission.acquire(
                tokens=budget, tenant=tenant,
                priority=PRIORITIES[rank], quota_tokens=quota_cost)
        except RequestRejected as e:
            if traced:
                # a rejected request still leaves a terminated span
                # carrying the verdict — post-mortems start from WHY
                trace_ctx.record("admission", t_adm, outcome=e.reason,
                                 terminated=True)
            raise
        except Exception as e:
            if traced:
                trace_ctx.record("admission", t_adm,
                                 outcome=type(e).__name__,
                                 terminated=True)
            raise
        if traced:
            trace_ctx.record("admission", t_adm, outcome="admitted")
        if deadline_ms == "default":
            deadline_ms = self.config.deadline_ms
        req = _GenRequest(
            prompt, max_new,
            float(temperature) if do_sample else 0.0, int(top_k),
            float(top_p), int(seed), eos_token_id,
            deadline_from_ms(deadline_ms), budget, ctx=trace_ctx,
            tenant=tenant, priority=rank)
        with self._cond:
            if self._closed:
                self._admission.release()
                self._admission.release_tokens(budget)
                if traced:
                    trace_ctx.record("queue_wait", req.t_submit_ns,
                                     outcome="closed", terminated=True)
                raise EngineClosed()
            self._pending.append(req)
            self._cond.notify()
        return GenerationStream(req)

    def generate(self, prompt, timeout: Optional[float] = None,
                 **kw) -> np.ndarray:
        """Blocking submit: the full generated sequence."""
        return self.submit(prompt, **kw).result(timeout=timeout)

    # -- operations ----------------------------------------------------
    def pause(self):
        """Stop admitting queued requests into slots (running slots
        keep decoding); admission keeps filling up to the bounds, then
        sheds — the deterministic-overload test hook."""
        with self._cond:
            self._paused = True

    def resume(self):
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    def stats(self) -> Dict[str, object]:
        from ..profiler import metrics as _metrics
        snap = _metrics.snapshot()
        return {k: v for k, v in snap.items()
                if k.startswith(self.metrics_prefix + ".")}

    @property
    def occupancy(self) -> int:
        """Occupied decode slots — the live-load signal a fleet
        router's least-loaded dispatch reads from the registry."""
        return sum(1 for r in self._slot_req if r is not None)

    @property
    def parked(self) -> int:
        """Requests currently preempted to host memory (paged engine
        only; always 0 on the contiguous engine)."""
        return len(self._parked)

    def swap_weights(self, params, buffers=None, *,
                     timeout: float = 60.0):
        """Zero-downtime weight hot-swap: replace the model's weight
        set BETWEEN engine steps.

        The scheduler applies the swap at the next token boundary (or
        immediately when idle): running decodes finish their current
        fused step on the old weights, every subsequent prefill/decode
        reads the new ones — no stream drops, no slot resets, and no
        recompile (the session's executables take weights as
        *arguments*; the tree is validated shape/dtype-exact first).
        Blocks until the scheduler has applied the swap; raises the
        application error if it failed (the old weights stay live)."""
        import jax.numpy as jnp
        cur_p, cur_b = self.model.functional_state()
        new_p = {k: jnp.asarray(getattr(v, "_data", v))
                 for k, v in params.items()}
        _check_swap_tree(cur_p, new_p, "params")
        new_b = None
        if buffers is not None:
            new_b = {k: jnp.asarray(getattr(v, "_data", v))
                     for k, v in buffers.items()}
            _check_swap_tree(cur_b, new_b, "buffers")
        done = threading.Event()
        holder: Dict[str, BaseException] = {}
        with self._cond:
            if self._closed:
                raise EngineClosed()
            if self._swap is not None:
                raise RuntimeError(
                    "another weight swap is already pending")
            self._swap = (new_p, new_b, done, holder)
            self._cond.notify_all()
        if not done.wait(timeout):
            with self._cond:
                if self._swap is not None and self._swap[2] is done:
                    # withdrawn before the scheduler claimed it: the
                    # swap will never apply, the timeout is honest
                    self._swap = None
                    raise TimeoutError(
                        f"weight swap not applied within {timeout}s "
                        "(scheduler wedged mid-step?)")
            # the scheduler popped the swap while we timed out — it is
            # applying RIGHT NOW; raising here would leave the caller
            # believing the old weights are live while the served set
            # flips under it.  Wait the application out.
            if not done.wait(timeout):
                raise TimeoutError(
                    f"weight swap claimed by the scheduler but not "
                    f"applied within another {timeout}s (model rebind "
                    "wedged?)")
        err = holder.get("error")
        if err is not None:
            raise err
        from ..profiler import metrics as _metrics
        with self._mlock:
            _metrics.counter(
                f"{self.metrics_prefix}.weight_swaps",
                "zero-downtime weight hot-swaps applied").inc()
        if _flight.active:
            _flight.note("serve", "weights_swap",
                         engine=self.metrics_prefix)

    def _apply_swap(self):
        """Scheduler-side swap application — called only between
        boundaries, on the scheduler thread, so no executable is
        mid-step while the model's arrays are rebound."""
        with self._cond:
            swap, self._swap = self._swap, None
        if swap is None:
            return
        new_p, new_b, done, holder = swap
        try:
            self.model.load_functional_state(new_p, new_b)
        except BaseException as e:     # noqa: BLE001 — surfaced to caller
            holder["error"] = e
        finally:
            done.set()

    def _drain_swap(self, exc: BaseException):
        """Resolve a swap the scheduler will never apply (engine
        closing) so the caller's wait can't hang."""
        with self._cond:
            swap, self._swap = self._swap, None
        if swap is not None:
            swap[3]["error"] = exc
            swap[2].set()

    def close(self, timeout: Optional[float] = 60.0):
        """Reject new work, let queued + running requests finish, stop
        the scheduler."""
        self.ready = False
        self._admission.close()
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stop = True
            self._paused = False
            self._cond.notify_all()
        self._scheduler.join(timeout=timeout)
        self._drain_swap(EngineClosed())

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- scheduler -----------------------------------------------------
    def _occupied(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is not None]

    def _loop(self):
        while True:
            with self._cond:
                while self._swap is None and not self._imports and \
                        ((not self._stop and not self._pending
                          and not self._parked
                          and not self._occupied()) or
                         (self._paused and not self._occupied()
                          and not self._stop)):
                    self._cond.wait()
                if self._stop and not self._pending \
                        and not self._parked and not self._occupied():
                    break
            if self._swap is not None:
                # between boundaries by construction: the previous
                # fused step has returned, the next hasn't dispatched
                self._apply_swap()
                continue
            try:
                self._admit()
                occ = self._occupied()
                if not occ:
                    continue
                self._decode_round(occ)
            except BaseException as e:  # noqa: BLE001 — fail everything in flight
                self._fail_all(e)

    def _trace_boundary(self, name: str, t0: int, t1: int,
                        slots: List[int], **fields):
        """Request-trace accounting for one fused engine step: ONE
        ``batch::<name>`` span linked to every traced member's root
        (fan-in causality) plus a per-member ``<name>`` child span
        pointing back at it.  Call sites gate on ``_rtrace.active``."""
        ctxs, reqs = [], []
        for s in slots:
            r = self._slot_req[s]
            if r is not None and r.ctx is not None:
                ctxs.append(r.ctx)
                reqs.append((s, r))
        if not ctxs:
            return
        bspan = _rtrace.batch_span(f"batch::{name}", t0, t1, ctxs,
                                   **fields)
        for s, r in reqs:
            r.ctx.record(name, t0, t1, batch_span=bspan, slot=s,
                         position=int(self._positions[s]))

    def _decode_round(self, occ: List[int]):
        """One token boundary: a fused decode step for every occupied
        slot (the paged engine overrides this with block-table decode
        and, when armed, speculative verify)."""
        t0 = _tracer.now_ns() if _rtrace.active else 0
        tok, self._caches = self.session.decode(
            self._caches, self._last_tok, self._positions,
            self._keys, self._temps, self._tks, self._tps,
            live_rows=len(occ))
        if t0:
            self._trace_boundary("decode", t0, _tracer.now_ns(), occ,
                                 occupancy=len(occ))
        with self._mlock:
            self._m_occ.observe(len(occ))
        self._positions = self._positions + 1
        # copy: np.asarray over a device buffer is read-only,
        # and _admit writes per-slot entries in place
        self._last_tok = np.array(tok, np.int32)
        for s in occ:
            self._emit(s, int(tok[s]))

    def _pop_pending(self) -> _GenRequest:
        """Priority-ordered dequeue with bounded aging (caller holds
        ``_cond``, ``_pending`` non-empty).  Effective rank =
        ``max(0, rank - waited // aging_s)`` — a batch request climbs
        one priority class per ``aging_s`` seconds queued, so it
        cannot starve forever behind a sustained interactive stream;
        ties break FIFO by queue position.  ``aging_s=0`` disables
        aging (strict priority)."""
        aging = self._aging_s
        now = time.monotonic() if aging > 0 else 0.0
        best_i, best_key = 0, None
        for i, r in enumerate(self._pending):
            eff = r.priority
            if aging > 0:
                eff = max(0, eff - int((now - r.t_submit) / aging))
            key = (eff, i)
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        req = self._pending[best_i]
        del self._pending[best_i]
        return req

    def _admit(self):
        """Token-boundary admission: move queued requests into free
        slots, grouped per prompt-length bucket, one masked prefill per
        group; running neighbours' cache rows are untouched."""
        took: List[Tuple[int, _GenRequest]] = []
        with self._cond:
            if self._paused:
                return
            free = [i for i, r in enumerate(self._slot_req)
                    if r is None]
            while self._pending and free:
                req = self._pop_pending()
                self._admission.release()
                if req.expired():
                    self._shed(req)
                    continue
                if req.cancelled:
                    self._retire(req, slot=None)
                    continue
                took.append((free.pop(0), req))
        if not took:
            return
        groups: Dict[int, List[Tuple[int, _GenRequest]]] = {}
        for slot, req in took:
            pb = self.session.prompt_bucket(len(req.prompt))
            groups.setdefault(pb, []).append((slot, req))
        for pb, members in sorted(groups.items()):
            S = self.slots
            ids = np.zeros((S, pb), np.int32)
            plens = np.ones((S,), np.int32)
            mask = np.zeros((S,), bool)
            for slot, req in members:
                n = len(req.prompt)
                ids[slot, :n] = req.prompt
                plens[slot] = n
                mask[slot] = True
                self._slot_req[slot] = req
                self._keys[slot] = np.asarray(
                    jax_random_key(req.seed), np.uint32)
                self._temps[slot] = req.temperature
                self._tks[slot] = req.top_k
                self._tps[slot] = req.top_p
                self._note_slot_admit(slot, req)
            t0 = _tracer.now_ns() if _rtrace.active else 0
            tok, self._caches = self.session.prefill(
                self._caches, ids, plens, mask, self._keys,
                self._temps, self._tks, self._tps)
            if t0:
                self._trace_boundary(
                    "prefill", t0, _tracer.now_ns(),
                    [s for s, _r in members], bucket=pb)
            for slot, req in members:
                self._positions[slot] = plens[slot]
                self._last_tok[slot] = tok[slot]
                self._emit(slot, int(tok[slot]))

    def _note_slot_admit(self, slot: int, req: _GenRequest):
        """Queue-wait span closes + flight slot-admit event for one
        request entering a decode slot."""
        if req.ctx is not None and _rtrace.active:
            req.ctx.record("queue_wait", req.t_submit_ns, slot=slot)
        if _flight.active:
            _flight.note("serve", "slot_admit",
                         engine=self.metrics_prefix, slot=slot,
                         request=req.request_id,
                         prompt=int(req.prompt.size))

    def _emit(self, slot: int, tok: int):
        req = self._slot_req[slot]
        if req is None:
            return
        now = time.monotonic()
        with self._mlock:
            if req.t_first is None:
                req.t_first = now
                self._m_ttft.observe((now - req.t_submit) * 1e3)
            else:
                self._m_itl.observe((now - req.t_last) * 1e3)
        req.t_last = now
        req.tokens.append(tok)
        req.queue.put(tok)
        hit_eos = req.eos is not None and tok == int(req.eos)
        out_of_room = self._positions[slot] + 1 >= self.max_length
        budget_done = len(req.tokens) >= req.max_new
        if hit_eos or req.cancelled or out_of_room or budget_done:
            req.finish_reason = (
                "cancelled" if req.cancelled else
                "eos" if hit_eos else
                "cache_full" if out_of_room and not budget_done else
                "max_new_tokens")
            self._retire(req, slot)

    def _release_resources(self, req: _GenRequest):
        """THE accounting seam: every way a request leaves the engine
        (finish, cancel, deadline shed, kv-block shed, engine failure)
        returns its reservations through this one method — token budget
        here, plus KV block references in the paged override.  One
        place to audit means no path can leak."""
        self._admission.release_tokens(req.budget)

    def _note_tenant(self, req: _GenRequest, what: str, n: int = 1,
                     latency_ms: Optional[float] = None):
        """One bump on a ``<prefix>.tenant.<t>.*`` accounting series —
        the tenant-labeled counters a fleet ``/metrics`` aggregation
        sums across replicas.  No-op for untenanted requests.  Callers
        must NOT hold ``_mlock``."""
        if req.tenant is None:
            return
        from ..profiler import metrics as _metrics
        base = f"{self.metrics_prefix}.tenant.{req.tenant}"
        with self._mlock:
            _metrics.counter(
                f"{base}.{what}",
                f"per-tenant {what} (requests or tokens)").inc(n)
            if latency_ms is not None:
                _metrics.histogram(
                    f"{base}.latency_ms",
                    "per-tenant end-to-end request latency"
                    ).observe(latency_ms)

    def _retire(self, req: _GenRequest, slot: Optional[int]):
        if slot is not None:
            self._slot_req[slot] = None
        self._release_resources(req)
        reason = req.finish_reason or \
            ("cancelled" if req.cancelled else "done")
        if _flight.active:
            _flight.note("serve", "slot_retire",
                         engine=self.metrics_prefix, slot=slot,
                         request=req.request_id, reason=reason,
                         tokens=len(req.tokens))
        if not req.future.done():
            req.future.set_result(np.asarray(req.tokens, np.int32))
            with self._mlock:
                if req.cancelled:
                    self._m_cancelled.inc()
                else:
                    self._m_done.inc()
            if not req.cancelled:
                self._note_tenant(
                    req, "completed",
                    latency_ms=(time.monotonic() - req.t_submit) * 1e3)
                self._note_tenant(req, "tokens_out", len(req.tokens))
        req.queue.put(None)

    def _shed(self, req: _GenRequest):
        with self._mlock:
            self._admission.shed_deadline()
        self._note_tenant(req, "shed")
        self._release_resources(req)
        if req.ctx is not None and _rtrace.active:
            req.ctx.record("queue_wait", req.t_submit_ns,
                           outcome="shed_deadline", terminated=True)
        exc = DeadlineExceeded(
            "request deadline expired while queued (engine overloaded "
            "relative to the deadline)")
        if not req.future.done():
            req.future.set_exception(exc)
        req.queue.put(exc)

    def _fail_all(self, exc: BaseException):
        with self._cond:
            pending = list(self._pending)
            self._pending.clear()
            parked, self._parked = list(self._parked), []
        for r in parked:
            r.parked = None   # drop the host-side swap payload
        victims = pending + parked + \
            [r for r in self._slot_req if r is not None]
        self._slot_req = [None] * self.slots
        if _memscope.active and _memscope.is_oom(exc):
            # OOM forensics before the generic failure dump: census +
            # pool occupancy + the flight ring, then victims fail with
            # the original error exactly as before
            _memscope.oom_dump(
                exc, context=f"engine:{self.metrics_prefix}",
                pool=getattr(self, "pool", None),
                prefix_cache=getattr(self, "prefix_cache", None))
        if _flight.active:
            _flight.note("serve", "engine_failure",
                         engine=self.metrics_prefix,
                         error=f"{type(exc).__name__}: {exc}",
                         victims=len(victims))
            # post-mortem artifact: the last N things this engine did,
            # written next to the gang's other dumps when
            # PADDLE_FLIGHT_DIR is configured
            _flight.dump(reason="engine-failure")
        for req in victims:
            self._release_resources(req)
            if req.ctx is not None and _rtrace.active:
                req.ctx.record("failed", req.t_submit_ns,
                               outcome=type(exc).__name__,
                               terminated=True)
            if not req.future.done():
                req.future.set_exception(exc)
                with self._mlock:
                    self._m_failed.inc()
            req.queue.put(exc)
        for _ in pending:
            self._admission.release()


def jax_random_key(seed: int):
    """Per-request base PRNG key — derived from the request's OWN seed
    so its sampled stream is independent of slot placement and
    batchmates (the decode-gate parity contract)."""
    import jax
    return np.asarray(jax.random.PRNGKey(int(seed)), np.uint32)


# ---------------------------------------------------------------------------
# paged-KV serving memory: block-pool continuous batching
# ---------------------------------------------------------------------------

class PagedGenerationEngine(GenerationEngine):
    """:class:`GenerationEngine` over the paged KV-cache subsystem
    (``paddle_tpu/generation/paged_kv.py``): same continuous-batching
    scheduler, same client surface, but KV memory is a shared
    refcounted block pool instead of one worst-case ``(max_length, H,
    D)`` buffer per slot.

    What changes operationally:

    - **admission** switches from the worst-case token budget to live
      block-pool occupancy: ``submit`` reserves nothing (the
      ``<name>.kv.blocks_in_flight`` gauge replaces
      ``tokens_in_flight`` as the admission signal), blocks are
      allocated lazily as each request actually grows, and a pool that
      cannot supply a block sheds the request with a typed
      ``RequestRejected(reason="kv_blocks")`` — never a corrupted
      batch (the ``kv.block_alloc`` chaos site injects exactly this);
    - **prefix cache**: prompts sharing a prefix with any earlier
      prompt (sha256 content-addressed, ``prefix_cache_blocks`` cap)
      skip straight to a chunked prefill of the uncached suffix —
      shared system prompts prefill once; partially shared blocks are
      copied-on-write before a request appends into them;
    - **int8 KV** (``kv_cache_dtype='int8'``): blocks stored int8 with
      per-token-per-head scales, dequantized inside the attention
      executable — ~3.6x less HBM per block (k+v int8 plus two f32
      per-token-per-head scale planes; 4096 -> 1152 bytes/token on
      the bench config), tolerance-level numerics;
    - **speculative decoding** (``speculative_k > 0``): the n-gram
      prompt-lookup drafter proposes up to k tokens per boundary and
      ONE batched verify executable commits the longest agreeing
      prefix — streams stay bit-identical to non-speculative decode
      (greedy and sampled; the drafter only changes how many forwards
      produce them).  ``<name>.spec.proposed`` / ``.accepted``
      counters and the ``.accept_rate`` gauge account it.

    Executable population stays bounded exactly like the contiguous
    engine: block tables and pool state are step *data*, never part of
    a compile key — one chunk executable per pow2 suffix bucket, one
    width-1 decode, one verify width, one block-copy helper.

    With ``block_size`` dividing ``max_length``, paged greedy decode
    is bit-exact against the contiguous PR 6 references
    (``tools/paged_gate.py`` pins it under chaos).
    """

    # -- construction hooks -------------------------------------------
    def _make_session(self, model, cfg: GenerationEngineConfig,
                      max_len: int):
        from ..generation import PagedGenerationSession
        return PagedGenerationSession(
            model, batch_capacity=cfg.max_slots, max_length=max_len,
            block_size=cfg.block_size, num_blocks=cfg.num_blocks,
            kv_dtype=cfg.kv_cache_dtype,
            prompt_bucket_min=cfg.prompt_bucket_min, name=cfg.name)

    def _make_admission(self, cfg: GenerationEngineConfig
                        ) -> AdmissionController:
        # no token budget: paged admission is queue depth at submit
        # plus live block-pool occupancy at allocation time
        return AdmissionController(
            cfg.max_queue, max_rows=None, name=cfg.name,
            max_tokens=None, quotas=self._make_quotas(cfg))

    def _token_reservation(self, prompt, max_new: int) -> int:
        return 0

    def _init_slot_state(self):
        from ..generation import BlockPool, PrefixCache
        cfg = self.config
        ses = self.session
        self._init_slot_arrays()
        self._arenas = ses.init_arenas()
        self._table = np.full((self.slots, ses.blocks_per_slot), -1,
                              np.int32)
        self.pool = BlockPool(ses.num_blocks, ses.block_size,
                              name=cfg.name)
        self.pool.block_bytes = ses.arena_bytes_per_block()
        self.prefix_cache = PrefixCache(
            self.pool, cfg.prefix_cache_blocks, name=cfg.name)
        self.speculative_k = max(int(cfg.speculative_k), 0)
        from ..profiler import metrics as _metrics
        p = cfg.name
        self._m_spec_proposed = _metrics.counter(
            f"{p}.spec.proposed", "draft tokens proposed by the "
            "prompt-lookup drafter")
        self._m_spec_accepted = _metrics.counter(
            f"{p}.spec.accepted", "draft tokens the verify step "
            "accepted (each one a forward pass saved)")
        self._g_spec_rate = _metrics.gauge(
            f"{p}.spec.accept_rate", "accepted/proposed draft ratio "
            "(engine lifetime)")
        self._m_preempted = _metrics.counter(
            f"{p}.request.preempted", "decode slots preempted to host "
            "memory under block-pool pressure")
        self._m_resumed = _metrics.counter(
            f"{p}.request.resumed", "preempted requests swapped back "
            "into a decode slot")
        self._g_parked = _metrics.gauge(
            f"{p}.requests_parked", "requests currently swapped out "
            "to host memory awaiting blocks")
        if _memscope.active:
            self._note_memory_tags()

    def _kv_arena_bytes(self) -> int:
        # paged: the pre-allocated arena, not the live-array walk
        return int(self.pool.num_blocks) * \
            int(getattr(self.pool, "block_bytes", 0))

    def memory_breakdown(self) -> Dict[str, int]:
        out = super().memory_breakdown()
        out["mem_prefix_cache_bytes"] = \
            len(self.prefix_cache) * \
            int(getattr(self.pool, "block_bytes", 0))
        return out

    def _warmup(self):
        """Every chunk-width executable (one per pow2 suffix bucket +
        the width-1 decode + the verify width when speculative is
        armed) compiled over throwaway arenas with all-zero feeds —
        every write is dropped by the table, so warmup is
        mathematically inert and peak memory stays one arena set."""
        from .bucketing import seq_buckets
        ses = self.session
        S = self.slots
        keys = np.zeros((S, 2), np.uint32)
        temps = np.zeros((S,), np.float32)
        tks = np.zeros((S,), np.int32)
        tps = np.ones((S,), np.float32)
        zeros = np.zeros((S,), np.int32)
        arenas = ses.init_arenas()
        table = np.full((S, ses.blocks_per_slot), -1, np.int32)
        errors = []
        for pb in seq_buckets(self.max_length,
                              self.config.prompt_bucket_min):
            try:
                _tok, arenas = ses.prefill(
                    arenas, table, np.zeros((S, pb), np.int32), zeros,
                    zeros, keys, temps, tks, tps, live_rows=0)
            except Exception as e:  # noqa: BLE001 — best-effort, but loud
                errors.append((f"pchunk:{pb}", e))
        try:
            ses.decode(arenas, table, zeros, zeros, keys, temps, tks,
                       tps, live_rows=0)
        except Exception as e:      # noqa: BLE001
            errors.append(("pchunk:1", e))
        if self.config.speculative_k > 0:
            W = int(self.config.speculative_k) + 1
            try:
                ses.verify(arenas, table, np.zeros((S, W), np.int32),
                           zeros, zeros, keys, temps, tks, tps,
                           live_rows=0)
            except Exception as e:  # noqa: BLE001
                errors.append((f"pverify:{W}", e))
        self._finish_warmup(errors)

    # -- block accounting ---------------------------------------------
    def _release_resources(self, req: _GenRequest):
        """The accounting seam, paged edition: token budget (a no-op —
        paged submit reserves none) AND every KV block reference the
        request holds, in one place."""
        super()._release_resources(req)
        if req.blocks:
            self.pool.decref(req.blocks)
            req.blocks = []

    # -- disaggregated KV transfer (serving/disagg.py drives these) ----
    def export_prefix_chain(self, tokens) -> Optional[bytes]:
        """Serialize this engine's longest cached prefix chain for
        ``tokens`` into a ``kv_wire`` blob (``None`` on cache miss) —
        the prefill side of disaggregated serving.

        Thread-safe from any thread: ``lookup`` transfers pool
        references that pin the chain for the duration, the cached
        blocks are immutable by the copy-on-write discipline (a writer
        always copies a shared block first), and the arena gather is
        pure — a concurrent decode round can replace ``self._arenas``
        without invalidating the snapshot this reads."""
        from ..generation import kv_wire
        toks = np.ascontiguousarray(tokens, dtype=np.int32).reshape(-1)
        chain, covered = self.prefix_cache.lookup(toks)
        if not chain:
            return None
        try:
            payload = self.session.swap_out_blocks(self._arenas, chain)
            return kv_wire.serialize_chain(
                toks[:covered], covered, self.session.block_size,
                payload)
        finally:
            self.pool.decref(chain)

    def import_prefix_chain(self, blob: bytes,
                            timeout: Optional[float] = 300.0) -> int:
        """Verify a ``kv_wire`` blob, allocate blocks for it, and hand
        it to the scheduler thread to swap into the arenas and insert
        into the prefix cache at the next token boundary — the decode
        side of disaggregated serving.  Returns the covered token
        count; subsequent submits of a prompt sharing the prefix hit
        the cache exactly as if this engine had prefilled it.

        Raises :class:`~..generation.kv_wire.KVTransferCorrupt`
        (counted, zero unverified bytes adopted) on a bad blob,
        ``BlockPoolExhausted`` when the pool cannot hold the chain,
        and :class:`EngineClosed` on a closed/stopping engine —
        in every case the caller simply decodes without the shipment
        (a local re-prefill), never over suspect KV."""
        from ..generation import blocks_for_tokens, kv_wire
        doc = kv_wire.deserialize_chain(
            blob, expect_block_size=self.session.block_size,
            expect_spec=self.session.block_spec(self._arenas))
        blocks = self.pool.alloc(blocks_for_tokens(
            doc["covered"], self.session.block_size))
        imp = {"tokens": doc["tokens"], "covered": doc["covered"],
               "blocks": blocks, "payload": doc["payload"],
               "done": threading.Event(), "error": None}
        with self._cond:
            if self._closed or self._stop:
                self.pool.decref(blocks)
                raise EngineClosed("generation engine is closed")
            self._imports.append(imp)
            self._cond.notify_all()
        if not imp["done"].wait(timeout):
            # leave the entry queued: the scheduler still owns applying
            # it and the decref that balances the alloc above
            raise TimeoutError("KV chain import timed out")
        if imp["error"] is not None:
            raise imp["error"]
        return imp["covered"]

    def _apply_imports(self):
        """Adopt queued shipped-in chains (scheduler thread, token
        boundary): ``device_put`` each payload into its pre-allocated
        blocks, then offer the chain to the prefix cache (which takes
        its own references).  The import's alloc-time hold is released
        either way, so retained blocks end cache-owned at refcount 1
        and already-cached duplicates free immediately.  A failing
        import faults only its caller, never the engine."""
        while True:
            with self._cond:
                if not self._imports:
                    return
                imp = self._imports.pop(0)
            try:
                self._arenas = self.session.swap_in_blocks(
                    self._arenas, imp["blocks"], imp["payload"])
                self.prefix_cache.insert(imp["tokens"], imp["blocks"])
                if _flight.active:
                    _flight.note("kv", "chain_import",
                                 engine=self.metrics_prefix,
                                 covered=int(imp["covered"]),
                                 blocks=len(imp["blocks"]))
            except BaseException as e:  # noqa: BLE001 — fault the importer only
                imp["error"] = e
            finally:
                self.pool.decref(imp["blocks"])
                imp["done"].set()

    def _drain_imports(self, exc: BaseException):
        """Fail every queued import (engine close / loop death):
        release the alloc-time holds and wake the waiting callers."""
        with self._cond:
            imps, self._imports = list(self._imports), []
        for imp in imps:
            self.pool.decref(imp["blocks"])
            imp["error"] = exc
            imp["done"].set()

    def _prepare_slot(self, slot: int, req: _GenRequest):
        """Prefix-cache lookup + block allocation + copy-on-write for
        one admitted request; fills the slot's table row.  Returns the
        request's COW ``(src, dst)`` block pair (or ``None``) instead
        of dispatching the copy — the caller batches all pairs of one
        admission round into a single ``copy_blocks`` call, so shared
        partial-tail prefixes cost one launch per ``batch_capacity``
        copies, not one per request.  Raises
        :class:`BlockPoolExhausted` with every transferred reference
        returned (the caller sheds typed)."""
        from ..generation import BlockPoolExhausted, blocks_for_tokens
        ses = self.session
        bs = ses.block_size
        plen = int(req.prompt.size)
        chain, cached_len = self.prefix_cache.lookup(req.prompt)
        # always re-feed >= 1 token: the chunk executable samples the
        # token AFTER each row's window, so a fully-cached prompt still
        # feeds its last token (writing bit-identical k/v into a COW
        # copy of the tail block)
        cached = min(cached_len, plen - 1)
        fb = cached // bs               # first block this row writes
        total = blocks_for_tokens(plen, bs)
        # bind the ambient request identity across the allocation so
        # the pool's kv.exhausted flight event carries request_id
        if _rtrace.active and req.ctx is not None:
            _rtrace.set_current(req.ctx)
        try:
            fresh = self.pool.alloc(total - fb)
        except BlockPoolExhausted:
            if chain:
                self.pool.decref(chain)
            raise
        finally:
            if _rtrace.active:
                _rtrace.set_current(None)
        row = chain[:fb] + fresh
        cow = None
        if fb < len(chain):
            # the write window starts inside a shared cached block:
            # copy it into this row's first fresh block, return the
            # shared holds we no longer use
            cow = (chain[fb], fresh[0])
            self.pool.decref(chain[fb:])
        req.blocks = row
        req.cached_len = cached
        self._table[slot, :] = -1
        self._table[slot, :len(row)] = row
        self._slot_req[slot] = req
        self._keys[slot] = np.asarray(jax_random_key(req.seed),
                                      np.uint32)
        self._temps[slot] = req.temperature
        self._tks[slot] = req.top_k
        self._tps[slot] = req.top_p
        return cow

    def _ensure_blocks(self, slot: int, req: _GenRequest,
                       upto_pos: int):
        """Grow the slot's table to cover writes through absolute
        position ``upto_pos`` (lazy decode-time growth — the admission
        win over worst-case reservation).  Raises
        :class:`BlockPoolExhausted`."""
        from ..generation import blocks_for_tokens
        need = blocks_for_tokens(int(upto_pos) + 1,
                                 self.session.block_size)
        have = len(req.blocks)
        if need <= have:
            return
        if _rtrace.active and req.ctx is not None:
            _rtrace.set_current(req.ctx)
        try:
            fresh = self.pool.alloc(need - have)
        finally:
            if _rtrace.active:
                _rtrace.set_current(None)
        req.blocks.extend(fresh)
        self._table[slot, have:have + len(fresh)] = fresh

    def _shed_kv(self, req: _GenRequest, slot: Optional[int], cause):
        """Pool exhaustion (organic or ``kv.block_alloc``-injected):
        shed the request with the typed error — the live batch never
        sees a partial allocation."""
        with self._mlock:
            self._admission.shed_kv_blocks()
        if slot is not None:
            self._slot_req[slot] = None
            self._table[slot, :] = -1
        if _memscope.active:
            # exhaustion forensics even though the shed is graceful:
            # the dump says WHAT filled the pool when capacity planning
            # asks later (one artifact per process; the flight event
            # fires every time)
            _memscope.oom_dump(
                cause if isinstance(cause, BaseException)
                else RuntimeError(str(cause)),
                context=f"kv_shed:{self.metrics_prefix}",
                pool=self.pool, prefix_cache=self.prefix_cache)
        if _flight.active:
            _flight.note("serve", "kv_shed",
                         engine=self.metrics_prefix, slot=slot,
                         request=req.request_id, cause=str(cause))
        if req.ctx is not None and _rtrace.active:
            req.ctx.record("shed", req.t_submit_ns,
                           outcome="kv_blocks", terminated=True)
        self._release_resources(req)
        exc = RequestRejected(
            f"paged KV block pool exhausted ({cause}); request shed — "
            "retry when running generations free blocks, or provision "
            "more num_blocks", reason="kv_blocks")
        if not req.future.done():
            req.future.set_exception(exc)
        req.queue.put(exc)

    # -- preemption to host memory ------------------------------------
    def _pick_victim(self, max_rank: int,
                     exclude: Optional[int] = None) -> Optional[int]:
        """Choose the live slot to preempt for a rank-``max_rank``
        requester: strictly lower priority only (batch never bumps
        batch), preferring the lowest class first, then the slot
        holding the most blocks (one swap frees the most memory), then
        the lowest slot index (determinism — the gates assert exact
        preempt counts)."""
        best, best_key = None, None
        for s, r in enumerate(self._slot_req):
            if r is None or s == exclude or r.priority <= max_rank:
                continue
            if not r.tokens:
                # placed this round but not yet prefilled: the slot's
                # position and KV bytes are not valid swap state
                continue
            key = (-r.priority, -len(r.blocks), s)
            if best_key is None or key < best_key:
                best, best_key = s, key
        return best

    def _preempt_for(self, req: _GenRequest,
                     exclude: Optional[int] = None) -> bool:
        """Free blocks for ``req`` by preempting one strictly
        lower-priority live slot to host memory.  Returns False when
        no eligible victim exists (the caller sheds the requester
        typed instead — preemption never bumps an equal-or-higher
        class)."""
        victim = self._pick_victim(req.priority, exclude=exclude)
        if victim is None:
            return False
        self._preempt_slot(victim)
        return True

    def _preempt_slot(self, slot: int):
        """Swap one live decode slot out to host memory: gather its
        blocks' contents (pinned host memory when the backend has the
        ``pinned_host`` kind; plain numpy on CPU CI), park the request
        with its sampling state, and free the blocks through
        ``_release_resources``.  The parked stream resumes bit-exact:
        the per-step sample key is ``fold_in(base_key, position)`` and
        both position and KV bytes are restored verbatim, so the
        continuation is the very token sequence an unpreempted run
        would have produced."""
        req = self._slot_req[slot]
        from ..utils import chaos as _chaos
        if _chaos.active:
            try:
                _chaos.hit("serve.preempt")
            except Exception as e:  # noqa: BLE001 — injected swap fail
                # a failed swap-out must not corrupt the batch: shed
                # the victim typed (blocks still freed) instead of
                # parking state we could not capture
                self._shed_kv(req, slot, e)
                return
        nblocks = len(req.blocks)
        pos = int(self._positions[slot])
        host = self.session.swap_out_blocks(self._arenas, req.blocks)
        req.parked = {"host": host, "nblocks": nblocks, "pos": pos,
                      "last": int(self._last_tok[slot])}
        self._slot_req[slot] = None
        self._table[slot, :] = -1
        self._release_resources(req)     # returns the device blocks
        with self._cond:
            self._parked.append(req)
        with self._mlock:
            self._m_preempted.inc()
            self._g_parked.set(len(self._parked))
        self._note_tenant(req, "preempted")
        if _flight.active:
            _flight.note("serve", "preempt",
                         engine=self.metrics_prefix, slot=slot,
                         request=req.request_id, tenant=req.tenant,
                         priority=req.priority_name, blocks=nblocks,
                         position=pos)
        if req.ctx is not None and _rtrace.active:
            req.ctx.record("preempt", _tracer.now_ns(), slot=slot,
                           blocks=nblocks)

    def _sweep_parked(self):
        """Deadline pass over the parked set: a stream whose deadline
        expired (or was cancelled) while swapped out sheds typed
        ``deadline_preempted`` — resuming it would burn blocks on a
        stream nobody is waiting for."""
        with self._cond:
            dead = [r for r in self._parked
                    if r.expired() or r.cancelled]
            for r in dead:
                self._parked.remove(r)
        for req in dead:
            req.parked = None            # release the host-side state
            if req.cancelled:
                self._retire(req, slot=None)
                continue
            with self._mlock:
                self._admission.shed_deadline(preempted=True)
                self._g_parked.set(len(self._parked))
            self._note_tenant(req, "shed")
            self._release_resources(req)
            if req.ctx is not None and _rtrace.active:
                req.ctx.record("shed", req.t_submit_ns,
                               outcome="deadline_preempted",
                               terminated=True)
            exc = DeadlineExceeded(
                "request deadline expired while preempted to host "
                "memory", reason="deadline_preempted")
            if not req.future.done():
                req.future.set_exception(exc)
            req.queue.put(exc)

    def _try_resume(self):
        """Admission-tail resume pass: swap parked requests back into
        free slots as the pool refills, highest aged priority first.
        A pool that cannot cover a parked stream while other slots are
        live simply waits (their retirements will free blocks); with
        nothing live it drops the prefix cache's holds and, if the
        stream still cannot fit, sheds it typed rather than wedging
        the scheduler."""
        from ..generation import BlockPoolExhausted
        cleared_cache = False
        while True:
            with self._cond:
                if not self._parked or self._paused:
                    return
                free = [i for i, r in enumerate(self._slot_req)
                        if r is None]
                if not free:
                    return
                aging = self._aging_s
                now = time.monotonic()

                def _eff(r):
                    e = r.priority
                    if aging > 0:
                        e = max(0, e - int((now - r.t_submit) / aging))
                    return e

                req = min(self._parked,
                          key=lambda r: (_eff(r), r.t_submit))
                slot = free[0]
            try:
                blocks = self.pool.alloc(req.parked["nblocks"])
            except BlockPoolExhausted as e:
                if self._occupied():
                    return       # retirements will free blocks
                if not cleared_cache:
                    self.prefix_cache.clear()
                    cleared_cache = True
                    continue
                # the pool physically cannot hold this stream even
                # empty: shed typed rather than park forever
                with self._cond:
                    self._parked.remove(req)
                with self._mlock:
                    self._g_parked.set(len(self._parked))
                req.parked = None
                self._shed_kv(req, None, e)
                continue
            self._resume_into(slot, req, blocks)

    def _resume_into(self, slot: int, req: _GenRequest,
                     blocks: List[int]):
        """Swap a parked request back in: ``device_put`` the host
        payload into the fresh ``blocks``, rewrite the slot's table
        row, restore position/last-token/sampling params.  Block ids
        may differ from the preempted set — the table rewrite absorbs
        that; contents are bit-identical."""
        park = req.parked
        self._arenas = self.session.swap_in_blocks(
            self._arenas, blocks, park["host"])
        req.blocks = blocks
        req.parked = None
        with self._cond:
            self._parked.remove(req)
        self._table[slot, :] = -1
        self._table[slot, :len(blocks)] = blocks
        self._slot_req[slot] = req
        self._positions[slot] = park["pos"]
        self._last_tok[slot] = park["last"]
        self._keys[slot] = np.asarray(jax_random_key(req.seed),
                                      np.uint32)
        self._temps[slot] = req.temperature
        self._tks[slot] = req.top_k
        self._tps[slot] = req.top_p
        with self._mlock:
            self._m_resumed.inc()
            self._g_parked.set(len(self._parked))
        self._note_tenant(req, "resumed")
        if _flight.active:
            _flight.note("serve", "resume",
                         engine=self.metrics_prefix, slot=slot,
                         request=req.request_id, tenant=req.tenant,
                         priority=req.priority_name,
                         blocks=len(blocks), position=park["pos"])
        if req.ctx is not None and _rtrace.active:
            req.ctx.record("resume", _tracer.now_ns(), slot=slot,
                           blocks=len(blocks))

    def _retire(self, req: _GenRequest, slot: Optional[int]):
        if slot is not None:
            self._table[slot, :] = -1
        super()._retire(req, slot)

    def _fail_all(self, exc: BaseException):
        super()._fail_all(exc)
        self._drain_imports(exc)
        self._table[:, :] = -1
        with self._mlock:
            self._g_parked.set(0)

    def close(self, timeout: Optional[float] = 60.0):
        super().close(timeout=timeout)
        # imports stranded by the loop's exit fail typed (their alloc
        # holds release here), THEN the cache lets go — so the pool
        # drains to all-free (the leak canary in the tests)
        self._drain_imports(EngineClosed("generation engine is closed"))
        self.prefix_cache.clear()

    # -- scheduler overrides ------------------------------------------
    def _admit(self):
        """Token-boundary admission, paged edition: adopt shipped-in
        KV chains, deadline-sweep the parked set, admit queued
        requests (preempting lower-priority slots under pool
        pressure), then resume parked streams into whatever slots and
        blocks remain."""
        self._apply_imports()
        self._sweep_parked()
        self._admit_pending()
        self._try_resume()

    def _admit_pending(self):
        """Queued-request admission: prefix-cache lookup + block
        allocation per request, then ONE chunked prefill per
        suffix-length bucket feeding each row's uncached suffix at its
        true offset.  Pool exhaustion preempts the lowest strictly
        lower-priority live slot and retries; with no eligible victim
        the *incoming* request sheds typed."""
        from ..generation import BlockPoolExhausted, blocks_for_tokens
        took: List[Tuple[int, _GenRequest]] = []
        with self._cond:
            if self._paused:
                return
            free = [i for i, r in enumerate(self._slot_req)
                    if r is None]
            while self._pending and free:
                req = self._pop_pending()
                self._admission.release()
                if req.expired():
                    self._shed(req)
                    continue
                if req.cancelled:
                    self._retire(req, slot=None)
                    continue
                took.append((free.pop(0), req))
        if not took:
            return
        placed: List[Tuple[int, _GenRequest]] = []
        cows: List[Tuple[int, int]] = []
        for slot, req in took:
            shed = False
            while True:
                try:
                    cow = self._prepare_slot(slot, req)
                    break
                except BlockPoolExhausted as e:
                    if self._preempt_for(req, exclude=slot):
                        continue     # blocks freed — retry the alloc
                    self._shed_kv(req, None, e)
                    shed = True
                    break
            if shed:
                continue
            if cow is not None:
                cows.append(cow)
            self._note_slot_admit(slot, req)
            placed.append((slot, req))
        if not placed:
            return
        if cows:
            # one batched copy-on-write launch for the whole round
            self._arenas = self.session.copy_blocks(
                self._arenas, [s for s, _ in cows],
                [d for _, d in cows])
        groups: Dict[int, List[Tuple[int, _GenRequest]]] = {}
        for slot, req in placed:
            flen = len(req.prompt) - req.cached_len
            groups.setdefault(self.session.prompt_bucket(flen),
                              []).append((slot, req))
        for pb, members in sorted(groups.items()):
            S = self.slots
            ids = np.zeros((S, pb), np.int32)
            starts = np.zeros((S,), np.int32)
            feed = np.zeros((S,), np.int32)
            for slot, req in members:
                suffix = req.prompt[req.cached_len:]
                ids[slot, :len(suffix)] = suffix
                starts[slot] = req.cached_len
                feed[slot] = len(suffix)
            t0 = _tracer.now_ns() if _rtrace.active else 0
            tok, self._arenas = self.session.prefill(
                self._arenas, self._table, ids, starts, feed,
                self._keys, self._temps, self._tks, self._tps,
                live_rows=len(members))
            if t0:
                self._trace_boundary(
                    "prefill", t0, _tracer.now_ns(),
                    [s for s, _r in members], bucket=pb)
            for slot, req in members:
                # offer the now-filled prompt blocks to the prefix
                # cache BEFORE emit (emit may retire the request,
                # releasing its holds)
                n = len(req.prompt)
                self.prefix_cache.insert(
                    req.prompt,
                    req.blocks[:blocks_for_tokens(
                        n, self.session.block_size)])
                self._positions[slot] = n
                self._last_tok[slot] = tok[slot]
                self._emit(slot, int(tok[slot]))

    def _decode_round(self, occ: List[int]):
        from ..generation import BlockPoolExhausted, draft_row
        k = self.speculative_k
        if k > 0:
            drafts: Dict[int, List[int]] = {}
            for s in occ:
                req = self._slot_req[s]
                ctx = np.concatenate(
                    [req.prompt, np.asarray(req.tokens, np.int32)])
                room = self.max_length - int(self._positions[s])
                drafts[s] = draft_row(ctx, k, room,
                                      ngram=self.config.spec_ngram)
            if any(drafts.values()):
                self._verify_round(occ, drafts, k)
                return
        # plain paged decode: each live row writes one token at its
        # position — grow its table lazily first; pool pressure
        # preempts a strictly lower-priority neighbour before shedding
        victims = []
        for s in occ:
            req = self._slot_req[s]
            if req is None:
                continue     # preempted for an earlier row this pass
            while True:
                try:
                    self._ensure_blocks(s, req,
                                        int(self._positions[s]))
                    break
                except BlockPoolExhausted as e:
                    if self._preempt_for(req, exclude=s):
                        continue
                    victims.append((s, req, e))
                    break
        for s, req, e in victims:
            self._shed_kv(req, s, e)
        occ = self._occupied()
        if not occ:
            return
        t0 = _tracer.now_ns() if _rtrace.active else 0
        tok, self._arenas = self.session.decode(
            self._arenas, self._table, self._last_tok,
            self._positions, self._keys, self._temps, self._tks,
            self._tps, live_rows=len(occ))
        if t0:
            self._trace_boundary("decode", t0, _tracer.now_ns(), occ,
                                 occupancy=len(occ))
        with self._mlock:
            self._m_occ.observe(len(occ))
        self._positions = self._positions + 1
        self._last_tok = np.array(tok, np.int32)
        for s in occ:
            self._emit(s, int(tok[s]))

    def _verify_round(self, occ: List[int],
                      drafts: Dict[int, List[int]], k: int):
        """Speculative boundary: one batched verify at width k+1;
        each row commits the longest prefix of its drafts the model's
        own sampler agrees with, plus the correction token — the
        committed stream is exactly what sequential decode would have
        produced."""
        from ..generation import (BlockPoolExhausted, accept_span,
                                  fill_verify_row)
        W = k + 1
        S = self.slots
        ids = np.zeros((S, W), np.int32)
        feed = np.zeros((S,), np.int32)
        victims, live = [], []
        for s in occ:
            req = self._slot_req[s]
            if req is None:
                continue     # preempted for an earlier row this pass
            d = drafts.get(s) or []
            fill_verify_row(ids, feed, s, int(self._last_tok[s]), d)
            shed = False
            while True:
                try:
                    self._ensure_blocks(
                        s, req, int(self._positions[s]) + len(d))
                    break
                except BlockPoolExhausted as e:
                    if self._preempt_for(req, exclude=s):
                        continue
                    feed[s] = 0          # shed row stays inert
                    victims.append((s, req, e))
                    shed = True
                    break
            if not shed:
                live.append(s)
        for s, req, e in victims:
            self._shed_kv(req, s, e)
        # a later row's preemption may have parked an earlier live row:
        # its writes drop through the -1 table, but keep it out of the
        # feed and the live count
        live2 = []
        for s in live:
            if self._slot_req[s] is None:
                feed[s] = 0
            else:
                live2.append(s)
        live = live2
        if not live:
            return
        t0 = _tracer.now_ns() if _rtrace.active else 0
        toks, self._arenas = self.session.verify(
            self._arenas, self._table, ids, self._positions, feed,
            self._keys, self._temps, self._tks, self._tps,
            live_rows=len(live))
        if t0:
            # speculative boundary: the verify step IS this round's
            # decode work — one fused span, every live row linked
            self._trace_boundary("decode", t0, _tracer.now_ns(), live,
                                 occupancy=len(live), verify_width=W)
        with self._mlock:
            self._m_occ.observe(len(live))
        proposed = sum(len(drafts.get(s) or []) for s in live)
        accepted = 0
        for s in live:
            span = accept_span(drafts.get(s) or [], toks[s])
            for j, t in enumerate(span):
                self._positions[s] += 1
                self._last_tok[s] = int(t)
                self._emit(s, int(t))
                # count only drafts that actually committed (span[-1]
                # is the correction/bonus token, not a draft; a row
                # retiring mid-span discards the rest)
                if j < len(span) - 1:
                    accepted += 1
                if self._slot_req[s] is None:
                    break               # retired mid-span (eos/budget)
        with self._mlock:
            self._m_spec_proposed.inc(proposed)
            self._m_spec_accepted.inc(accepted)
            total = self._m_spec_proposed.value
            self._g_spec_rate.set(
                (self._m_spec_accepted.value / total) if total else 0.0)
