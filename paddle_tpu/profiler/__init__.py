"""paddle.profiler — profiler v2: scheduler-driven, host-span tracer,
op summary tables.

Reference parity: ``python/paddle/profiler/profiler.py`` (Profiler,
ProfilerState, make_scheduler, export_chrome_tracing) +
``platform/profiler.h:216`` (RecordEvent RAII, chrome-trace export,
op-level summary).  On TPU the device-side tracing (the reference's
CUPTI path) is jax.profiler's XLA/TPU trace, viewable in
TensorBoard/Perfetto; host spans are collected by the pure-Python
:mod:`.tracer` (always available) and, when the optional native ``.so``
is loaded, the C++ ring buffer as well.  Metrics (counters / gauges /
histograms fed by the instrumented hot paths) live in :mod:`.metrics`.

**Always on:** the launch record (:mod:`.tracer`: a ``cat="launch"``
span for every trace, lowering and backend compile or persistent-cache
fetch JAX makes, for the package's import and a train step's build;
``tracer.launch_report()``; the counters ``compile.backend``,
``compile.cache_hit``, ``compile.cache_miss``, ``compile.recompiles``,
``launch.dropped``) and the flight recorder (:mod:`.flight`, which also
gets a ``mem`` / ``compile`` note for every recompile of a step the
program built).  Importing this
package registers the only ``jax.monitoring`` listeners of
``paddle_tpu``.  **Behind ``tracer.active``** (``enable_host_tracer``,
a ``Profiler`` window): every other host span and the dispatch,
collective, dataloader and step metrics.  Behind flags of their own:
:mod:`.memscope` (``FLAGS_mem_accounting``; its compile ledger is the
call sites' annotation of what the launch spans time from inside) and
:mod:`.rtrace`.  ``export_chrome_tracing`` writes all of it on one clock,
the Unix epoch's.
"""
from __future__ import annotations

import contextlib
import enum
import json
import os
import time
import warnings

import jax

from ..utils import flags as _flags
from . import flight  # noqa: F401  (always-on flight recorder)
from . import memscope  # noqa: F401 (device-memory accounting / goodput)
from . import metrics  # noqa: F401  (public submodule: paddle.profiler.metrics)
from . import rtrace  # noqa: F401   (per-request distributed tracing)
from . import tracer  # noqa: F401   (public submodule: paddle.profiler.tracer)

__all__ = ["Profiler", "ProfilerState", "make_scheduler", "RecordEvent",
           "enable_host_tracer", "disable_host_tracer",
           "export_chrome_tracing", "profiler", "start_profiler",
           "stop_profiler", "metrics", "tracer", "rtrace", "flight",
           "memscope"]

_active = {"dir": None}
_hint = {"device_trace": False}   # one-shot behavior-change notices


# ---------------------------------------------------------------------------
# optional native (C++) collector — never required, never raises
# ---------------------------------------------------------------------------

_native = {"cls": None, "failed": False, "warned": False}


def _load_native():
    """The native Profiler class, or None.  Caches the outcome; any
    import/build failure degrades to the pure-Python tracer."""
    if _native["failed"]:
        return None
    if _native["cls"] is None:
        try:
            from ..native import Profiler as _NP, available
            if not available():
                raise RuntimeError("native library unavailable")
            _native["cls"] = _NP
        except Exception:
            _native["failed"] = True
            return None
    return _native["cls"]


def _warn_native_once():
    if not _native["warned"]:
        _native["warned"] = True
        warnings.warn(
            "paddle_tpu.native is unavailable; host spans are collected "
            "by the pure-Python tracer only (functionally identical, "
            "slightly higher per-span overhead)", RuntimeWarning,
            stacklevel=3)


class RecordEvent:
    """Named host-side span (reference platform/profiler RecordEvent RAII).

    Feeds jax.profiler (TensorBoard/Perfetto device-timeline
    correlation) plus whichever host collector is live: the pure-Python
    tracer when it is enabled, else the native C++ collector when that
    one is.  Never raises — a missing/broken native library degrades to
    the pure tracer with a single warning."""

    __slots__ = ("name", "args", "_ctx", "_t0", "_nt0")

    def __init__(self, name: str, args: dict = None):
        self.name = name
        self.args = args
        self._ctx = None
        self._t0 = None
        self._nt0 = None

    def __enter__(self):
        try:
            self._ctx = jax.profiler.TraceAnnotation(self.name)
            self._ctx.__enter__()
        except Exception:
            self._ctx = None
        if tracer.active:
            self._t0 = tracer.now_ns()
        else:
            NP = _native["cls"]
            if NP is None and not _native["failed"]:
                NP = _load_native()
                if NP is None:
                    _warn_native_once()
            if NP is not None:
                try:
                    if NP.enabled():
                        self._nt0 = NP.now_ns()
                except Exception:
                    pass
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
            self._ctx = None
        if self._t0 is not None:
            tracer.record(self.name, self._t0, tracer.now_ns(),
                          args=self.args)
            self._t0 = None
        if self._nt0 is not None:
            NP = _native["cls"]
            if NP is not None:
                try:
                    import threading
                    NP.record(self.name, self._nt0, NP.now_ns(),
                              threading.get_ident() % (1 << 31))
                except Exception:
                    pass
            self._nt0 = None
        return False

    begin = __enter__

    def end(self):
        self.__exit__(None, None, None)


def enable_host_tracer(capacity: int = None):
    """Turn on host-span collection.  The pure-Python tracer always
    engages; the native C++ ring buffer engages too when the ``.so`` is
    available (a missing library warns exactly once and never raises).
    Capacity defaults to ``FLAGS_host_tracer_capacity``."""
    cap = int(capacity or _flags.get_flag("FLAGS_host_tracer_capacity"))
    tracer.enable(cap)
    NP = _load_native()
    if NP is None:
        _warn_native_once()
        return
    try:
        NP.enable(cap)
    except Exception:
        _warn_native_once()


def disable_host_tracer():
    tracer.disable()
    NP = _native["cls"]
    if NP is not None:
        try:
            NP.disable()
        except Exception:
            pass


def _native_trace_events():
    """traceEvents recorded by the native collector (merged on export),
    taken from its own clock (``steady_clock``) to the Unix epoch's like
    every other exported span."""
    NP = _native["cls"]
    if NP is None:
        return []
    try:
        if not NP.event_count():
            return []
        # read once, like ``tracer.EPOCH_OFFSET_NS``: two exports agree
        off_us = _native.setdefault(
            "epoch_offset_ns", time.time_ns() - NP.now_ns()) / 1e3
        import tempfile
        fd, tmp = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            NP.dump_chrome_trace(tmp)
            with open(tmp) as f:
                data = json.load(f)
            evs = data.get("traceEvents", [])
            for e in evs:
                e.setdefault("cat", "native")
                if "ts" in e:
                    e["ts"] += off_us
            return evs
        finally:
            os.unlink(tmp)
    except Exception:
        return []


def export_chrome_tracing(path: str, events=None) -> str:
    """Write collected host spans as a chrome://tracing JSON file
    (reference profiler chrome-trace report).  Merges the pure-Python
    tracer's spans with any native-collector spans; works with or
    without ``_paddle_native.so``.  Load the file in chrome://tracing
    or https://ui.perfetto.dev alongside a jax.profiler device trace."""
    doc = tracer.chrome_trace_dict(events)
    doc["traceEvents"].extend(_native_trace_events())
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


# ---------------------------------------------------------------------------
# scheduler (reference paddle.profiler.make_scheduler)
# ---------------------------------------------------------------------------

class ProfilerState(enum.IntEnum):
    """Per-step profiler action (reference profiler.ProfilerState)."""
    CLOSED = 0            # not collecting
    READY = 1             # warmup: tracer on, window discarded
    RECORD = 2            # collecting
    RECORD_AND_RETURN = 3  # last record step of a cycle


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0):
    """Step-number -> ProfilerState function cycling
    ``[closed, ready, record]`` after ``skip_first`` steps, for
    ``repeat`` cycles (0 = forever) — reference
    ``paddle.profiler.make_scheduler`` semantics."""
    if record <= 0:
        raise ValueError("record span must be >= 1 step")
    if closed < 0 or ready < 0 or skip_first < 0 or repeat < 0:
        raise ValueError("closed/ready/skip_first/repeat must be >= 0")
    cycle = closed + ready + record

    def scheduler_fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        return (ProfilerState.RECORD_AND_RETURN if pos == cycle - 1
                else ProfilerState.RECORD)

    return scheduler_fn


def _always_record(step: int) -> ProfilerState:
    return ProfilerState.RECORD


# ---------------------------------------------------------------------------
# legacy fluid-style API (device trace via jax.profiler)
# ---------------------------------------------------------------------------

def start_profiler(state=None, tracer_option=None, log_dir="profile_log"):
    _active["dir"] = log_dir
    jax.profiler.start_trace(log_dir)


def stop_profiler(sorted_key=None, profile_path=None):
    if _active["dir"] is not None:
        jax.profiler.stop_trace()
        _active["dir"] = None


@contextlib.contextmanager
def profiler(state=None, sorted_key=None, profile_path=None,
             tracer_option=None, log_dir="profile_log"):
    start_profiler(state, tracer_option, log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# ---------------------------------------------------------------------------
# Profiler v2
# ---------------------------------------------------------------------------

_RECORDING = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)


class Profiler:
    """paddle.profiler.Profiler-style API, scheduler-driven.

    ``step()`` advances the state machine: CLOSED steps cost nothing,
    READY steps warm the tracer, RECORD steps collect host spans, and
    when a record window closes (RECORD_AND_RETURN -> next state, or
    ``stop()``) the window's spans are snapshotted and
    ``on_trace_ready(self)`` fires.  ``scheduler`` is a callable from
    :func:`make_scheduler`, a ``(start, end)`` tuple recording steps
    ``[start, end)``, or None to record every step.  ``timer_only=True``
    keeps step timing/ips but collects no spans.  ``with_device_trace``
    (opt-in, off by default) additionally drives ``jax.profiler``
    start/stop_trace around record windows (TensorBoard/Perfetto device
    timeline in ``log_dir``)."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, log_dir="profile_log", capacity=None,
                 with_device_trace=None):
        self.log_dir = log_dir
        self.timer_only = timer_only
        self.on_trace_ready = on_trace_ready
        self._capacity = capacity
        if scheduler is None:
            self._scheduler = _always_record
        elif callable(scheduler):
            self._scheduler = scheduler
        else:
            a, b = scheduler
            if b <= a:
                raise ValueError(f"scheduler range {scheduler} is empty")

            def _range_sched(step, _a=a, _b=b):
                if step < _a - 1 or step >= _b:
                    return ProfilerState.CLOSED
                if step == _a - 1:
                    return ProfilerState.READY
                return (ProfilerState.RECORD_AND_RETURN if step == _b - 1
                        else ProfilerState.RECORD)

            self._scheduler = _range_sched
        self._state = ProfilerState.CLOSED
        self.step_num = 0
        self._running = False
        self._events = []       # last completed record window
        self._cycle = 0
        self._device_trace = bool(with_device_trace) and not timer_only
        self._device_trace_unset = with_device_trace is None
        self._device_tracing = False
        self._step_t0 = None
        # running (count, total) only — a multi-million-step fit must
        # not accumulate per-step floats (the span buffer is bounded
        # for the same reason); percentiles live in the step-latency
        # histogram, which is itself bucketed
        self._step_count = 0
        self._step_total = 0.0
        self._samples = 0
        self._tracer_preexisting = False

    @property
    def current_state(self) -> ProfilerState:
        return self._state

    @property
    def events(self):
        """Spans of the last completed record window."""
        return list(self._events)

    # -- lifecycle -----------------------------------------------------
    def start(self):
        # pre-v2 Profiler always ran a jax.profiler device trace when
        # timer_only was False; v2 collects host spans and makes the
        # (expensive, file-emitting) device trace opt-in.  Tell legacy
        # callers once instead of silently dropping their trace.
        if (self._device_trace_unset and not self.timer_only
                and not _hint["device_trace"]):
            _hint["device_trace"] = True
            warnings.warn(
                "Profiler now collects host spans by default; pass "
                "with_device_trace=True for the jax.profiler device "
                "trace (TensorBoard/Perfetto) that pre-v2 start() "
                "always produced", stacklevel=2)
        self._running = True
        self.step_num = 0
        self._step_count = 0
        self._step_total = 0.0
        self._samples = 0
        self._step_t0 = time.perf_counter()
        # a free-running enable_host_tracer() session outlives this
        # Profiler: record windows still clear/drain the shared buffer,
        # but stop() must not turn the user's tracer off behind them
        self._tracer_preexisting = tracer.active
        if not self.timer_only:
            self._transition(self._scheduler(0))
        return self

    def step(self, num_samples: int = None):
        """Advance one iteration: time the step, drive the scheduler,
        and fire ``on_trace_ready`` when a record window closes."""
        if not self._running:
            return
        now = time.perf_counter()
        dt = now - self._step_t0
        self._step_t0 = now
        self._step_count += 1
        self._step_total += dt
        if num_samples:
            self._samples += int(num_samples)
        if self._state in _RECORDING:
            metrics.histogram("profiler.step_latency_ms").observe(dt * 1e3)
        self.step_num += 1
        if not self.timer_only:
            self._transition(self._scheduler(self.step_num))

    def stop(self):
        if not self._running:
            return
        if self._state in _RECORDING:
            self._finish_window()
        self._stop_device_trace()
        if not self.timer_only and not self._tracer_preexisting:
            tracer.disable()
        self._state = ProfilerState.CLOSED
        self._running = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- state machine -------------------------------------------------
    def _transition(self, new: ProfilerState):
        old = self._state
        rec_old = old in _RECORDING
        rec_new = new in _RECORDING
        # leaving a record window, or rolling straight into the next cycle
        if rec_old and (not rec_new
                        or old is ProfilerState.RECORD_AND_RETURN):
            self._finish_window()
            if rec_new:
                tracer.clear()
        if new is not ProfilerState.CLOSED and not tracer.active:
            tracer.enable(self._capacity)
        if rec_new and not rec_old:
            tracer.clear()      # drop warmup (READY) spans
            self._start_device_trace()
        if not rec_new:
            self._stop_device_trace()
        if new is ProfilerState.CLOSED and not self._tracer_preexisting:
            tracer.disable()
        self._state = new

    def _finish_window(self):
        self._events = tracer.drain()
        self._cycle += 1
        if self.on_trace_ready is not None:
            try:
                self.on_trace_ready(self)
            except Exception as e:
                warnings.warn(f"profiler on_trace_ready raised: {e!r}")

    def _start_device_trace(self):
        if self._device_trace and not self._device_tracing:
            try:
                jax.profiler.start_trace(self.log_dir)
                self._device_tracing = True
            except Exception as e:
                warnings.warn(f"device trace unavailable: {e!r}")
                self._device_trace = False

    def _stop_device_trace(self):
        if self._device_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    # -- reporting -----------------------------------------------------
    def export(self, path: str = None) -> str:
        """Chrome-trace JSON of the last record window.  Pure-tracer
        spans only: during a record window the pure tracer is the live
        collector, so the native ring (never drained per-window) would
        contribute out-of-window spans — use the module-level
        :func:`export_chrome_tracing` for an unwindowed merged dump."""
        path = path or os.path.join(self.log_dir, "paddle_trace.json")
        return tracer.export_chrome_tracing(path, evs=self._events)

    def step_info(self) -> str:
        """Benchmark line: steps, avg step latency, ips (reference
        Profiler timer_only output)."""
        n = self._step_count
        if not n:
            return "no steps recorded"
        total = self._step_total
        avg_ms = total / n * 1e3
        msg = f"steps: {n}, avg step: {avg_ms:.3f} ms"
        if self._samples and total > 0:
            msg += f", ips: {self._samples / total:.2f} samples/s"
        return msg

    def summary(self, sorted_by: str = "total", top: int = None,
                printout: bool = True, **kw) -> str:
        """Op-level table (total/avg/max time, call counts) over the
        last record window — the reference profiler's summary report."""
        evs = self._events or tracer.events()
        stats = tracer.summarize(evs)
        key = {"total": "total_ns", "avg": "avg_ns", "max": "max_ns",
               "calls": "calls"}.get(sorted_by, "total_ns")
        rows = sorted(stats.items(), key=lambda kv: kv[1][key],
                      reverse=True)
        if top:
            rows = rows[:top]
        grand = sum(s["total_ns"] for _n, s in stats.items()) or 1
        name_w = max([len(n) for n, _s in rows] + [10])
        lines = [f"{'name':<{name_w}} {'calls':>7} {'total_ms':>10} "
                 f"{'avg_ms':>9} {'max_ms':>9} {'ratio':>6}"]
        lines.append("-" * len(lines[0]))
        for name, s in rows:
            lines.append(
                f"{name:<{name_w}} {s['calls']:>7} "
                f"{s['total_ns'] / 1e6:>10.3f} {s['avg_ns'] / 1e6:>9.3f} "
                f"{s['max_ns'] / 1e6:>9.3f} "
                f"{100.0 * s['total_ns'] / grand:>5.1f}%")
        if not rows:
            lines.append("(no host spans recorded)")
        lines.append(self.step_info())
        table = "\n".join(lines)
        if printout:
            print(table, flush=True)
        return table
