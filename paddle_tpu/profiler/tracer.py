"""Pure-Python host-span tracer with chrome://tracing export.

Reference parity: ``platform/profiler.h:216`` (RecordEvent host events,
bounded event buffer, chrome-trace report).  This is the always-available
collector — no native ``.so``, nothing of jax but its ``monitoring``
registry — so every layer of the
framework can be instrumented unconditionally and the whole thing still
works in a bare interpreter.  Device-side traces remain jax.profiler's
job (TensorBoard/Perfetto); the file this module exports can be loaded
into the same Perfetto UI alongside them.

Two records live here.  **Always on: the launch ring.**  Every trace,
lowering and backend compile (or persistent-cache fetch) that JAX makes
is one ``cat="launch"`` span, fed by ``jax.monitoring``'s own listeners
(registered once, below) and by two spans of the program's own
(``import`` of the package, ``build`` of a train step).  No switch: a
launch is over before anyone could turn tracing on, and the listeners
run only when JAX traces, lowers or compiles — a steady step makes no
call into them.  :func:`launch_report` reduces the ring to seconds a
phase and a function.  **Behind ``active``: everything else** (dispatch,
collective, dataloader, hapi, serving, rtrace and ``RecordEvent``
spans).

Clocks: durations are measured on ``now_ns`` (``perf_counter_ns``);
launch spans arrive on the Unix-epoch clock ``jax.monitoring`` hands
over.  ``EPOCH_OFFSET_NS``, read once at import, takes the first to the
second, and everything that leaves the process (``chrome_trace_dict``,
``export_chrome_tracing``, ``launch_report``) is on the epoch clock —
the clock a device trace's events are on once its ``Task Environment``
plane's ``profile_start_time`` is added to them (PERF.md section 3).

Hot-path contract: ``active`` is a module-level bool.  Instrumented code
does ONE predicate read when tracing is off::

    if tracer.active:
        t0 = tracer.now_ns()
    ...
    if tracer.active:
        tracer.on_dispatch(op, t0)

Spans live in a bounded ring buffer (``FLAGS_host_tracer_capacity``);
beyond capacity the oldest spans drop, so an unbounded training run
cannot OOM the host through its own profiler.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils import concurrency as _conc
from ..utils import flags as _flags
from . import flight as _flight
from . import metrics as _metrics

__all__ = ["active", "enable", "disable", "is_enabled", "clear", "events",
           "drain", "record", "now_ns", "chrome_trace_dict",
           "export_chrome_tracing", "summarize", "op_table",
           "EPOCH_OFFSET_NS", "LAUNCH_ID", "record_launch",
           "launch_events", "launch_spans", "launch_report"]

# module-level fast predicate — the single check hot paths gate on
active = False

_lock = _conc.Lock(name="profiler.tracer", lazy=True)
_events: collections.deque = collections.deque(maxlen=1 << 20)

# event tuple layout: (name, start_ns, end_ns, tid, cat, args)
_Event = Tuple[str, int, int, int, str, Optional[dict]]

now_ns = time.perf_counter_ns

# now_ns() + EPOCH_OFFSET_NS is the Unix-epoch clock: taken once, so two
# exports of one process agree
EPOCH_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def enable(capacity: Optional[int] = None):
    """Start collecting host spans (ring capacity from the flag unless
    given).  Re-enabling with a new capacity preserves buffered spans."""
    global active, _events
    cap = int(capacity or _flags.get_flag("FLAGS_host_tracer_capacity"))
    with _lock:
        if _events.maxlen != cap:
            _events = collections.deque(_events, maxlen=cap)
        active = True


def disable():
    global active
    active = False


def is_enabled() -> bool:
    return active


def clear():
    _events.clear()


def events() -> List[_Event]:
    return list(_events)


def drain() -> List[_Event]:
    """Snapshot and empty the buffer (one profiler record window)."""
    with _lock:
        evs = list(_events)
        _events.clear()
    return evs


def record(name: str, start_ns: int, end_ns: int, tid: Optional[int] = None,
           cat: str = "host", args: Optional[dict] = None):
    """Append one completed span.  Timestamps are ``now_ns()`` values."""
    _events.append((name, start_ns, end_ns,
                    tid if tid is not None
                    else threading.get_ident() % (1 << 31), cat, args))


# ---------------------------------------------------------------------------
# the launch record — always on (see the module docstring)
# ---------------------------------------------------------------------------

# one identifier for every launch span of this process
LAUNCH_ID = f"{os.getpid()}-{time.time_ns()}"

# spans on the Unix-epoch nanosecond clock, same tuple layout as above
# with cat="launch".  The launches of the benchmark's cells record 2.0 k
# (GPT) to 7.4 k spans (Qwen3-Next; PERF.md section 3), 11.0 k by the
# process's end with the benchmark's float32 reference, so the largest
# fits four times over and its whole process three times; eager mode
# compiles many small functions, hence the bound (about 11 MB when full).
_launch: collections.deque = collections.deque(maxlen=1 << 15)

_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# what the persistent cache said inside the backend span now open on
# this thread: its events arrive before the span that wraps them
_cache_said = threading.local()
# the functions the program named with a ``build`` span -> how often each
# has reached the backend since.  Only these have an identity: JAX hands
# over a name, and ``<lambda>`` or an eager op's is shared by many
# functions, each compiled once
_built: Dict[str, int] = {}
# compiles run on any thread; a plain lock (the instrumented ones feed
# the metrics registry) for the ring's drop count and the backend counts
_launch_lock = threading.Lock()


def record_launch(name: str, start_ns: int, end_ns: int, fun: str,
                  **fields):
    """Append one launch span.  Timestamps are ``time.time_ns()``
    values; ``fun`` is the function the span belongs to.  A ``build``
    span names ``fun`` as a function of the program's own, new with this
    build: from then on its second arrival at the backend is a
    recompile."""
    event = (name, int(start_ns), int(end_ns),
             threading.get_ident() % (1 << 31), "launch",
             {"fun": fun, **fields})
    with _launch_lock:
        if name == "build":
            _built[fun] = 0
        if len(_launch) == _launch.maxlen:
            _metrics.counter(
                "launch.dropped", "launch spans the full ring dropped "
                "(oldest first)").inc()
        _launch.append(event)


def _on_jax_event(event, **_):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _cache_said.cache = "miss"      # until a hit says otherwise
    elif event == "/jax/compilation_cache/cache_hits":
        _cache_said.cache = "hit"


def _on_jax_duration(event, secs, **_):
    if event == "/jax/compilation_cache/cache_retrieval_time_sec":
        _cache_said.retrieval_s = secs


def _on_jax_time_span(event, start, end, fun_name="", **_):
    phase = _JAX_PHASES.get(event)
    if phase is None:
        return
    # the trace is named ``f``, lowering and backend ``jit(f)``
    fun = fun_name[4:-1] if fun_name.startswith("jit(") \
        and fun_name.endswith(")") else fun_name
    start_ns, end_ns = int(start * 1e9), int(end * 1e9)
    if phase != "backend":
        record_launch(phase, start_ns, end_ns, fun)
        return
    cache = _cache_said.__dict__.pop("cache", "off")
    fields = {"cache": cache}
    if cache == "hit":
        fields["retrieval_s"] = _cache_said.__dict__.pop("retrieval_s", 0.0)
    record_launch(phase, start_ns, end_ns, fun, **fields)
    _metrics.counter("compile.backend", "functions that reached the "
                     "backend: a compile, or a fetch from the persistent "
                     "cache").inc()
    if cache != "off":
        _metrics.counter(f"compile.cache_{cache}").inc()
    with _launch_lock:
        before = _built.get(fun)
        if before is not None:
            _built[fun] = before + 1
    if before:
        _metrics.counter("compile.recompiles", "backend compiles of a "
                         "function the program built (a `build` span) "
                         "that had reached the backend since").inc()
        if _flight.active:
            _flight.note("mem", "compile", site=fun, cause="retrace",
                         provenance="jit", cache=cache,
                         wall_ms=round((end - start) * 1e3, 1))


def _listen_to_jax():
    try:
        from jax import monitoring
    except ImportError:         # a bare interpreter: only record_launch
        return
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    monitoring.register_event_time_span_listener(_on_jax_time_span)


_listen_to_jax()


def launch_events() -> List[_Event]:
    return list(_launch)


def _union_ns(intervals) -> int:
    """Nanoseconds covered by any of the (start, end) intervals."""
    total, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total, reach = total + b - a, b
        elif b > reach:
            total, reach = total + b - reach, b
    return total


def launch_spans(evs: Optional[List[_Event]] = None) -> List[dict]:
    """The launch spans as dicts, oldest first, each with its ``id``,
    the ``parent`` that caused it — the innermost launch span of the
    same thread that contains it, ``None`` for a root — the ``root`` it
    lies under (itself, for a root) and its ``self_ns``: its duration
    less what its children cover."""
    evs = launch_events() if evs is None else evs
    spans = [{"id": i, "name": name, "start_ns": t0, "end_ns": t1,
              "tid": tid, "parent": None, "root": i, **args}
             for i, (name, t0, t1, tid, _cat, args) in enumerate(evs)]
    covered: Dict[int, list] = {}
    open_on: Dict[int, list] = {}
    # the spans arrive children first; by start, a parent comes first
    for s in sorted(spans, key=lambda s: (s["start_ns"], -s["end_ns"])):
        stack = open_on.setdefault(s["tid"], [])
        while stack and stack[-1]["end_ns"] < s["end_ns"]:
            stack.pop()
        if stack:
            s["parent"], s["root"] = stack[-1]["id"], stack[-1]["root"]
            covered.setdefault(s["parent"], []).append(
                (s["start_ns"], s["end_ns"]))
        stack.append(s)
    for s in spans:
        s["self_ns"] = s["end_ns"] - s["start_ns"] \
            - _union_ns(covered.get(s["id"], ()))
    return spans


def launch_report(evs: Optional[List[_Event]] = None) -> dict:
    """The launch by root function (a span with no parent belongs to
    the function it names)::

        {"launch": LAUNCH_ID, "spans": n, "dropped": n,
         "functions": {fun: {
             "seconds": {"trace": s, "lower": s, "backend": s, ...},
             "self_seconds": {"trace": s, ...},
             "cache": {"hit": n, "miss": n, "off": n},
             "retrieval_s": s, "compiles": n,
             "children": {fun: {"self_s": s, "total_s": s, "spans": n}}}}}

    ``seconds`` is the union of a phase's root intervals, so a nested
    inner jit's trace is not added to its caller's twice, and
    ``self_seconds`` what of it no child covers; ``children`` are all
    spans below the function's roots, by the function they name, with
    their self time and the union of their intervals (``total_s``: a
    launcher with the kernel body it traces); ``compiles`` is how often
    a function of that name reached the backend — of a step the program
    built, more than 1 is a recompile (``compile.recompiles``, a flight
    note); of ``<lambda>`` or an eager op it is as many functions, or
    shapes of one."""
    return _report_of(launch_spans(evs))


def _report_of(spans: List[dict]) -> dict:
    functions: Dict[str, dict] = {}
    phase_ivs: Dict[tuple, list] = {}       # (root function, phase)
    child_ivs: Dict[tuple, list] = {}       # (root function, child)
    for s in spans:
        root = spans[s["root"]]
        f = functions.setdefault(root["fun"], {
            "seconds": {}, "self_seconds": {},
            "cache": {"hit": 0, "miss": 0, "off": 0}, "retrieval_s": 0.0,
            "compiles": 0, "children": {}})
        interval = (s["start_ns"], s["end_ns"])
        if s is not root:
            child = f["children"].setdefault(
                s["fun"], {"self_s": 0.0, "total_s": 0.0, "spans": 0})
            child["self_s"] += s["self_ns"] / 1e9
            child["spans"] += 1
            child_ivs.setdefault((root["fun"], s["fun"]), []).append(interval)
            continue
        f["self_seconds"][s["name"]] = \
            f["self_seconds"].get(s["name"], 0.0) + s["self_ns"] / 1e9
        phase_ivs.setdefault((s["fun"], s["name"]), []).append(interval)
        if s["name"] == "backend":
            f["compiles"] += 1
            f["cache"][s["cache"]] += 1
            f["retrieval_s"] += s.get("retrieval_s", 0.0)
    for (fun, phase), ivs in phase_ivs.items():
        functions[fun]["seconds"][phase] = _union_ns(ivs) / 1e9
    for (fun, child), ivs in child_ivs.items():
        functions[fun]["children"][child]["total_s"] = _union_ns(ivs) / 1e9
    dropped = _metrics.get("launch.dropped")
    return {"launch": LAUNCH_ID, "spans": len(spans),
            "dropped": dropped.value if dropped else 0,
            "functions": functions}


# ---------------------------------------------------------------------------
# instrumentation hooks — called by framework hot paths AFTER checking
# ``active``, so each one may allocate freely
# ---------------------------------------------------------------------------

def on_dispatch(op_name: str, start_ns: int):
    """One eager op went through core.dispatch."""
    end_ns = time.perf_counter_ns()
    record("op::" + op_name, start_ns, end_ns, cat="dispatch")
    _metrics.counter("dispatch.count").inc()
    _metrics.counter("dispatch.op." + op_name).inc()
    _metrics.counter("dispatch.time_ns").inc(end_ns - start_ns)


def on_cache_event(kind: str):
    """Eager jit/vjp cache outcome: 'hit' | 'miss' | 'uncacheable'."""
    _metrics.counter("dispatch.jit_cache." + kind).inc()


def on_trace_time(ns: int):
    """Time spent re-tracing (jax.vjp / jit build) — what the cache saves."""
    _metrics.counter("dispatch.trace_time_ns").inc(ns)


def on_collective(name: str, start_ns: int, nbytes: int, world: int = 0):
    end_ns = time.perf_counter_ns()
    args: Dict[str, Any] = {"bytes": nbytes}
    if world:
        args["world"] = world
    record("cc::" + name, start_ns, end_ns, cat="collective", args=args)
    _metrics.counter(f"collective.{name}.count").inc()
    _metrics.counter(f"collective.{name}.bytes").inc(nbytes)


def on_data_wait(start_ns: int, depth: Optional[int] = None):
    """Consumer-side wait for the next DataLoader batch."""
    end_ns = time.perf_counter_ns()
    record("io::batch_wait", start_ns, end_ns, cat="dataloader")
    _metrics.counter("dataloader.batches").inc()
    _metrics.histogram("dataloader.batch_wait_ms").observe(
        (end_ns - start_ns) / 1e6)
    if depth is not None:
        _metrics.gauge("dataloader.queue_depth").set(depth)


def on_queue_depth(name: str, depth: int):
    _metrics.gauge(name + ".queue_depth").set(depth)


def on_step_phase(phase: str, start_ns: int, end_ns: Optional[int] = None,
                  mode: str = "train") -> int:
    """One phase of a hapi train-loop step: ``data_wait`` (blocked on
    the input pipeline for the next batch), ``device`` (inside the
    jitted-step dispatch call — in a steady sync-free loop the device
    backpressure surfaces here), ``host`` (everything else: state
    plumbing, callbacks, bookkeeping).  Histograms + total-ns counters
    let the bench compute data_wait_frac / host_frac / device_frac and
    attribute a utilization win instead of asserting it.  Returns the
    span duration in ns."""
    if end_ns is None:
        end_ns = time.perf_counter_ns()
    record(f"step::{phase}", start_ns, end_ns, cat="hapi")
    dt = end_ns - start_ns
    _metrics.histogram(f"{mode}.step.{phase}_ms").observe(dt / 1e6)
    _metrics.counter(f"{mode}.step.{phase}_ns").inc(dt)
    # memscope peak watermark rides the phase boundary (one predicate
    # read when memory accounting is off)
    from . import memscope as _memscope
    if _memscope.active:
        _memscope.on_phase(phase)
    return dt


def on_step_host(dt_ns: int, mode: str = "train"):
    """Host-side remainder of one loop step (body minus the dispatch
    'device' phase).  Not a contiguous span — metrics only; the full
    body span is already recorded by :func:`on_hapi_step`."""
    _metrics.histogram(f"{mode}.step.host_ms").observe(dt_ns / 1e6)
    _metrics.counter(f"{mode}.step.host_ns").inc(dt_ns)


def on_serving_phase(name: str, start_ns: int,
                     end_ns: Optional[int] = None) -> int:
    """One serving-side generation phase span — ``<prefix>.prefill``
    (prompt ingestion filling the KV-cache) or ``<prefix>.decode`` (one
    token across the in-flight batch).  The chrome-trace view then
    shows the prefill stalls a continuous batcher injects between
    decode steps, which is the thing to stare at when time-to-first-
    token and inter-token latency fight each other.  Latency histograms
    for the same phases live in the metrics registry (the session owns
    those; this is the tracer span only).  Returns the span ns."""
    if end_ns is None:
        end_ns = time.perf_counter_ns()
    record(f"serve::{name}", start_ns, end_ns, cat="serving")
    from . import memscope as _memscope
    if _memscope.active:
        _memscope.on_phase(name)
    return end_ns - start_ns


def on_hapi_step(start_ns: int, num_samples: int = 0, mode: str = "train"):
    """One hapi Model loop step (latency is host wall time; with the
    lazy-loss pipeline this is enqueue latency, not device step time)."""
    end_ns = time.perf_counter_ns()
    record(f"hapi::{mode}_step", start_ns, end_ns, cat="hapi")
    dt_ns = end_ns - start_ns
    _metrics.histogram(f"hapi.{mode}_step_latency_ms").observe(dt_ns / 1e6)
    if num_samples:
        _metrics.counter(f"hapi.{mode}_samples").inc(num_samples)
        if dt_ns > 0:
            _metrics.gauge(f"hapi.{mode}_ips").set(
                num_samples / (dt_ns / 1e9))


# ---------------------------------------------------------------------------
# export / aggregation
# ---------------------------------------------------------------------------

def chrome_trace_dict(evs: Optional[List[_Event]] = None) -> dict:
    """chrome://tracing document ('X' complete events; ts/dur in us,
    ts on the Unix-epoch clock).  Overlapping spans on one tid render
    nested in Perfetto/chrome.  With no ``evs`` given the document is
    the whole process: the buffered spans, the launch spans (each with
    its ``id`` and ``parent``) and, under ``launchReport``,
    :func:`launch_report`."""
    pid = os.getpid()

    def doc_event(name, ts_ns, dur_ns, tid, cat, args):
        e = {"name": name, "cat": cat or "host", "ph": "X",
             "ts": ts_ns / 1e3, "dur": dur_ns / 1e3, "pid": pid, "tid": tid}
        if args:
            e["args"] = dict(args)
        return e

    whole = evs is None
    tevs = [doc_event(name, t0 + EPOCH_OFFSET_NS, t1 - t0, tid, cat, args)
            for name, t0, t1, tid, cat, args in (events() if whole else evs)]
    doc = {"traceEvents": tevs, "displayTimeUnit": "ms"}
    if whole:
        spans = launch_spans()
        tevs.extend(
            doc_event(s["name"], s["start_ns"], s["end_ns"] - s["start_ns"],
                      s["tid"], "launch",
                      {k: s[k] for k in s if k not in
                       ("name", "start_ns", "end_ns", "tid", "root",
                        "self_ns")}
                      | {"launch": LAUNCH_ID})
            for s in spans)
        doc["launchReport"] = _report_of(spans)
    return doc


def export_chrome_tracing(path: str,
                          evs: Optional[List[_Event]] = None) -> str:
    """Write the buffered (or given) spans as a chrome-trace JSON file.
    Prefer :func:`paddle_tpu.profiler.export_chrome_tracing`, which also
    merges spans from the native collector when that is in use."""
    doc = chrome_trace_dict(evs)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def summarize(evs: Optional[List[_Event]] = None) -> Dict[str, dict]:
    """Aggregate spans by name: calls, total/avg/max/min ns."""
    if evs is None:
        evs = events()
    out: Dict[str, dict] = {}
    for name, t0, t1, _tid, _cat, _args in evs:
        dur = t1 - t0
        s = out.get(name)
        if s is None:
            out[name] = {"calls": 1, "total_ns": dur,
                         "max_ns": dur, "min_ns": dur}
        else:
            s["calls"] += 1
            s["total_ns"] += dur
            if dur > s["max_ns"]:
                s["max_ns"] = dur
            if dur < s["min_ns"]:
                s["min_ns"] = dur
    for s in out.values():
        s["avg_ns"] = s["total_ns"] / s["calls"]
    return out


# ---------------------------------------------------------------------------
# op-level aggregation: which ops dispatched, and their host time
# ---------------------------------------------------------------------------
def op_table(evs: Optional[List[_Event]] = None) -> Dict[str, dict]:
    """``summarize()`` restricted to dispatched ops (``op::`` spans),
    keyed by bare op name."""
    return {name[len("op::"):]: row for name, row in summarize(evs).items()
            if name.startswith("op::")}

