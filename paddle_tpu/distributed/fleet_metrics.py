"""Fleet metrics aggregation: every rank's registry, one endpoint.

Each rank's PR 1 metrics registry is visible only inside its own
process; the supervisor — the one process that already watches the
whole gang — is where the fleet view belongs.  Three pieces:

- **publish** (worker side): ``Model.fit``'s heartbeat closure calls
  :func:`publish` at the same cadence as the supervise heartbeat,
  putting a JSON registry snapshot under a generation-prefixed Store
  key (``/paddle/fleetmetrics/<job>/g<gen>/<rank>``).  The payload
  carries a ``clock`` pair (``perf_ns``, ``unix``): when it was taken,
  on both of the process's clocks.
- **aggregate** (supervisor side): :func:`collect` +
  :func:`aggregate_prometheus` merge the per-rank snapshots into one
  Prometheus text document where every series carries a ``rank``
  label, plus ``<name>_fleet{stat="min|max|sum"}`` rollups for scalar
  metrics.  :class:`FleetMetricsServer` serves it on ``/metrics``
  (``Content-Type: text/plain; version=0.0.4``) with a ``/fleet``
  JSON companion; ``distributed.launch --supervise`` starts one when
  ``PADDLE_FLEET_METRICS_PORT`` is set.
- **trace merge**: :func:`merge_chrome_traces` folds per-rank chrome
  traces (written by :func:`write_rank_trace`) into one rank-laned
  timeline — each rank becomes a ``pid`` lane.  An exported trace's
  ``ts`` is already on the Unix-epoch clock (``profiler/tracer.py``),
  the one axis every host shares, so the lanes need no shift and a
  cross-rank stall reads as the horizontal gap it is.
"""
from __future__ import annotations

import json
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = ["METRICS_PREFIX", "metrics_key", "publish", "collect",
           "aggregate_prometheus", "merge_chrome_traces",
           "write_rank_trace", "clock_pair", "FleetMetricsServer"]

METRICS_PREFIX = "/paddle/fleetmetrics/"


def metrics_key(job: str, generation, rank) -> str:
    """Generation-prefixed so a slow-dying rank from generation N can
    never pollute generation N+1's fleet view (same fencing discipline
    as the supervise heartbeat keys)."""
    return f"{METRICS_PREFIX}{job}/g{generation}/{rank}"


def clock_pair() -> Dict[str, float]:
    """A ``(perf_ns, unix)`` sample of this process's two clocks.
    ``tracer.events()`` timestamps are ``perf_counter_ns`` values with
    a process-local epoch; the pair maps them onto the shared unix
    axis: ``unix_at(ts) = unix + (ts - perf_ns) / 1e9``.  (An exported
    trace needs no pair: its ``ts`` is on the unix axis already.)"""
    return {"perf_ns": time.perf_counter_ns(), "unix": time.time()}


def publish(store, job: str, generation, rank, step=None,
            snapshot: Optional[Dict[str, Any]] = None):
    """Put one registry snapshot under this rank's fleet-metrics key.
    Rides the heartbeat cadence — callers own the rate limiting."""
    from ..profiler import metrics as _metrics
    payload = {"rank": str(rank), "step": step, "clock": clock_pair(),
               "metrics": snapshot if snapshot is not None
               else _metrics.snapshot()}
    store.put(metrics_key(job, generation, rank),
              json.dumps(payload, default=float))


def collect(store, job: str, generation) -> Dict[str, dict]:
    """``{rank: payload}`` for every rank that published under this
    generation.  Unparseable payloads are skipped — a torn write must
    not take the fleet view down."""
    out: Dict[str, dict] = {}
    try:
        rows = store.list_prefix(f"{METRICS_PREFIX}{job}/g{generation}/")
    except Exception:
        return out
    for k, v in rows.items():
        rank = k.rsplit("/", 1)[-1]
        try:
            payload = json.loads(v)
            if isinstance(payload, dict) and "metrics" in payload:
                out[rank] = payload
        except (ValueError, TypeError):
            continue
    return out


_PROM_BAD = re.compile(r"[^a-zA-Z0-9_]")


def aggregate_prometheus(per_rank: Dict[str, dict]) -> str:
    """Merge per-rank snapshots into one Prometheus text document.

    Scalar metrics (counters/gauges) become ``name{rank="r"} v`` series
    plus ``name_fleet{stat="min"|"max"|"sum"}`` rollups; histogram
    snapshots contribute ``name_count``/``name_sum`` and quantile
    series per rank (quantiles cannot be merged honestly, so they stay
    labeled, never rolled up)."""
    names: Dict[str, Dict[str, Any]] = {}
    for rank in sorted(per_rank):
        for name, val in (per_rank[rank].get("metrics") or {}).items():
            names.setdefault(name, {})[rank] = val
    lines: List[str] = []
    for name in sorted(names):
        pname = _PROM_BAD.sub("_", name)
        by_rank = names[name]
        scalars = {r: v for r, v in by_rank.items()
                   if isinstance(v, (int, float))}
        if scalars:
            lines.append(f"# TYPE {pname} gauge")
            for r, v in sorted(scalars.items()):
                lines.append(f'{pname}{{rank="{r}"}} {v}')
            vals = list(scalars.values())
            for stat, v in (("min", min(vals)), ("max", max(vals)),
                            ("sum", sum(vals))):
                lines.append(f'{pname}_fleet{{stat="{stat}"}} {v}')
            continue
        dicts = {r: v for r, v in by_rank.items()
                 if isinstance(v, dict)}
        if not dicts:
            continue
        lines.append(f"# TYPE {pname} summary")
        counts, sums = [], []
        for r, snap in sorted(dicts.items()):
            for q in ("p50", "p95", "p99"):
                if snap.get(q) is not None:
                    lines.append(
                        f'{pname}{{rank="{r}",quantile='
                        f'"0.{q[1:]}"}} {snap[q]}')
            lines.append(f'{pname}_count{{rank="{r}"}} '
                         f'{snap.get("count", 0)}')
            counts.append(float(snap.get("count", 0)))
            if snap.get("sum") is not None:
                lines.append(f'{pname}_sum{{rank="{r}"}} {snap["sum"]}')
                sums.append(float(snap["sum"]))
        lines.append(f'{pname}_fleet_count{{stat="sum"}} '
                     f'{sum(counts)}')
        if sums:
            lines.append(f'{pname}_fleet_sum{{stat="sum"}} {sum(sums)}')
    return "\n".join(lines) + ("\n" if lines else "")


def write_rank_trace(path: str, rank=None,
                     events: Optional[list] = None) -> str:
    """Export this process's tracer ring as a chrome trace carrying
    the rank and the clock its ``ts`` is on (``"unix"``: what
    ``tracer.chrome_trace_dict`` writes), which
    :func:`merge_chrome_traces` checks before it calls lanes aligned."""
    import os

    from ..profiler import tracer as _tracer
    doc = _tracer.chrome_trace_dict(events)
    doc["metadata"] = {
        "rank": str(rank if rank is not None
                    else os.environ.get("PADDLE_TRAINER_ID", "0")),
        "clock": "unix",
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def merge_chrome_traces(docs: List[dict]) -> dict:
    """One rank-laned timeline from per-rank chrome traces.

    Every input doc (as written by :func:`write_rank_trace`) becomes
    one ``pid`` lane named ``rank <r>``.  The docs' ``ts`` are on the
    Unix-epoch clock, shared across hosts, so no lane is shifted
    against another; the whole timeline is rebased so t=0 is the
    earliest event (keeps Perfetto's axis readable).  A doc that does
    not say its clock is ``"unix"`` keeps its lane and its own
    timebase, and the result says ``aligned: False`` (better partial
    than dropped)."""
    lanes = []
    for i, doc in enumerate(docs):
        meta = doc.get("metadata") or {}
        lanes.append((str(meta.get("rank", i)), meta.get("clock") == "unix",
                      [e for e in doc.get("traceEvents") or []
                       if e.get("ph") == "X"]))
    base = min((float(e.get("ts", 0.0)) for _r, _u, evs in lanes
                for e in evs), default=0.0)
    merged = []
    for li, (rank, _unix, evs) in enumerate(lanes):
        try:
            pid = int(rank)
        except ValueError:
            pid = 100000 + li
        merged.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": f"rank {rank}"}})
        for e in evs:
            e2 = dict(e)
            e2["pid"] = pid
            e2["ts"] = float(e.get("ts", 0.0)) - base
            merged.append(e2)
    return {"traceEvents": merged, "displayTimeUnit": "ms",
            "metadata": {"ranks": [r for r, _u, _e in lanes],
                         "aligned": all(u for _r, u, _e in lanes)}}


class FleetMetricsServer:
    """Supervisor-side aggregated ``/metrics`` endpoint.

    Reads the fleet-metrics Store prefix at scrape time (no caching —
    the store is the cache) for whatever generation ``generation_fn``
    currently reports, so a post-shrink scrape shows the surviving
    gang, not ghosts.  ``/fleet`` returns the raw per-rank payloads as
    JSON (step, clock, snapshot age) for dashboards that want more
    than Prometheus text."""

    def __init__(self, store_spec: str, job: str,
                 generation_fn: Callable[[], Any],
                 host: str = "127.0.0.1", port: int = 0):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        from .fleet.elastic.manager import store_from_spec
        self._store = store_from_spec(store_spec)
        self._job = job
        self._generation_fn = generation_fn
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # pragma: no cover
                pass

            def _send(self, code, body: bytes, ctype: str):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                try:
                    per_rank = collect(outer._store, outer._job,
                                       outer._generation_fn())
                except Exception as e:  # noqa: BLE001 — store blip
                    self._send(503, json.dumps(
                        {"error": repr(e)}).encode(),
                        "application/json")
                    return
                if self.path == "/metrics":
                    self._send(200,
                               aggregate_prometheus(per_rank).encode(),
                               "text/plain; version=0.0.4")
                elif self.path == "/fleet":
                    now = time.time()
                    body = {r: {"step": p.get("step"),
                                "age_s": round(now - p.get(
                                    "clock", {}).get("unix", now), 3),
                                "metrics": p.get("metrics")}
                            for r, p in per_rank.items()}
                    self._send(200, json.dumps(
                        body, default=float).encode(),
                        "application/json")
                else:
                    self._send(404, json.dumps(
                        {"error": f"no route {self.path}; try "
                         "/metrics or /fleet"}).encode(),
                        "application/json")

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.host, self.port = self._httpd.server_address[:2]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "FleetMetricsServer":
        from ..utils import concurrency as _conc
        self._thread = _conc.spawn(self._httpd.serve_forever,
                                   name="fleet-metrics-http")
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10)
