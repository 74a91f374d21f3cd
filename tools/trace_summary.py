#!/usr/bin/env python
"""trace_summary — chrome-trace JSON -> top-N ops table / request waterfall.

Reads a trace written by ``paddle_tpu.profiler.export_chrome_tracing``
(or any chrome://tracing file with 'X' complete events) and prints the
per-name aggregate the in-process ``Profiler.summary()`` would show:
call count, total/avg/max duration and share of the traced wall time.

    python tools/trace_summary.py trace.json
    python tools/trace_summary.py trace.json -n 20 --sort avg --cat dispatch
    python tools/trace_summary.py trace.json --request <trace-or-request-id>
    python tools/trace_summary.py trace.json --compiles
    python tools/trace_summary.py trace.json --launch
    python tools/trace_summary.py trace.json --request <id> \\
        --flight /tmp/flight/flight.r0.g0.json

``--request`` selects the per-request spans recorded by the rtrace
layer (``cat="rtrace"``, matched on ``args.trace_id`` or
``args.request_id``) and renders them as a waterfall: offset from the
request's first span, duration, name, and the outcome/link fields —
the single-request story (ingress -> admission -> queue -> prefill ->
decode... -> egress) that the aggregate table averages away.
``--flight`` (repeatable) folds flight-recorder dump events stamped
with the same request id into that waterfall, so the operational
verdicts (kv_shed, exhaustion, retire reason) line up with the spans.

``--compiles`` prints the memscope compile-ledger view off the
``cat="compile"`` spans: per site x cause x provenance, how many
compiles and how much wall they burned.

``--launch`` prints the launch record (``cat="launch"`` spans, reduced
by the tracer to the ``launchReport`` the export carries): a row a root
function — seconds of import / build / trace / lower / backend, what the
persistent cache said, how often the function reached the backend — and
under it the five functions below it with most self time.  The op table
leaves the launch spans out (``--cat launch`` lists them raw).

``--memplan`` treats the positional argument as a static memory plan
JSON (``MemoryPlan.to_doc()`` from static/passes/memory_plan.py, e.g.
dumped by tools/memplan_gate.py) instead of a chrome trace, and renders
the per-op live-byte timeline with tag columns and the peak marker:

    python tools/trace_summary.py plan.json --memplan -n 20

Pure stdlib so it runs anywhere the trace file lands (CI artifact
viewers, dev laptops without the framework installed).
"""
import argparse
import json
import sys


def aggregate(events, cat=None):
    """{name: {calls, total_us, avg_us, max_us}} over 'X' events."""
    stats = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        # one category when asked; else all but the launch spans, which
        # have a table of their own (--launch)
        if (e.get("cat") != cat) if cat else (e.get("cat") == "launch"):
            continue
        dur = float(e.get("dur", 0.0))
        s = stats.setdefault(e.get("name", "?"),
                             {"calls": 0, "total_us": 0.0, "max_us": 0.0})
        s["calls"] += 1
        s["total_us"] += dur
        if dur > s["max_us"]:
            s["max_us"] = dur
    for s in stats.values():
        s["avg_us"] = s["total_us"] / s["calls"]
    return stats


def format_table(stats, sort="total", top=None):
    key = {"total": "total_us", "avg": "avg_us", "max": "max_us",
           "calls": "calls"}[sort]
    rows = sorted(stats.items(), key=lambda kv: kv[1][key], reverse=True)
    if top:
        rows = rows[:top]
    grand = sum(s["total_us"] for s in stats.values()) or 1.0
    name_w = max([len(n) for n, _ in rows] + [10])
    head = (f"{'name':<{name_w}} {'calls':>7} {'total_ms':>10} "
            f"{'avg_ms':>9} {'max_ms':>9} {'ratio':>6}")
    lines = [head, "-" * len(head)]
    for name, s in rows:
        lines.append(
            f"{name:<{name_w}} {s['calls']:>7} {s['total_us'] / 1e3:>10.3f} "
            f"{s['avg_us'] / 1e3:>9.3f} {s['max_us'] / 1e3:>9.3f} "
            f"{100.0 * s['total_us'] / grand:>5.1f}%")
    if not rows:
        lines.append("(no complete events in trace)")
    return "\n".join(lines)


def request_spans(events, ident):
    """rtrace spans matching ``ident`` (a full trace_id, an
    ``X-Request-Id``, or an unambiguous prefix of either), start-sorted.
    Batch-step spans that *link* the request are folded in too — the
    fused engine work the request shared with its batchmates."""
    spans, batch = [], []
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "rtrace":
            continue
        a = e.get("args") or {}
        tid, rid = a.get("trace_id", ""), a.get("request_id", "")
        if tid == ident or rid == ident or \
                (len(ident) >= 8 and (tid.startswith(ident)
                                      or rid.startswith(ident))):
            spans.append(e)
        elif a.get("links"):
            batch.append(e)
    # batch spans live on the process trace, so match them through the
    # trace_ids of the directly-matched spans — this way a request-id
    # (or prefix) lookup folds them in just like a trace_id lookup
    roots = {(e.get("args") or {}).get("trace_id") for e in spans}
    for e in batch:
        if any(ln.get("trace_id") in roots
               for ln in (e.get("args") or {}).get("links") or ()):
            spans.append(e)
    spans.sort(key=lambda e: float(e.get("ts", 0.0)))
    return spans


def format_waterfall(spans, ident):
    if not spans:
        return f"(no rtrace spans match {ident!r} — was " \
               "FLAGS_request_trace on when the trace was recorded?)"
    t0 = min(float(e.get("ts", 0.0)) for e in spans)
    meta_keys = ("outcome", "terminated", "status", "slot", "bucket",
                 "occupancy", "members", "rows", "path")
    head = (f"{'offset_ms':>10} {'dur_ms':>9}  span")
    lines = [f"request {ident}", head, "-" * 64]
    for e in spans:
        a = e.get("args") or {}
        extra = " ".join(f"{k}={a[k]}" for k in meta_keys if k in a)
        parent = "" if a.get("parent_id") or not a.get("links") \
            else " [batch]"
        lines.append(
            f"{(float(e.get('ts', 0.0)) - t0) / 1e3:>10.3f} "
            f"{float(e.get('dur', 0.0)) / 1e3:>9.3f}  "
            f"{e.get('name', '?')}{parent}"
            + (f"  ({extra})" if extra else ""))
    ids = {a for a in ((e.get("args") or {}).get("request_id")
                       for e in spans) if a}
    if ids:
        lines.append(f"request ids: {', '.join(sorted(ids))}")
    return "\n".join(lines)


def compile_table(events):
    """Per (site, cause, provenance) compile accounting off the
    ``cat="compile"`` spans memscope mirrors into the trace ring."""
    rows = {}
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "compile":
            continue
        a = e.get("args") or {}
        site = e.get("name", "?").replace("compile::", "", 1)
        k = (site, a.get("cause", "?"), a.get("provenance", "?"))
        r = rows.setdefault(k, {"count": 0, "total_us": 0.0})
        r["count"] += 1
        r["total_us"] += float(e.get("dur", 0.0))
    if not rows:
        return "(no compile spans in trace — was FLAGS_mem_accounting " \
               "on with the tracer live?)"
    site_w = max([len(k[0]) for k in rows] + [8])
    head = (f"{'site':<{site_w}} {'cause':<12} {'provenance':<11} "
            f"{'count':>6} {'total_ms':>10}")
    lines = [head, "-" * len(head)]
    for (site, cause, prov), r in sorted(
            rows.items(), key=lambda kv: kv[1]["total_us"],
            reverse=True):
        lines.append(f"{site:<{site_w}} {cause:<12} {prov:<11} "
                     f"{r['count']:>6} {r['total_us'] / 1e3:>10.3f}")
    return "\n".join(lines)


LAUNCH_PHASES = ("import", "build", "trace", "lower", "backend")


def launch_table(report, top=None, children=5):
    """The tracer's ``launch_report()`` as a table: root functions by
    their seconds, each followed by its costliest children."""
    funs = (report or {}).get("functions") or {}
    if not funs:
        return "(no launch record in trace — written by " \
               "paddle_tpu.profiler.export_chrome_tracing with no " \
               "events given?)"
    rows = sorted(funs.items(), reverse=True,
                  key=lambda kv: sum(kv[1]["seconds"].values()))
    hidden = len(rows) - len(rows[:top])
    rows = rows[:top]
    name_w = max([len(n) for n, _ in rows] + [10])
    head = f"{'function':<{name_w}} " + " ".join(
        f"{p + '_s':>9}" for p in LAUNCH_PHASES) + \
        f" {'cache':<14} {'compiles':>8}"
    lines = [f"launch {report.get('launch')}: {report.get('spans')} "
             f"spans, {report.get('dropped')} dropped", head,
             "-" * len(head)]
    for fun, f in rows:
        cache = " ".join(f"{k}:{n}" for k, n in f["cache"].items() if n)
        lines.append(
            f"{fun:<{name_w}} " + " ".join(
                f"{f['seconds'][p]:>9.3f}" if p in f["seconds"]
                else f"{'-':>9}" for p in LAUNCH_PHASES)
            + f" {cache or '-':<14} {f['compiles']:>8}")
        below = sorted(f["children"].items(), reverse=True,
                       key=lambda kv: kv[1]["self_s"])
        for name, c in below[:children]:
            lines.append(f"    {c['self_s']:>9.3f} s self {c['total_s']:>9.3f}"
                         f" s in all {c['spans']:>5} spans  {name}")
    if hidden:
        lines.append(f"... and {hidden} more root functions")
    return "\n".join(lines)


def flight_events_for(paths, ident):
    """Events from flight-recorder dump files whose ``request_id``
    field matches ``ident`` (or a >=8-char prefix), as synthetic
    zero-duration rtrace spans the waterfall can interleave.  Flight
    timestamps are unix seconds, and so is an exported trace's ``ts``
    (in us) since the launch record; a trace from before it is on
    ``perf_counter_ns``, so folded events still sort by their own time
    among themselves and render with an ``[flight]`` marker instead of
    an offset."""
    out = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"trace_summary: skipping flight dump {path}: {e}",
                  file=sys.stderr)
            continue
        for ev in doc.get("events") or []:
            fields = ev.get("fields") or {}
            rid = str(fields.get("request_id")
                      or fields.get("request") or "")
            if not rid:
                continue
            if rid == ident or (len(ident) >= 8
                                and rid.startswith(ident)):
                out.append({"t": float(ev.get("t", 0.0)),
                            "name": f"{ev.get('cat')}.{ev.get('event')}",
                            "fields": {k: v for k, v in fields.items()
                                       if k not in ("request_id",
                                                    "request")},
                            "source": path})
    out.sort(key=lambda e: e["t"])
    return out


def format_flight_tail(flight_evs):
    if not flight_evs:
        return ""
    lines = ["", "flight events (same request, flight-recorder clock):"]
    for ev in flight_evs:
        extra = " ".join(f"{k}={v}" for k, v in ev["fields"].items())
        lines.append(f"  [flight] {ev['t']:.3f} {ev['name']}"
                     + (f"  ({extra})" if extra else ""))
    return "\n".join(lines)


def format_memplan(doc, top=None):
    """Render a ``MemoryPlan.to_doc()`` JSON: header with the peak, then
    the per-op timeline (optionally only the top-N rows by live bytes,
    kept in program order) with per-tag byte columns."""
    if doc.get("kind") != "memory_plan":
        return "(not a memory plan: expected a JSON object with " \
               "kind='memory_plan' — is this a MemoryPlan.to_doc() dump?)"
    mb = 1024.0 * 1024.0
    peak_op = doc.get("peak_op") or {}
    lines = [
        f"memory plan: peak {doc.get('peak_bytes', 0) / mb:.3f} MB at "
        f"op#{peak_op.get('idx', '?')} '{peak_op.get('type', '?')}' "
        f"({doc.get('live_ops', '?')} live / {doc.get('n_ops', '?')} ops, "
        f"static {doc.get('static_bytes', 0) / mb:.3f} MB)"]
    by_tag = doc.get("static_by_tag") or {}
    if by_tag:
        lines.append("static: " + "  ".join(
            f"{k}={v / mb:.3f}MB" for k, v in sorted(by_tag.items()) if v))
    head = (f"{'op':>4} {'type':<24} {'kind':<8} {'live_mb':>9} "
            f"{'params':>8} {'acts':>8} {'grads':>8} {'opt':>8}")
    lines += [head, "-" * len(head)]
    rows = doc.get("timeline") or []
    if top and len(rows) > top:
        keep = {r["idx"] for r in sorted(
            rows, key=lambda r: r.get("live_bytes", 0), reverse=True)[:top]}
        rows = [r for r in rows if r["idx"] in keep]
    peak_idx = peak_op.get("idx")
    for r in rows:
        t = r.get("by_tag") or {}
        mark = "  <- peak" if r.get("idx") == peak_idx else ""
        lines.append(
            f"{r.get('idx', '?'):>4} {r.get('type', '?'):<24.24} "
            f"{r.get('kind', '?'):<8} "
            f"{r.get('live_bytes', 0) / mb:>9.3f} "
            f"{t.get('params', 0) / mb:>8.3f} "
            f"{t.get('activations', 0) / mb:>8.3f} "
            f"{t.get('grads', 0) / mb:>8.3f} "
            f"{t.get('opt_state', 0) / mb:>8.3f}{mark}")
    if not rows:
        lines.append("(empty timeline)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="chrome-trace JSON file")
    ap.add_argument("-n", "--top", type=int, default=30,
                    help="show only the top N rows (default 30)")
    ap.add_argument("--sort", choices=("total", "avg", "max", "calls"),
                    default="total")
    ap.add_argument("--cat", default=None,
                    help="restrict to one category (dispatch, collective, "
                         "dataloader, hapi, rtrace, ...)")
    ap.add_argument("--request", default=None, metavar="ID",
                    help="print the span waterfall of one request "
                         "(trace_id / X-Request-Id, or a prefix)")
    ap.add_argument("--flight", action="append", default=[],
                    metavar="PATH",
                    help="flight-recorder dump(s) to fold into the "
                         "--request waterfall (repeatable)")
    ap.add_argument("--compiles", action="store_true",
                    help="print the compile-ledger table "
                         "(cat='compile' spans: site/cause/provenance)")
    ap.add_argument("--launch", action="store_true",
                    help="print the launch record (cat='launch' spans: "
                         "trace / lower / backend seconds a function)")
    ap.add_argument("--memplan", action="store_true",
                    help="treat the positional arg as a static memory "
                         "plan JSON (MemoryPlan.to_doc()) and render "
                         "its per-op live-byte timeline")
    args = ap.parse_args(argv)
    with open(args.trace) as f:
        doc = json.load(f)
    if args.memplan:
        print(format_memplan(doc, top=args.top))
        return 0
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    if args.compiles:
        print(compile_table(events))
        return 0
    if args.launch:
        print(launch_table(doc.get("launchReport")
                           if isinstance(doc, dict) else None,
                           top=args.top))
        return 0
    if args.request:
        print(format_waterfall(request_spans(events, args.request),
                               args.request))
        tail = format_flight_tail(
            flight_events_for(args.flight, args.request))
        if tail:
            print(tail)
        return 0
    print(format_table(aggregate(events, cat=args.cat),
                       sort=args.sort, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
