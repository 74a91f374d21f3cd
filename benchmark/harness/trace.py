"""Reduction of a profiler trace to the numbers the per-layer readers use.

Everything works on plain tuples ``(name, start_ns, dur_ns)`` so that the
arithmetic can be tested on a synthetic trace; only ``load`` touches the
profiler's file (through ``jax.profiler.ProfileData`` alone).
"""
import glob
import os
import re
from collections import defaultdict

# the line of a device plane that holds one event per executed operation;
# the plane's other lines (steps, modules, framework scopes) cover the
# same intervals again and must not be added to it
DEVICE_OP_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str, span_prefix: str = "bench."):
    """-> (device_events, host_spans).

    device_events: {device plane name: [(name, start_ns, dur_ns), ...]}
    host_spans: [(name, start_ns, dur_ns)] of the benchmark's own
    ``TraceAnnotation`` spans (names starting with ``span_prefix``).
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    device_events, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != DEVICE_OP_LINE:
                    continue
                device_events[plane.name] = [
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        host_spans.append((ev.name, float(ev.start_ns),
                                           float(ev.duration_ns)))
    device_events = {k: v for k, v in device_events.items() if v}
    if not device_events:
        raise ValueError(
            f"the trace under {trace_dir} holds no device operation "
            f"(no '{DEVICE_OP_LINE}' line on a /device: plane)")
    return device_events, host_spans


def clip(events, t0_ns, t1_ns):
    """Events cut to the window [t0, t1]."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0_ns), min(s + d, t1_ns)
        if b > a:
            out.append((name, a, b - a))
    return out


def intervals_union(events):
    """Merged [start, end) intervals covered by any event."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    merged = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def busy_seconds(events) -> float:
    """Seconds in which at least one operation ran (overlaps count once)."""
    return sum(b - a for a, b in intervals_union(events)) / 1e9


def matching(events, patterns):
    """Events whose name matches any of the regular expressions.  A set
    of patterns that matches nothing is an error, never a zero."""
    rx = [re.compile(p) for p in patterns]
    hit = [e for e in events if any(r.search(e[0]) for r in rx)]
    if not hit:
        raise LookupError(
            f"patterns {patterns} match no device event; names seen: "
            f"{sorted({e[0] for e in events})[:40]}")
    return hit


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO instruction;
    its short name is the instruction's own name with the running number
    taken off, so that the 24 layers' copies of one fusion add up."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def op_table(events):
    """{full name: [seconds, count]} of every operation."""
    table = defaultdict(lambda: [0.0, 0])
    for name, _s, d in events:
        table[name][0] += d / 1e9
        table[name][1] += 1
    return dict(table)


def leaf_events(events):
    """Events that span no other event.  The operations line lists a
    control-flow parent (a while loop, a conditional) beside the children
    it ran; summing times over all of them would count those twice."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(order):
        if i + 1 < len(order) and order[i + 1][1] < s + d \
                and order[i + 1][1] + order[i + 1][2] <= s + d:
            continue
        out.append((name, s, d))
    return out


def top_ops(events, n=10):
    """The n kinds of operation that took most device time, by short
    name, parents left out: [[name, seconds]]."""
    total = defaultdict(float)
    for name, _s, d in leaf_events(events):
        total[short_name(name)] += d / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, host_spans, t0_ns, t1_ns, n=10):
    """Idle time of the device by what the host was doing: each gap
    between device operations inside [t0, t1] goes to the innermost host
    span that covers the gap's middle, or to ``unattributed``."""
    merged = intervals_union(events)
    gaps, cur = [], t0_ns
    for a, b in merged:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1_ns > cur:
        gaps.append((cur, t1_ns))
    total = defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        best = None
        for name, s, d in host_spans:
            if s <= mid <= s + d and (best is None or d < best[1]):
                best = (name, d)
        total[best[0] if best else "unattributed"] += (b - a) / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
