"""Which part of the program a device operation belongs to.

A program marks its parts with ``jax.named_scope``.  Which names are
scopes is data: the metric file that selects them says so
(``layer_metrics/scope_time.py``), and nothing here knows a program's
names.  XLA carries a scope as far as the device trace: every operation
that came from traced code has the framework's name for it,
``jit(step)/jvp(ffn)/dot_general``, in the ``tf_op`` stat of its *event
metadata*.  ``jax.profiler.ProfileData``
shows an event's own stats but not its metadata's (looked at on the chip,
PR 28: an ``XLA Ops`` event lists ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, nothing else), and
the raw trace has no framework-scope line.  So ``scope_map`` reads the
``.xplane.pb`` itself — it is a protobuf, and the few fields needed are
read from its wire format here, with no package beyond Python's own.

Operations the compiler made without traced code behind them (async
copies, some layout copies) have no ``tf_op``: they are in no scope.
"""
import functools
import glob
import os
import re

from benchmark.harness import trace

PHASES = ("forward", "backward", "recompute")

# one path component, its transformation wrappers taken off:
# ``transpose(jvp(ffn))`` -> ``ffn``; a jitted function's own name,
# ``jit(ffn)``, is no scope
_COMPONENT = re.compile(r"(?!p?jit\()(?:\w+\()*([\w.\-]+)\)*:?$")


def classify(op_name, names):
    """-> (scope or None, phase) of one framework operation name.

    The scope is the innermost component of the path that is one of
    ``names``.  The phase is ``recompute`` if the path holds
    ``rematted_computation``, else ``backward`` if it holds
    ``transpose(``, else ``forward``.
    """
    scope = None
    for part in reversed(op_name.split("/")):
        m = _COMPONENT.match(part)
        if m and m.group(1) in names:
            scope = m.group(1)
            break
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return scope, phase


# ---------------------------------------------------------------------------
# the trace file: XSpace{planes=1} / XPlane{name=2, event_metadata=4 (map),
# stat_metadata=5 (map)} / XEventMetadata{name=2, stats=5} /
# XStat{metadata_id=1, str_value=5, ref_value=7} / XStatMetadata{name=2}
# (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------
def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an
    int for a varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _map_entries(plane, field):
    """The values of a ``map<int64, message>`` field, by key."""
    out = {}
    for no, entry in _fields(plane):
        if no == field:
            pair = dict(_fields(entry))
            out[pair.get(1, 0)] = pair[2]
    return out


def _plane_tf_ops(plane):
    stat_names = {}
    for key, meta in _map_entries(plane, 5).items():
        stat_names[key] = str(dict(_fields(meta)).get(2, b""), "utf-8")
    out = {}
    for meta in _map_entries(plane, 4).values():
        name, tf_op = None, None
        for no, value in _fields(meta):
            if no == 2:
                name = str(value, "utf-8")
            elif no == 5:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:
                    tf_op = str(stat[5], "utf-8")
                elif 7 in stat:      # a string shared through the table
                    tf_op = stat_names.get(stat[7])
        if name and tf_op:
            out[name] = tf_op
    return out


@functools.lru_cache(maxsize=2)
def scope_map(xplane_path):
    """-> {device event name: framework operation name} over the device
    planes of one trace file.  Parsed once for each path."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name = next((str(v, "utf-8") for n, v in _fields(plane) if n == 2),
                    "")
        if name.startswith("/device:"):
            out.update(_plane_tf_ops(plane))
    return out


def newest_xplane(root):
    """The trace of the run that is being read: ``run.py`` traces into
    ``<checkout>/.bench_trace/<cell>/`` and deletes the directory only
    after the readers have run, but hands them no path."""
    found = []
    for d in glob.glob(os.path.join(root, ".bench_trace", "*", "")):
        try:
            found.append(trace.find_xplane(d))
        except FileNotFoundError:
            pass
    if not found:
        raise FileNotFoundError(f"no trace under {root}/.bench_trace/*/")
    return max(found, key=os.path.getmtime)
