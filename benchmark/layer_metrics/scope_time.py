"""Device time of the parts a program names with ``jax.named_scope``.

The metric's file says which names are scopes: ``scopes`` lists the ones
it selects (an operation belongs to the innermost of them on its path),
and, optionally, ``phases`` (forward, backward, recompute; all when left
out).  So a program that brings a new scope brings a metric file that
names it, and nothing else changes.  The value is, per chip, the union
of the selected leaf events' intervals inside the window over the
window's steps, in ms, mean over the chips.  Leaves only: a ``while``
parent spans the scoped children the trace lists beside it.

``null`` among ``scopes`` selects the operations under no scope.  What
counts as a scope there is every name that any ``scope_time`` metric file
in this directory selects, and the file's own ``known_scopes`` (scopes
the program sets and no metric reads alone).  With ``"share_of_busy":
true`` the value is the selection's share of the device's busy time, in
%, and ``except_kernels`` names the ``kernel_roofline`` metric files
whose patterns take their events (and what those span) out of the
selection: the kernels are found by compiler-made names, not by a scope,
and are attributed all the same.

A program whose trace carries none of the scopes in question — a commit
from before the scope came — has no such part to read: the reader says so
on stderr and returns None, and the metric is left out of the line.  A
selection that matches no event of a program that does carry the scope is
an error, never a zero.
"""
import glob
import json
import os
import sys

from benchmark.harness import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _specs():
    for path in sorted(glob.glob(os.path.join(HERE, "*.json"))):
        with open(path) as f:
            yield json.load(f)


def scope_names(spec):
    """The names that are scopes for this metric."""
    names = {s for s in spec["scopes"] if s is not None}
    if None in spec["scopes"]:
        names.update(spec.get("known_scopes", []))
        for other in _specs():
            if other.get("reader") == "scope_time":
                names.update(s for s in other["scopes"] if s is not None)
    return names


def _kernel_patterns(metrics):
    out = []
    for name in metrics:
        with open(os.path.join(HERE, name + ".json")) as f:
            out += json.load(f)["patterns"]
    return out


def read(run, spec, xplane_path=None):
    op_names = scopes.scope_map(xplane_path or scopes.newest_xplane(ROOT))
    names = scope_names(spec)
    kinds = {n: scopes.classify(op, names) for n, op in op_names.items()}
    seen = sorted({scope for scope, _phase in kinds.values() if scope})
    if not seen:
        print(f"scope_time: the program's trace carries none of the scopes "
              f"{sorted(names)}: nothing to read", file=sys.stderr)
        return None
    want = set(spec["scopes"])
    phases = set(spec.get("phases", scopes.PHASES))
    patterns = _kernel_patterns(spec.get("except_kernels", []))
    values = []
    for events in run["events"]:
        hit = [e for e in trace.leaf_events(events)
               if (kind := kinds.get(e[0], (None, "forward")))[0] in want
               and kind[1] in phases]
        if not hit:
            raise LookupError(
                f"no device event under scopes {spec['scopes']} in phases "
                f"{sorted(phases)}; scopes seen: {seen}")
        kernels = trace.matching(events, patterns) if patterns else []
        seconds = trace.busy_seconds(hit + kernels) \
            - trace.busy_seconds(kernels)
        if spec.get("share_of_busy"):
            values.append(100.0 * seconds / trace.busy_seconds(events))
        else:
            values.append(1e3 * seconds / run["win"]["steps"])
    return sum(values) / len(values)
