"""The comparison that decides ``correct`` for a training cell: a cell
whose file says ``"check": "training"``.  ``run.py`` finds this module by
that name and calls ``compare``; ``control.py`` calls ``controls``.

Both sides hand over the same evidence of the first steps:

    {"loss": [l1, l2, l3],
     "grad_norm": {leaf: norm of the first gradient as the optimizer got it},
     "change_norm": {leaf: norm of (parameters after the steps - initial)}}

Norms are compared by the worst leaf: the gap between the program's norm
and the reference's (not the norm of a difference), against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Leaves whose reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone and are left out of the change.
"""
import functools
import math
import statistics


def leaf_norms(tree, keep=None):
    """{name: norm} of every leaf of a parameter tree, as one jitted-able
    function of the tree.  ``keep`` maps a leaf's name to the axes along
    which it is split into leaves of their own — a stacked leaf that
    holds one layer per leading index keeps axis 0, a packed q/k/v bias
    (L, 3, D) keeps (0, 1) so that the key's bias, whose gradient is
    nought under softmax, is a leaf by itself."""
    import jax
    import jax.numpy as jnp
    keep = keep or {}
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        kept = tuple(keep.get(name, ()))
        x = leaf.astype(jnp.float32)
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(
            a for a in range(x.ndim) if a not in kept)))
    return out


def flatten_norms(norms):
    """{name: float}; a split leaf becomes name/i or name/i/j."""
    import numpy as np
    flat = {}
    for name, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        for idx in np.ndindex(*v.shape):
            flat["/".join([name, *map(str, idx)])] = float(v[idx])
    return flat


def flatten_evidence(ev):
    return {k: list(map(float, v)) if k == "loss" else flatten_norms(v)
            for k, v in ev.items()}


def _gaps(prog, ref, keep=None):
    """{leaf: gap} — |program's norm - reference's| over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    gaps = {}
    for name, r in ref.items():
        if keep is not None and name not in keep:
            continue
        p = prog.get(name)
        gaps[name] = math.inf if p is None or not math.isfinite(p) \
            else abs(p - r) / max(r, med)
    return gaps


def _worst(gaps):
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def _spread(gaps, n=5):
    """How the gaps lie over the leaves: the median, the ninth decile
    and the n worst leaves — for the look that a limit is set from."""
    vals = sorted(gaps.values())
    worst = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
    return {"median": statistics.median(vals),
            "p90": vals[int(0.9 * (len(vals) - 1))],
            "worst": [[k, v] for k, v in worst]}


def training_numbers(prog, ref):
    """-> {number: (value, where)} of the numbers a limit can name, and
    under ``spread`` how the gaps lie over the leaves."""
    n = min(len(prog["loss"]), len(ref["loss"]))
    loss = [math.inf if not math.isfinite(prog["loss"][i])
            else abs(prog["loss"][i] - ref["loss"][i]) / abs(ref["loss"][i])
            for i in range(n)]
    med_g = statistics.median(ref["grad_norm"].values())
    moved = {k for k, g in ref["grad_norm"].items() if g >= 1e-3 * med_g}
    grad = _gaps(prog["grad_norm"], ref["grad_norm"])
    change = _gaps(prog["change_norm"], ref["change_norm"], keep=moved)
    return {"loss_gap": (max(loss), f"step {loss.index(max(loss)) + 1}"),
            "grad_gap": _worst(grad), "change_gap": _worst(change),
            "spread": {"grad": _spread(grad), "change": _spread(change),
                       "loss": loss}}


def judge(numbers, limits):
    """-> (correct, {number: [value, limit]}); every number named in
    ``limits`` must be present, finite and within its limit."""
    checked, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name][0]
        checked[name] = [value, limit]
        if not (math.isfinite(value) and value <= limit):
            ok = False
    return ok, checked


# ---------------------------------------------------------------------------
# what run.py and control.py call
# ---------------------------------------------------------------------------
def leaf_norms_for(cell_file):
    """``leaf_norms`` with the cell's own split of stacked leaves."""
    return functools.partial(leaf_norms, keep=cell_file.get("leaf_axes"))


def _reference_evidence(cell, reference, seed, **kw):
    return flatten_evidence(reference.evidence(
        cell.config, cell.traffic, seed, leaf_norms_for(cell.cell),
        **{**cell.cell.get("reference_args", {}), **kw}))


def _verdict(evidence, truth, limits):
    numbers = training_numbers(evidence, truth)
    spread = numbers.pop("spread")
    ok, checked = judge(numbers, limits)
    info = {"numbers": {k: v[0] for k, v in numbers.items()},
            "where": {k: v[1] for k, v in numbers.items()},
            "spread": spread}
    return ok, checked, info


def compare(cell, reference, seed, produced):
    """``produced`` is the evidence the driver recorded while it drove
    the timed object through its first steps.  The plain reference
    repeats those steps from the seed.
    -> (correct, {number: [value, limit]}, info for the result's line)"""
    return _verdict(flatten_evidence(produced),
                    _reference_evidence(cell, reference, seed),
                    cell.cell["limits"])


def controls(cell, reference, seed, variants):
    """The reference put in the program's place once for each variant —
    computed in a lower ``precision`` or with a ``fault`` planted — and
    judged as a run is, against the reference as itself and the cell's
    limits.  -> {variant's name: (correct, checked, info)}"""
    truth = _reference_evidence(cell, reference, seed)
    return {v["name"]: _verdict(
        _reference_evidence(cell, reference, seed, **{
            k: v[k] for k in ("precision", "fault") if k in v}),
        truth, cell.cell["limits"]) for v in variants}
