"""A named part's share of its roofline: the least time the chip could
take for a piece of work in one step (the larger of operations / peak and
bytes / bandwidth, from the configuration's shapes:
``flops/<family>.py::kernel_work``) over the device time per step under
the ``jax.named_scope``s the metric's file lists, all phases (forward,
recompute, backward).  For work that XLA operations do under a scope of
the program — the same work whatever implements it.  A Mosaic kernel
called inside the scope would be renamed by it (PERF.md section 7 row 9)
and is found by ``kernel_roofline``'s patterns instead.

A program whose trace carries none of the scopes reads as nothing
(``scope_time``'s rule): None, and the metric is left out of the line.
"""
from benchmark.layer_metrics import scope_time


def read(run, spec):
    ms = scope_time.read(run, {"scopes": spec["scopes"]})
    if ms is None:
        return None
    ops, nbytes = run["flops"].kernel_work(
        run["config"], run["traffic"], spec["work"])
    least = max(ops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / run["chips"] / (ms / 1e3)
