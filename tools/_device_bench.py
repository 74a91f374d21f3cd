"""Device-truthful micro-benchmark timing.

Wall-clock includes host dispatch and whatever else the host is doing;
per-op device self times from a profiler capture do not.  ``device_time``
runs a chained loop (data dependence through a scalar carry) inside ONE
jit, captures a trace of it, and returns summed device self-time per
iteration.
"""
import glob
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax import lax


def chain_run(f, args, weights=(), iters=8):
    """Build + compile the chained loop; returns run(args, weights).
    The loop body consumes run's PARAMETERS (not closure constants —
    baked-in arrays would let XLA treat the whole loop as a constant
    and would ignore re-invocations with fresh data)."""
    @jax.jit
    def run(args, weights):
        def body(c, _):
            out = f(*[a + c.astype(a.dtype) for a in args], *weights)
            leaves = jax.tree_util.tree_leaves(out)
            return sum(jnp.sum(o.astype(jnp.float32))
                       for o in leaves) * 1e-20, None
        c, _ = lax.scan(body, jnp.zeros(()), None, length=iters)
        return c

    return run


def device_time(f, args, weights=(), iters=8):
    """Seconds of device self-time per iteration of ``f(*args,
    *weights)`` (trace-measured; contention-immune)."""
    from xprof.convert import raw_to_tool_data as rtd
    run = chain_run(f, args, weights, iters)
    float(run(args, weights))            # compile + warm outside capture
    logdir = tempfile.mkdtemp(prefix="devbench_")
    try:
        with jax.profiler.trace(logdir):
            float(run(args, weights))
        paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            return None
        data, _ = rtd.xspace_to_tool_data([paths[-1]], "hlo_stats", {})
        if isinstance(data, bytes):
            data = data.decode()
        tbl = json.loads(data)
        ids = [c["id"] for c in tbl["cols"]]
        total = 0.0
        for row in tbl["rows"]:
            r = {i: (c or {}).get("v") for i, c in zip(ids, row["c"])}
            total += float(r.get("total_self_time") or 0.0)
        return total / 1e6 / iters
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def wall_time(f, args, weights=(), iters=8):
    run = chain_run(f, args, weights, iters)
    float(run(args, weights))
    t0 = time.perf_counter()
    float(run(args, weights))
    return (time.perf_counter() - t0) / iters
