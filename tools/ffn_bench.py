"""Isolate the flagship FFN matmul shapes on device to pin the XLA
emitter behavior the round-4 profile flagged (down-projection chain at
~half the up-projection's TFLOP/s).

Chained big-loop timing (lax.scan inside one jit) so per-dispatch
latency amortizes; each variant prints achieved TFLOP/s.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax


def chain_bench(f, args, weights, iters=8):
    """f(*args, *weights); args get the carry perturbation (data
    dependence chains the iterations), weights pass through untouched.
    Everything is an explicit jit argument — closure constants would
    embed as HLO literals."""
    def body(c, _):
        out = f(*[a + c.astype(a.dtype) for a in args], *weights)
        return jnp.sum(out.astype(jnp.float32)) * 1e-20, None

    @jax.jit
    def run(args, weights):
        c, _ = lax.scan(body, jnp.zeros(()), None, length=iters)
        return c

    float(run(args, weights))
    t0 = time.perf_counter()
    float(run(args, weights))
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=128)
    ap.add_argument("--T", type=int, default=512)
    ap.add_argument("--D", type=int, default=768)
    ap.add_argument("--F", type=int, default=3072)
    args = ap.parse_args()
    B, T, D, F = args.B, args.T, args.D, args.F
    # generate on-device: no big host->device transfers
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    dev = jax.jit(lambda: (
        jax.random.normal(ks[0], (B, T, D), jnp.bfloat16),
        jax.random.normal(ks[1], (B * T, D), jnp.bfloat16),
        jax.random.normal(ks[2], (B, T, F), jnp.bfloat16),
        jax.random.normal(ks[3], (B * T, F), jnp.bfloat16),
        jax.random.normal(ks[4], (D, F), jnp.bfloat16) * 0.02,
        jax.random.normal(ks[5], (F, D), jnp.bfloat16) * 0.02,
    ))
    x, x2, up, up2, w_up, w_dn = jax.block_until_ready(dev())
    w_dnT = jax.block_until_ready(jax.jit(jnp.transpose)(w_dn))
    g = jnp.ones((D,), jnp.bfloat16)
    b = jnp.zeros((D,), jnp.bfloat16)
    mm = 2 * B * T * D * F  # flops of one up- or down-projection

    def ln(x, g, b, eps=1e-5):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * g + b

    cases = [
        ("up 3d: (B,T,D)@(D,F)", lambda x, w: x @ w, (x,), (w_up,), mm),
        ("up 2d: (BT,D)@(D,F)", lambda x, w: x @ w, (x2,), (w_up,), mm),
        ("dn 3d: (B,T,F)@(F,D)", lambda u, w: u @ w, (up,), (w_dn,), mm),
        ("dn 2d: (BT,F)@(F,D)", lambda u, w: u @ w, (up2,), (w_dn,), mm),
        ("dn 3d via wT dot_general", lambda u, w: lax.dot_general(
            u, w, (((2,), (1,)), ((), ()))), (up,), (w_dnT,), mm),
        ("dn 3d +residual", lambda u, x, w: x + u @ w, (up, x),
         (w_dn,), mm),
        ("gelu+dn 3d", lambda u, x, w: x + jax.nn.gelu(u) @ w,
         (up, x), (w_dn,), mm),
        ("full ffn chain (ln,up,gelu,dn,res)",
         lambda x, wu, wd: x + jax.nn.gelu(ln(x, g, b) @ wu) @ wd, (x,),
         (w_up, w_dn), 2 * mm),
        ("full ffn f32-accum dn",
         lambda x, wu, wd: x + lax.dot_general(
             jax.nn.gelu(ln(x, g, b) @ wu), wd,
             (((2,), (0,)), ((), ())),
             preferred_element_type=jnp.float32).astype(x.dtype), (x,),
         (w_up, w_dn), 2 * mm),
    ]
    prof = os.environ.get("FFN_BENCH_PROFILE", "1") == "1"
    for name, f, a, w, flops in cases:
        if prof:
            t = profile_bench(name, f, a, w)
            if t is None:
                continue
        else:
            t = chain_bench(f, a, w)
        print(f"{name:42s} {t*1e3:8.3f} ms  {flops/t/1e12:6.1f} TF/s")


def profile_bench(name, f, args, weights, iters=8):
    """Device-truthful timing: capture an xprof trace of the chained
    loop and sum per-op *device self time* — wall clock carries host
    load, device op durations don't (how the r4 per-op tables were
    measured)."""
    import glob
    import json
    import shutil
    import tempfile
    from xprof.convert import raw_to_tool_data as rtd

    def body(c, _):
        out = f(*[a + c.astype(a.dtype) for a in args], *weights)
        return jnp.sum(out.astype(jnp.float32)) * 1e-20, None

    @jax.jit
    def run(args, weights):
        c, _ = lax.scan(body, jnp.zeros(()), None, length=iters)
        return c

    float(run(args, weights))  # compile outside the capture
    logdir = tempfile.mkdtemp(prefix="ffnprof_")
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
        return total / 1e6 / iters  # us -> s, per iteration
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


if __name__ == "__main__":
    main()
