"""Bench the routed flash_attention (mode gate as shipped) vs XLA math
across T.  Default timing is DEVICE SELF-TIME from an xprof capture of
the chained loop (independent of host load); pass
--wall for wall-clock.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from _device_bench import device_time, wall_time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--B", type=int, default=128)
    ap.add_argument("--H", type=int, default=12)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--Ts", default="512,1024")
    ap.add_argument("--grad", action="store_true")
    ap.add_argument("--wall", action="store_true")
    args = ap.parse_args()
    use_wall = args.wall
    if not use_wall:
        try:
            # probe the exact dependency device_time uses, not just the
            # top-level package (version skew can lack the converter)
            from xprof.convert import raw_to_tool_data  # noqa: F401
        except ImportError:
            print("xprof converter not importable: falling back to "
                  "--wall timing (contention-sensitive on shared chips)",
                  file=sys.stderr)
            use_wall = True
    if use_wall:
        timer = wall_time
    else:
        def timer(f, a):
            # device_time returns None when the capture produced no
            # xplane, 0.0 when hlo_stats had no self-time rows, and can
            # raise on converter skew; fall back to wall per-row.
            try:
                t = device_time(f, a)
            except Exception as e:
                print(f"device capture failed ({e!r}): wall timing for "
                      "this row", file=sys.stderr)
                t = None
            if not t:
                t = wall_time(f, a)
            return t
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    for T in [int(t) for t in args.Ts.split(",")]:
        B = args.B * 512 // T  # constant tokens
        rng = np.random.RandomState(0)
        shape = (B, T, args.H, args.d)
        q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        flops = 2 * 2 * B * args.H * T * T * args.d

        def pall(q):
            return fa.flash_attention(q, q, q, causal=True)

        def xla(q):
            qf = jnp.swapaxes(q, 1, 2).reshape(B * args.H, T, args.d)
            o = fa._xla_attention(qf, qf, qf, 1.0 / np.sqrt(args.d), True)
            return jnp.swapaxes(o.reshape(B, args.H, T, args.d), 1, 2)

        for name, f in [("pallas", pall), ("xla", xla)]:
            if args.grad:
                g = lambda q, f=f: jax.grad(
                    lambda x: jnp.sum(f(x).astype(jnp.float32)))(q)
                t = timer(g, (q,))
                eff = 3 * flops / t / 1e12
            else:
                t = timer(f, (q,))
                eff = flops / t / 1e12
            print(f"T={T:5d} B={B:4d} {name:7s} "
                  f"{'fwd+bwd' if args.grad else 'fwd':7s} "
                  f"{t*1e3:8.2f} ms  ({eff:5.1f} T eff)")


if __name__ == "__main__":
    main()
