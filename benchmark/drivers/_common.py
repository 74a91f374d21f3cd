"""What the drivers share: the check on the program's kernel selection
and the timed loop that keeps one step in flight."""
import time

import numpy as np


def no_interpreted_kernels(tag):
    """Prints which implementation each kernel took and fails the run on
    an interpreted one (never right on a chip)."""
    from paddle_tpu.ops import pallas
    sel = pallas.selections()
    print(f"[{tag}] kernel selections: {sel}", flush=True)
    bad = [k for k in sel if k.endswith(".interpret")]
    if bad:
        raise RuntimeError(f"interpreted kernels in a timed run: {bad}")


def steps_until(dispatch, seconds, settle):
    """Calls ``dispatch()`` (-> the step's loss, not yet waited for) until
    ``seconds`` have passed, waiting for step i-1's loss after step i is
    dispatched, then for the last one and for ``settle()``.  The window
    runs from the first dispatch to the end of the last step.
    -> (steps, steps with a loss that is not finite, window seconds)"""
    from jax.profiler import TraceAnnotation
    steps, prev, bad = 0, None, 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        with TraceAnnotation("bench.dispatch"):
            loss = dispatch()
        steps += 1
        if prev is not None:
            with TraceAnnotation("bench.wait_previous_step"):
                bad += not np.isfinite(float(prev))
        prev = loss
        if time.perf_counter() >= deadline:
            break
    with TraceAnnotation("bench.wait_last_step"):
        bad += not np.isfinite(float(prev))
        settle()
    return steps, int(bad), time.perf_counter() - t0


def window_result(steps, bad, window_s, batch):
    samples = steps * batch
    return {"samples": samples, "steps": steps, "window_s": window_s,
            "attempted": steps, "failed": bad,
            "end_to_end": {"train_samples_per_s": samples / window_s}}
