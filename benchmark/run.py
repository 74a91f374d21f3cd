#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set up (weights and data from the seed, the cell's shapes warmed, the
first steps recorded for the check), measure for ``--seconds``, compare
what the timed path produced with the plain reference, print one JSON
line.  Needs the accelerator the cell asks for: without it the exit code
is not 0 and no result is printed.  This file knows no cell,
configuration or metric by name: it finds each by the name
``BENCHMARK.json`` gives, in a file of its own (see README.md).
"""
import time
_PROCESS_START = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import types         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(*parts, what):
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise SystemExit(f"run.py: no {what} at {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def find_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(
            f"run.py: no {kind} module {os.path.relpath(path, ROOT)}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def load_cell(workload):
    """The cell's entries of BENCHMARK.json and the files they name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: BENCHMARK.json has no workload "
                         f"{workload!r}; it has {sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise SystemExit(f"run.py: BENCHMARK.json has no configuration "
                         f"{entry['config']!r}")
    with open(os.path.join(ROOT, configs[entry["config"]]["file"])) as f:
        config = json.load(f)
    cell = _load_json("workloads", workload + ".json", what="cell file")

    def wanted(metric):
        return workload in metric.get("workloads", [workload])

    return types.SimpleNamespace(
        name=workload, chips=entry["chips"], config=config, cell=cell,
        traffic=cell["traffic"],
        end_to_end=[m for m in bench["end_to_end"] if wanted(m)],
        per_layer=[m for m in bench["per_layer"] if wanted(m)])


def require_chips(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX reports "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    return devs


def memory_peak(devs):
    """Peak bytes on the fullest chip.  On the TPU runtime an executable's
    temporaries are not part of ``peak_bytes_in_use``: they are reserved
    apart (``peak_bytes_reserved``), and what is free is the limit less
    both — so the peak is their sum."""
    def peak(d):
        s = d.memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0)) \
            + int(s.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devs)


class CompileCounter:
    """Counts backend compiles (or cache fetches) while ``armed``."""

    def __init__(self):
        from jax import monitoring
        self.armed, self.n = False, 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def read_layer_metrics(cell, run):
    """Every per-layer metric of the cell through its own reader; a
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in cell.per_layer:
        spec = _load_json("layer_metrics", m["name"] + ".json",
                          what="per-layer metric file")
        value = find_module("layer_metrics", spec["reader"]).read(run, spec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, seed, seconds, trace, devs, peaks):
    """Everything of a run after the look for a chip: set-up, window,
    reference, comparison.  -> the result line as a dict."""
    driver = find_module("drivers", cell.cell["driver"])
    reference = find_module("references", cell.cell["reference"])
    flops = find_module("flops", cell.cell["flops"])
    checker = find_module("checks", cell.cell["check"])
    from benchmark.harness import trace as tr
    import jax
    compiles = CompileCounter()
    phases = {"to_devices": time.perf_counter() - _PROCESS_START}
    ctx = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, cell=cell.cell, seed=seed,
        reference=reference, check=checker, phases=phases)
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - _PROCESS_START

    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.armed = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            win = driver.window(state, seconds)
    finally:
        compiles.armed = False
        if trace:
            jax.profiler.stop_trace()
    mem_peak = memory_peak(devs)
    mem_stats = {k: int(v) for k, v in (devs[0].memory_stats() or {}).items()}
    produced = state.produced
    driver.release(state)
    del state

    # the cell's own comparison with the plain reference, once the window
    # has closed, the peak has been read and the program's state is freed
    t_ref = time.perf_counter()
    ok, checked, check_info = checker.compare(cell, reference, seed, produced)
    checked["compiles_in_window"] = [compiles.n, 0]
    checked["failed"] = [win["failed"], 0]
    ok = ok and not compiles.n and not win["failed"]
    ref_s = time.perf_counter() - t_ref

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(ok), "attempted": win["attempted"],
              "failed": win["failed"]}
    if not trace:
        values = dict(win["end_to_end"], setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
    else:
        dev_events, host_spans = tr.load(trace_dir)
        outer = [s for s in host_spans if s[0] == "bench.window"]
        if not outer:
            raise RuntimeError("the trace holds no bench.window span")
        t0_ns, t1_ns = outer[0][1], outer[0][1] + outer[0][2]
        per_dev = [tr.clip(ev, t0_ns, t1_ns) for ev in dev_events.values()]
        window_s = (t1_ns - t0_ns) / 1e9
        busy_s = sum(tr.busy_seconds(ev) for ev in per_dev) / len(per_dev)
        device["busy_s"], device["window_s"] = busy_s, window_s
        # what a reader may read; ``win`` is whatever the cell's driver
        # returned from its window (a training driver: steps, samples,
        # window_s by the host's clock)
        run = {"events": per_dev, "window_s": window_s, "busy_s": busy_s,
               "win": win, "peaks": peaks, "chips": cell.chips,
               "config": cell.config, "traffic": cell.traffic,
               "flops": flops, "memory_peak_bytes": mem_peak}
        result["metrics"] = read_layer_metrics(cell, run)
        inner = [s for s in host_spans if s[0] != "bench.window"]
        result["breakdown"] = {
            "device_ops": tr.top_ops(per_dev[0]),
            "idle_gaps": tr.idle_gaps(per_dev[0], inner, t0_ns, t1_ns)}
        shutil.rmtree(trace_dir, ignore_errors=True)
        # every operation's full name, time and count, for whoever has to
        # write a metric's patterns: small, and overwritten by each run
        with open(trace_dir + ".ops.json", "w") as f:
            json.dump(sorted(([n, *v] for n, v in
                              tr.op_table(per_dev[0]).items()),
                             key=lambda r: -r[1]), f)
    result["device"] = device
    result["info"] = {"workload": cell.name, "seed": seed, "window": win,
                      "setup_s": setup_s, "setup_phases": phases,
                      "reference_s": ref_s,
                      "memory_stats": mem_stats, "check": check_info}
    result["checked"] = checked
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # JAX's persistent compile cache: where the environment says, or at
    # one fixed path inside the checkout (the path is part of the key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    cell = load_cell(args.workload)
    from benchmark.harness import peaks as peaks_mod
    import jax
    devs = require_chips(cell.chips)[:cell.chips]
    peaks = peaks_mod.lookup(devs[0].device_kind)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = run_cell(cell, args.seed, args.seconds, args.trace, devs, peaks)
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["checked"].items():
        print(f"checked {name} = {value:.6g} (limit {limit:.6g})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.exit(main())
