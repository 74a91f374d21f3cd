#!/usr/bin/env python3
"""The control and the planted faults of a cell, at the cell's own size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For every seed the plain reference is put in the program's place once
for each variant the cell's file lists under ``controls`` — computed in
the precision below the configuration's, or with a fault planted — and
judged by the cell's own comparison and limits, as a run is.  One JSON
line per seed gives each variant's verdict and every number beside its
limit.  Every variant has to come out not correct: the exit code is 1
if one passes.  ``run.py`` never runs this.  Needs the cell's chips,
like ``run.py``.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def verdicts(cell, seed):
    """{variant: {"correct": bool, "checked": {number: [value, limit]},
    "worst": the three worst leaves of each norm}} for one seed."""
    from benchmark import run as bench_run
    reference = bench_run.find_module("references", cell.cell["reference"])
    checker = bench_run.find_module("checks", cell.cell["check"])
    out = {}
    for name, (ok, checked, info) in checker.controls(
            cell, reference, seed, cell.cell["controls"]).items():
        out[name] = {"correct": bool(ok), "checked": checked,
                     "numbers": info["numbers"],
                     "worst": {k: v["worst"][:3] for k, v in
                               info["spread"].items() if "worst" in v}}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from benchmark import run as bench_run
    cell = bench_run.load_cell(args.workload)
    bench_run.require_chips(cell.chips)
    passed = []
    for seed in map(int, args.seeds.split(",")):
        out = verdicts(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variants": out}), flush=True)
        for name, v in out.items():
            print(f"seed {seed} {name}: correct = {v['correct']}  " + "  ".join(
                f"{k} {x:.6g} (limit {lim:.6g})"
                for k, (x, lim) in v["checked"].items()), file=sys.stderr)
            if v["correct"]:
                passed.append((seed, name))
    if passed:
        print(f"control.py: these came out correct and must not: {passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.exit(main())
