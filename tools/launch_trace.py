#!/usr/bin/env python
"""launch_trace — run a Python program and write its launch record.

    python tools/launch_trace.py out.json program.py [its arguments ...]
    python tools/trace_summary.py out.json --launch

The program runs in this process as ``__main__``; when it ends, however
it ends, ``paddle_tpu.profiler.export_chrome_tracing(out.json)`` writes
what the always-on launch ring holds (``cat="launch"`` spans and the
``launchReport``) with whatever host spans the program collected.  The
launch record needs no switch, so the program needs no edit (it starts
when the program imports ``paddle_tpu``; nothing is imported for it here):

    python tools/launch_trace.py gpt.json benchmark/run.py \\
        --workload gpt2-medium.train-t1024 --seed 7 --seconds 10 --trace 1

The frames this file and ``runpy`` put under the program are taken out of
JAX's source locations (``source_info_util.register_exclusion``): a Mosaic
kernel's body carries the call stack of its call site, the persistent
compile cache's key hashes that body, and two frames more would make a
warm launch cold.  For that JAX is imported here, before the program
starts: a stopwatch the program starts itself does not see that import
(1.6 s), the record's spans are unaffected.
"""
import os
import runpy
import sys


def main():
    if len(sys.argv) < 3:
        raise SystemExit(__doc__)
    # the absolute path, as the interpreter gives a script it runs
    # itself: the program's own frames are in those locations too
    out, program = sys.argv[1], os.path.abspath(sys.argv[2])
    sys.argv = sys.argv[2:]
    sys.path.insert(0, os.path.dirname(program))
    sys.path.insert(1, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:        # private to JAX: when it moves, the tool still runs
        from jax._src.source_info_util import register_exclusion
    except ImportError:
        print("launch_trace: jax._src.source_info_util.register_exclusion "
              "is gone: this tool's frames stay in JAX's source locations, "
              "so a step with Mosaic kernels misses the persistent cache "
              "here (cold `backend` seconds)", file=sys.stderr)
    else:
        for frame_file in (__file__, runpy.__file__, "<frozen runpy>"):
            register_exclusion(frame_file)
    try:
        runpy.run_path(program, run_name="__main__")
    finally:
        from paddle_tpu import profiler
        profiler.export_chrome_tracing(out)
        print(f"launch_trace: wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
