"""Seconds of one phase of the launch, from the program's own record.

The program keeps a launch record (``paddle_tpu.profiler.tracer``): a
span for every trace, lowering and backend compile — or fetch from the
persistent cache — that JAX made, and for the package's ``import`` and a
step's ``build``, each under the function it belongs to; its
``launch_report()`` gives, per root function, the seconds of each phase
as the union of that phase's intervals.  The metric's file names the
``phase``.  Whose, the program says itself: the functions it built —
those that own a ``build`` span, which ``build_spmd_train_step`` records
under the name it jits the step under — unless the file lists
``functions`` (the ``import`` spans, which bear a module's name).  The
value is the seconds of that phase over those functions.

The readers run in the process that launched, so the record is read in
place and nothing of ``run`` is used.  Spans are selected by function,
not by time: the float32 reference compiles before the readers run, and
nothing of it is built under a ``build`` span.

Nothing to read, so None and the metric left out of the line: a program
that keeps no such record (a commit from before it came; the reader says
so on stderr), and a launch that built no step.  A step that was built
and has no span of the phase — or a record that holds none of the listed
functions — is an error, never a zero; the error says how many spans the
record's ring has dropped.
"""
import sys


def read(run, spec, report=None):
    if report is None:
        try:
            from paddle_tpu.profiler import tracer
            report = tracer.launch_report()
        except (ImportError, AttributeError):
            print("launch_span: the program keeps no launch record: "
                  "nothing to read", file=sys.stderr)
            return None
    phase, functions = spec["phase"], report["functions"]
    wanted = spec.get("functions")
    if wanted is None:
        wanted = [name for name, f in functions.items()
                  if "build" in f["seconds"]]
        if not wanted:
            return None
        found = wanted
    else:
        found = [name for name in wanted if name in functions]
    missing = [name for name in found
               if phase not in functions[name]["seconds"]]
    if not found or missing:
        raise LookupError(
            f"the launch record holds no root '{phase}' span of "
            f"{missing or wanted} ({report['spans']} spans held, "
            f"{report['dropped']} dropped); root functions: "
            f"{sorted(functions)[:40]}")
    return sum(functions[name]["seconds"][phase] for name in found)
