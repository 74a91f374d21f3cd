"""The launch reader: on a synthetic report, on a program without the
record, on a launch that built no step, on a built step without the
phase, and on the record a tiny step's launch really leaves."""
import json
import os

import pytest

from benchmark.layer_metrics import launch_span

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = ("setup_import_s", "setup_step_trace_s", "setup_step_lower_s",
           "setup_step_backend_s")


def spec_of(metric):
    with open(os.path.join(HERE, "..", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)


def report(functions, dropped=0):
    return {"launch": "test", "spans": 7, "dropped": dropped,
            "functions": {
                name: {"seconds": seconds, "children": {},
                       "cache": {"hit": 0, "miss": 0, "off": 0},
                       "retrieval_s": 0.0, "compiles": 0}
                for name, seconds in functions.items()}}


@pytest.mark.parametrize("metric,value", [
    ("setup_import_s", 1.75), ("setup_step_trace_s", 2.5),
    ("setup_step_lower_s", 1.25), ("setup_step_backend_s", 4.0)])
def test_each_metric_reads_its_phase_of_its_functions(metric, value):
    rep = report({
        "paddle_tpu": {"import": 1.75},
        "lfm2_moe_spmd_train_step": {"build": 0.5, "trace": 2.5,
                                     "lower": 1.25, "backend": 4.0},
        # the reference's and the check's jits were built by no one
        "loss_and_grads": {"trace": 30.0, "lower": 30.0, "backend": 30.0},
        "<lambda>": {"trace": 9.0, "backend": 9.0}})
    assert launch_span.read({}, spec_of(metric), report=rep) == value


def test_the_step_metrics_take_whatever_step_the_program_built():
    """No list of names: a new configuration's step, or two steps of one
    launch, are read with no edit to a metric file."""
    for metric in METRICS[1:]:
        assert "functions" not in spec_of(metric)
    spec = spec_of("setup_step_trace_s")
    assert launch_span.read({}, spec, report=report(
        {"a_step_of_a_later_pr": {"build": 0.1, "trace": 3.0},
         "other": {"trace": 1.0}})) == 3.0
    assert launch_span.read({}, spec, report=report(
        {"train_step": {"build": 0.1, "trace": 3.0},
         "eval_step": {"build": 0.1, "trace": 0.5}})) == 3.5


def test_a_program_without_the_record_reads_as_none(monkeypatch, capsys):
    from paddle_tpu.profiler import tracer
    monkeypatch.delattr(tracer, "launch_report")
    for metric in METRICS:
        assert launch_span.read({}, spec_of(metric)) is None
    assert "no launch record" in capsys.readouterr().err


@pytest.mark.parametrize("functions", [
    {}, {"paddle_tpu": {"import": 1.0}, "serve_prefill": {"trace": 1.0}}])
def test_a_launch_that_built_no_step_has_no_step_metric(functions):
    """A serving or ``fit`` cell: nothing to read, the metric left out."""
    for metric in METRICS[1:]:
        assert launch_span.read({}, spec_of(metric),
                                report=report(functions, dropped=3)) is None


@pytest.mark.parametrize("metric,functions", [
    # the step was built and never traced
    ("setup_step_trace_s", {"gpt_spmd_train_step": {"build": 0.1}}),
    # one of two built steps lacks the phase
    ("setup_step_backend_s", {"a_step": {"build": 0.1, "backend": 1.0},
                              "b_step": {"build": 0.1, "trace": 1.0}}),
    # the listed functions are not in the record
    ("setup_import_s", {}),
    ("setup_import_s", {"some_step": {"build": 0.1, "trace": 1.0}})])
def test_a_record_without_the_spans_is_an_error_never_a_zero(
        metric, functions):
    with pytest.raises(LookupError, match="3 dropped"):
        launch_span.read({}, spec_of(metric),
                         report=report(functions, dropped=3))


def test_it_reads_the_record_a_launch_leaves(monkeypatch):
    """A tiny GPT step launched in this process: every step metric reads
    a positive number of seconds off the program's own record, and
    nothing of ``run`` (``setup_import_s`` on a real import:
    ``tests/test_launch_spans.py``, in a process of its own)."""
    import collections
    import jax.numpy as jnp
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    from paddle_tpu.profiler import tracer
    # an empty ring: in a long test process the shared one may be full
    monkeypatch.setattr(tracer, "_launch",
                        collections.deque(maxlen=tracer._launch.maxlen))
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=16, ffn_mult=2)
    step, init = build_spmd_train_step(cfg, build_mesh({"dp": 1}),
                                       compute_dtype=jnp.bfloat16)
    params, opt = init(0)
    ids = jnp.zeros((2, 16), jnp.int32)
    step(params, opt, ids, ids)
    values = {m: launch_span.read(None, spec_of(m)) for m in METRICS[1:]}
    assert all(v > 0 for v in values.values()), values
    row = tracer.launch_report()["functions"]["gpt_spmd_train_step"]
    assert values["setup_step_trace_s"] == row["seconds"]["trace"]
    assert set(row["seconds"]) == {"build", "trace", "lower", "backend"}
    assert row["compiles"] == 1
