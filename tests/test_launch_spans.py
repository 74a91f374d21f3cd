"""The launch record: ``cat="launch"`` spans fed by ``jax.monitoring`` and
by the program's ``import`` and ``build`` spans, their parents, the
report's union and self times, the recompile note, the persistent
cache's verdict, the ring's bound, the exported clock, and the ``build``
span the benchmark's step metrics select by.
"""
import collections
import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.profiler as prof
from paddle_tpu.profiler import flight, metrics, tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000


@pytest.fixture(autouse=True)
def own_ring(monkeypatch):
    """Each test writes into an empty ring of the real size: in a long
    test process the shared one is full (eager tests compile thousands of
    functions), positions shift with every append and the launch's own
    spans have long dropped out."""
    monkeypatch.setattr(tracer, "_launch",
                        collections.deque(maxlen=tracer._launch.maxlen))


def span(name, t0_ms, t1_ms, fun, tid=1, **fields):
    """One event tuple as the ring holds it."""
    return (name, t0_ms * MS, t1_ms * MS, tid, "launch",
            {"fun": fun, **fields})


def fresh(tag):
    """A jitted function nobody has traced, which calls an inner jit.
    -> (the function, the inner one's name)"""
    def inner(x):
        return x * 2
    inner.__name__ = inner.__qualname__ = f"inner_{tag}"
    inner = jax.jit(inner)

    def outer(x):
        return inner(x) + 1
    outer.__name__ = outer.__qualname__ = f"outer_{tag}"
    return jax.jit(outer), f"inner_{tag}"


# ---------------------------------------------------------------------------
# what JAX's listeners record
# ---------------------------------------------------------------------------
def test_nested_jits_give_nested_spans_with_the_right_parent():
    f, inner_name = fresh("nest")
    f(jnp.ones(4)).block_until_ready()
    spans = tracer.launch_spans()
    mine = {(s["name"], s["fun"]): s for s in spans}
    outer_trace = mine[("trace", "outer_nest")]
    inner_trace = mine[("trace", inner_name)]
    assert outer_trace["parent"] is None
    assert inner_trace["parent"] == outer_trace["id"]
    assert inner_trace["root"] == outer_trace["id"]
    assert outer_trace["start_ns"] <= inner_trace["start_ns"] \
        and inner_trace["end_ns"] <= outer_trace["end_ns"]
    # the inner jit is lowered and compiled inside its caller's module
    assert ("lower", inner_name) not in mine
    assert ("backend", inner_name) not in mine
    # and every span of the process says which launch it belongs to
    doc = tracer.chrome_trace_dict()
    launch = [e for e in doc["traceEvents"] if e["cat"] == "launch"]
    assert {e["args"]["launch"] for e in launch} == {tracer.LAUNCH_ID}


def test_jit_is_taken_off_fun_so_three_phases_carry_one_name():
    f, _ = fresh("name")
    f(jnp.ones(3)).block_until_ready()
    phases = {s["name"]: s for s in tracer.launch_spans()
              if s["fun"] == "outer_name"}
    assert set(phases) == {"trace", "lower", "backend"}
    assert phases["trace"]["end_ns"] <= phases["lower"]["start_ns"] + MS
    assert phases["lower"]["end_ns"] <= phases["backend"]["start_ns"] + MS
    assert not any(s["fun"].startswith("jit(") for s in tracer.launch_spans())


def built(fun):
    """Name ``fun`` as a function the program built, as
    ``build_spmd_train_step`` does its step."""
    tracer.record_launch("build", time.time_ns(), time.time_ns(), fun=fun)


def recompiles():
    return metrics.counter("compile.recompiles").value


def compile_notes(site):
    return [e[3] for e in flight.events() if e[1:3] == ("mem", "compile")
            and e[3]["site"] == site]


def test_a_second_backend_compile_is_a_recompile_in_flight_and_counter():
    f, _ = fresh("again")
    built("outer_again")
    x4, x5 = jnp.ones(4), jnp.ones(5)       # made first: they compile too
    before = {k: metrics.counter(k).value for k in
              ("compile.recompiles", "compile.backend")}
    f(x4).block_until_ready()
    assert recompiles() == before["compile.recompiles"]
    assert metrics.counter("compile.backend").value == \
        before["compile.backend"] + 1
    assert not compile_notes("outer_again")
    f(x5).block_until_ready()               # a new shape: the same function
    assert recompiles() == before["compile.recompiles"] + 1
    notes = compile_notes("outer_again")
    assert len(notes) == 1 and notes[0]["cause"] == "retrace"
    assert tracer.launch_report()["functions"]["outer_again"]["compiles"] == 2
    # built anew under the same name, it is a new function
    g, _ = fresh("again")
    built("outer_again")
    g(x4).block_until_ready()
    assert recompiles() == before["compile.recompiles"] + 1
    assert len(compile_notes("outer_again")) == 1


def test_a_shared_name_is_no_identity_and_no_recompile():
    """JAX hands over a name.  Two lambdas compiled once each, and an
    eager op jitted again for a new shape, share theirs in a healthy
    launch: no verdict, only the report's count by name."""
    x4, x5 = jnp.ones(4), jnp.ones(5)
    before = recompiles()
    jax.jit(lambda x: x * 3)(x4).block_until_ready()
    jax.jit(lambda x: x - 3)(x4).block_until_ready()
    f, _ = fresh("unbuilt")
    f(x4).block_until_ready()
    f(x5).block_until_ready()
    assert recompiles() == before
    assert not compile_notes("<lambda>") and not compile_notes("outer_unbuilt")
    functions = tracer.launch_report()["functions"]
    assert functions["<lambda>"]["compiles"] == 2
    assert functions["outer_unbuilt"]["compiles"] == 2


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache on, in a directory of the test's own (the
    suite runs with it off: conftest.py)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (True, str(tmp_path), 0.0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_a_persistent_cache_hit_is_recorded_with_its_retrieval_seconds(
        persistent_cache):
    def cached_fn(x):
        return jnp.tanh(x) * 3
    x = jnp.ones(7)
    hits0 = metrics.counter("compile.cache_hit").value
    jax.jit(cached_fn)(x).block_until_ready()
    jax.clear_caches()
    jax.jit(cached_fn)(x).block_until_ready()
    first, second = [s for s in tracer.launch_spans()
                     if (s["name"], s["fun"]) == ("backend", "cached_fn")]
    assert first["cache"] == "miss" and "retrieval_s" not in first
    assert second["cache"] == "hit" and second["retrieval_s"] > 0
    assert metrics.counter("compile.cache_hit").value == hits0 + 1
    row = tracer.launch_report()["functions"]["cached_fn"]
    assert row["cache"] == {"hit": 1, "miss": 1, "off": 0}
    assert row["retrieval_s"] == second["retrieval_s"]


def test_with_the_cache_off_the_backend_span_says_so():
    assert not jax.config.jax_enable_compilation_cache   # conftest.py
    f, _ = fresh("off")
    f(jnp.ones(2)).block_until_ready()
    backend, = [s for s in tracer.launch_spans()
                if (s["name"], s["fun"]) == ("backend", "outer_off")]
    assert backend["cache"] == "off"


def test_a_steady_call_records_nothing():
    f, _ = fresh("steady")
    f(jnp.ones(4)).block_until_ready()
    n = len(tracer.launch_events())
    for _ in range(3):
        f(jnp.ones(4)).block_until_ready()
    assert len(tracer.launch_events()) == n


def test_paddle_tpu_registers_jax_listeners_in_one_place():
    """One listener of each kind, all the tracer's; no other module of
    the package registers any."""
    from jax._src import monitoring
    ours = [cb for cb in (monitoring.get_event_listeners()
                          + monitoring.get_event_duration_listeners()
                          + monitoring.get_event_time_span_listeners())
            if getattr(cb, "__module__", "").startswith("paddle_tpu")]
    assert sorted(cb.__name__ for cb in ours) == [
        "_on_jax_duration", "_on_jax_event", "_on_jax_time_span"]
    assert {cb.__module__ for cb in ours} == {"paddle_tpu.profiler.tracer"}
    sites = [p for p in glob.glob(os.path.join(ROOT, "paddle_tpu", "**",
                                               "*.py"), recursive=True)
             if "monitoring.register_" in open(p).read()]
    assert [os.path.relpath(p, ROOT) for p in sites] == \
        ["paddle_tpu/profiler/tracer.py"]


# ---------------------------------------------------------------------------
# the program's own spans
# ---------------------------------------------------------------------------
def test_the_import_spans_cover_the_package_and_the_step_builders():
    """In a process of its own, as a launch is: one root ``import`` span
    for the package and one for ``paddle_tpu.models``, imported apart, and
    ``setup_import_s`` reads both."""
    code = """
import json, time
import paddle_tpu, paddle_tpu.models
from paddle_tpu.profiler import tracer
from benchmark.layer_metrics import launch_span
spec = json.load(open("benchmark/layer_metrics/setup_import_s.json"))
print(json.dumps({"spans": [s for s in tracer.launch_spans()
                            if s["name"] == "import"],
                  "metric": launch_span.read(None, spec),
                  "now_ns": time.time_ns()}))
"""
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                       capture_output=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    package, models = out["spans"]
    assert (package["fun"], models["fun"]) == ("paddle_tpu",
                                               "paddle_tpu.models")
    assert package["parent"] is None and models["parent"] is None
    assert package["end_ns"] <= models["start_ns"] <= out["now_ns"]
    seconds = [(s["end_ns"] - s["start_ns"]) / 1e9 for s in (package, models)]
    assert min(seconds) > 0.01
    assert out["metric"] == pytest.approx(sum(seconds))


def _tiny_config(model):
    if model == "gpt":
        from paddle_tpu.models import GPTConfig
        return GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=16, ffn_mult=2)
    if model == "lfm2":
        from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig
        return Lfm2MoeConfig(
            vocab_size=128, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_dense_layers=1, num_experts=8,
            layer_types=("conv", "full_attention", "conv"),
            num_experts_per_tok=2, num_experts_held=4,
            num_attention_heads=4, num_key_value_heads=2)
    if model == "qwen3_next":
        from paddle_tpu.models import Qwen3NextConfig
        return Qwen3NextConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            full_attention_interval=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            num_experts_held=4, gdn_chunk=8)
    from paddle_tpu.models.joyai_flash import JoyAIFlashConfig
    return JoyAIFlashConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        n_routed_experts=8, num_experts_per_tok=2, num_experts_held=4)


def _metric_file(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("model,step_name", [
    ("gpt", "gpt_spmd_train_step"),
    ("lfm2", "lfm2_moe_spmd_train_step"),
    ("qwen3_next", "qwen3_next_spmd_train_step"),
    ("joyai", "joyai_flash_spmd_train_step")])
def test_build_names_the_step_for_the_step_metrics(model, step_name):
    """``build`` records the step under the name it is jitted under, so
    JAX's spans of it lie under the same root and the step metrics, which
    list no name, read them."""
    from benchmark.layer_metrics import launch_span
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    step, _ = build_spmd_train_step(
        _tiny_config(model), build_mesh({"dp": 1}),
        compute_dtype=jnp.bfloat16, remat_policy="ctx")
    assert step.__name__ == step_name
    build, = [s for s in tracer.launch_spans() if s["name"] == "build"]
    assert build["fun"] == step_name and build["parent"] is None
    now = time.time_ns()
    for k, (metric, phase) in enumerate((("setup_step_trace_s", "trace"),
                                         ("setup_step_lower_s", "lower"),
                                         ("setup_step_backend_s", "backend"))):
        spec = _metric_file(metric)
        assert spec == {"reader": "launch_span", "phase": phase,
                        "note": spec["note"]}
        with pytest.raises(LookupError, match=step_name):
            launch_span.read(None, spec)    # built, and not yet traced
        # what JAX reports of a jit of that name
        tracer._on_jax_time_span(
            next(e for e, p in tracer._JAX_PHASES.items() if p == phase),
            now / 1e9 + k, now / 1e9 + k + 0.25, fun_name=f"jit({step_name})")
        assert launch_span.read(None, spec) == pytest.approx(0.25)
    assert _metric_file("setup_import_s")["functions"] == \
        ["paddle_tpu", "paddle_tpu.models"]


def test_the_launch_metrics_move_setup_s_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    launch = [m for m in bench["per_layer"] if m["layer"] == "launch"]
    assert [m["name"] for m in launch] == [
        "setup_import_s", "setup_step_trace_s", "setup_step_lower_s",
        "setup_step_backend_s"]
    for m in launch:
        assert m["moves"] == "setup_s" and "workloads" not in m
        assert (m["unit"], m["better"], m["source"]) == \
            ("s", "lower", "program_counter")


# ---------------------------------------------------------------------------
# the report, on synthetic spans
# ---------------------------------------------------------------------------
def test_trace_seconds_are_the_union_not_the_sum():
    evs = [span("trace", 2, 5, "launcher"),         # children arrive first
           span("trace", 6, 7, "launcher"),
           span("trace", 0, 10, "step"),
           span("lower", 10, 14, "step"),
           span("backend", 14, 20, "step", cache="miss"),
           # the same function traced again, overlapping on another thread
           span("trace", 8, 12, "step", tid=2)]
    row = tracer.launch_report(evs)["functions"]["step"]
    assert row["seconds"] == {"trace": 0.012, "lower": 0.004,
                              "backend": 0.006}
    assert row["children"] == {
        "launcher": {"self_s": 0.004, "total_s": 0.004, "spans": 2}}
    # the step's own: 10 - 4 of the launchers + 4 of the second trace
    assert row["self_seconds"] == {"trace": pytest.approx(0.010),
                                   "lower": 0.004, "backend": 0.006}
    assert row["compiles"] == 1 and row["cache"]["miss"] == 1


def test_self_time_is_the_duration_less_what_the_children_cover():
    evs = [span("trace", 3, 4, "leaf"),
           span("trace", 2, 6, "mid"),
           span("trace", 7, 9, "mid"),
           span("trace", 0, 10, "top")]
    spans = {(s["fun"], s["start_ns"] // MS): s
             for s in tracer.launch_spans(evs)}
    assert spans[("leaf", 3)]["self_ns"] == 1 * MS
    assert spans[("mid", 2)]["self_ns"] == 3 * MS
    assert spans[("top", 0)]["self_ns"] == 4 * MS
    assert spans[("leaf", 3)]["parent"] == spans[("mid", 2)]["id"]
    assert spans[("leaf", 3)]["root"] == spans[("top", 0)]["id"]
    report = tracer.launch_report(evs)
    assert list(report["functions"]) == ["top"]
    top = report["functions"]["top"]
    # a launcher with what it traces: the union, not its self time
    assert top["children"]["mid"] == {
        "self_s": pytest.approx(0.005), "total_s": 0.006, "spans": 2}
    assert top["children"]["leaf"]["total_s"] == 0.001
    assert top["self_seconds"]["trace"] \
        + sum(c["self_s"] for c in top["children"].values()) \
        == pytest.approx(top["seconds"]["trace"])


def test_a_span_of_another_thread_is_nobodys_child():
    evs = [span("trace", 2, 5, "worker_fn", tid=2),
           span("trace", 0, 10, "main_fn", tid=1)]
    report = tracer.launch_report(evs)
    assert set(report["functions"]) == {"worker_fn", "main_fn"}
    assert all(s["parent"] is None for s in tracer.launch_spans(evs))


def test_spans_from_two_threads_land_under_their_own_thread():
    fns = [fresh(f"thread{i}")[0] for i in range(2)]
    threads = [threading.Thread(
        target=lambda f=f: f(jnp.ones(4)).block_until_ready()) for f in fns]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    roots = {s["fun"]: s["tid"] for s in tracer.launch_spans()
             if s["name"] == "trace" and s["fun"].startswith("outer_thread")}
    assert len(roots) == 2 and len(set(roots.values())) == 2
    for s in tracer.launch_spans():
        if s["parent"] is not None:
            assert tracer.launch_spans()[s["parent"]]["tid"] == s["tid"]


# ---------------------------------------------------------------------------
# the ring and the export
# ---------------------------------------------------------------------------
def test_the_ring_is_bounded_and_counts_what_it_dropped(monkeypatch):
    monkeypatch.setattr(tracer, "_launch", collections.deque(maxlen=4))
    dropped0 = metrics.counter("launch.dropped").value
    for i in range(7):
        tracer.record_launch("trace", i * MS, (i + 1) * MS, fun=f"f{i}")
    assert [e[5]["fun"] for e in tracer.launch_events()] == \
        ["f3", "f4", "f5", "f6"]
    assert metrics.counter("launch.dropped").value == dropped0 + 3
    assert tracer.launch_report()["dropped"] == dropped0 + 3
    monkeypatch.undo()          # the real ring: a launch fits four times
    assert tracer._launch.maxlen == 1 << 15


def test_many_threads_lose_no_span_and_no_drop(monkeypatch):
    """More writers than cores into a small ring: what is held and what
    was counted as dropped add up to what was written."""
    monkeypatch.setattr(tracer, "_launch", collections.deque(maxlen=64))
    dropped0 = metrics.counter("launch.dropped").value
    threads, each = 16, 500
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=lambda i=i: [
            tracer.record_launch("trace", j, j + 1, fun=f"t{i}")
            for j in range(each)]) for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert len(tracer.launch_events()) == 64
    assert metrics.counter("launch.dropped").value - dropped0 \
        == threads * each - 64


def test_export_holds_the_launch_spans_on_the_epoch_clock(tmp_path):
    before = time.time()
    f, _ = fresh("export")
    f(jnp.ones(4)).block_until_ready()
    tracer.enable()
    t0 = tracer.now_ns()
    time.sleep(0.005)
    tracer.record("op::probe", t0, tracer.now_ns(), cat="dispatch")
    path = prof.export_chrome_tracing(str(tmp_path / "t.json"))
    tracer.disable()
    tracer.clear()
    after = time.time()
    with open(path) as f:
        doc = json.load(f)
    mine = [e for e in doc["traceEvents"] if e["cat"] == "launch"
            and e["args"]["fun"] == "outer_export"]
    assert {e["name"] for e in mine} == {"trace", "lower", "backend"}
    probe, = [e for e in doc["traceEvents"] if e["name"] == "op::probe"]
    for e in mine + [probe]:       # ts in us since the Unix epoch
        assert before * 1e6 <= e["ts"] and e["ts"] + e["dur"] <= after * 1e6
    assert 5e3 <= probe["dur"] < 1e6
    # events() itself stays on now_ns
    assert abs(tracer.now_ns() + tracer.EPOCH_OFFSET_NS
               - time.time_ns()) < 50 * MS
    # parents are spans of the same document; the report rides along
    ids = {e["args"]["id"] for e in doc["traceEvents"]
           if e["cat"] == "launch"}
    assert all(e["args"]["parent"] in ids | {None}
               for e in doc["traceEvents"] if e["cat"] == "launch")
    assert doc["launchReport"]["functions"]["outer_export"]["compiles"] == 1
    # a window's spans, given by hand, are exported alone
    alone = tracer.chrome_trace_dict([("x", 0, 1, 1, "host", None)])
    assert len(alone["traceEvents"]) == 1 and "launchReport" not in alone


def test_export_takes_the_native_collectors_spans_to_the_epoch_clock(
        tmp_path):
    """The C++ ring stamps ``steady_clock``; in the exported file its
    spans lie on the epoch clock beside the tracer's and the launch's."""
    NP = prof._load_native()
    if NP is None:
        pytest.skip("native library unavailable")
    before = time.time()
    NP.enable(1024)
    try:
        t0 = NP.now_ns()
        NP.record("native::probe", t0, t0 + 2000, 7)
        built("exported_beside")
        path = prof.export_chrome_tracing(str(tmp_path / "t.json"))
    finally:
        NP.disable()
    after = time.time()
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    probe, = [e for e in evs if e["name"] == "native::probe"]
    build, = [e for e in evs if e["cat"] == "launch"]
    for e in (probe, build):
        assert before * 1e6 - 1 <= e["ts"] <= after * 1e6 + 1
    assert probe["dur"] == pytest.approx(2.0)


def test_trace_summary_prints_the_launch_table(tmp_path):
    evs = [span("trace", 2, 5, "flash_fwd_launcher"),
           span("trace", 0, 10, "my_train_step"),
           span("lower", 10, 14, "my_train_step"),
           span("backend", 14, 2014, "my_train_step", cache="hit",
                retrieval_s=1.5),
           span("import", 0, 1800, "paddle_tpu", tid=2)]
    doc = {"traceEvents": [], "launchReport": tracer.launch_report(evs)}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    script = os.path.join(ROOT, "tools", "trace_summary.py")
    r = subprocess.run([sys.executable, script, str(path), "--launch"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    rows = {ln.split()[0]: ln.split() for ln in r.stdout.splitlines()
            if ln and not ln.startswith((" ", "-", "launch"))}
    #        function  import build trace   lower   backend cache compiles
    assert rows["my_train_step"][1:] == \
        ["-", "-", "0.010", "0.004", "2.000", "hit:1", "1"]
    assert rows["paddle_tpu"][1:] == ["1.800", "-", "-", "-", "-", "-", "0"]
    child, = [ln for ln in r.stdout.splitlines()
              if "flash_fwd_launcher" in ln]
    assert child.split()[:9] == ["0.003", "s", "self", "0.003", "s", "in",
                                 "all", "1", "spans"]
    # the op table leaves the launch spans out
    path.write_text(json.dumps(tracer.chrome_trace_dict()))
    r = subprocess.run([sys.executable, script, str(path)],
                       capture_output=True, text=True, timeout=120)
    assert "backend" not in r.stdout and "import" not in r.stdout


def test_launch_trace_runs_a_program_and_writes_its_record(tmp_path):
    """``tools/launch_trace.py``: the program runs as ``__main__`` under
    the absolute path the interpreter itself would give it (its frames
    are in JAX's locations, hence in the compile cache's key), its exit
    code comes through, and the record is written however it ends."""
    (tmp_path / "prog.py").write_text(
        "import sys, jax, jax.numpy as jnp, paddle_tpu\n"
        "def traced_by_prog(x):\n    return x + 1\n"
        "jax.jit(traced_by_prog)(jnp.ones(3)).block_until_ready()\n"
        "print(sys._getframe().f_code.co_filename, __name__, sys.argv[1:])\n"
        "sys.exit(3)\n")
    tool = os.path.join(ROOT, "tools", "launch_trace.py")
    r = subprocess.run([sys.executable, tool, "out.json", "prog.py", "--x"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 3, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path / "prog.py"), "__main__",
                                "['--x']"]
    with open(tmp_path / "out.json") as f:
        doc = json.load(f)
    row = doc["launchReport"]["functions"]["traced_by_prog"]
    assert set(row["seconds"]) == {"trace", "lower", "backend"}
    assert "paddle_tpu" in doc["launchReport"]["functions"]

