"""The scope reader: ``classify`` on the names the compiled step really
carries, the trace file's wire format, and ``scope_time`` on a synthetic
trace."""
import json
import os
import re

import pytest

from benchmark.harness import scopes
from benchmark.layer_metrics import scope_time

MS = 1e6   # ns
STEP = "jit(gpt_spmd_train_step)/"
# the scopes ``build_spmd_train_step`` sets; the harness knows none of them
PROGRAM = {"embed", "unstack", "attn_qkv", "attn_out", "ffn", "final_ln",
           "loss_head", "optimizer"}


@pytest.fixture(scope="module")
def step_op_names():
    """op_name of every instruction of the tiny step, ``ctx`` remat."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=16, ffn_mult=2)
    step, init = build_spmd_train_step(
        cfg, build_mesh({"dp": 1}, devices=jax.devices()[:1]),
        remat_policy="ctx", compute_dtype=jnp.bfloat16)
    params, opt = init(0)
    ids = jnp.zeros((4, 16), jnp.int32)
    text = step.lower(params, opt, ids, ids).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("tail,kind", [
    ("jvp(ffn)/dot_general", ("ffn", "forward")),
    ("jvp(attn_qkv)/btd,dse->btse/dot_general", ("attn_qkv", "forward")),
    ("transpose(jvp(jvp()))/checkpoint/attn_out/dot_general",
     ("attn_out", "backward")),
    ("transpose(jvp(jvp()))/checkpoint/rematted_computation/ffn/tanh",
     ("ffn", "recompute")),
    ("transpose(jvp(unstack))/concatenate", ("unstack", "backward")),
    ("jvp(embed)/gather", ("embed", "forward")),
    ("transpose(jvp(final_ln))/mul", ("final_ln", "backward")),
    ("jvp(loss_head)/dot_general", ("loss_head", "forward")),
    ("optimizer/sqrt", ("optimizer", "forward")),
    # attention is a sibling of the scopes, never a child
    ("jvp(bqd,bkd->bqk)/dot_general", (None, "forward")),
    ("transpose(jvp(jvp()))/remat2", (None, "backward")),
])
def test_classify_on_the_compiled_steps_own_names(step_op_names, tail, kind):
    assert STEP + tail in step_op_names     # the name is real, not made up
    assert scopes.classify(STEP + tail, PROGRAM) == kind
    # as the device trace writes it: "<op_name>:<op type>"
    assert scopes.classify(STEP + tail + ":", PROGRAM) == kind


def test_the_metric_files_name_every_scope_of_the_program(step_op_names):
    """What counts as a scope is the metric files' to say: together they
    name every scope the step sets, so nothing the program named is
    counted as unattributed."""
    known = scope_time.scope_names(_metric_file("unattributed_device_pct"))
    assert known == PROGRAM
    seen = {scopes.classify(n, known) for n in step_op_names}
    assert {scope for scope, _ in seen} == PROGRAM | {None}
    assert {phase for _, phase in seen} == set(scopes.PHASES)


def test_a_scope_is_a_scope_only_to_the_file_that_names_it():
    name = STEP + "jvp(ffn)/attn_core/mul"
    assert scopes.classify(name, {"ffn"}) == ("ffn", "forward")
    assert scopes.classify(name, {"ffn", "attn_core"})[0] == "attn_core"
    assert scopes.classify(name, {"optimizer"}) == (None, "forward")
    assert scope_time.scope_names({"scopes": ["attn_core"]}) == {"attn_core"}


def test_a_new_metric_file_brings_its_scope(tmp_path, monkeypatch):
    """A later program's scope needs no edit here: its metric file names
    it, and the share under no scope then counts it as attributed."""
    path = _write_trace(str(tmp_path))
    spec = {"scopes": [None], "share_of_busy": True}
    monkeypatch.setattr(scope_time, "HERE", str(tmp_path))
    with open(tmp_path / "ffn_ms.json", "w") as f:
        json.dump({"reader": "scope_time", "scopes": ["ffn"]}, f)
    # ffn is known, optimizer (2 ms) is not: kernel 1 + in_loop 3 + copy 1
    # + opt 2 of 14 busy
    assert scope_time.read(RUN, spec, path) == pytest.approx(100 * 7 / 14)
    with open(tmp_path / "optimizer_ms.json", "w") as f:
        json.dump({"reader": "scope_time", "scopes": ["optimizer"]}, f)
    assert scope_time.read(RUN, spec, path) == pytest.approx(100 * 5 / 14)


def test_the_innermost_scope_wins():
    names = {"ffn", "optimizer"}
    assert scopes.classify("jit(f)/jvp(ffn)/optimizer/mul", names)[0] \
        == "optimizer"
    assert scopes.classify("jit(f)/jvp(optimizer)/ffn/mul", names)[0] == "ffn"
    assert scopes.classify("jit(ffn)/mul", names) == (None, "forward")


# ---------------------------------------------------------------------------
# a synthetic trace file, written in the wire format the reader parses
# ---------------------------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _plane(name, stat_names, events):
    """events: [(event name, {stat id: str or int (a ref)})]"""
    body = _field(2, name)
    for i, (ev_name, stats) in enumerate(events, 1):
        meta = _field(1, i) + _field(2, ev_name)
        for sid, value in stats.items():
            meta += _field(5, _field(1, sid) + _field(
                7 if isinstance(value, int) else 5, value))
        body += _field(4, _entry(i, meta))
    for sid, sname in stat_names.items():
        body += _field(5, _entry(sid, _field(1, sid) + _field(2, sname)))
    return _field(1, body)


NAMES = {
    "fwd": "%fusion.1 = bf16[8] fusion(...)",
    "bwd": "%fusion.2 = bf16[8] fusion(...)",
    "remat": "%fusion.3 = bf16[8] fusion(...)",
    "opt": "%fusion.4 = f32[8] fusion(...)",
    "copy": "%copy.5 = bf16[8] copy(...)",
    "loop": "%while.6 = (s32[]) while(...)",
    "kernel": '%jvp__.7 = bf16[8] custom-call(...), '
              'custom_call_target="tpu_custom_call"',
    "in_loop": "%fusion.8 = f32[8,50257] fusion(...)",
    # in the program, and so in the trace's metadata, but not in the window
    "not_run": "%slice.9 = bf16[8] slice(...)",
}


def _write_trace(root, scoped=True):
    ffn = "jvp(ffn)" if scoped else "jvp()"
    tf = {1: "tf_op", 2: "hlo_category", 3: STEP + "optimizer/mul:"}
    device = _plane("/device:TPU:0", tf, [
        (NAMES["fwd"], {2: "fusion", 1: STEP + ffn + "/dot_general:"}),
        (NAMES["bwd"], {1: STEP + "transpose(" + ffn + ")/dot_general:"}),
        (NAMES["remat"], {1: STEP + "transpose(jvp())/checkpoint/"
                          "rematted_computation/" + ("ffn" if scoped else "x")
                          + "/tanh:"}),
        (NAMES["opt"], {1: 3 if scoped else STEP + "mul:"}),   # by reference
        (NAMES["copy"], {2: "data formatting"}),               # no tf_op
        (NAMES["loop"], {1: STEP + "transpose(jvp())/while:"}),
        (NAMES["kernel"], {1: STEP + "jvp()/pallas_call:"}),
        (NAMES["in_loop"], {1: STEP + "transpose(jvp())/while/body/mul:"}),
        (NAMES["not_run"], {1: STEP + ("jvp(unstack)" if scoped else "jvp()")
                            + "/slice:"}),
    ])
    host = _plane("/host:CPU", tf, [("%fusion.1 = on the host",
                                     {1: STEP + "jvp(ffn)/add:"})])
    d = os.path.join(root, ".bench_trace", "cell", "plugins", "profile", "t0")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(host + device)
    return path


# one chip, a 20 ms window of two steps; the while loop (5..11 ms) spans
# a scoped child and the loss head's unscoped one
EVENTS = [[
    (NAMES["fwd"], 0 * MS, 2 * MS),
    (NAMES["fwd"], 1 * MS, 2 * MS),          # overlaps: 3 ms, not 4
    (NAMES["kernel"], 3 * MS, 1 * MS),
    (NAMES["loop"], 5 * MS, 6 * MS),         # parent
    (NAMES["bwd"], 5 * MS, 2 * MS),
    (NAMES["in_loop"], 8 * MS, 3 * MS),
    (NAMES["remat"], 12 * MS, 1 * MS),
    (NAMES["opt"], 14 * MS, 2 * MS),
    (NAMES["copy"], 17 * MS, 1 * MS),
]]
RUN = {"events": EVENTS, "win": {"steps": 2}}
BLOCK = ["attn_qkv", "attn_out", "ffn"]


def _metric_file(name):
    with open(os.path.join(scope_time.HERE, name + ".json")) as f:
        return json.load(f)


def test_scope_map_reads_the_device_planes_of_the_file(tmp_path):
    path = _write_trace(str(tmp_path))
    found = scopes.scope_map(path)
    assert found[NAMES["fwd"]] == STEP + "jvp(ffn)/dot_general:"
    assert found[NAMES["opt"]] == STEP + "optimizer/mul:"    # a ref_value
    assert NAMES["copy"] not in found
    assert "%fusion.1 = on the host" not in found
    assert scopes.newest_xplane(str(tmp_path)) == path
    with pytest.raises(FileNotFoundError):
        scopes.newest_xplane(str(tmp_path / "nowhere"))


@pytest.mark.parametrize("spec,value", [
    ({"scopes": BLOCK, "phases": ["forward"]}, 1.5),     # the union / 2 steps
    ({"scopes": BLOCK, "phases": ["backward"]}, 1.0),    # the child, not
    ({"scopes": BLOCK, "phases": ["recompute"]}, 0.5),   # its while parent
    ({"scopes": BLOCK}, 3.0),
    ({"scopes": ["optimizer"]}, 1.0),
    # unscoped leaves: kernel 1 + in_loop 3 + copy 1 = 5 ms of 14 busy
    ({"scopes": [None], "share_of_busy": True}, 100 * 5 / 14),
])
def test_scope_time_is_the_union_of_the_selected_leaves(tmp_path, spec, value):
    path = _write_trace(str(tmp_path))
    assert scope_time.read(RUN, spec, path) == pytest.approx(value)


def test_kernels_found_by_pattern_are_attributed(tmp_path, monkeypatch):
    """The Mosaic call and the loss head's loop (with what it spans) are
    matched by the roofline metrics' patterns: only the copy is left."""
    path = _write_trace(str(tmp_path))
    for name, pattern in (("k1", r"^%jvp__[\w.]* = bf16\[.*tpu_custom_call"),
                          ("k2", r"^%while[\w.]* = \(")):
        with open(tmp_path / (name + ".json"), "w") as f:
            json.dump({"reader": "kernel_roofline", "patterns": [pattern]}, f)
    monkeypatch.setattr(scope_time, "HERE", str(tmp_path))
    spec = {"scopes": [None], "known_scopes": ["ffn", "optimizer"],
            "share_of_busy": True, "except_kernels": ["k1", "k2"]}
    assert scope_time.read(RUN, spec, path) == pytest.approx(100 * 1 / 14)


def test_a_selection_that_matches_nothing_raises(tmp_path):
    """The program carries the scope, the window holds none of its
    events: an error, never a zero."""
    path = _write_trace(str(tmp_path))
    with pytest.raises(LookupError, match="unstack"):
        scope_time.read(RUN, {"scopes": ["unstack"]}, path)
    with pytest.raises(LookupError):
        scope_time.read(RUN, {"scopes": ["optimizer"],
                              "phases": ["backward"]}, path)


def test_a_program_without_the_scope_has_nothing_to_read(
        tmp_path, monkeypatch, capsys):
    """A commit from before a scope came, run under the benchmark that
    reads it (the driver lays a PR's benchmark files over the parent): no
    metric and no error, said aloud — also through the path ``run.py``
    takes, which hands over no file."""
    path = _write_trace(str(tmp_path / "scoped"))
    assert scope_time.read(RUN, {"scopes": ["attn_core"]}, path) is None
    assert "attn_core" in capsys.readouterr().err
    _write_trace(str(tmp_path), scoped=False)
    monkeypatch.setattr(scope_time, "ROOT", str(tmp_path))
    for name in ("block_fwd_ms", "unstack_ms", "unattributed_device_pct"):
        assert scope_time.read(RUN, _metric_file(name)) is None
    assert "nothing to read" in capsys.readouterr().err


def test_the_metric_files_select_what_benchmark_json_promises():
    with open(os.path.join(scope_time.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, phases in (("block_fwd_ms", ["forward"]),
                         ("block_bwd_ms", ["backward"]),
                         ("block_recompute_ms", ["recompute"])):
        spec = _metric_file(name)
        assert (spec["reader"], spec["scopes"], spec["phases"]) \
            == ("scope_time", BLOCK, phases)
        assert listed[name]["workloads"] == ["gpt2-medium.train-t1024"]
    spec = _metric_file("unattributed_device_pct")
    assert set(spec["scopes"]) == {None} and spec["share_of_busy"]
    # every kernel the cell finds by pattern is taken out of it
    assert sorted(spec["except_kernels"]) == sorted(
        n for n in listed if _metric_file(n)["reader"] == "kernel_roofline")
