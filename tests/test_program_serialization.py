"""Program save/load round-trip (round-3 VERDICT item 5).

Reference parity: ``framework/framework.proto:234`` (ProgramDesc
round-trips), ``fluid/io.py:1847`` (program + persistables save/load),
``paddle.static.save/load/serialize_program/deserialize_program``.

The contract under test: build, train 2 steps, save, reload in a FRESH
process (subprocess, no model code), continue — the loss curve
continues exactly.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle


def _build(prog, sp):
    with paddle.static.program_guard(prog, sp):
        x = paddle.static.data("x", [8, 4], "float32")
        y = paddle.static.data("y", [8, 1], "float32")
        lin = paddle.nn.Linear(4, 1)
        loss = paddle.mean((lin(x) - y) ** 2)
        paddle.optimizer.Adam(learning_rate=0.05).minimize(loss)
    return loss


def _data():
    rng = np.random.RandomState(0)
    xv = rng.rand(8, 4).astype(np.float32)
    yv = xv @ np.array([[1.0], [2.0], [-1.0], [0.5]], np.float32)
    return xv, yv


def test_save_load_params_roundtrip(tmp_path):
    paddle.enable_static()
    try:
        prog, sp = paddle.static.Program(), paddle.static.Program()
        loss = _build(prog, sp)
        exe = paddle.static.Executor()
        exe.run(sp)
        xv, yv = _data()
        exe.run(prog, feed={"x": xv, "y": yv}, fetch_list=[loss])
        w0 = {n: np.asarray(p._data)
              for n, p in prog.parameters.items()}
        path = str(tmp_path / "ck")
        paddle.static.save(prog, path)
        # clobber, then restore
        for p in prog.parameters.values():
            p._data = p._data * 0.0
        paddle.static.load(prog, path)
        for n, p in prog.parameters.items():
            np.testing.assert_allclose(np.asarray(p._data), w0[n])
        assert os.path.exists(path + ".pdopt")   # Adam slots saved too
    finally:
        paddle.disable_static()


def test_serialize_deserialize_same_process(tmp_path):
    paddle.enable_static()
    try:
        prog, sp = paddle.static.Program(), paddle.static.Program()
        loss = _build(prog, sp)
        exe = paddle.static.Executor()
        exe.run(sp)
        xv, yv = _data()
        exe.run(prog, feed={"x": xv, "y": yv}, fetch_list=[loss])
        data = paddle.static.serialize_program(fetch_vars=[loss],
                                               program=prog)
        lp = paddle.static.deserialize_program(data)
        # op table introspectable (framework.proto parity)
        types = [o["type"] for o in lp.ops]
        assert "linear" in types and any(t.endswith("_grad")
                                         for t in types)
        # stepping the deserialized program matches the live one
        want = float(exe.run(prog, feed={"x": xv, "y": yv},
                             fetch_list=[loss])[0])
        got = float(np.asarray(exe.run(lp, feed={"x": xv, "y": yv})[0]))
        np.testing.assert_allclose(got, want, rtol=1e-6)
    finally:
        paddle.disable_static()


def test_resume_training_in_fresh_process(tmp_path):
    paddle.enable_static()
    try:
        prog, sp = paddle.static.Program(), paddle.static.Program()
        loss = _build(prog, sp)
        exe = paddle.static.Executor()
        exe.run(sp)
        xv, yv = _data()
        for _ in range(2):
            exe.run(prog, feed={"x": xv, "y": yv}, fetch_list=[loss])
        path = str(tmp_path / "ck")
        paddle.static.save(prog, path)
        paddle.static.save_program(prog, path + ".pdmodel",
                                   fetch_vars=[loss])
        expected = [float(exe.run(prog, feed={"x": xv, "y": yv},
                                  fetch_list=[loss])[0])
                    for _ in range(3)]
    finally:
        paddle.disable_static()

    child = textwrap.dedent(f"""
        import numpy as np
        import paddle_tpu as paddle
        lp = paddle.static.load_program({path + '.pdmodel'!r})
        paddle.static.load(lp, {path!r})
        rng = np.random.RandomState(0)
        xv = rng.rand(8, 4).astype(np.float32)
        yv = xv @ np.array([[1.], [2.], [-1.], [0.5]], np.float32)
        exe = paddle.static.Executor()
        got = [float(np.asarray(
            exe.run(lp, feed={{"x": xv, "y": yv}})[0]))
            for _ in range(3)]
        print("RESUMED", ",".join(repr(g) for g in got))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=240,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESUMED")][0]
    got = [float(v) for v in line.split(" ", 1)[1].split(",")]
    np.testing.assert_allclose(got, expected, rtol=1e-5)


def test_fetch_subset_and_errors(tmp_path):
    paddle.enable_static()
    try:
        prog, sp = paddle.static.Program(), paddle.static.Program()
        loss = _build(prog, sp)
        exe = paddle.static.Executor()
        exe.run(sp)
        data = paddle.static.serialize_program(fetch_vars=[loss],
                                               program=prog)
    finally:
        paddle.disable_static()
    lp = paddle.static.deserialize_program(data)
    xv, yv = _data()
    with pytest.raises(KeyError, match="not in the serialized"):
        lp.run_step({"x": xv, "y": yv}, fetch_list=["nonexistent"])
    with pytest.raises(KeyError, match="missing feed"):
        lp.run_step({"x": xv})
