"""C serving ABI: libpaddle_tpu_capi.so driven two ways — in-process
via ctypes (fast; covers every PD_* function the Go wrapper uses) and
as a true embedded-interpreter C program (demo_main.c compiled and run
as a subprocess, parity-checked against the Python predictor).

Mirrors the reference's C API tests
(paddle/fluid/inference/tests/api/analyzer_capi_exp_tester.cc and
capi_exp/lod_demo.cc usage).
"""
import ctypes
import os
import shutil
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference import capi
from paddle_tpu.jit import InputSpec

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no C++ toolchain")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    paddle.seed(7)
    net = SmallNet()
    prefix = str(tmp_path_factory.mktemp("capi") / "model")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([2, 8], "float32", name="x")])
    x = (0.01 * np.arange(16, dtype=np.float32) - 1.0).reshape(2, 8)
    want = np.asarray(net(paddle.to_tensor(x))._data)
    return prefix, x, want


@pytest.fixture(scope="module")
def lib():
    if not capi.build():
        pytest.skip("capi build failed")
    L = ctypes.CDLL(capi.lib_path())
    L.PD_ConfigCreate.restype = ctypes.c_void_p
    L.PD_ConfigSetProgFile.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    L.PD_ConfigSetModel.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_char_p]
    L.PD_ConfigDisableGpu.argtypes = [ctypes.c_void_p]
    L.PD_ConfigEnableTpu.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    L.PD_ConfigUseTpu.restype = ctypes.c_int32
    L.PD_ConfigUseTpu.argtypes = [ctypes.c_void_p]
    L.PD_ConfigUseGpu.restype = ctypes.c_int32
    L.PD_ConfigUseGpu.argtypes = [ctypes.c_void_p]
    L.PD_ConfigSetPrecision.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    L.PD_ConfigDestroy.argtypes = [ctypes.c_void_p]
    L.PD_ConfigGetProgFile.restype = ctypes.c_char_p
    L.PD_ConfigGetProgFile.argtypes = [ctypes.c_void_p]
    L.PD_ConfigGetParamsFile.restype = ctypes.c_char_p
    L.PD_ConfigGetParamsFile.argtypes = [ctypes.c_void_p]
    L.PD_PredictorCreate.restype = ctypes.c_void_p
    L.PD_PredictorCreate.argtypes = [ctypes.c_void_p]
    L.PD_PredictorClone.restype = ctypes.c_void_p
    L.PD_PredictorClone.argtypes = [ctypes.c_void_p]
    L.PD_PredictorDestroy.argtypes = [ctypes.c_void_p]
    L.PD_PredictorGetInputNum.restype = ctypes.c_size_t
    L.PD_PredictorGetInputNum.argtypes = [ctypes.c_void_p]
    L.PD_PredictorGetOutputNum.restype = ctypes.c_size_t
    L.PD_PredictorGetOutputNum.argtypes = [ctypes.c_void_p]
    L.PD_PredictorGetInputNames.restype = ctypes.c_void_p
    L.PD_PredictorGetInputNames.argtypes = [ctypes.c_void_p]
    L.PD_PredictorGetOutputNames.restype = ctypes.c_void_p
    L.PD_PredictorGetOutputNames.argtypes = [ctypes.c_void_p]
    L.PD_PredictorGetInputHandle.restype = ctypes.c_void_p
    L.PD_PredictorGetInputHandle.argtypes = [ctypes.c_void_p,
                                             ctypes.c_char_p]
    L.PD_PredictorGetOutputHandle.restype = ctypes.c_void_p
    L.PD_PredictorGetOutputHandle.argtypes = [ctypes.c_void_p,
                                              ctypes.c_char_p]
    L.PD_PredictorRun.restype = ctypes.c_int32
    L.PD_PredictorRun.argtypes = [ctypes.c_void_p]
    L.PD_TensorReshape.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                   ctypes.POINTER(ctypes.c_int32)]
    L.PD_TensorCopyFromCpuFloat.argtypes = [ctypes.c_void_p,
                                            ctypes.c_void_p]
    L.PD_TensorCopyToCpuFloat.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    L.PD_TensorCopyFromCpuInt64.argtypes = [ctypes.c_void_p,
                                            ctypes.c_void_p]
    L.PD_TensorCopyToCpuInt64.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    L.PD_TensorGetShape.restype = ctypes.c_void_p
    L.PD_TensorGetShape.argtypes = [ctypes.c_void_p]
    L.PD_TensorGetDataType.restype = ctypes.c_int32
    L.PD_TensorGetDataType.argtypes = [ctypes.c_void_p]
    L.PD_TensorGetName.restype = ctypes.c_char_p
    L.PD_TensorGetName.argtypes = [ctypes.c_void_p]
    L.PD_TensorDestroy.argtypes = [ctypes.c_void_p]
    L.PD_OneDimArrayInt32Destroy.argtypes = [ctypes.c_void_p]
    L.PD_OneDimArrayCstrDestroy.argtypes = [ctypes.c_void_p]
    L.PD_GetVersion.restype = ctypes.c_char_p
    L.PD_GetLastErrorMessage.restype = ctypes.c_char_p
    return L


class _CstrArray(ctypes.Structure):
    _fields_ = [("size", ctypes.c_size_t),
                ("data", ctypes.POINTER(ctypes.c_char_p))]


class _Int32Array(ctypes.Structure):
    _fields_ = [("size", ctypes.c_size_t),
                ("data", ctypes.POINTER(ctypes.c_int32))]


def _names(L, ptr):
    arr = _CstrArray.from_address(ptr)
    out = [arr.data[i].decode() for i in range(arr.size)]
    L.PD_OneDimArrayCstrDestroy(ptr)
    return out


def _run_c_path(L, predictor, x, check_dtype=True):
    """Drive one predictor through the full C ABI feed/run/fetch path."""
    in_names = _names(L, L.PD_PredictorGetInputNames(predictor))
    assert in_names == ["x"]
    inp = L.PD_PredictorGetInputHandle(predictor, b"x")
    shape = (ctypes.c_int32 * 2)(*x.shape)
    L.PD_TensorReshape(inp, 2, shape)
    buf = np.ascontiguousarray(x, dtype=np.float32)
    L.PD_TensorCopyFromCpuFloat(inp, buf.ctypes.data_as(ctypes.c_void_p))
    assert L.PD_PredictorRun(predictor) == 1, \
        L.PD_GetLastErrorMessage().decode()
    out_names = _names(L, L.PD_PredictorGetOutputNames(predictor))
    out = L.PD_PredictorGetOutputHandle(predictor, out_names[0].encode())
    shp_ptr = L.PD_TensorGetShape(out)
    shp = _Int32Array.from_address(shp_ptr)
    got_shape = [shp.data[i] for i in range(shp.size)]
    L.PD_OneDimArrayInt32Destroy(shp_ptr)
    got = np.zeros(got_shape, dtype=np.float32)
    L.PD_TensorCopyToCpuFloat(out, got.ctypes.data_as(ctypes.c_void_p))
    if check_dtype:
        assert L.PD_TensorGetDataType(out) == 0  # PD_DATA_FLOAT32
    L.PD_TensorDestroy(inp)
    L.PD_TensorDestroy(out)
    return got


class TestCapiInProcess:
    def test_config_roundtrip(self, lib, artifact):
        prefix, _, _ = artifact
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetProgFile(cfg, prefix.encode())
        assert lib.PD_ConfigGetProgFile(cfg).decode() == prefix
        lib.PD_ConfigDestroy(cfg)

    def test_full_predict_parity(self, lib, artifact):
        prefix, x, want = artifact
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetProgFile(cfg, prefix.encode())
        lib.PD_ConfigDisableGpu(cfg)
        predictor = lib.PD_PredictorCreate(cfg)
        lib.PD_ConfigDestroy(cfg)
        assert predictor, lib.PD_GetLastErrorMessage().decode()
        assert lib.PD_PredictorGetInputNum(predictor) == 1
        got = _run_c_path(lib, predictor, x)
        # output names materialize at first run (lazy, like the engine)
        assert lib.PD_PredictorGetOutputNum(predictor) >= 1
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # clone shares the artifact; same answer through a fresh handle
        clone = lib.PD_PredictorClone(predictor)
        assert clone, lib.PD_GetLastErrorMessage().decode()
        np.testing.assert_allclose(_run_c_path(lib, clone, x), want,
                                   rtol=1e-5, atol=1e-6)
        lib.PD_PredictorDestroy(clone)
        lib.PD_PredictorDestroy(predictor)
        assert lib.PD_GetVersion().decode() == paddle.__version__

    def test_config_device_and_model_knobs(self, lib, artifact):
        prefix, _, _ = artifact
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetModel(cfg, (prefix + ".pdmodel").encode(),
                              (prefix + ".pdiparams").encode())
        assert lib.PD_ConfigGetProgFile(cfg).decode().endswith(".pdmodel")
        assert lib.PD_ConfigGetParamsFile(cfg).decode().endswith(
            ".pdiparams")
        lib.PD_ConfigEnableTpu(cfg, 0)
        assert lib.PD_ConfigUseTpu(cfg) == 1
        assert lib.PD_ConfigUseGpu(cfg) == 0
        lib.PD_ConfigDisableGpu(cfg)
        assert lib.PD_ConfigUseTpu(cfg) == 0
        lib.PD_ConfigDestroy(cfg)

    def test_precision_knob_and_int64_marshalling(self, lib, artifact):
        """SetPrecision routes into the reduced-precision re-trace path;
        int64 copy-from feeds through dtype canonicalization (x64 off ->
        int32 on device) and int64 copy-to casts the fetched output."""
        prefix, x, want = artifact
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetProgFile(cfg, prefix.encode())
        lib.PD_ConfigDisableGpu(cfg)
        lib.PD_ConfigSetPrecision(cfg, 2)  # PD_PRECISION_BFLOAT16
        predictor = lib.PD_PredictorCreate(cfg)
        lib.PD_ConfigDestroy(cfg)
        assert predictor, lib.PD_GetLastErrorMessage().decode()
        got = _run_c_path(lib, predictor, x, check_dtype=False)
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
        # int64 fetch of the float output exercises the cast marshalling
        out_names = _names(lib, lib.PD_PredictorGetOutputNames(predictor))
        out = lib.PD_PredictorGetOutputHandle(predictor,
                                              out_names[0].encode())
        as_i64 = np.zeros(want.shape, dtype=np.int64)
        lib.PD_TensorCopyToCpuInt64(out,
                                    as_i64.ctypes.data_as(ctypes.c_void_p))
        np.testing.assert_array_equal(as_i64, got.astype(np.int64))
        lib.PD_TensorDestroy(out)
        # int64 feed: marshalls through frombuffer('int64'); the engine
        # canonicalizes to device int32 (x64 off) — pin values + dtype
        # through the handle rather than running the float32 program
        inp = lib.PD_PredictorGetInputHandle(predictor, b"x")
        ids = np.arange(16, dtype=np.int64).reshape(2, 8)
        shape = (ctypes.c_int32 * 2)(2, 8)
        lib.PD_TensorReshape(inp, 2, shape)
        lib.PD_TensorCopyFromCpuInt64(inp,
                                      ids.ctypes.data_as(ctypes.c_void_p))
        assert lib.PD_TensorGetDataType(inp) == 2  # PD_DATA_INT32
        back = np.zeros((2, 8), dtype=np.int64)
        lib.PD_TensorCopyToCpuInt64(inp,
                                    back.ctypes.data_as(ctypes.c_void_p))
        np.testing.assert_array_equal(back, ids)
        lib.PD_TensorDestroy(inp)
        lib.PD_PredictorDestroy(predictor)

    def test_concurrent_predictors_thread_safety(self, lib, artifact):
        """Serving ABI contract: any C thread may call in (PyGILState
        discipline).  ctypes releases the GIL around the foreign call,
        so N python threads driving N predictor clones exercises real
        concurrent entry into the C ABI."""
        import threading
        prefix, x, want = artifact
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetProgFile(cfg, prefix.encode())
        lib.PD_ConfigDisableGpu(cfg)
        base = lib.PD_PredictorCreate(cfg)
        lib.PD_ConfigDestroy(cfg)
        assert base, lib.PD_GetLastErrorMessage().decode()
        _run_c_path(lib, base, x)        # warm (lazy output names)
        clones = [lib.PD_PredictorClone(base) for _ in range(4)]
        results, errors = [None] * 4, []

        def drive(i):
            try:
                for _ in range(5):
                    results[i] = _run_c_path(lib, clones[i], x)
            except Exception as e:       # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        for got in results:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        for c in clones:
            lib.PD_PredictorDestroy(c)
        lib.PD_PredictorDestroy(base)

    def test_error_message_on_bad_model(self, lib, tmp_path):
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetProgFile(cfg,
                                 str(tmp_path / "nope.pdmodel").encode())
        predictor = lib.PD_PredictorCreate(cfg)
        lib.PD_ConfigDestroy(cfg)
        assert not predictor
        assert lib.PD_GetLastErrorMessage()


@pytest.mark.slow
class TestCapiEmbedded:
    """demo_main.c: a plain C program that boots its own interpreter."""

    def test_demo_program_parity(self, artifact, tmp_path):
        prefix, x, want = artifact
        if not capi.build():
            pytest.skip("capi build failed")
        exe = str(tmp_path / "capi_demo")
        here = os.path.dirname(capi.header_path())
        cmd = (["g++", "-O2", os.path.join(here, "demo_main.c"),
                "-I" + here, capi.lib_path(),
                "-Wl,-rpath," + here, "-o", exe]
               + capi.python_link_args())
        subprocess.run(cmd, check=True, capture_output=True, timeout=240)
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH",
                                                             "")
        # the artifact fixture exports on the CPU backend; pin the
        # demo's embedded interpreter to cpu too
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([exe, prefix, "2", "8"], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.splitlines()
        vals = np.array([float(l.split()[1]) for l in lines
                         if l.startswith("v ")], dtype=np.float32)
        shape = [int(t) for l in lines if l.startswith("shape")
                 for t in l.split()[1:]]
        assert shape == list(want.shape)
        np.testing.assert_allclose(vals.reshape(want.shape), want,
                                   rtol=1e-4, atol=1e-5)
