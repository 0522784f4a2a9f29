"""C inference API: build the shared lib, export a model, serve it from C.

Reference: paddle/fluid/inference/capi/pd_predictor.cc + its C tests.
Two layers of proof: the ctypes test exercises the exact C ABI in-
process; the subprocess test runs a REAL standalone C executable with
no Python on its command line (marked slow — it builds a binary and
cold-starts an embedded interpreter + XLA).
"""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.static import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def exported_model(tmp_path_factory):
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    path = str(tmp_path_factory.mktemp("export") / "lin")
    paddle.jit.save(model, path,
                    input_spec=[InputSpec([1, 4], "float32")])
    x = np.arange(4, dtype=np.float32).reshape(1, 4) * 0.1
    expect = np.asarray(model(paddle.to_tensor(x)).data)
    return path, x, expect


@pytest.fixture(scope="module")
def capi_lib(tmp_path_factory):
    from paddle_tpu.inference.capi.build import build_library
    out = str(tmp_path_factory.mktemp("capi") / "libpd_inference.so")
    try:
        return build_library(out)
    except Exception as e:  # no compiler in exotic envs: skip, not fail
        pytest.skip(f"cannot build C library: {e}")


class PDTensor(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("shape", ctypes.c_int64 * 8),
                ("ndim", ctypes.c_int32),
                ("dtype", ctypes.c_char * 16)]


def test_capi_run_matches_python(exported_model, capi_lib):
    path, x, expect = exported_model
    lib = ctypes.CDLL(capi_lib)
    lib.PD_NewPredictor.restype = ctypes.c_void_p
    lib.PD_NewPredictor.argtypes = [ctypes.c_char_p]
    lib.PD_PredictorRun.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(PDTensor), ctypes.c_int32,
        ctypes.POINTER(ctypes.POINTER(PDTensor)),
        ctypes.POINTER(ctypes.c_int32)]
    lib.PD_GetLastError.restype = ctypes.c_char_p

    pred = lib.PD_NewPredictor(path.encode())
    assert pred, lib.PD_GetLastError()

    xin = np.ascontiguousarray(x)
    t = PDTensor()
    t.data = xin.ctypes.data_as(ctypes.c_void_p)
    t.ndim = 2
    t.shape[0], t.shape[1] = 1, 4
    t.dtype = b"float32"

    outs = ctypes.POINTER(PDTensor)()
    n_outs = ctypes.c_int32()
    rc = lib.PD_PredictorRun(pred, ctypes.byref(t), 1,
                             ctypes.byref(outs), ctypes.byref(n_outs))
    assert rc == 0, lib.PD_GetLastError()
    assert n_outs.value == 1
    out_t = outs[0]
    assert out_t.dtype.decode().startswith("float32")
    shape = tuple(out_t.shape[i] for i in range(out_t.ndim))
    assert shape == (1, 2)
    vals = np.ctypeslib.as_array(
        ctypes.cast(out_t.data, ctypes.POINTER(ctypes.c_float)),
        shape=shape).copy()
    np.testing.assert_allclose(vals, expect, rtol=1e-5, atol=1e-6)

    lib.PD_TensorsFree(outs, n_outs)
    lib.PD_DeletePredictor(ctypes.c_void_p(pred))


def test_capi_error_reporting(capi_lib):
    lib = ctypes.CDLL(capi_lib)
    lib.PD_NewPredictor.restype = ctypes.c_void_p
    lib.PD_NewPredictor.argtypes = [ctypes.c_char_p]
    lib.PD_GetLastError.restype = ctypes.c_char_p
    pred = lib.PD_NewPredictor(b"/nonexistent/model")
    assert not pred
    assert b"PD_NewPredictor" in lib.PD_GetLastError()


@pytest.mark.slow
def test_standalone_c_binary_serves_export(exported_model,
                                           tmp_path_factory):
    from paddle_tpu.inference.capi.build import build_demo
    path, x, expect = exported_model
    try:
        exe = build_demo(str(tmp_path_factory.mktemp("demo") /
                             "pd_capi_demo"))
    except Exception as e:
        pytest.skip(f"cannot build demo: {e}")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"    # a CPU-only child
    proc = subprocess.run([exe, path, "4"], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, \
        f"rc={proc.returncode}\nstdout={proc.stdout}\nstderr={proc.stderr}"
    assert "CAPI-DEMO-OK" in proc.stdout
    # the demo feeds the same ramp input the fixture used
    first = float(proc.stdout.split("OUT 0")[1].split(":")[1].split()[0])
    assert first == pytest.approx(float(expect[0, 0]), rel=1e-4)
