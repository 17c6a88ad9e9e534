"""C inference API: build libpaddle_inference_c.so, compile a C host
program against it, and predict from pure C (reference:
paddle/fluid/inference/capi_exp/ + test/cpp/inference/api smokes)."""
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, static
from paddle_tpu.jit import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_c_api_predicts_from_c_host(tmp_path):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    x = np.ones((2, 8), np.float32)
    ref = net(paddle.to_tensor(x)).numpy()

    prefix = str(tmp_path / "model")
    static.save_inference_model(
        prefix, [InputSpec([2, 8], "float32", "x")], None, layer=net)

    from paddle_tpu.inference.capi import build_c_api, header_path
    so = build_c_api(output_dir=str(tmp_path))
    assert os.path.exists(so) and os.path.exists(header_path())

    exe = str(tmp_path / "capi_smoke")
    smoke = os.path.join(os.path.dirname(__file__), "capi_smoke.c")
    r = subprocess.run(
        ["gcc", smoke, "-o", exe,
         f"-I{os.path.dirname(header_path())}",
         f"-L{os.path.dirname(so)}", f"-Wl,-rpath,{os.path.dirname(so)}",
         "-lpaddle_inference_c"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([exe, prefix], capture_output=True, text=True,
                       timeout=600, env=env)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    parts = r.stdout.split()
    assert parts[0] == "OK" and int(parts[1]) == ref.size
    got = np.array([float(v) for v in parts[2:]]).reshape(ref.shape)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    # int8 path from C: output within weight-only-quant tolerance
    r = subprocess.run([exe, prefix, "1"], capture_output=True, text=True,
                       timeout=600, env=env)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    parts = r.stdout.split()
    got8 = np.array([float(v) for v in parts[2:]]).reshape(ref.shape)
    np.testing.assert_allclose(got8, ref, rtol=0.1, atol=0.1)
