"""Amalgamated predict build: one-file TU compiles, and a C client process
using ONLY libmxtpu_predict.so (via the standalone ctypes wrapper in
amalgamation/python) reproduces the in-process Module predictions.

Reference: amalgamation/ (single-file predict build + python wrapper)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AMAL = os.path.join(ROOT, "amalgamation")


def _train_tiny(tmp_path):
    np.random.seed(0)
    mx.random.seed(0)
    X = np.random.randn(64, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=3, optimizer_params={"learning_rate": 0.5})
    arg, aux = mod.get_params()
    prefix = str(tmp_path / "model")
    mx.model.save_checkpoint(prefix, 3, net, arg, aux)
    expected = mod.predict(it, num_batch=1).asnumpy()
    return prefix, X, expected


@pytest.mark.skipif(
    not os.path.exists(os.path.join(AMAL, "libmxtpu_predict.so")),
    reason="amalgamation not built (cd amalgamation && make)")
def test_amalgamated_predictor_subprocess(tmp_path):
    prefix, X, expected = _train_tiny(tmp_path)
    np.save(str(tmp_path / "x.npy"), X[:16])
    np.save(str(tmp_path / "expected.npy"), expected)
    script = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(pydir)r)
sys.path.insert(0, %(root)r)
import numpy as np
from mxnet_predict import Predictor
X = np.load(%(x)r)
expected = np.load(%(exp)r)
symbol = open(%(prefix)r + "-symbol.json").read()
params = open(%(prefix)r + "-0003.params", "rb").read()
p = Predictor(symbol, params, {"data": (16, 6), "softmax_label": (16,)})
p.forward(data=X)
out = p.get_output(0)
assert out.shape == expected.shape, (out.shape, expected.shape)
assert np.allclose(out, expected, atol=1e-5), np.abs(out - expected).max()
print("AMALGAMATION_OK")
"""
    code = script % {"pydir": os.path.join(AMAL, "python"), "root": ROOT,
                     "x": str(tmp_path / "x.npy"),
                     "exp": str(tmp_path / "expected.npy"),
                     "prefix": prefix}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=240, env=env, cwd=ROOT)
    if res.returncode != 0 and "libpython" in res.stderr \
            and "cannot open shared object file" in res.stderr:
        # the checked-in .so was linked against a different interpreter
        # (container image drift) — stale build, not a code regression
        pytest.skip("libmxtpu_predict.so links a libpython this image "
                    "does not ship — rebuild with `cd amalgamation && "
                    "make`")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "AMALGAMATION_OK" in res.stdout
