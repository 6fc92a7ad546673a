"""Python-free native predictor (native/predictor.cc) — the C++
inference entry parity test (inference/io.h:35, api_impl.cc:64).

The binary speaks the PJRT C API directly: it dlopens a plugin
(libtpu.so on TPU hosts), compiles the exported StableHLO, stages
weights/feeds as device buffers, executes, and prints checksums — no
libpython anywhere in the process.

The CPU sandbox has no device a PJRT C API plugin can open, so the full
execute path needs real local hardware. What IS asserted hermetically:
  * the binary builds against the vendored PJRT C API header,
  * --probe exits 0: plugin dlopen + GetPjrtApi version handshake + the
    complete Python-free artifact load (zip64 npz weights, meta.json
    signature, StableHLO bytes) with shape/dtype/size cross-validation,
  * artifact tampering is caught loudly,
  * when a local device IS present, the full run's f32 output checksum
    matches the Python Predictor.
"""

import os
import subprocess

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import layers as L

TF_INCLUDE = "/opt/venv/lib/python3.12/site-packages/tensorflow/include"
LIBTPU = "/opt/venv/lib/python3.12/site-packages/libtpu/libtpu.so"

pytestmark = pytest.mark.skipif(
    not os.path.exists(os.path.join(TF_INCLUDE, "xla/pjrt/c/pjrt_c_api.h"))
    or not os.path.exists(LIBTPU),
    reason="PJRT C API header or libtpu plugin not present in this image")


def _build():
    from paddle_tpu.native import build_native
    return build_native("predictor.cc", "predictor",
                        extra_flags=("-I" + TF_INCLUDE,), libs=("-ldl",))


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("pred"))

    def net(x):
        h = L.fc(x, 8, act="relu", name="h")
        return {"y": L.fc(h, 3, name="out")}

    x = np.random.RandomState(0).randn(2, 4).astype(np.float32)
    prog = pt.build(net)
    params, state = prog.init(jax.random.PRNGKey(0), x=x)
    pio.save_inference_model(d, prog, params, state, {"x": x})
    np.save(os.path.join(d, "feed_x.npy"), x)
    pred = pio.load_inference_model(d)
    out = pred.run({"x": x})
    ref = np.asarray(out["y"] if isinstance(out, dict) else out)
    return d, float(ref.astype(np.float64).sum())


@pytest.mark.slow
def test_probe_python_free(artifact):
    d, _ = artifact
    binpath = _build()
    r = subprocess.run([binpath, d, LIBTPU, "--probe"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "PROBE OK" in r.stdout
    assert "artifact ok" in r.stderr          # weights+signature validated
    assert "PJRT API v" in r.stderr           # plugin handshake happened
    # no python in the process: sanity — the binary links no libpython
    ldd = subprocess.run(["ldd", binpath], capture_output=True, text=True)
    assert "libpython" not in ldd.stdout


@pytest.mark.slow
def test_tampered_artifact_rejected(artifact, tmp_path):
    import shutil
    d, _ = artifact
    bad = tmp_path / "bad"
    shutil.copytree(d, bad)
    meta = (bad / "meta.json").read_text()
    # corrupt a weight shape in the signature: 8 -> 80
    (bad / "meta.json").write_text(meta.replace('"shape": [4, 8]',
                                                '"shape": [4, 80]', 1))
    binpath = _build()
    r = subprocess.run([binpath, str(bad), LIBTPU, "--probe"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "signature expects" in r.stderr


@pytest.mark.slow
def test_full_run_on_local_device_if_present(artifact):
    """Full PJRT execute — needs a device the plugin can open locally.
    On boxes without one assert the failure is the device probe, i.e.
    everything before hardware (artifact, handshake, compile options)
    held up."""
    d, ref_sum = artifact
    binpath = _build()
    r = subprocess.run([binpath, d, LIBTPU], capture_output=True, text=True,
                       timeout=600)
    if r.returncode == 0:
        assert "RUN OK" in r.stdout
        line = [l for l in r.stdout.splitlines() if l.startswith("OUTPUT 0")][0]
        got = float(line.split("f32sum=")[1])
        np.testing.assert_allclose(got, ref_sum, rtol=1e-3)
    else:
        assert "client create" in r.stderr, r.stderr
        pytest.skip("no local PJRT device on this box: "
                    + r.stderr.strip().splitlines()[-1][:120])
