"""Python-free native trainer (native/trainer.cc) — the C++ training
entry parity test (train/demo/demo_trainer.cc: drive the whole epoch
loop from C++, no Python in the process).

Hermetic assertions on this box (no local PJRT device — same
constraint as test_native_predictor.py):
  * save_train_artifact exports a carry-aligned one-step StableHLO
    whose REPLAY (jax.export deserialize, outputs fed back positionally
    as the next step's inputs — exactly the C++ buffer swap) matches
    in-process Trainer training step-for-step,
  * the binary builds against the vendored PJRT header,
  * --probe exits 0: full artifact load + carry/seed/feed layout
    validation + plugin handshake,
  * artifact tampering (a truncated weight) dies loudly.
"""

import json
import os
import subprocess

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu import layers as L
from paddle_tpu import optimizer as opt

TF_INCLUDE = "/opt/venv/lib/python3.12/site-packages/tensorflow/include"
LIBTPU = "/opt/venv/lib/python3.12/site-packages/libtpu/libtpu.so"

# only the subprocess tests need the native toolchain; the export and
# replay tests are pure-Python and must run everywhere (they guard the
# carry-ordering / meta-binding contract)
needs_native = pytest.mark.skipif(
    not os.path.exists(os.path.join(TF_INCLUDE, "xla/pjrt/c/pjrt_c_api.h"))
    or not os.path.exists(LIBTPU),
    reason="PJRT C API header or libtpu plugin not present in this image")


def _build():
    from paddle_tpu.native import build_native
    return build_native("trainer.cc", "trainer",
                        extra_flags=("-I" + TF_INCLUDE,), libs=("-ldl",))


def _net(x, label):
    h = L.fc(x, 16, act="relu", name="h")
    logits = L.fc(h, 3, name="out")
    return {"loss": L.mean(L.softmax_with_cross_entropy(logits, label))}


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("native_train"))
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 6).astype(np.float32),
            "label": rng.randint(0, 3, (8, 1)).astype(np.int64)}
    prog = pt.build(_net)
    tr = pt.Trainer(prog, opt.Momentum(0.1, 0.9), loss_name="loss")
    tr.startup(sample_feed=feed)
    pio.save_train_artifact(d, tr, feed)
    return d, tr, feed


def test_artifact_layout(artifact):
    d, _, feed = artifact
    meta = json.load(open(os.path.join(d, "meta_train.json")))
    n = meta["num_carry"]
    srcs = [i["source"] for i in meta["inputs"]]
    # carry prefix, then the seed scalar, then feeds — the layout the
    # C++ driver swap-loop assumes
    assert all(s in ("params.npz", "opt.npz", "state.npz") for s in srcs[:n])
    assert srcs[n] == "seed" and meta["inputs"][n]["shape"] == []
    assert srcs[n + 1:] == ["feed"] * len(feed)
    for f in ("train_step.mlir", "params.npz", "opt.npz", "state.npz",
              "feed_x.npy", "feed_label.npy"):
        assert os.path.exists(os.path.join(d, f)), f


def test_exported_step_replay_matches_trainer(artifact):
    """Replay the serialized artifact with positional carry feedback —
    the exact C++ execution model (output i becomes input i, seed =
    step index) — and pin it against in-process Trainer training."""
    d, tr, feed = artifact
    exported = jax.export.deserialize(
        open(os.path.join(d, "train_step.jaxexp"), "rb").read())
    meta = json.load(open(os.path.join(d, "meta_train.json")))
    n_carry = meta["num_carry"]
    feed_names = meta["feed_names"]

    # initial carry straight from the npz artifact through the meta
    # binding (meta names are byte-identical to npz members — exactly
    # how the C++ driver stages buffers); tree STRUCTURE comes from the
    # live trainer, which is what was exported
    import jax.tree_util as jtu
    from paddle_tpu.io import _flat_leaves_in_tree_order
    host = jax.device_get((tr.scope.params, tr.scope.opt_state,
                           tr.scope.state))
    blobs = {n: dict(np.load(os.path.join(d, n), allow_pickle=False))
             for n in ("params.npz", "opt.npz", "state.npz")}
    leaves = [blobs[i["source"]][i["name"]] for i in meta["inputs"][:n_carry]]
    assert len(leaves) == len(jtu.tree_leaves(host))
    p, o, s = jtu.tree_unflatten(jtu.tree_structure(host), leaves)
    feeds = [np.load(os.path.join(d, f"feed_{k}.npy")) for k in feed_names]

    # in-process reference: 3 Trainer steps with the same per-step keys
    losses_ref = []
    for step in range(3):
        out = tr.step(feed, rng=jax.random.PRNGKey(np.uint32(step)))
        losses_ref.append(float(out["loss"]))

    losses = []
    for step in range(3):
        p, o, s, loss = exported.call(p, o, s, np.uint32(step), *feeds)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, losses_ref, rtol=1e-5, atol=1e-6)
    assert losses[-1] < losses[0]


@needs_native
def test_probe_python_free(artifact):
    d, _, _ = artifact
    binary = _build()
    env = {k: v for k, v in os.environ.items() if k != "LD_PRELOAD"}
    r = subprocess.run([binary, d, LIBTPU, "--probe"], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PROBE OK" in r.stdout
    assert "artifact ok" in r.stderr


@needs_native
def test_tampered_artifact_dies(artifact, tmp_path):
    d, _, _ = artifact
    binary = _build()
    import shutil
    bad = str(tmp_path / "bad")
    shutil.copytree(d, bad)
    blob = open(os.path.join(bad, "params.npz"), "rb").read()
    open(os.path.join(bad, "params.npz"), "wb").write(blob[:len(blob) // 2])
    r = subprocess.run([binary, bad, LIBTPU, "--probe"], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0
    assert "trainer:" in r.stderr
