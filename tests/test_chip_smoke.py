"""chip_smoke.py rehearsed without the chip (the `on-chip-measurement`
guide, section 2): every phase at a tiny size on the CPU with the
kernels interpreted, the four-chip phase on four of the eight virtual
devices, and the script itself under JAX_PLATFORMS=cpu, where it must
fail. Beside it the two rules the smoke leans on: where the compile
cache lives, and that a TPU place cannot be met by a CPU."""

import os
import re
import subprocess
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

import chip_smoke
import paddle_tpu as pt
from paddle_tpu.core import config
from paddle_tpu.core.errors import EnforceError
from paddle_tpu.models import gpt

BATCH, SEQ = 4, 64


@pytest.fixture
def tiny_gpt():
    config.set_flag("default_compute_dtype", "bfloat16")
    yield gpt.base_config(vocab_size=1024, max_len=SEQ, d_model=64,
                          d_inner=128, num_heads=4, num_layers=2,
                          use_flash=True, fused_ce=True, dtype="bfloat16")
    config.set_flag("default_compute_dtype", "float32")


def test_kernel_phase_rehearsal():
    chip_smoke.kernel_phase(
        0, gpt_shape=(1, 2, 128, 64), transformer_shape=(1, 2, 64, 64),
        sala={"sparse": dict(rows=1, kv_heads=1, group=2, d=32, total=512,
                             queries=64, p0=448, window_blocks=2, n_sel=3),
              "select": dict(rows=1, kv_heads=1, group=2, d=32, total=1024,
                             queries=128, p0=896, window_blocks=2, topk=6),
              "lightning": dict(rows=1, heads=2, d=32, seq=512)},
        brumby=dict(rows=1, heads=4, kv_heads=2, d=16, seq=300),
        phi4={"mamba": dict(rows=1, d_inner=256, seq=150),
              "window": dict(shape=(1, 2, 128, 32), window=96),
              "generator": dict(hidden=64, heads=4, window=24, vocab=503,
                                rows=1, prompt=200, new=4, chunk=80)})


def test_a_failed_check_raises(monkeypatch):
    monkeypatch.setattr(chip_smoke, "BF16_TOL", 0.0)
    with pytest.raises(RuntimeError, match="differs from the dense"):
        chip_smoke.kernel_case("tiny", (1, 1, 64, 64), True, "none", seed=0)


def test_train_and_serve_phases_rehearsal(tiny_gpt, capsys):
    trainer = chip_smoke.train_phase(tiny_gpt, BATCH, SEQ, seed=0)
    chip_smoke.serve_phase(tiny_gpt, trainer.scope.params, BATCH,
                           prompt_len=16, new_tokens=8, seed=0)
    out = capsys.readouterr().out
    assert "[train] losses:" in out and "compiles_since_warmup 0" in out
    assert '"ok"' not in out  # only main() prints the result line


def test_four_chip_phase_rehearsal(tiny_gpt, capsys):
    chip_smoke.four_chip_phase(tiny_gpt, BATCH, SEQ, seed=0,
                               devices=jax.devices()[:4])
    out = capsys.readouterr().out
    ruled, spread = map(int, re.search(
        r"tp-ruled parameters: (\d+), sharded across more than one "
        r"device: (\d+)", out).groups())
    assert ruled == spread > 0
    assert '"all-reduce"' in out


def test_script_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


# -- where the compile cache lives -------------------------------------------


def test_cache_dir_env_wins_over_flag(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/jax_cache")
    monkeypatch.setattr(config._REGISTRY["compile_cache_dir"], "value",
                        "/flag/says/here")
    assert config.compile_cache_dir() == "/srv/jax_cache"


def test_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert config.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")


def test_cache_dir_is_a_fixed_path(monkeypatch, tmp_path):
    """No pid, time or temporary name: the directory is part of the
    cache key, so it is the same from any cwd and on every call."""
    import tempfile

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = config.compile_cache_dir()
    monkeypatch.chdir(tmp_path)
    assert config.compile_cache_dir() == first
    assert str(os.getpid()) not in first
    assert not first.startswith(tempfile.gettempdir() + os.sep)


# -- a place that cannot be met ----------------------------------------------


def test_tpu_place_on_a_cpu_process_raises():
    with pytest.raises(EnforceError, match="no tpu device"):
        pt.TPUPlace(0).device()


def test_default_place_is_whatever_is_here():
    assert pt.default_place().device().platform == "cpu"
    assert pt.CUDAPlace(0).device().platform == "cpu"  # API parity only
