"""Test config: force an 8-device virtual CPU mesh (SURVEY §4's
"multi-place in-process fixtures" analog — the XLA host-device-count
trick) so sharding paths are exercised without TPU hardware."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # whatever the session's default backend
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

# Persistent XLA compile cache: the suite's cost is dominated by
# hundreds of small-model compiles that are identical from run to run.
# Cache them on disk so only the first run on a box pays. Where
# JAX_COMPILATION_CACHE_DIR is set jax already keeps its cache there
# and nothing here points it elsewhere (the rule of
# core/config.compile_cache_dir).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(os.path.dirname(__file__), "..", ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# jaxlib 0.9's CPU runtime cannot reliably RELOAD serialized
# multi-device executables: cpu_aot_loader rejects the cached machine
# features ("+prefer-no-scatter ... not supported on the host"), one
# partition thread dies, and the surviving threads deadlock at the
# collective rendezvous until its 40s termination timeout aborts the
# whole process ("Fatal Python error: Aborted" at an array fetch).
# Fresh compiles are fine — only the disk->executable round trip is
# broken — so gate persistent-cache READS to single-device programs:
# sharded tests recompile once per process (they are small models),
# every other program keeps the cache.
from jax._src import compiler as _jax_compiler

_orig_cache_read = _jax_compiler._cache_read


def _single_device_cache_read(module_name, cache_key, compile_options,
                              backend, executable_devices):
    if len(executable_devices) > 1:
        return None, None
    # The same runtime also mis-reloads DONATING executables: a
    # disk-reloaded train step occasionally loses the donation alias
    # info and a fetched output reads clobbered memory (observed as a
    # sporadic garbage/NaN loss right after a checkpoint save in the
    # resume-continuity tests — reproducible only with a warm cache,
    # never with fresh compiles). Gate the trainer's donating step
    # programs (train_step / run_k_steps) out of cache reads too;
    # forward/eval/infer programs keep the big cache win.
    try:  # one predicate, shared with the production gate
        from paddle_tpu.executor import DONATING_STEP_MODULE_TAGS as _tags
    except Exception:
        _tags = ("train_step", "run_k_steps")
    if any(tag in (module_name or "") for tag in _tags):
        return None, None
    return _orig_cache_read(module_name, cache_key, compile_options,
                            backend, executable_devices)


_jax_compiler._cache_read = _single_device_cache_read

import numpy as np
import pytest

assert jax.devices()[0].platform == "cpu", "tests must run on the virtual CPU mesh"
assert jax.device_count() == 8, "xla_force_host_platform_device_count=8 not in effect"


@pytest.fixture
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _seed_numpy():
    np.random.seed(0)

