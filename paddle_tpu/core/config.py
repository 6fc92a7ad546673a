"""Global config / flag system.

Analog of the reference's three-tier flag system (SURVEY §5): gflags
read from env at import (python/paddle/fluid/__init__.py:112-133),
strategy objects, and build options. Here: a typed flag registry with
env-var override (``PDTPU_<NAME>``), plus dataclass strategy objects
living in paddle_tpu.parallel.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict


@dataclasses.dataclass
class _Flag:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str
    value: Any = None


_REGISTRY: Dict[str, _Flag] = {}


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


def define_flag(name: str, default: Any, help: str = "") -> None:
    if isinstance(default, bool):
        parser: Callable[[str], Any] = _parse_bool
    elif isinstance(default, int):
        parser = int
    elif isinstance(default, float):
        parser = float
    else:
        parser = str
    env = os.environ.get(f"PDTPU_{name.upper()}")
    value = parser(env) if env is not None else default
    _REGISTRY[name] = _Flag(name, default, parser, help, value)


def get_flag(name: str) -> Any:
    return _REGISTRY[name].value


def set_flag(name: str, value: Any) -> None:
    _REGISTRY[name].value = value


def flags() -> Dict[str, Any]:
    return {k: f.value for k, f in _REGISTRY.items()}


_determinism_saved: Dict[str, Any] = {}


def enable_determinism() -> None:
    """Wire the ``deterministic`` flag (FLAGS_cpu_deterministic analog)
    to real knobs: bitwise-reproducible matmul precision, the
    sharding-invariant threefry RNG, and XLA's deterministic-ops flag
    for any backend initialized after this call. Invoked automatically
    at package import when ``PDTPU_DETERMINISTIC=1``."""
    import jax

    if not _determinism_saved:
        _determinism_saved["matmul_precision"] = jax.config.jax_default_matmul_precision
        _determinism_saved["threefry"] = jax.config.jax_threefry_partitionable
        _determinism_saved["xla_flags"] = os.environ.get("XLA_FLAGS")
    jax.config.update("jax_default_matmul_precision", "highest")
    jax.config.update("jax_threefry_partitionable", True)
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_gpu_deterministic_ops=false" in xla_flags:
        xla_flags = xla_flags.replace("--xla_gpu_deterministic_ops=false",
                                      "--xla_gpu_deterministic_ops=true")
        os.environ["XLA_FLAGS"] = xla_flags
    elif "--xla_gpu_deterministic_ops" not in xla_flags:
        os.environ["XLA_FLAGS"] = (xla_flags + " --xla_gpu_deterministic_ops=true").strip()
    set_flag("deterministic", True)


def disable_determinism() -> None:
    """Restore the jax-config state captured by :func:`enable_determinism`
    (the XLA env flag only affects backends not yet initialized)."""
    import jax

    if _determinism_saved:
        jax.config.update("jax_default_matmul_precision",
                          _determinism_saved.pop("matmul_precision"))
        jax.config.update("jax_threefry_partitionable",
                          _determinism_saved.pop("threefry"))
        old_xla = _determinism_saved.pop("xla_flags")
        if old_xla is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = old_xla
    set_flag("deterministic", False)


# Core flags — counterparts of the whitelisted gflags the reference
# re-reads from env (fluid/__init__.py:112-133).
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf (FLAGS_check_nan_inf analog)")
define_flag("benchmark", False, "Synchronize after each step and log timings (FLAGS_benchmark)")
define_flag("deterministic", False, "Force deterministic reductions (FLAGS_cpu_deterministic)")
define_flag("default_compute_dtype", "float32", "Compute dtype for layers ('bfloat16' on TPU for MXU)")
define_flag("seed", 0, "Global random seed (startup-program seed analog)")
define_flag("rng_impl", "auto",
            "PRNG key impl: auto|threefry2x32|rbg. 'auto' picks XLA's "
            "native RngBitGenerator on TPU (threefry synthesizes random "
            "bits from many VPU ops and can dominate dropout-heavy "
            "steps) and threefry elsewhere / under determinism")
define_flag("compile_cache_dir", "",
            "Persistent XLA compilation cache directory wired by "
            "Trainer.startup (empty = off). Repeated bench/CI runs skip "
            "recompiling the (fused) train step; hit/miss is logged on "
            "the first dispatch. Env PDTPU_COMPILE_CACHE_DIR. Yields to "
            "JAX_COMPILATION_CACHE_DIR (see compile_cache_dir())")


def compile_cache_dir() -> str:
    """The one rule for where the persistent compile cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (no code
    then points the cache anywhere else), else the ``compile_cache_dir``
    flag, else ``<checkout>/.jax_cache``. Always a fixed path — the
    directory is part of the cache key, so one made from a pid, the
    time or a temporary name would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return get_flag("compile_cache_dir") or os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on at :func:`compile_cache_dir`
    and cache every program, however small or quick to compile. Call
    before the first compile; returns the directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    d = compile_cache_dir()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the cache singleton latches its directory at first use: drop it so
    # the setting takes effect even mid-process
    compilation_cache.reset_cache()
    return d
define_flag("flash_block_q", 0,
            "flash-attention q rows a grid step brings in; 0 = chosen "
            "from the call's shape (ops/flash_attention.plan_blocks). "
            "Env PDTPU_FLASH_BLOCK_Q")
define_flag("flash_block_k", 0,
            "flash-attention k rows a grid step brings in; 0 = chosen "
            "from the call's shape (see flash_block_q)")


def default_rng_impl() -> str:
    """Resolve the ``rng_impl`` flag. Determinism forces threefry: RBG
    bit-streams are backend/partitioning-dependent, threefry's are not
    (with jax_threefry_partitionable, see enable_determinism)."""
    impl = get_flag("rng_impl")
    if impl != "auto":
        return impl
    if get_flag("deterministic"):
        return "threefry2x32"
    import jax
    try:
        d = jax.devices()[0]
        desc = ((getattr(d, "platform", "") or "")
                + " " + (getattr(d, "device_kind", "") or "")).lower()
    except Exception:
        return "threefry2x32"
    return "rbg" if "tpu" in desc else "threefry2x32"


def make_prng_key(seed: int):
    """PRNGKey under the resolved ``rng_impl`` — the one key-construction
    point the executor/trainer path uses, so the whole step's dropout/
    init randomness follows the flag. TYPED keys (jax.random.key): a raw
    u32 key array loses its impl at the first jit boundary and gets
    reinterpreted as threefry; the typed dtype carries it."""
    import jax
    return jax.random.key(seed, impl=default_rng_impl())
