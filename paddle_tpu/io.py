"""Checkpoint save/load + inference export.

Analog of python/paddle/fluid/io.py: save_vars/save_persistables
(io.py:89/:252 — a program of save ops per var), load_persistables
(io.py:464), save/load_inference_model (io.py:544/:669 — prune +
serialized ProgramDesc). Here persistable state is name-keyed pytrees →
a single .npz per collection (+ JSON meta); the inference model is a
serialized ``jax.export`` StableHLO artifact next to its weights — the
ProgramDesc-file analog, portable across processes and (with matching
XLA version) machines.

Resharding on load (the pserver slice/merge analog,
io.py:881 _load_slice_up_vars): arrays are saved unsharded (fully
gathered); loading places them per the current mesh/rules, so mesh
reshapes between save and load work by construction. A mesh CHANGE is
gated, not implicit: ``load_trainer`` raises a structured
``resilience.ReshardError`` on a ``meta.mesh_axes`` mismatch, and
``resilience.reshard_restore`` is the explicit elastic door (static
feasibility proof + bit-exact re-placement).

Exception to "saved unsharded": ``DistStrategy(zero_sharding=True)``
checkpoints are SHARD-AWARE — params and partitioned optimizer leaves
live in per-shard ``*.zero{i}.npz`` files (one ``(k,)`` row each,
written gather-free from each owning device), with the shard count +
logical flat spec in ``meta.zero``. Same-N restore is shard-local; any
layout change (N→M, ZeRO↔replicated) trips the same ``ReshardError``
gate and goes through the elastic door, which gathers the rows back to
logical on the host (``load_persistables`` does this transparently)
and repartitions for the target.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .core import profiler
from .core.errors import EnforceError, enforce

SEP = "||"  # path separator for nested pytree keys (param names use '/')


def _log():
    return logging.getLogger("paddle_tpu.io")


class InvalidRequest(EnforceError, ValueError):
    """A serving/inference feed failed structural validation: missing or
    extra feed key, shape or dtype mismatch, off-bucket batch size, or a
    non-finite payload. Carries ``field`` (the offending feed name) and
    ``reason`` so servers can answer with a structured error instead of
    a raw ``KeyError`` or an XLA abort."""

    def __init__(self, field: str, reason: str):
        super().__init__(f"invalid request: feed {field!r} {reason}")
        self.field = field
        self.reason = reason

# numpy's npz format stores ml_dtypes extension types (bfloat16, fp8) as
# raw void bytes that can't round-trip; encode them as a same-width
# integer view with a "@dtype" key suffix instead.
_EXOTIC_DTYPES = {"bfloat16": np.uint16,
                  "float8_e4m3fn": np.uint8, "float8_e5m2": np.uint8}


# -- pytree <-> flat dict ----------------------------------------------------


def _mangle_key(prefix: str, dtype: np.dtype):
    """(stored key, stored dtype) for a leaf of logical dtype ``dtype``
    named ``prefix`` — the key/dtype half of :func:`_mangle_leaf`,
    shared with the spec-only flattener (:func:`flat_spec`) so a spec
    computed without touching array data can never disagree with what
    ``save_persistables`` actually writes."""
    if dtype.name in _EXOTIC_DTYPES:
        return f"{prefix}@{dtype.name}", np.dtype(_EXOTIC_DTYPES[dtype.name])
    if (prefix.endswith("@raw")
            or any(prefix.endswith(f"@{dt}") and dtype == enc
                   for dt, enc in _EXOTIC_DTYPES.items())):
        # a genuine integer param whose NAME ends in '@bfloat16' etc.
        # (or '@raw' itself) would be indistinguishable from our
        # encoding on load — escape with a '@raw' marker (load strips
        # exactly one suffix, so escaping nests safely)
        return f"{prefix}@raw", dtype
    return prefix, dtype


def _mangle_leaf(prefix: str, arr: np.ndarray):
    """Single source of truth for leaf-key mangling: the npz member name
    written by _flatten and the meta.json name written by
    _flat_leaves_in_tree_order must stay byte-identical (the native
    predictor looks meta names up in the npz table)."""
    key, dtype = _mangle_key(prefix, arr.dtype)
    return key, (arr.view(dtype) if dtype != arr.dtype else arr)


def _flatten(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{SEP}{k}" if prefix else str(k)))
    elif tree is None:
        pass
    else:
        key, val = _mangle_leaf(prefix, np.asarray(tree))
        out[key] = val
    return out


def _flat_leaves_in_tree_order(tree: Any, prefix: str = ""):
    """(npz_key, value) pairs in jax's pytree flatten order (per-level
    sorted ORIGINAL keys, depth-first) — NOT sorted mangled npz keys,
    which diverge ('a2' vs 'a||x' sorts differently than 'a' vs 'a2';
    '@bfloat16' suffixes shift order). Used by save_inference_model to
    bind npz members to executable argument positions."""
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree, key=str):
            out += _flat_leaves_in_tree_order(
                tree[k], f"{prefix}{SEP}{k}" if prefix else str(k))
    elif tree is None:
        pass
    else:
        out.append(_mangle_leaf(prefix, np.asarray(tree)))
    return out


def flat_spec(tree: Any, prefix: str = "") -> Dict[str, Dict[str, Any]]:
    """The flat ``{npz key: {"shape": [...], "dtype": "..."}}`` spec
    :func:`save_persistables` would record for ``tree`` — computed from
    shapes/dtypes ONLY (no ``device_get``, no flattened copies): the
    trainer-side half of the static checkpoint-compatibility check in
    ``analysis.contracts``. Key mangling (exotic-dtype ``@bfloat16``
    suffixes, ``@raw`` escapes) goes through the same :func:`_mangle_key`
    the save path uses, so the two can never drift."""
    out: Dict[str, Dict[str, Any]] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(flat_spec(v, f"{prefix}{SEP}{k}" if prefix else str(k)))
    elif tree is None:
        pass
    else:
        shape = getattr(tree, "shape", None)
        dtype = getattr(tree, "dtype", None)
        if shape is None or dtype is None:
            arr = np.asarray(tree)
            shape, dtype = arr.shape, arr.dtype
        key, stored = _mangle_key(prefix, np.dtype(dtype))
        out[key] = {"shape": list(shape), "dtype": str(np.dtype(stored))}
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    import ml_dtypes

    out: Dict[str, Any] = {}
    for key, v in flat.items():
        if "@" in key:
            maybe_key, _, dtname = key.rpartition("@")
            # only strip the suffix for markers *we* appended on save; a
            # user param literally named "x@foo" passes through intact,
            # and "x@bfloat16" of genuine integer dtype arrives escaped
            # as "x@bfloat16@raw"
            if dtname == "raw":
                key = maybe_key
            elif dtname in _EXOTIC_DTYPES and v.dtype == _EXOTIC_DTYPES[dtname]:
                key = maybe_key
                v = v.view(np.dtype(getattr(ml_dtypes, dtname)))
        parts = key.split(SEP)
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


# -- persistables ------------------------------------------------------------


def save_persistables(dirname: str, params: Dict[str, jax.Array],
                      state: Optional[Dict[str, jax.Array]] = None,
                      opt_state: Optional[Dict[str, Any]] = None,
                      meta: Optional[Dict[str, Any]] = None) -> Dict[str, Dict[str, Any]]:
    """Save all persistable vars (save_persistables analog, io.py:252).
    Sharded arrays are gathered to host first. Returns the flat
    shape/dtype spec per npz file ({filename: {flat key: {"shape",
    "dtype"}}}) — ``save_trainer`` records it in the checkpoint
    manifest."""
    os.makedirs(dirname, exist_ok=True)
    spec: Dict[str, Dict[str, Any]] = {}

    def _dump(name, tree):
        flat = _flatten(jax.device_get(tree))
        np.savez(os.path.join(dirname, name), **flat)
        spec[name] = {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                      for k, v in flat.items()}

    _dump("params.npz", params)
    if state is not None:
        _dump("state.npz", state)
    if opt_state is not None:
        _dump("opt_state.npz", opt_state)
    with open(os.path.join(dirname, "meta.json"), "w") as f:
        json.dump(meta or {}, f)
    return spec


def _zero_split_flat(tree: Any, n: int, partitioned) -> Tuple[List[Dict[str, np.ndarray]],
                                                              Dict[str, np.ndarray]]:
    """Split a ZeRO-partitioned scope tree into n per-shard flat dicts
    (one host ``(k,)`` row each, read from ``addressable_shards`` — no
    all-gather on the save path) plus one flat dict of the replicated
    leaves. ``partitioned`` is the ZeroSpec's mangled-key set."""
    shard_flats: List[Dict[str, np.ndarray]] = [dict() for _ in range(n)]
    base: Dict[str, np.ndarray] = {}

    def walk(t, pfx):
        if isinstance(t, dict):
            for k, v in t.items():
                walk(v, f"{pfx}{SEP}{k}" if pfx else str(k))
            return
        if t is None:
            return
        key, _ = _mangle_key(pfx, np.dtype(t.dtype))
        if key not in partitioned:
            k2, val = _mangle_leaf(pfx, np.asarray(jax.device_get(t)))
            base[k2] = val
            return
        rows: List[Optional[np.ndarray]] = [None] * n
        for s in t.addressable_shards:
            lo = int(s.index[0].start or 0)
            data = np.asarray(s.data)
            for j in range(data.shape[0]):
                if rows[lo + j] is None:
                    rows[lo + j] = data[j]
        enforce(all(r is not None for r in rows),
                f"save_trainer(zero_sharding): shard rows of {pfx!r} are "
                "not all process-addressable — multi-host ZeRO saves need "
                "every host to write its own shard files (not implemented)")
        for i in range(n):
            shard_flats[i][key] = _mangle_leaf(pfx, rows[i])[1]

    walk(tree, "")
    return shard_flats, base


def _save_zero_persistables(dirname: str, trainer, params, state, opt_state,
                            meta) -> Dict[str, Dict[str, Any]]:
    """ZeRO variant of :func:`save_persistables`: partitioned leaves go
    to per-shard files ``params.zero{i}.npz`` / ``opt_state.zero{i}.npz``
    (each member one ``(k,)`` row, gather-free), replicated opt leaves
    keep the base ``opt_state.npz``. ``meta.zero`` records the shard
    count + the LOGICAL flat spec (the N→M gather's reassembly map and
    the contract checker's currency); the returned spec covers the REAL
    files for the manifest CRC pass."""
    os.makedirs(dirname, exist_ok=True)
    zero = trainer._zero
    spec: Dict[str, Dict[str, Any]] = {}

    def _write(name, flat):
        np.savez(os.path.join(dirname, name), **flat)
        spec[name] = {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                      for k, v in flat.items()}

    pshards, pbase = _zero_split_flat(params, zero.n,
                                      zero.partitioned["params.npz"])
    enforce(not pbase, "zero_sharding partitions every param leaf")
    for i, flat in enumerate(pshards):
        _write(f"params.zero{i}.npz", flat)
    if state is not None:
        _write("state.npz", _flatten(jax.device_get(state)))
    if opt_state is not None:
        oshards, obase = _zero_split_flat(opt_state, zero.n,
                                          zero.partitioned["opt_state.npz"])
        _write("opt_state.npz", obase)
        if oshards[0]:
            for i, flat in enumerate(oshards):
                _write(f"opt_state.zero{i}.npz", flat)
    with open(os.path.join(dirname, "meta.json"), "w") as f:
        json.dump(meta or {}, f)
    return spec


def _merge_nested(dst: Dict[str, Any], src: Dict[str, Any]) -> Dict[str, Any]:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge_nested(dst[k], v)
        else:
            dst[k] = v
    return dst


def _gather_zero_collection(dirname: str, stem: str,
                            zero_meta: Dict[str, Any]) -> Dict[str, Any]:
    """Concatenate a ZeRO checkpoint's per-shard ``(k,)`` rows back into
    logical leaves — the host-side gather of the N→M elastic fallback
    (``load_persistables`` calls this transparently, so every consumer
    of the gathered path — drift checks, reshard placement, predictors —
    sees the same logical trees a replicated checkpoint yields).
    Returns ``{}`` when the collection has no partitioned leaves."""
    n = int(zero_meta["shards"])
    spec = (zero_meta.get("arrays") or {}).get(f"{stem}.npz") or {}
    paths = [os.path.join(dirname, f"{stem}.zero{i}.npz") for i in range(n)]
    if not any(os.path.exists(p) for p in paths):
        return {}
    missing = [os.path.basename(p) for p in paths if not os.path.exists(p)]
    if missing:
        raise FileNotFoundError(
            f"ZeRO checkpoint is missing shard files {missing[:3]} "
            f"({len(missing)} of {n})")
    flat: Dict[str, np.ndarray] = {}
    flats: List[Dict[str, np.ndarray]] = []
    for p in paths:
        with np.load(p, allow_pickle=False) as z:
            flats.append({k: np.array(z[k]) for k in z.files})
    for key in flats[0]:
        ent = spec.get(key)
        if ent is None:
            raise KeyError(
                f"{stem} shard member {key!r} is absent from the "
                "checkpoint's meta.zero.arrays spec")
        shape = tuple(ent["shape"])
        size = int(np.prod(shape)) if shape else 1
        flat[key] = np.concatenate(
            [f[key] for f in flats])[:size].reshape(shape)
    return _unflatten(flat)


def load_persistables(dirname: str) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray],
                                             Optional[Dict[str, Any]], Dict[str, Any]]:
    """Load (params, state, opt_state, meta) (load_persistables analog).
    ZeRO checkpoints (``meta.zero``) are gathered to logical shapes on
    the host — the explicit N→M fallback; the gather-free same-N path
    lives in ``load_trainer``."""

    def _load(name):
        p = os.path.join(dirname, name)
        if not os.path.exists(p):
            return None
        with np.load(p, allow_pickle=False) as z:
            # fresh writable copies, NOT the npz-backed views: jax's CPU
            # backend zero-copies device_put of host arrays when it can,
            # and a Trainer later DONATES those buffers — in-place XLA
            # reuse of memory owned by the zip reader corrupts values
            # transiently (observed as NaN losses after resume; the
            # fault-injection suite pins this via resume continuity)
            return _unflatten({k: np.array(z[k]) for k in z.files})

    meta_path = os.path.join(dirname, "meta.json")
    meta = {}
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    zero = meta.get("zero")
    if zero:
        params = _gather_zero_collection(dirname, "params", zero)
        state = _load("state.npz") or {}
        opt_state = _load("opt_state.npz")
        opart = _gather_zero_collection(dirname, "opt_state", zero)
        if opart:
            opt_state = _merge_nested(opt_state if opt_state is not None
                                      else {}, opart)
    else:
        params = _load("params.npz") or {}
        state = _load("state.npz") or {}
        opt_state = _load("opt_state.npz")
    if opt_state is not None:
        # empty sub-dicts ("global"/"accums" for stateless optimizers)
        # flatten to nothing on save — restore the keys
        opt_state.setdefault("global", {})
        opt_state.setdefault("accums", {})
    return params, state, opt_state, meta


def _fsync_tree(dirname: str) -> None:
    """fsync every regular file in ``dirname`` (and the dir itself):
    the atomic-rename commit is only meaningful if the data it commits
    has reached the disk."""
    for name in os.listdir(dirname):
        p = os.path.join(dirname, name)
        if not os.path.isfile(p):
            continue
        fd = os.open(p, os.O_RDONLY)
        try:
            os.fsync(fd)
        except OSError:
            pass  # fs without fsync support (tmpfs variants): best effort
        finally:
            os.close(fd)
    _fsync_dir(dirname)


def _fsync_dir(dirname: str) -> None:
    try:
        fd = os.open(dirname, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def save_trainer(dirname: str, trainer,
                 extra_meta: Optional[Dict[str, Any]] = None) -> None:
    """Checkpoint a Trainer (params+state+opt_state+step) — the
    CheckpointConfig/save_checkpoint analog (contrib/trainer.py:100).

    **Atomic + validated**: the collections are written to a
    ``<dirname>.tmp.<pid>`` sibling, fsynced, covered by a
    ``manifest.json`` (format version, global_step, per-file CRC32 +
    size, flat shape/dtype spec), and renamed into place. A crash at
    ANY point (see the ``save_trainer:*`` crash points in
    ``testing.faults``) leaves either the previous committed checkpoint
    or the new one — never a torn directory that ``load_trainer``
    trusts. ``extra_meta`` entries ride in the checkpoint meta (``fit``
    stores epoch/epoch_step for resume)."""
    import shutil

    from . import resilience

    meta = {"global_step": trainer.global_step}
    ls = getattr(trainer.scope, "loss_scale_state", None)
    if ls:
        meta["loss_scale_state"] = {k: float(v) for k, v in ls.items()}
    # the mesh the checkpoint was WRITTEN at: arrays are stored
    # unsharded, but recording the axes lets the static contract
    # verifier (analysis.contracts) name the N->M reshard a restore at
    # a different mesh implies and judge its feasibility. Recorded
    # UNCONDITIONALLY ({} for a single-device trainer): a meshless
    # checkpoint restored at dp=N is the 1->N elastic case and must
    # trip the same ReshardError gate — only checkpoints that predate
    # this key (no mesh_axes at all) pass ungated
    meta["mesh_axes"] = resilience.trainer_mesh_axes(trainer) or {}
    # ZeRO checkpoints are shard-aware: meta.zero_axes gates the
    # implicit restore path (same-N only), meta.zero carries the shard
    # count + LOGICAL flat spec the N→M gather fallback reassembles by
    zero = getattr(trainer, "_zero", None)
    if zero is not None:
        meta["zero_axes"] = dict(zero.axes_dict)
        meta["zero"] = {"shards": zero.n, "axes": dict(zero.axes_dict),
                        "arrays": zero.arrays}
    if extra_meta:
        meta.update(extra_meta)
    # checkpoints always store logical layer order: undo the trainer's
    # interleaved pipeline rest layout (no-op otherwise)
    params, opt_state = trainer.stacked_to_logical(
        trainer.scope.params, trainer.scope.opt_state)
    path = os.path.abspath(dirname)
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    # clean ANY stale tmp for this tag (a prior process's torn save
    # leaves <tag>.tmp.<other-pid> behind; fit also sweeps the whole
    # dir at startup with the unfiltered form)
    resilience.sweep_tmp_dirs(parent, tag=os.path.basename(path))
    tmp = f"{path}{resilience.TMP_MARKER}{os.getpid()}"
    if zero is not None:
        spec = _save_zero_persistables(tmp, trainer, params,
                                       trainer.scope.state, opt_state, meta)
    else:
        spec = save_persistables(tmp, params, trainer.scope.state,
                                 opt_state, meta=meta)
    resilience.crash_point("save_trainer:files-written")
    _fsync_tree(tmp)
    resilience.write_manifest(tmp, meta=meta, arrays=spec)
    resilience.crash_point("save_trainer:manifest-written")
    if os.path.isdir(path):
        # overwrite of an existing tag: the old dir must vanish before
        # the rename (rename onto a non-empty dir fails). The window
        # where neither exists only loses THIS tag — older tags are
        # untouched and the resume scanner falls back to them.
        shutil.rmtree(path)
    os.rename(tmp, path)
    _fsync_dir(parent)


def load_trainer(dirname: str, trainer, allow_reshard: bool = False) -> None:
    """Restore a Trainer in place, re-placing arrays on the trainer's
    device/mesh (resharding-on-load).

    The checkpoint is validated against its manifest first (CRC32 per
    file, format version); any mismatch — or an npz that fails to parse
    — raises a structured :class:`~paddle_tpu.resilience.CheckpointCorrupt`
    instead of a random decoder error. Pre-manifest (legacy) directories
    load without validation.

    A checkpoint whose recorded ``meta.mesh_axes`` differ from the
    trainer's mesh used to "load" and then die later — in ``put_batch``'s
    ``device_put`` or a retrace shape error deep inside the first step.
    It now raises a structured
    :class:`~paddle_tpu.resilience.ReshardError` at LOAD time naming the
    saved vs. target axes. A mesh change is a supported operation, just
    an explicit one: go through
    :func:`~paddle_tpu.resilience.reshard_restore` (or
    ``fit(resume=True, elastic=True)``), which proves feasibility with
    the static contract checker first — or pass ``allow_reshard=True``
    to skip the gate (the arrays are stored unsharded, so placement per
    the target rules is the whole reshard). Size-1 axes are normalized
    away: ``{"dp": 1}`` and no mesh place identically and do not trip
    the gate; checkpoints that predate mesh metadata pass through
    (the saved mesh is unknowable)."""
    from . import resilience

    # the mesh gate needs only the manifest META — run it BEFORE the
    # full per-file CRC pass, so a mesh-mismatched restore (which
    # reshard_restore will load again, paying the CRC sweep there) is
    # rejected from one cheap JSON read, not a double scan of the
    # checkpoint bytes
    if not allow_reshard:
        meta_man = resilience.read_manifest(dirname)  # None for legacy
        saved_axes = ((meta_man or {}).get("meta") or {}).get("mesh_axes")
        target_axes = resilience.trainer_mesh_axes(trainer)
        if saved_axes is not None and \
                resilience.normalize_mesh_axes(saved_axes) != \
                resilience.normalize_mesh_axes(target_axes):
            raise resilience.ReshardError(
                dirname, saved_axes, target_axes,
                f"checkpoint was saved at mesh axes {saved_axes} but the "
                f"target trainer runs "
                f"{target_axes or 'a single device'} — restoring across a "
                "mesh change is an elastic reshard; use "
                "resilience.reshard_restore(checkpoint_dir, trainer) or "
                "fit(resume=True, elastic=True) (or load_trainer("
                "allow_reshard=True) to skip the feasibility check)")
        # ZeRO gate: a shard-aware checkpoint restores implicitly only
        # at the same shard layout. A zero<->replicated flip or a
        # shard-count change (the static ckpt:zero-mismatch finding's
        # runtime counterpart) goes through the explicit elastic door,
        # which gathers the shards to logical and repartitions.
        if meta_man is not None:
            saved_zero = ((meta_man.get("meta") or {}).get("zero_axes")
                          or {})
            tz = getattr(trainer, "_zero", None)
            target_zero = dict(tz.axes_dict) if tz is not None else {}
            if resilience.normalize_mesh_axes(saved_zero) != \
                    resilience.normalize_mesh_axes(target_zero):
                raise resilience.ReshardError(
                    dirname, saved_axes, target_axes,
                    f"checkpoint zero_sharding axes "
                    f"{saved_zero or None} differ from the target "
                    f"trainer's {target_zero or None} — restoring across "
                    "a ZeRO shard-layout change is an elastic reshard "
                    "(gather-then-repartition); use "
                    "resilience.reshard_restore(checkpoint_dir, trainer) "
                    "or fit(resume=True, elastic=True) (or load_trainer("
                    "allow_reshard=True) to skip the feasibility check)")
    manifest = resilience.validate_checkpoint(dirname)  # None for legacy
    zero_meta = ((manifest or {}).get("meta") or {}).get("zero")
    tz = getattr(trainer, "_zero", None)
    if (tz is not None and zero_meta
            and resilience.normalize_mesh_axes(zero_meta.get("axes") or {})
            == resilience.normalize_mesh_axes(tz.axes_dict)
            and resilience.normalize_mesh_axes(
                ((manifest or {}).get("meta") or {}).get("mesh_axes") or {})
            == resilience.normalize_mesh_axes(
                resilience.trainer_mesh_axes(trainer) or {})):
        # same-N same-mesh ZeRO→ZeRO: shard-local restore, no gather on
        # the hot path (each device adopts its own rows)
        _load_trainer_zero_local(dirname, trainer, manifest)
        return
    try:
        params, state, opt_state, meta = load_persistables(dirname)
    except Exception as e:
        raise resilience.CheckpointCorrupt(
            dirname, f"unreadable collection: {type(e).__name__}: {e}") from e
    if not params:
        raise resilience.CheckpointCorrupt(
            dirname, "no parameters found (params.npz missing or empty)")
    if manifest:
        # a ZeRO manifest's "arrays" spec covers the per-shard files;
        # the gathered trees compare against the LOGICAL spec in
        # meta.zero.arrays instead
        man_arr = (dict(manifest, arrays=zero_meta.get("arrays") or {})
                   if zero_meta else manifest)
        _check_arrays_spec(man_arr, dirname, params=params, state=state,
                           opt_state=opt_state)
    _check_trainer_param_drift(dirname, trainer, params)
    if opt_state is not None:
        # stateless-optimizer per-param accums are empty dicts, which
        # flatten to nothing on save — restore the per-param keys
        for k in params:
            opt_state["accums"].setdefault(k, {})
    # checkpoints are logical layer order; a trainer running the
    # interleaved pipeline layout re-permutes on the way in (no-op
    # otherwise)
    params, opt_state = trainer.stacked_from_logical(params, opt_state)
    if tz is not None:
        # repartition the gathered logical trees into this trainer's
        # (N, k) rows — the second half of the N→M elastic fallback
        from jax.sharding import NamedSharding, PartitionSpec
        from .parallel import zero as zero_mod
        params = zero_mod.partition_params(params, tz, trainer.mesh)
        opt_state = (zero_mod.partition_opt_state(opt_state, tz,
                                                  trainer.mesh)
                     if opt_state is not None else None)
        state = jax.device_put(
            state, NamedSharding(trainer.mesh, PartitionSpec()))
    elif trainer.mesh is not None:
        from .parallel import api as par_api
        params, state, opt_state = par_api.shard_scope(
            trainer.mesh, trainer.sharding_rules, params, state, opt_state)
    else:
        dev = trainer.place.device()
        params = jax.device_put(params, dev)
        state = jax.device_put(state, dev)
        opt_state = jax.device_put(opt_state, dev) if opt_state is not None else None
    # restore exact leaf dtypes (npz roundtrips are exact, but int scalars
    # may come back as 0-d arrays)
    if opt_state is not None:
        opt_state["step"] = jnp.asarray(opt_state["step"], jnp.int32)
    trainer.scope.params, trainer.scope.state, trainer.scope.opt_state = params, state, opt_state
    trainer.global_step = int(meta.get("global_step", 0))
    # kept for fit(resume=True): epoch/epoch_step and anything else the
    # saver stored ride here (resilience.restore_latest reads it)
    trainer._last_loaded_meta = dict(meta)
    _restore_loss_scale(trainer, meta, dirname)


def _load_trainer_zero_local(dirname: str, trainer, manifest) -> None:
    """Same-N, same-mesh restore of a ZeRO checkpoint: every device
    adopts its own ``(k,)`` rows straight from the per-shard files via
    ``jax.make_array_from_callback`` — no gather on the restore path,
    mirroring the gather-free save. The CRC pass already ran
    (``validate_checkpoint``); this adds the logical-spec drift gate
    (same contract as :func:`_check_trainer_param_drift`)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from . import resilience
    from .parallel import zero as zero_mod

    zero = trainer._zero
    meta = (manifest.get("meta") or {})
    zm = meta.get("zero") or {}
    n = int(zm.get("shards") or zero.n)
    saved = (zm.get("arrays") or {}).get("params.npz") or {}
    want = zero.arrays["params.npz"]
    if {k: (tuple(v["shape"]), str(v["dtype"])) for k, v in saved.items()} \
            != {k: (tuple(v["shape"]), str(v["dtype"]))
                for k, v in want.items()}:
        missing = sorted(set(want) - set(saved))[:3]
        extra = sorted(set(saved) - set(want))[:3]
        raise resilience.CheckpointCorrupt(
            dirname, f"ZeRO checkpoint params diverge from the trainer's "
            f"logical spec (missing: {missing}, unexpected: {extra}) — "
            "the model config drifted since this checkpoint was written")

    def shard_trees(stem):
        paths = [os.path.join(dirname, f"{stem}.zero{i}.npz")
                 for i in range(n)]
        if not any(os.path.exists(p) for p in paths):
            return None
        out = []
        for p in paths:
            try:
                with np.load(p, allow_pickle=False) as z:
                    out.append(_unflatten({k: np.array(z[k])
                                           for k in z.files}))
            except Exception as e:
                raise resilience.CheckpointCorrupt(
                    dirname, f"unreadable shard file "
                    f"{os.path.basename(p)}: {type(e).__name__}: {e}") from e
        return out

    ns = zero_mod.shard_sharding(trainer.mesh, zero.axes)
    repl = NamedSharding(trainer.mesh, PartitionSpec())

    def rows_to_array(*rows):
        rows = [np.asarray(r) for r in rows]

        def cb(index):
            lo = int(index[0].start or 0)
            hi = index[0].stop
            hi = n if hi is None else int(hi)
            return np.stack(rows[lo:hi])

        return jax.make_array_from_callback((n,) + rows[0].shape, ns, cb)

    ptrees = shard_trees("params")
    if ptrees is None:
        raise resilience.CheckpointCorrupt(
            dirname, "ZeRO checkpoint has no params.zero*.npz shard files")
    params = jax.tree.map(rows_to_array, *ptrees)

    def _load_flat(name):
        p = os.path.join(dirname, name)
        if not os.path.exists(p):
            return None
        try:
            with np.load(p, allow_pickle=False) as z:
                return _unflatten({k: np.array(z[k]) for k in z.files})
        except Exception as e:
            raise resilience.CheckpointCorrupt(
                dirname, f"unreadable collection {name}: "
                f"{type(e).__name__}: {e}") from e

    state = jax.device_put(_load_flat("state.npz") or {}, repl)
    opt_state = _load_flat("opt_state.npz")
    otrees = shard_trees("opt_state")
    if opt_state is not None or otrees is not None:
        opt_state = jax.device_put(opt_state or {}, repl)
        if otrees is not None:
            _merge_nested(opt_state, jax.tree.map(rows_to_array, *otrees))
        opt_state.setdefault("global", {})
        opt_state.setdefault("accums", {})
        for k in zero.shapes:
            opt_state["accums"].setdefault(k, {})
        if "step" in opt_state:
            opt_state["step"] = jax.device_put(
                jnp.asarray(opt_state["step"], jnp.int32), repl)
    trainer.scope.params, trainer.scope.state, trainer.scope.opt_state = \
        params, state, opt_state
    trainer.global_step = int(meta.get("global_step", 0))
    trainer._last_loaded_meta = dict(meta)
    _restore_loss_scale(trainer, meta, dirname)


def _check_trainer_param_drift(dirname: str, trainer, params) -> None:
    """A checkpoint whose PARAMETER spec diverges from the trainer it is
    restored into (renamed layer, resized dim, dtype change — i.e. the
    model config drifted since the save) used to load "successfully" and
    then die as a shape error deep inside the next step's retrace, or
    worse, train garbage. Raise a structured
    :class:`~paddle_tpu.resilience.CheckpointCorrupt` at LOAD time
    naming the drifted entries instead — the runtime counterpart of the
    ``ckpt:*`` findings ``analysis.contracts.check_artifacts`` reports
    without touching the checkpoint. Only runs on a started trainer
    (``scope.params`` populated); state/opt-state drift stays a
    warning-level static finding (the runtime falls back by rebuilding
    them)."""
    from . import resilience

    have = getattr(getattr(trainer, "scope", None), "params", None)
    if not have:
        return
    # the trainer may hold the interleaved-pipeline row layout; that is
    # a row PERMUTATION of the logical layout — shapes/dtypes/names are
    # identical, so the spec comparison is layout-agnostic. A ZeRO
    # trainer's scope holds (N, k) rows; its LOGICAL spec was recorded
    # in the ZeroSpec at startup.
    tz = getattr(trainer, "_zero", None)
    want = (dict(tz.arrays["params.npz"]) if tz is not None
            else flat_spec(have))
    got = flat_spec(params)
    if set(want) != set(got):
        missing = sorted(set(want) - set(got))[:3]
        extra = sorted(set(got) - set(want))[:3]
        raise resilience.CheckpointCorrupt(
            dirname, f"checkpoint params diverge from the trainer's "
            f"(missing: {missing}, unexpected: {extra}) — the model "
            "config drifted since this checkpoint was written")
    drift = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if drift:
        k, (g, w) = sorted(drift.items())[0]
        raise resilience.CheckpointCorrupt(
            dirname, f"checkpoint param {k!r} is {g} but the trainer "
            f"expects {w} ({len(drift)} drifted entr"
            f"{'y' if len(drift) == 1 else 'ies'} total) — the model "
            "config drifted since this checkpoint was written")


def _check_arrays_spec(manifest: Dict[str, Any], dirname: str,
                       **collections) -> None:
    """Verify the loaded trees against the manifest's flat shape/dtype
    spec — the per-leaf half of checkpoint validation (CRC32 guarantees
    the bytes; this guarantees the decoded structure matches what the
    saver recorded, catching a manifest/npz pair that drifted out of
    sync). Costs a dict re-flatten of data already in memory."""
    from . import resilience

    spec = manifest.get("arrays") or {}
    fname = {"params": "params.npz", "state": "state.npz",
             "opt_state": "opt_state.npz"}
    for coll, tree in collections.items():
        want = spec.get(fname[coll])
        if want is None or tree is None:
            continue
        got = {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
               for k, v in _flatten(tree).items()}
        if set(got) != set(want):
            missing = sorted(set(want) - set(got))[:3]
            extra = sorted(set(got) - set(want))[:3]
            raise resilience.CheckpointCorrupt(
                dirname, f"{fname[coll]} members diverge from manifest "
                f"(missing: {missing}, unexpected: {extra})")
        for k, w in want.items():
            if got[k] != w:
                raise resilience.CheckpointCorrupt(
                    dirname, f"{fname[coll]}:{k} is {got[k]} on disk but "
                    f"the manifest records {w}")


def _restore_loss_scale(trainer, meta: Dict[str, Any], dirname: str) -> None:
    """Loss-scale state across checkpoint/trainer config drift: a
    checkpoint that predates dynamic loss scaling restored into a
    scaler-running trainer (or vice versa) must warn and fall back to
    the scaler's initial state, not KeyError."""
    import warnings

    ls_meta = meta.get("loss_scale_state")
    if trainer.loss_scaler is None:
        if ls_meta:
            warnings.warn(
                f"checkpoint {dirname!r} carries loss_scale_state but the "
                "trainer has no loss scaler — ignoring it (configure "
                "DistStrategy.loss_scale to adopt it)")
        return
    init = trainer.loss_scaler.init_state()
    if not ls_meta:
        warnings.warn(
            f"checkpoint {dirname!r} has no loss_scale_state but the "
            "trainer runs a loss scaler — falling back to the scaler's "
            "initial state (scale will re-calibrate)")
        ls_meta = {}
    missing = {"scale", "good_steps", "overflows"} - set(ls_meta)
    if ls_meta and missing:
        warnings.warn(
            f"checkpoint {dirname!r} loss_scale_state is missing "
            f"{sorted(missing)} — those fields fall back to the scaler's "
            "initial values")
    trainer.scope.loss_scale_state = jax.device_put({
        "scale": jnp.float32(ls_meta.get("scale", float(init["scale"]))),
        "good_steps": jnp.int32(ls_meta.get("good_steps",
                                            int(init["good_steps"]))),
        "overflows": jnp.int32(ls_meta.get("overflows",
                                           int(init["overflows"]))),
    })


# -- inference model (save/load_inference_model analog) ----------------------


def _in_spec(flat_sources, exported):
    """Flat (source, name) binding -> the ordered input spec native
    drivers consume. ONE emission point for both artifact kinds
    (save_inference_model / save_train_artifact): the invariant that
    spec names stay byte-identical to npz member names (via
    _mangle_leaf) and positionally aligned to exported.in_avals must
    not fork."""
    enforce(len(flat_sources) == len(exported.in_avals),
            f"export signature mismatch: {len(flat_sources)} leaves vs "
            f"{len(exported.in_avals)} in_avals")
    return [{"source": src, "name": name,
             "dtype": str(av.dtype), "shape": list(av.shape)}
            for (src, name), av in zip(flat_sources, exported.in_avals)]


def _recover_renamed_aside(path: str) -> None:
    """Crash recovery for the two-rename overwrite window: a save that
    died between rename-aside and commit leaves the only good artifact
    at ``<path>.tmp.<pid>.old`` with nothing at ``path``. Restore it
    BEFORE the tmp sweep — the sweep's ``<tag>.tmp.*`` pattern would
    otherwise delete the sole surviving copy while the replacement save
    could still fail before committing."""
    from . import resilience

    if os.path.isdir(path):
        return
    olds = sorted(p for p in
                  (os.path.join(os.path.dirname(path), n)
                   for n in os.listdir(os.path.dirname(path) or "."))
                  if p.startswith(f"{path}{resilience.TMP_MARKER}")
                  and p.endswith(".old") and os.path.isdir(p))
    if not olds:
        return
    newest = max(olds, key=os.path.getmtime)
    os.rename(newest, path)
    _log().warning("recovered artifact %s from interrupted overwrite (%s)",
                   path, os.path.basename(newest))


def _infer_batch_info(example_feed: Dict[str, Any]) -> Tuple[int, List[str]]:
    """(batch_size, batched_feed_names) of an example feed: the batch is
    the leading dim of the first (sorted) non-scalar feed; every feed
    sharing that leading dim is treated as batched — the axis shape
    buckets and request padding operate on."""
    batch = 0
    for k in sorted(example_feed):
        v = np.asarray(example_feed[k])
        if v.ndim >= 1:
            batch = int(v.shape[0])
            break
    batched = [k for k in sorted(example_feed)
               if np.asarray(example_feed[k]).ndim >= 1
               and np.asarray(example_feed[k]).shape[0] == batch]
    return batch, batched


def _resize_batch(v: np.ndarray, n: int) -> np.ndarray:
    """Example feed at a different bucket size: slice down or tile up
    along dim 0 (values only seed the trace — shapes/dtypes matter)."""
    if v.shape[0] >= n:
        return np.ascontiguousarray(v[:n])
    reps = -(-n // v.shape[0])  # ceil
    return np.ascontiguousarray(
        np.concatenate([v] * reps, axis=0)[:n])


def save_inference_model(dirname: str, program, params: Dict[str, jax.Array],
                         state: Dict[str, jax.Array], example_feed: Dict[str, Any],
                         batch_buckets: Optional[Sequence[int]] = None) -> None:
    """Export program.apply (inference mode, params baked as inputs) as a
    serialized StableHLO artifact + weights (io.py:544 analog: prune to
    feed/fetch + serialize ProgramDesc + save params).

    **Atomic + validated commit** (the ``save_trainer`` discipline
    applied to deployment artifacts): everything is written to a
    ``<dirname>.tmp.<pid>`` sibling, fsynced, covered by a
    ``resilience.write_manifest`` manifest (per-file CRC32 + size, flat
    shape/dtype spec of the weight collections), and renamed into place.
    A crash mid-EXPORT leaves the previous artifact committed; when
    OVERWRITING an existing artifact the old one is renamed aside first,
    so the only no-artifact-at-``dirname`` window is two renames wide
    (a crash inside it preserves the old artifact under a ``.tmp.*.old``
    marker, and a concurrent loader fails loudly rather than reading a
    torn tree). ``load_inference_model`` / a hot-reloading
    ``serving.PredictorServer`` reject torn or bit-flipped artifacts
    with a structured :class:`~paddle_tpu.resilience.CheckpointCorrupt`.

    ``batch_buckets`` exports ADDITIONAL fixed batch sizes of the same
    program (``model.b{N}.stablehlo`` siblings): the precompiled shape
    bucket set a :class:`~paddle_tpu.serving.PredictorServer` pads
    ragged request batches up to, so adversarial batch shapes can never
    trigger a recompile on the request path. The example feed's own
    batch size is always a bucket."""
    with profiler.record_event("io.save_inference_model"):
        _save_inference_model(dirname, program, params, state, example_feed,
                              batch_buckets)


def _save_inference_model(dirname, program, params, state, example_feed,
                          batch_buckets) -> None:
    import shutil

    from . import resilience

    feed_names = sorted(example_feed)
    batch, batched_feeds = _infer_batch_info(example_feed)
    buckets = sorted(set(int(b) for b in (batch_buckets or [])) | {batch})
    enforce(all(b > 0 for b in buckets),
            f"batch_buckets must be positive, got {buckets}")

    def infer_fn(params_, state_, *feed_vals):
        feed = dict(zip(feed_names, feed_vals))
        out, _ = program.apply(params_, state_, training=False, **feed)
        return out

    with profiler.record_event("io.write_params"):   # device -> host
        host_params, host_state = jax.device_get(params), jax.device_get(state)

    def _export_at(feed, bucket):
        with profiler.record_event("io.export", bucket=bucket):
            vals = [jnp.asarray(np.asarray(feed[k])) for k in feed_names]
            return jax.export.export(jax.jit(infer_fn))(
                host_params, host_state, *vals)

    exported = _export_at(example_feed, batch)
    bucket_exports = {}
    for b in buckets:
        if b == batch:
            continue
        bucket_exports[b] = _export_at(
            {k: (_resize_batch(np.asarray(v), b) if k in batched_feeds
                 else np.asarray(v))
             for k, v in example_feed.items()}, b)

    path = os.path.abspath(dirname)
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    _recover_renamed_aside(path)
    resilience.sweep_tmp_dirs(parent, tag=os.path.basename(path))
    tmp = f"{path}{resilience.TMP_MARKER}{os.getpid()}"
    os.makedirs(tmp)

    with open(os.path.join(tmp, "model.stablehlo"), "wb") as f:
        f.write(exported.serialize())
    for b, exp in bucket_exports.items():
        with open(os.path.join(tmp, f"model.b{b}.stablehlo"), "wb") as f:
            f.write(exp.serialize())
    with profiler.record_event("io.write_params"):   # host -> npz
        flat_params, flat_state = _flatten(host_params), _flatten(host_state)
        np.savez(os.path.join(tmp, "params.npz"), **flat_params)
        np.savez(os.path.join(tmp, "state.npz"), **flat_state)
    arrays_spec = {name: {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                          for k, v in flat.items()}
                   for name, flat in (("params.npz", flat_params),
                                      ("state.npz", flat_state))}
    # Python-free deployment artifact (inference/io.h:35 analog): the raw
    # StableHLO bytecode plus the flat call signature, so native/
    # predictor.cc can compile+run through the PJRT C API with no
    # libpython. Inputs are the flattened (params, state, *feeds) leaves
    # in exported.in_avals order; "source" tells the C++ loader which
    # npz member (or feed) supplies each argument.
    with open(os.path.join(tmp, "model.mlir"), "wb") as f:
        f.write(exported.mlir_module_serialized)
    param_leaves = _flat_leaves_in_tree_order(host_params)
    state_leaves = _flat_leaves_in_tree_order(host_state)
    flat_sources = ([("params.npz", k) for k, _ in param_leaves]
                    + [("state.npz", k) for k, _ in state_leaves]
                    + [("feed", k) for k in feed_names])
    flat_vals = ([v for _, v in param_leaves] + [v for _, v in state_leaves]
                 + [np.asarray(example_feed[k]) for k in feed_names])
    in_spec = _in_spec(flat_sources, exported)
    for (src, name), val, av in zip(flat_sources, flat_vals, exported.in_avals):
        enforce(tuple(val.shape) == tuple(av.shape),
                f"export arg order broke: {src}:{name} has shape {val.shape}, "
                f"aval expects {av.shape}")
        # npz members store exotic dtypes as integer views ('@bfloat16'
        # suffix); the ORIGINAL dtype must still match the aval
        if src != "feed" and "@" not in name:
            enforce(val.dtype.name == str(av.dtype),
                    f"export arg order broke: {src}:{name} is {val.dtype.name},"
                    f" aval expects {av.dtype}")
    out_spec = [{"dtype": str(av.dtype), "shape": list(av.shape)}
                for av in exported.out_avals]
    meta = {"feed_names": feed_names, "inputs": in_spec, "outputs": out_spec,
            "batch_size": batch, "batched_feeds": batched_feeds,
            "batch_buckets": buckets}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    resilience.crash_point("save_inference_model:files-written")
    _fsync_tree(tmp)
    resilience.write_manifest(tmp, meta={"kind": "inference_model"},
                              arrays=arrays_spec)
    resilience.crash_point("save_inference_model:manifest-written")
    old = None
    if os.path.isdir(path):
        # overwrite: move the committed artifact ASIDE (one rename)
        # rather than rmtree-ing it first — the no-artifact window is
        # two renames wide instead of a full recursive delete, and a
        # crash inside it leaves the previous artifact intact under the
        # .tmp marker (a concurrent load during the window fails
        # loudly; a hot-reloading PredictorServer rolls back and keeps
        # serving its in-memory model)
        old = f"{path}{resilience.TMP_MARKER}{os.getpid()}.old"
        os.rename(path, old)
        resilience.crash_point("save_inference_model:committing")
    os.rename(tmp, path)
    _fsync_dir(parent)
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)


def artifact_fingerprint(dirname: str) -> Tuple[Dict[str, Any], str]:
    """(manifest, token) of a committed ``save_inference_model`` dir.

    The token is content-addressed — ``<basename>-<crc32:08x>`` over the
    sorted ``name:crc:size`` lines of the manifest's file table — so two
    hosts can agree an artifact is already present without moving bytes:
    the fleet's FETCH/ARTIFACT distribution keys its receive cache on it,
    making re-ships of an unchanged artifact a no-op negotiation."""
    import zlib

    from . import resilience

    path = os.path.abspath(dirname)
    man = resilience.read_manifest(path)
    enforce(man is not None,
            f"artifact_fingerprint: {dirname!r} has no manifest — only "
            "committed save_inference_model dirs can be distributed")
    lines = "\n".join(f"{name}:{spec['crc32']}:{spec['size']}"
                      for name, spec in sorted(man["files"].items()))
    crc = zlib.crc32(lines.encode()) & 0xFFFFFFFF
    return man, f"{os.path.basename(path)}-{crc:08x}"


def save_train_artifact(dirname: str, trainer, example_feed: Dict[str, Any]) -> None:
    """Export ONE optimizer step of a started Trainer as a StableHLO
    artifact the Python-free native trainer (native/trainer.cc) can
    drive — train/demo/demo_trainer.cc parity, where the reference saves
    a ProgramDesc its C++ Executor replays.

    The exported function is
        step(params, opt_state, state, seed, *feeds)
          -> (params', opt_state', state', loss)
    with params/opt_state/state flattened in sorted-key order on BOTH
    sides, so output i is input i's next value for i < num_carry — the
    C++ loop swaps buffers positionally with no name resolution. The
    per-step RNG enters as a u32 scalar seed (PRNGKey built inside the
    traced step: threefry, so the artifact is backend-portable); the
    C++ driver feeds the step index.
    """
    program, optimizer = trainer.program, trainer.optimizer
    enforce(trainer.scope.params is not None, "save_train_artifact: call "
            "trainer.startup() first")
    enforce(getattr(trainer, "loss_scaler", None) is None,
            "save_train_artifact: dynamic loss scaling not supported in the "
            "native step (export a bfloat16/float32 trainer)")
    enforce(getattr(trainer, "mesh", None) is None,
            "save_train_artifact: single-device export only")
    loss_name = trainer.loss_name
    os.makedirs(dirname, exist_ok=True)
    feed_names = sorted(example_feed)

    def step(params_, opt_state_, state_, seed, *feed_vals):
        feed = dict(zip(feed_names, feed_vals))
        rng = jax.random.PRNGKey(seed)

        def loss_fn(p, st):
            out, new_state = program.apply(p, st, training=True, rng=rng,
                                           **feed)
            loss = out[loss_name] if isinstance(out, dict) else out
            return loss, new_state

        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params_, state_)
        new_params, new_opt = optimizer.update(grads, opt_state_, params_,
                                               program.param_info)
        return new_params, new_opt, new_state, loss.astype(jnp.float32)

    host = jax.device_get((trainer.scope.params, trainer.scope.opt_state,
                           trainer.scope.state))
    host_params, host_opt, host_state = host
    example_vals = [jnp.asarray(np.asarray(example_feed[k]))
                    for k in feed_names]
    exported = jax.export.export(jax.jit(step))(
        host_params, host_opt, host_state, np.uint32(0), *example_vals)
    with open(os.path.join(dirname, "train_step.mlir"), "wb") as f:
        f.write(exported.mlir_module_serialized)
    # the jax-side serialization as well (save_inference_model's
    # model.stablehlo analog): lets a Python process deserialize and
    # replay the IDENTICAL artifact (tests do), not a re-trace
    with open(os.path.join(dirname, "train_step.jaxexp"), "wb") as f:
        f.write(exported.serialize())
    np.savez(os.path.join(dirname, "params.npz"), **_flatten(host_params))
    np.savez(os.path.join(dirname, "opt.npz"), **_flatten(host_opt))
    np.savez(os.path.join(dirname, "state.npz"), **_flatten(host_state))

    param_leaves = _flat_leaves_in_tree_order(host_params)
    opt_leaves = _flat_leaves_in_tree_order(host_opt)
    state_leaves = _flat_leaves_in_tree_order(host_state)
    flat_sources = ([("params.npz", k) for k, _ in param_leaves]
                    + [("opt.npz", k) for k, _ in opt_leaves]
                    + [("state.npz", k) for k, _ in state_leaves]
                    + [("seed", "seed")]
                    + [("feed", k) for k in feed_names])
    num_carry = len(param_leaves) + len(opt_leaves) + len(state_leaves)
    enforce(len(exported.out_avals) == num_carry + 1,
            "train export must emit carry + loss")
    for (src, name), in_av, out_av in zip(
            flat_sources[:num_carry], exported.in_avals[:num_carry],
            exported.out_avals[:num_carry]):
        enforce(tuple(in_av.shape) == tuple(out_av.shape)
                and in_av.dtype == out_av.dtype,
                f"carry leaf {src}:{name} not shape/dtype-stable across the "
                f"step ({in_av} vs {out_av})")
    # feed .npy files must carry the CANONICALIZED aval dtype (e.g. an
    # int64 label feed traces as int32 with x64 off) or the native
    # driver's dtype check rejects them at staging time
    for k, av in zip(feed_names, exported.in_avals[num_carry + 1:]):
        np.save(os.path.join(dirname, f"feed_{k}.npy"),
                np.asarray(example_feed[k]).astype(av.dtype))
    in_spec = _in_spec(flat_sources, exported)
    with open(os.path.join(dirname, "meta_train.json"), "w") as f:
        json.dump({"feed_names": feed_names, "num_carry": num_carry,
                   "inputs": in_spec}, f)


# process-wide count of predictor AOT compiles: the serving tests pin
# this across warmed-up traffic to prove off-bucket/adversarial request
# shapes can never reach a recompile on the request path
_aot_compiles = 0


def aot_compile_count() -> int:
    """Number of predictor AOT compiles performed by this process."""
    return _aot_compiles


def _aot_compile(exported):
    """AOT-compile an Exported at its own in_avals (the
    NativePaddlePredictor Init/Prepare split, api_impl.cc:64)."""
    global _aot_compiles
    flat = [jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in exported.in_avals]
    args, kwargs = jax.tree.unflatten(exported.in_tree, flat)
    compiled = jax.jit(exported.call).lower(*args, **kwargs).compile()
    _aot_compiles += 1
    # the module a device trace will show is ``jit_call(n)``: its text is
    # asked of the executable only if someone joins a trace to it
    profiler.register_program("jit_" + exported.call.__name__,
                              compiled.as_text)
    return compiled


class Predictor:
    """Loaded inference model (PaddlePredictor analog,
    paddle_inference_api.h:141: Run(inputs)->outputs; Clone is free —
    the executable is stateless and thread-safe).

    The executable is **AOT-compiled once** per shape bucket at
    construction: ``run()`` never re-enters tracing/compilation, it only
    validates + device_puts the feeds and executes. ``run`` validates
    the feed structurally first — a missing/extra key or a shape/dtype
    mismatch raises a typed :class:`InvalidRequest` naming the offending
    field instead of a raw ``KeyError`` or an XLA shape abort.

    ``batch_buckets`` maps each precompiled batch size to its
    executable; ``run`` dispatches on the request's batch dim (exact
    match only — padding ragged batches up to a bucket is the serving
    layer's job, :class:`paddle_tpu.serving.PredictorServer`)."""

    def __init__(self, exported, params, state, feed_names, _compiled=None,
                 bucket_exports: Optional[Dict[int, Any]] = None,
                 batch_size: Optional[int] = None,
                 batched_feeds: Optional[Sequence[str]] = None,
                 _buckets: Optional[Dict[int, Any]] = None):
        self._exported = exported
        with profiler.record_event("io.device_put"):
            self._params = jax.device_put(params)
            self._state = jax.device_put(state)
        self.feed_names = list(feed_names)
        # feed avals are the trailing in_avals (flat order is
        # (params..., state..., *feeds) with feeds in sorted-name order)
        self._feed_avals = dict(zip(self.feed_names,
                                    list(exported.in_avals)[-len(self.feed_names):]))
        if batch_size is None or batched_feeds is None:
            batch_size, batched_feeds = _infer_batch_info(
                {k: np.zeros(a.shape, np.int8)
                 for k, a in self._feed_avals.items()})
        self.batch_size = int(batch_size)
        self.batched_feeds = frozenset(batched_feeds)
        if _compiled is None:
            try:
                with profiler.record_event("io.aot_compile",
                                           bucket=self.batch_size):
                    _compiled = _aot_compile(exported)
            except Exception as e:
                # fall back to the jit dispatch cache: first run() traces,
                # subsequent calls still skip tracing/compilation. This
                # reintroduces trace-on-request — say so loudly instead
                # of silently degrading the serving latency contract.
                _log().warning(
                    "Predictor AOT compile failed (%s: %s); falling back to "
                    "the jit dispatch cache — the first run() of each feed "
                    "shape will trace+compile ON the request path",
                    type(e).__name__, e)
                _compiled = jax.jit(exported.call)
        self._compiled = _compiled
        if _buckets is not None:           # clone(): share everything
            self._buckets = _buckets
        else:
            self._buckets = {self.batch_size: self._compiled}
            for b, exp in (bucket_exports or {}).items():
                if int(b) == self.batch_size:
                    continue
                try:
                    with profiler.record_event("io.aot_compile",
                                               bucket=int(b)):
                        self._buckets[int(b)] = _aot_compile(exp)
                except Exception as e:
                    _log().warning(
                        "bucket %d AOT compile failed (%s: %s); falling back "
                        "to the jit dispatch cache for that bucket",
                        b, type(e).__name__, e)
                    self._buckets[int(b)] = jax.jit(exp.call)

    @property
    def batch_buckets(self) -> List[int]:
        """Precompiled batch sizes, ascending."""
        return sorted(self._buckets)

    def feed_spec(self, batch: Optional[int] = None) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """{feed name: (shape, dtype)} at bucket ``batch`` (default: the
        export's own batch size)."""
        batch = self.batch_size if batch is None else int(batch)
        out = {}
        for k, a in self._feed_avals.items():
            shape = tuple(a.shape)
            if k in self.batched_feeds:
                shape = (batch,) + shape[1:]
            out[k] = (shape, np.dtype(str(a.dtype)))
        return out

    def validate_feed(self, feed: Dict[str, Any],
                      allow_padding: bool = False) -> Tuple[int, int]:
        """Structural request validation. Returns ``(n, bucket)`` — the
        request's batch size and the precompiled bucket that serves it
        (``n == bucket`` unless ``allow_padding``, where the smallest
        bucket >= n is chosen). Raises :class:`InvalidRequest` naming
        the offending field for missing/extra keys, shape or dtype
        mismatches, and off-bucket batch sizes."""
        for k in self.feed_names:
            if k not in feed:
                raise InvalidRequest(k, "is missing from the feed "
                                     f"(expected keys: {self.feed_names})")
        for k in sorted(feed):
            if k not in self._feed_avals:
                raise InvalidRequest(
                    k, "is not a feed of this model "
                    f"(expected keys: {self.feed_names})")
        buckets = self.batch_buckets
        n = None
        arrs = {k: np.asarray(feed[k]) for k in self.feed_names}
        for k in self.feed_names:
            if k not in self.batched_feeds:
                continue
            v = arrs[k]
            if v.ndim < 1:
                raise InvalidRequest(k, "must be batched (got a scalar)")
            if n is None:
                n = int(v.shape[0])
            elif int(v.shape[0]) != n:
                raise InvalidRequest(
                    k, f"batch dim {v.shape[0]} disagrees with the "
                    f"request's batch size {n}")
        if n is None:
            n = self.batch_size
        if n == 0:
            raise InvalidRequest(
                sorted(self.batched_feeds)[0] if self.batched_feeds
                else self.feed_names[0], "has an empty batch")
        if allow_padding:
            fits = [b for b in buckets if b >= n]
            if not fits:
                raise InvalidRequest(
                    sorted(self.batched_feeds)[0] if self.batched_feeds
                    else self.feed_names[0],
                    f"batch size {n} exceeds the largest precompiled "
                    f"bucket (buckets: {buckets})")
            bucket = fits[0]
        else:
            if n not in self._buckets:
                raise InvalidRequest(
                    sorted(self.batched_feeds)[0] if self.batched_feeds
                    else self.feed_names[0],
                    f"batch size {n} is not a precompiled bucket "
                    f"(buckets: {buckets})")
            bucket = n
        spec = self.feed_spec(n)  # request-sized: padding happens later
        for k in self.feed_names:
            v = arrs[k]
            want_shape, want_dtype = spec[k]
            if tuple(v.shape) != want_shape:
                raise InvalidRequest(
                    k, f"has shape {tuple(v.shape)}, expected {want_shape}")
            got = v.dtype
            if got != want_dtype and \
                    jax.dtypes.canonicalize_dtype(got) != want_dtype:
                raise InvalidRequest(
                    k, f"has dtype {got}, expected {want_dtype}")
        return n, bucket

    def run(self, feed: Dict[str, Any]):
        n, bucket = self.validate_feed(feed, allow_padding=False)
        vals = [jnp.asarray(np.asarray(feed[k])) for k in self.feed_names]
        return self._buckets[bucket](self._params, self._state, *vals)

    def clone(self) -> "Predictor":
        # share the compiled executables and device-resident weights
        return Predictor(self._exported, self._params, self._state,
                         self.feed_names, _compiled=self._compiled,
                         batch_size=self.batch_size,
                         batched_feeds=self.batched_feeds,
                         _buckets=self._buckets)


def load_inference_model(dirname: str) -> Predictor:
    """Load + AOT-compile a :class:`Predictor` from a
    ``save_inference_model`` artifact.

    The artifact is validated against its manifest first (per-file
    CRC32 + size) — a torn or bit-flipped artifact raises a structured
    :class:`~paddle_tpu.resilience.CheckpointCorrupt` instead of a
    random decoder error three frames deep. Pre-manifest (legacy)
    directories load without validation."""
    with profiler.record_event("io.load_inference_model"):
        return _load_inference_model(dirname)


def _load_inference_model(dirname: str) -> Predictor:
    from . import resilience

    resilience.validate_checkpoint(dirname)  # None for legacy dirs
    try:
        with open(os.path.join(dirname, "model.stablehlo"), "rb") as f:
            exported = jax.export.deserialize(f.read())
        with profiler.record_event("io.read_params"):
            params, state, _, meta = load_persistables(dirname)
    except (resilience.CheckpointCorrupt, FileNotFoundError):
        raise
    except Exception as e:
        raise resilience.CheckpointCorrupt(
            dirname, f"unreadable artifact: {type(e).__name__}: {e}") from e
    bucket_exports = {}
    for b in meta.get("batch_buckets", []):
        p = os.path.join(dirname, f"model.b{b}.stablehlo")
        if not os.path.exists(p):
            continue
        try:
            with open(p, "rb") as f:
                bucket_exports[int(b)] = jax.export.deserialize(f.read())
        except Exception as e:
            raise resilience.CheckpointCorrupt(
                dirname, f"unreadable bucket export model.b{b}.stablehlo: "
                f"{type(e).__name__}: {e}") from e
    return Predictor(exported, params, state, meta["feed_names"],
                     bucket_exports=bucket_exports,
                     batch_size=meta.get("batch_size"),
                     batched_feeds=meta.get("batched_feeds"))


def read_artifact_meta(dirname: str) -> Dict[str, Any]:
    """Static metadata surface of a ``save_inference_model`` artifact:
    the parsed ``meta.json`` (feed names, flat input/output specs,
    batch buckets), the manifest (flat weight spec — read WITHOUT the
    CRC pass), and which per-bucket StableHLO files actually exist on
    disk. No deserialization, no AOT compile, no device work — this is
    what ``analysis.contracts`` and the serving pre-reload check reason
    over. Raises :class:`~paddle_tpu.resilience.CheckpointCorrupt` for
    a directory that is not a readable artifact."""
    from . import resilience

    if not os.path.isdir(dirname):
        raise resilience.CheckpointCorrupt(dirname, "not a directory")
    mpath = os.path.join(dirname, "meta.json")
    if not os.path.exists(mpath):
        raise resilience.CheckpointCorrupt(
            dirname, "no meta.json (not a save_inference_model artifact)")
    try:
        with open(mpath) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        raise resilience.CheckpointCorrupt(
            dirname, f"unreadable meta.json: {e}") from e
    manifest = resilience.read_manifest(dirname)  # None for legacy
    batch = int(meta.get("batch_size", 0) or 0)
    bucket_files = {}
    for b in meta.get("batch_buckets", []) or []:
        b = int(b)
        # the export's own batch size lives in model.stablehlo itself
        name = ("model.stablehlo" if b == batch
                else f"model.b{b}.stablehlo")
        bucket_files[b] = os.path.isfile(os.path.join(dirname, name))
    return {
        "path": dirname,
        "meta": meta,
        "manifest": manifest,
        "bucket_files": bucket_files,
        "model_file": os.path.isfile(os.path.join(dirname,
                                                  "model.stablehlo")),
    }


def artifact_feed_spec(meta: Dict[str, Any],
                       batch: Optional[int] = None) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """``{feed name: (shape, dtype)}`` at bucket ``batch`` (default:
    the export's own batch size), reconstructed from an artifact's
    ``meta.json`` dict alone — byte-for-byte the spec
    :meth:`Predictor.feed_spec` computes from the deserialized export,
    so a static pre-reload check and the live server can never
    disagree."""
    feeds = {e["name"]: e for e in meta.get("inputs", [])
             if e.get("source") == "feed"}
    enforce(set(feeds) == set(meta.get("feed_names", [])),
            f"artifact meta is inconsistent: inputs name feeds "
            f"{sorted(feeds)} but feed_names is {meta.get('feed_names')}")
    batch = int(meta["batch_size"]) if batch is None else int(batch)
    batched = set(meta.get("batched_feeds", []))
    out = {}
    for k, e in feeds.items():
        shape = tuple(int(d) for d in e["shape"])
        if k in batched:
            shape = (batch,) + shape[1:]
        out[k] = (shape, np.dtype(str(e["dtype"])))
    return out


def save_params(dirname: str, params, state=None, opt_state=None):
    """io.py:252 save_params analog — parameters (+state/opt_state when
    given)."""
    save_persistables(dirname, params, state or {}, opt_state)


def save_vars(dirname: str, vars: Dict[str, jax.Array], filename=None):
    """io.py:89 save_vars analog: save an arbitrary name→array dict."""
    save_persistables(dirname, dict(vars), {}, None)


def load_params(dirname: str):
    """io.py load_params analog: returns the parameter dict."""
    return load_persistables(dirname)[0]


def load_vars(dirname: str):
    """io.py:295 load_vars analog."""
    return load_persistables(dirname)[0]


# -- orbax backend: async + sharded checkpointing ----------------------------
# SURVEY §5's stated TPU plan ("orbax-style sharded async checkpoint of a
# pytree"): each host writes only its own array shards (scales to
# multi-host), and async mode overlaps serialization with the next train
# steps — the reference's per-pserver checkpoint block
# (_create_checkpoint_save_block) re-expressed for the SPMD world.


_async_checkpointer: Optional[Any] = None


def _orbax_checkpointer(async_save: bool):
    import orbax.checkpoint as ocp

    global _async_checkpointer
    if async_save:
        if _async_checkpointer is None:
            _async_checkpointer = ocp.AsyncCheckpointer(
                ocp.StandardCheckpointHandler())
        return _async_checkpointer
    return ocp.Checkpointer(ocp.StandardCheckpointHandler())


def save_sharded(dirname: str, tree: Dict[str, Any], async_save: bool = False):
    """Save a (possibly sharded) pytree via orbax. With async_save the
    call returns immediately after on-device arrays are snapshotted;
    call wait_for_checkpoints() (or save again) before reading the dir."""
    import orbax.checkpoint  # noqa: F401  (fail loudly if unavailable)

    wait_for_checkpoints()   # an in-flight async save may still own the dir
    path = os.path.abspath(dirname)
    if os.path.exists(path):
        import shutil
        shutil.rmtree(path)
    ckptr = _orbax_checkpointer(async_save)
    ckptr.save(path, tree)
    return ckptr


def load_sharded(dirname: str, target: Optional[Dict[str, Any]] = None):
    """Restore an orbax checkpoint. ``target`` (a pytree of arrays or
    ShapeDtypeStructs, optionally with shardings) directs dtypes/
    placement — pass the current scope to restore directly into the
    live mesh layout (checkpoint-across-mesh-reshape, io.py:881
    _load_slice_up_vars analog)."""
    import orbax.checkpoint as ocp

    wait_for_checkpoints()   # an in-flight async save may still own the dir
    ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())
    path = os.path.abspath(dirname)
    if target is None:
        return ckptr.restore(path)
    abstract = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=getattr(v, "sharding", None))
        if hasattr(v, "shape") else v, target)
    return ckptr.restore(path, args=ocp.args.StandardRestore(abstract))


def wait_for_checkpoints():
    """Block until all async checkpoint writes finished (barrier before
    reading a checkpoint dir or exiting)."""
    if _async_checkpointer is not None:
        _async_checkpointer.wait_until_finished()


def save_trainer_sharded(dirname: str, trainer, async_save: bool = True):
    """Orbax-backed Trainer checkpoint (async by default): params, state,
    opt_state, step — each host writing its own shards."""
    # logical layer order on disk (matches save_trainer): the device-
    # side de-permute is one gather per stacked leaf per checkpoint —
    # noise next to the write itself
    params, opt_state = trainer.stacked_to_logical(
        trainer.scope.params, trainer.scope.opt_state or {})
    tree = {
        "params": params,
        "state": trainer.scope.state,
        "opt_state": opt_state,
        "meta": {"global_step": trainer.global_step},
    }
    ls = getattr(trainer.scope, "loss_scale_state", None)
    if ls:
        tree["loss_scale_state"] = ls
    return save_sharded(dirname, tree, async_save=async_save)


def load_trainer_sharded(dirname: str, trainer) -> None:
    """Restore from save_trainer_sharded into the trainer's current
    mesh/sharding layout (works across mesh reshapes)."""
    wait_for_checkpoints()
    target = {
        "params": trainer.scope.params,
        "state": trainer.scope.state,
        "opt_state": trainer.scope.opt_state or {},
        "meta": {"global_step": 0},
    }
    # key the optional loss-scaler entry off the CHECKPOINT's contents —
    # a structure mismatch with the target makes orbax raise
    import orbax.checkpoint as ocp
    meta_tree = ocp.Checkpointer(ocp.StandardCheckpointHandler()).metadata(
        os.path.abspath(dirname))
    saved_keys = set(getattr(meta_tree, "item_metadata", meta_tree) or {})
    if "loss_scale_state" in saved_keys:
        ls = getattr(trainer.scope, "loss_scale_state", None)
        target["loss_scale_state"] = ls or {"scale": jnp.float32(0),
                                            "good_steps": jnp.int32(0),
                                            "overflows": jnp.int32(0)}
    restored = load_sharded(dirname, target=target)
    params, opt_state = trainer.stacked_from_logical(
        restored["params"], restored["opt_state"])
    trainer.scope.params = params
    trainer.scope.state = restored["state"]
    trainer.scope.opt_state = opt_state or None
    trainer.global_step = int(restored["meta"]["global_step"])
    # only adopt scaler state if this trainer actually runs a scaler —
    # step() donates the buffer and only a scaler refreshes it, so a
    # scaler-less trainer holding it would pass deleted arrays on step 2
    if "loss_scale_state" in restored and trainer.loss_scaler is not None:
        trainer.scope.loss_scale_state = restored["loss_scale_state"]
