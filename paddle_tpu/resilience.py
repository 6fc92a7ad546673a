"""Fault-tolerant training runtime: checkpoint manifests, resume
scanning, preemption handling, and the NaN/Inf guard policy.

The reference framework's fault-tolerance story lives in the Go
master/pserver (lease-timeout requeue in go/master/service.go, pserver
checkpoints in go/pserver/service.go). The *queue* side is reproduced in
``data.master``; this module supplies the *trainer* side so a worker
survives preemptions, torn checkpoints, and bad batches without human
intervention:

- **Manifests** (:func:`write_manifest` / :func:`validate_checkpoint`):
  every ``io.save_trainer`` checkpoint carries ``manifest.json`` with a
  format version, ``global_step``, per-file CRC32 checksums + sizes, and
  the flat shape/dtype spec of every array collection. Validation turns
  "a random npz error three frames deep" into a structured
  :class:`CheckpointCorrupt`.
- **Atomic commit protocol** (implemented in ``io.save_trainer``): files
  are written to a ``<dir>.tmp.<pid>`` sibling, fsynced, manifested, and
  renamed into place — a ``kill -9`` at ANY point leaves either the old
  checkpoint or the new one, never a half-written directory that
  ``load_trainer`` trusts. Scanners ignore ``*.tmp.*`` leftovers.
- **Resume scanning** (:func:`list_checkpoints` /
  :func:`restore_latest`): find the newest checkpoint that actually
  validates, falling back over corrupt ones — the restart half of the
  ``test_fault_tolerance_e2e`` contract, available to every
  ``fit(resume=True)`` caller instead of hand-rolled workers.
- **Elastic resharding** (:func:`reshard_restore` +
  :class:`ReshardError`): restore a checkpoint onto a trainer whose
  mesh DIFFERS from the saved ``meta.mesh_axes`` (dp N→M in either
  direction) with bit-exact model state — arrays are stored unsharded,
  so the reshard is a re-placement per the TARGET ``ShardingRules``
  (the exact normalization training placement uses). Feasibility is
  proven by the same static checker ``analysis.contracts`` runs in CI
  (``ckpt:mesh-reshard`` / ``ckpt:reshard-infeasible``), so the
  runtime error carries the static verdict's reason text verbatim.
  ``fit(resume=True, elastic=True)`` rides through a worker-count
  change this way instead of dying in ``device_put``.
- **Preemption** (:class:`PreemptionHandler`): SIGTERM/SIGINT (the TPU
  maintenance-event analog) sets a flag; ``fit`` checkpoints at the next
  chunk boundary, drains async orbax saves, and exits cleanly.
- **NaN/Inf guard** (:class:`GuardPolicy` + :class:`Incident`): policy
  and incident records for the Trainer's fused on-device guard — a
  non-finite step is discarded (params/opt_state restored from the
  on-device last-good snapshot, branchlessly inside the compiled step),
  recorded, and training continues; repeated incidents escalate to
  ``FloatingPointError``.
- **Deterministic fault injection** (:func:`crash_point` +
  ``testing.faults``): named crash points in the save path let tests
  kill a save at an exact phase without subprocess roulette.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import signal
import threading
import time
import zlib
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .core.errors import EnforceError

MANIFEST_NAME = "manifest.json"
MANIFEST_VERSION = 1
TMP_MARKER = ".tmp."  # uncommitted checkpoint dirs carry this in their name


def _log():
    return logging.getLogger("paddle_tpu.resilience")


class CheckpointCorrupt(EnforceError):
    """A checkpoint directory failed validation (torn write, truncated
    or bit-flipped file, missing member, unreadable manifest). Carries
    ``path`` and ``reason`` so callers can fall back programmatically."""

    def __init__(self, path: str, reason: str):
        super().__init__(f"corrupt checkpoint at {path}: {reason}")
        self.path = path
        self.reason = reason


class ReshardError(EnforceError):
    """A checkpoint restore implies a mesh reshard that was either not
    requested (``load_trainer`` without ``allow_reshard`` on a
    ``meta.mesh_axes`` mismatch) or is not expressible (the batch
    cannot divide the target data-shard product — the same verdict
    ``analysis.contracts`` reports statically as
    ``ckpt:reshard-infeasible``, whose finding text rides here as
    ``reason``). Distinct from :class:`CheckpointCorrupt` on purpose:
    the checkpoint is FINE — falling back to an older one would
    silently discard training progress, so resume scanning re-raises
    instead of skipping."""

    def __init__(self, path: str, saved_axes, target_axes, reason: str):
        super().__init__(f"cannot restore {path}: {reason}")
        self.path = path
        self.saved_axes = dict(saved_axes) if saved_axes else None
        self.target_axes = dict(target_axes) if target_axes else None
        self.reason = reason


# -- fault injection hooks ---------------------------------------------------
# The save/reshard paths call crash_point(tag) at each phase boundary;
# both registries are empty in production (one membership test per
# checkpoint/resize, not per step). testing.faults arms tags to simulate
# kill -9 at exact phases (crash_points -> raise InjectedCrash) or to run
# a side effect at the phase without dying (crash_callbacks — e.g. kill a
# pserver PROCESS mid-shard-split, testing.faults.acting).

crash_points: set = set()
crash_callbacks: Dict[str, Any] = {}


class InjectedCrash(BaseException):
    """Raised by an armed crash point. Derives from BaseException so
    ordinary ``except Exception`` recovery code cannot swallow it — the
    point is to model abrupt process death."""


def crash_point(tag: str) -> None:
    if crash_callbacks:
        cb = crash_callbacks.get(tag)
        if cb is not None:
            cb()
    if crash_points and tag in crash_points:
        raise InjectedCrash(tag)


# -- manifest ----------------------------------------------------------------


def _crc32_file(path: str, chunk: int = 1 << 20) -> Tuple[int, int]:
    crc, size = 0, 0
    with open(path, "rb") as f:
        while True:
            b = f.read(chunk)
            if not b:
                return crc & 0xFFFFFFFF, size
            crc = zlib.crc32(b, crc)
            size += len(b)


def write_manifest(dirname: str, meta: Optional[Dict[str, Any]] = None,
                   arrays: Optional[Dict[str, Dict[str, Any]]] = None) -> Dict[str, Any]:
    """Write ``manifest.json`` covering every regular file already in
    ``dirname``: format version, per-file CRC32 + size, the checkpoint
    ``meta`` (``global_step`` etc.), and the flat shape/dtype spec of
    each array collection (``arrays`` maps npz filename → {flat key:
    {"shape": [...], "dtype": "..."}}). The manifest is written LAST so
    its presence implies the files it describes were fully written."""
    files = {}
    for name in sorted(os.listdir(dirname)):
        p = os.path.join(dirname, name)
        if not os.path.isfile(p) or name == MANIFEST_NAME:
            continue
        crc, size = _crc32_file(p)
        files[name] = {"crc32": crc, "size": size}
    man = {"format_version": MANIFEST_VERSION,
           "global_step": int((meta or {}).get("global_step", 0)),
           "meta": meta or {},
           "files": files,
           "arrays": arrays or {}}
    tmp = os.path.join(dirname, MANIFEST_NAME + ".part")
    with open(tmp, "w") as f:
        json.dump(man, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(dirname, MANIFEST_NAME))
    return man


def read_manifest(dirname: str) -> Optional[Dict[str, Any]]:
    """Parse a checkpoint/artifact directory's ``manifest.json`` WITHOUT
    the CRC pass — the static metadata surface the cross-artifact
    verifier (``analysis.contracts``) reasons over: the flat shape/dtype
    spec (``manifest["arrays"]``), the checkpoint meta (global_step,
    loss_scale_state, mesh_axes), and the per-file size table.

    Returns ``None`` for a legacy (pre-manifest) directory; raises
    :class:`CheckpointCorrupt` for a missing/unreadable/wrong-version
    manifest — the same classification :func:`validate_checkpoint`
    makes, minus the streaming checksum read (which only a real restore
    should pay; a bit-flipped *payload* is invisible here by design,
    but a bit-flipped manifest is caught)."""
    if not os.path.isdir(dirname):
        raise CheckpointCorrupt(dirname, "not a directory")
    mpath = os.path.join(dirname, MANIFEST_NAME)
    if not os.path.exists(mpath):
        return None
    try:
        with open(mpath) as f:
            man = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorrupt(dirname, f"unreadable manifest: {e}") from e
    ver = man.get("format_version")
    if not isinstance(ver, int) or ver > MANIFEST_VERSION:
        raise CheckpointCorrupt(
            dirname, f"manifest format_version {ver!r} not supported "
            f"(this build reads <= {MANIFEST_VERSION})")
    return man


def validate_checkpoint(dirname: str) -> Optional[Dict[str, Any]]:
    """Verify a checkpoint directory against its manifest.

    Returns the parsed manifest on success, ``None`` for a legacy
    (pre-manifest) directory, and raises :class:`CheckpointCorrupt` on
    any mismatch: unreadable/wrong-version manifest, missing files,
    size or checksum mismatches.

    Cost: one streaming pass over every file — a restore therefore
    reads the checkpoint twice (CRC pass, then the actual load). That
    is the deliberate trade: size/parse checks alone cannot catch
    silent bit flips, and the whole point of validation is never
    handing a bitrotted parameter tensor to a resumed run."""
    man = read_manifest(dirname)
    if man is None:
        return None  # legacy checkpoint: caller decides how much to trust
    for name, spec in (man.get("files") or {}).items():
        p = os.path.join(dirname, name)
        if not os.path.isfile(p):
            raise CheckpointCorrupt(dirname, f"missing file {name!r}")
        crc, size = _crc32_file(p)
        if size != spec.get("size"):
            raise CheckpointCorrupt(
                dirname, f"{name!r} truncated/grown: {size} bytes on disk "
                f"vs {spec.get('size')} in manifest")
        if crc != spec.get("crc32"):
            raise CheckpointCorrupt(
                dirname, f"{name!r} checksum mismatch: crc32 {crc:#010x} "
                f"on disk vs {spec.get('crc32'):#010x} in manifest")
    if ((man.get("meta") or {}).get("zero")):
        # shard-aware checkpoints are all-or-nothing: a shard file on
        # disk that the manifest does not cover is a leftover from a
        # DIFFERENT checkpoint generation (partial overwrite, manual
        # copy) — loading it would stitch a Frankenstein mix of two
        # saves, so the whole directory is treated as corrupt and the
        # restore scanner falls back to the previous checkpoint as a
        # unit (torn shards are already caught by the CRC pass above)
        covered = set(man.get("files") or {})
        stray = sorted(name for name in os.listdir(dirname)
                       if ".zero" in name and name.endswith(".npz")
                       and os.path.isfile(os.path.join(dirname, name))
                       and name not in covered)
        if stray:
            raise CheckpointCorrupt(
                dirname, f"shard files {stray[:3]} on disk are not in the "
                "manifest — a mix of two checkpoint generations; refusing "
                "to restore any of it")
    return man


# -- append-only segment log helpers -----------------------------------------
# The telemetry series store (telemetry/store.py) persists through
# segmented append-only logs: every record is CRC-framed so a torn or
# bit-flipped record is detected and SKIPPED (never crashes recovery),
# and a finished segment is committed with an atomically-written CRC
# sidecar — the same tmp+fsync+replace discipline write_manifest uses
# for checkpoints. The framing/sealing primitives live HERE so
# durability stays one discipline: anything that must survive kill -9
# goes through resilience, whether it is a parameter tensor or a
# telemetry sample.

SEGMENT_META_SUFFIX = ".meta.json"


def frame_record(payload: bytes) -> bytes:
    """CRC-frame one record for an append-only segment log: one text
    line ``<crc32:08x> <len> <payload>\\n``. The payload must not
    contain raw newlines (JSON without indent qualifies) — framing is
    line-based so a reader can resync after a corrupt record."""
    if b"\n" in payload:
        raise ValueError("segment record payload must be newline-free")
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    return b"%08x %d " % (crc, len(payload)) + payload + b"\n"


def iter_records(path: str) -> Iterator[Tuple[bool, Any]]:
    """Stream a segment file's records: yields ``(True, payload_bytes)``
    for every intact record and ``(False, reason)`` for every line that
    fails its frame (bad header, length mismatch, CRC mismatch, torn
    tail with no newline). Corruption never raises — the caller counts
    and skips, recovery continues on the next line."""
    with open(path, "rb") as f:
        for raw in f:
            if not raw.endswith(b"\n"):
                yield False, "torn tail (no trailing newline)"
                continue
            line = raw[:-1]
            head = line.split(b" ", 2)
            if len(head) != 3:
                yield False, f"malformed record header ({line[:32]!r}...)"
                continue
            crc_s, len_s, payload = head
            try:
                want_crc = int(crc_s, 16)
                want_len = int(len_s)
            except ValueError:
                yield False, f"malformed record header ({line[:32]!r}...)"
                continue
            if len(payload) != want_len:
                yield False, (f"record length mismatch ({len(payload)} "
                              f"bytes vs {want_len} declared)")
                continue
            if zlib.crc32(payload) & 0xFFFFFFFF != want_crc:
                yield False, "record CRC mismatch (bit flip)"
                continue
            yield True, payload


def seal_segment(path: str, meta: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
    """Commit a finished segment: fsync the data file, then atomically
    write ``<path>.meta.json`` carrying the whole-file CRC32 + size
    (plus caller ``meta`` — first/last timestamps, record count). The
    sidecar is written tmp+fsync+replace (the write_manifest
    discipline), so its presence implies the segment it describes was
    fully written; a segment without a sidecar is either active or a
    kill artifact and is recovered record-by-record instead."""
    with open(path, "rb") as f:
        os.fsync(f.fileno())
    crc, size = _crc32_file(path)
    doc = dict(meta or {})
    doc.update({"crc32": crc, "size": size,
                "format_version": MANIFEST_VERSION})
    tmp = path + SEGMENT_META_SUFFIX + ".part"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path + SEGMENT_META_SUFFIX)
    return doc


def check_segment(path: str) -> Tuple[bool, str]:
    """Validate a SEALED segment against its sidecar: ``(True, "")``
    when size and whole-file CRC match, else ``(False, reason)``. A
    missing/unreadable sidecar is a finding too — sealed segments are
    committed WITH one."""
    mpath = path + SEGMENT_META_SUFFIX
    try:
        with open(mpath) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        return False, f"unreadable segment sidecar {mpath}: {e}"
    try:
        crc, size = _crc32_file(path)
    except OSError as e:
        return False, f"unreadable segment {path}: {e}"
    if size != meta.get("size"):
        return False, (f"segment truncated/grown: {size} bytes on disk vs "
                       f"{meta.get('size')} in sidecar")
    if crc != meta.get("crc32"):
        return False, (f"segment checksum mismatch: crc32 {crc:#010x} on "
                       f"disk vs {meta.get('crc32'):#010x} in sidecar")
    return True, ""


# -- checkpoint-directory scanning ------------------------------------------


@dataclasses.dataclass
class CheckpointInfo:
    path: str
    tag: str                      # directory basename (epoch_N / step_N)
    global_step: int              # from manifest (or legacy meta.json); -1 unknown
    mtime: float

    @property
    def sort_key(self):
        return (self.global_step, self.mtime, self.tag)


def _read_step(path: str) -> int:
    for name in (MANIFEST_NAME, "meta.json"):
        p = os.path.join(path, name)
        try:
            with open(p) as f:
                return int(json.load(f).get("global_step", -1))
        except (OSError, ValueError, TypeError):
            continue
    return -1


def list_checkpoints(root: str) -> List[CheckpointInfo]:
    """Scan ``root`` for committed checkpoint directories, OLDEST first
    (ascending ``global_step``, mtime tiebreak). Uncommitted ``*.tmp.*``
    leftovers from killed saves are ignored; validation is NOT performed
    here (see :func:`restore_latest`)."""
    out: List[CheckpointInfo] = []
    if not os.path.isdir(root):
        return out
    for name in os.listdir(root):
        if TMP_MARKER in name:
            continue
        p = os.path.join(root, name)
        if not os.path.isdir(p):
            continue
        has_payload = any(
            os.path.exists(os.path.join(p, f))
            for f in (MANIFEST_NAME, "meta.json", "params.npz"))
        if not has_payload:
            continue
        out.append(CheckpointInfo(path=p, tag=name,
                                  global_step=_read_step(p),
                                  mtime=os.path.getmtime(p)))
    out.sort(key=lambda c: c.sort_key)
    return out


def sweep_tmp_dirs(root: str, tag: Optional[str] = None) -> List[str]:
    """Remove uncommitted ``*.tmp.*`` checkpoint leftovers under
    ``root`` — torn saves from crashed/preempted processes would
    otherwise accumulate a full checkpoint's worth of disk each.
    ``tag`` restricts the sweep to one checkpoint tag's leftovers
    (``<tag>.tmp.*`` — what ``save_trainer`` clears before rewriting
    that tag); without it the whole dir is swept (fit startup).
    Single-writer assumption (one training process owns a checkpoint
    dir, as fit does): a live concurrent writer's tmp dir would be
    swept too, and its commit rename then fails loudly."""
    import shutil

    removed = []
    if not os.path.isdir(root):
        return removed
    prefix = f"{tag}{TMP_MARKER}" if tag is not None else None
    for name in os.listdir(root):
        if TMP_MARKER not in name:
            continue
        if prefix is not None and not name.startswith(prefix):
            continue
        p = os.path.join(root, name)
        if os.path.isdir(p):
            shutil.rmtree(p, ignore_errors=True)
            removed.append(p)
    if removed:
        _log().info("swept %d stale tmp checkpoint dir(s) under %s",
                    len(removed), root)
    return removed


def restore_latest(root: str, trainer, elastic: bool = False,
                   sample_feed: Optional[Dict[str, Any]] = None
                   ) -> Optional[Dict[str, Any]]:
    """Restore ``trainer`` from the newest checkpoint under ``root``
    that validates and loads, falling back over corrupt ones (warning
    each). Returns the checkpoint's meta dict, or ``None`` when no
    restorable checkpoint exists.

    A checkpoint saved at DIFFERENT mesh axes than the trainer's is not
    corruption: without ``elastic`` the structured
    :class:`ReshardError` propagates (falling back to an older
    checkpoint would silently discard progress — all checkpoints of a
    run share its mesh); with ``elastic=True`` the restore routes
    through :func:`reshard_restore`, which proves feasibility with the
    static checker and re-places every array per the trainer's target
    rules — the ``fit(resume=True, elastic=True)`` path."""
    from . import io as _io

    for info in reversed(list_checkpoints(root)):
        try:
            try:
                _io.load_trainer(info.path, trainer)
            except ReshardError as re_err:
                if not elastic:
                    _flight_reshard(re_err)
                    raise
                rep = reshard_restore(info.path, trainer,
                                      sample_feed=sample_feed)
                _log().info(
                    "elastic resume: resharded %s from mesh %s onto %s "
                    "(%d bytes re-placed in %.3fs)", info.path,
                    rep["saved_axes"], rep["target_axes"],
                    rep["bytes_moved"], rep["seconds"])
        except CheckpointCorrupt as e:
            _log().warning("skipping corrupt checkpoint %s (%s); "
                           "falling back to an older one", info.path, e.reason)
            continue
        meta = dict(getattr(trainer, "_last_loaded_meta", None) or {})
        meta.setdefault("global_step", trainer.global_step)
        _log().info("resumed from %s at global_step=%d", info.path,
                    trainer.global_step)
        return meta
    return None


# -- elastic resharding -------------------------------------------------------


def _flight_reshard(err: "ReshardError") -> None:
    """Journal + flight-dump a ReshardError about to unwind: a run
    refusing to come back up is exactly when an operator needs the
    black box (what the run restored from, what mesh it wanted)."""
    from .telemetry import flight_dump, get_journal

    get_journal().emit("ckpt.reshard_error", path=err.path,
                       saved_axes=err.saved_axes,
                       target_axes=err.target_axes,
                       reason=str(err.reason)[:500])
    flight_dump("reshard_error",
                detail={"path": err.path, "saved_axes": err.saved_axes,
                        "target_axes": err.target_axes,
                        "reason": str(err.reason)[:500]})


def normalize_mesh_axes(axes: Optional[Dict[str, Any]]) -> Dict[str, int]:
    """Canonical ``{axis: size}`` with size-1 axes dropped: a
    ``{"dp": 1}`` mesh and no mesh at all place arrays identically, so
    they must compare equal for the reshard gate."""
    return {str(k): int(v) for k, v in (axes or {}).items() if int(v) > 1}


def mesh_axes(mesh) -> Optional[Dict[str, int]]:
    """The ``meta.mesh_axes`` encoding of a ``jax.sharding.Mesh``
    (``None`` for no mesh). THE single encoder: ``io.save_trainer``
    records it, the ``load_trainer`` gate and the static reshard
    verdicts (``analysis.contracts``) compare against it — one
    implementation, so the save side and every check side can never
    drift."""
    if mesh is None:
        return None
    return {str(a): int(mesh.shape[a]) for a in mesh.axis_names}


def trainer_mesh_axes(trainer) -> Optional[Dict[str, int]]:
    """:func:`mesh_axes` of the trainer's mesh (``None`` for a
    single-device trainer)."""
    return mesh_axes(getattr(trainer, "mesh", None))


def reshard_restore(checkpoint_dir: str, trainer,
                    sample_feed: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Restore a checkpoint onto a trainer whose mesh DIFFERS from the
    saved ``meta.mesh_axes`` — the elastic-resharding door (dp N→M in
    either direction, single-device included).

    Checkpoint arrays are stored unsharded (fully gathered), so the
    redistribution is a re-placement per the TARGET trainer's
    ``ShardingRules`` — the restore goes through the exact
    ``parallel.api.shard_scope`` normalization training placement uses,
    so the resharded layout can never drift from what ``startup`` would
    build. Model state is bit-exact: same params/opt_state/mutable
    state/loss-scale state/rng-step meta as a same-mesh restore.

    Feasibility is proven FIRST with the static contract checker
    (``analysis.contracts.check_artifacts``) so the runtime and CI can
    never disagree: a pair the checker calls ``ckpt:reshard-infeasible``
    raises :class:`ReshardError` carrying that finding's text verbatim,
    BEFORE any trainer state is touched; a ``ckpt:mesh-reshard``
    (expressible) pair restores. ``sample_feed`` supplies the per-step
    batch for the divisibility half of the check — without it, batch
    feasibility is unchecked (mirroring the static verdict's wording)
    and an indivisible batch surfaces at the first ``put_batch``.

    Returns a report dict: ``saved_axes``/``target_axes``,
    ``global_step``, ``bytes_moved`` (checkpoint bytes re-placed) and
    ``seconds`` (restore wall time)."""
    from . import io as _io
    from .analysis import contracts as _contracts

    t0 = time.perf_counter()
    man = read_manifest(checkpoint_dir)  # CheckpointCorrupt if unreadable
    saved_axes = ((man or {}).get("meta") or {}).get("mesh_axes")
    target_axes = trainer_mesh_axes(trainer)
    report = _contracts.check_artifacts(
        trainer=trainer, checkpoint_dir=checkpoint_dir,
        sample_feed=sample_feed)
    infeasible = report.by_code("ckpt:reshard-infeasible")
    if infeasible:
        err = ReshardError(checkpoint_dir, saved_axes, target_axes,
                           infeasible[0].message)
        _flight_reshard(err)
        raise err
    _io.load_trainer(checkpoint_dir, trainer, allow_reshard=True)
    # the HBM dataset cache holds arrays laid out for the OLD mesh —
    # an elastic rejoin must drop them or epoch 2 would feed stale
    # shardings into the rebuilt step
    dc = getattr(trainer, "device_cache", None)
    if dc is not None:
        dc.invalidate("reshard_restore")
    from .telemetry import get_registry
    get_registry().counter(
        "paddle_tpu_resilience_reshards_total",
        "Elastic checkpoint restores onto a different mesh").inc()
    bytes_moved = sum(int(spec.get("size", 0))
                      for spec in ((man or {}).get("files") or {}).values())
    return {
        "meta": dict(getattr(trainer, "_last_loaded_meta", None) or {}),
        "saved_axes": dict(saved_axes) if saved_axes else None,
        "target_axes": dict(target_axes) if target_axes else None,
        "global_step": trainer.global_step,
        "bytes_moved": bytes_moved,
        "seconds": time.perf_counter() - t0,
    }


# -- preemption --------------------------------------------------------------


class PreemptionHandler:
    """SIGTERM/SIGINT → "checkpoint at the next chunk boundary and exit
    cleanly" (the TPU maintenance-event analog; the reference analog is
    the pserver checkpointing before the master requeues its lease).

    Use as a context manager; ``requested`` flips on the first signal.
    A SECOND signal of the same kind restores the previous handler and
    re-raises it, so a stuck run can still be killed interactively.
    Signal handlers only install in the main thread; elsewhere the
    handler degrades to an inert flag (``installed`` is False)."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, signals=None):
        self.signals = tuple(signals) if signals is not None else self.SIGNALS
        self._flag = threading.Event()
        self._old: Dict[int, Any] = {}
        self._callbacks: List[Any] = []
        self.installed = False
        self.signum: Optional[int] = None

    @property
    def requested(self) -> bool:
        return self._flag.is_set()

    def on_signal(self, callback) -> "PreemptionHandler":
        """Register ``callback()`` to run on the FIRST signal, right
        after the flag flips — lets a long-blocking consumer (e.g. a
        ``serving.PredictorServer`` starting its drain) react
        immediately instead of at its next flag poll. Callbacks run in
        signal-handler context: keep them to flag flips and
        non-blocking kicks; exceptions are swallowed (a crashing
        callback must not turn a clean preemption into an abort)."""
        self._callbacks.append(callback)
        return self

    def _handle(self, signum, frame):
        if self._flag.is_set():
            # second signal: the user really means it — restore the old
            # handler and re-deliver so default/previous semantics apply.
            # A non-Python-installed previous handler reads back as None
            # (signal.signal rejects it): fall back to SIG_DFL so the
            # escape hatch still kills the process.
            old = self._old.get(signum) or signal.SIG_DFL
            try:
                signal.signal(signum, old)
            except (ValueError, TypeError):
                signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        self.signum = signum
        self._flag.set()
        for cb in self._callbacks:
            try:
                cb()
            except Exception:
                pass
        _log().warning(
            "received %s: checkpointing at the next chunk boundary, then "
            "exiting (signal again to abort immediately)",
            signal.Signals(signum).name)

    def __enter__(self) -> "PreemptionHandler":
        if threading.current_thread() is threading.main_thread():
            for s in self.signals:
                self._old[s] = signal.signal(s, self._handle)
            self.installed = True
        return self

    def __exit__(self, *exc):
        if self.installed:
            for s, old in self._old.items():
                try:
                    signal.signal(s, old)
                except (ValueError, TypeError):
                    pass
            self._old.clear()
            self.installed = False
        return False


# -- scheduled elastic resize ------------------------------------------------


class ResizeRequest:
    """Scheduled ``fit(elastic=True)`` grow/shrink: the autoscaler's
    trainer-side analog. Where :class:`PreemptionHandler` reacts to a
    SIGTERM nobody planned, a ResizeRequest watches a request FILE an
    operator (or the autoscaler) drops next to the run::

        with ResizeRequest("/run/resize.json") as rz:
            fit(trainer, ..., elastic=True, resize=rz)

        # elsewhere: echo '{"dp": 4}' > /run/resize.json

    ``fit(resize=...)`` polls :attr:`requested` at the same chunk
    boundary it polls preemption: when the file appears (or the
    optional ``signal_num`` arrives — e.g. SIGUSR1), the run
    checkpoints at the boundary and returns cleanly with
    ``fit.resized`` journaled, so the launcher can relaunch at the new
    size and ``fit(elastic=True, resume=True)`` reshards the
    checkpoint onto the new mesh (:func:`reshard_restore`). The file's
    JSON body (:attr:`target`, e.g. ``{"dp": 4}``) is advisory — the
    relaunch decides the actual mesh; an empty or unparsable file
    reads as ``{}`` (a bare "resize now" kick).

    ``consume()`` removes the file and clears the flag — the launcher
    calls it after acting so a stale request can't re-trigger on the
    next run. Like PreemptionHandler, the signal handler installs only
    in the main thread and degrades to an inert flag elsewhere; the
    file watch works from any thread."""

    def __init__(self, path: str, signal_num: Optional[int] = None):
        self.path = path
        self.signal_num = signal_num
        self._flag = threading.Event()
        self._old: Any = None
        self.installed = False

    @property
    def requested(self) -> bool:
        return self._flag.is_set() or os.path.exists(self.path)

    @property
    def target(self) -> Dict[str, Any]:
        """The request body (``{}`` when absent/empty/unparsable)."""
        try:
            with open(self.path) as f:
                body = f.read().strip()
            doc = json.loads(body) if body else {}
            return doc if isinstance(doc, dict) else {}
        except (OSError, ValueError):
            return {}

    def request(self, target: Optional[Dict[str, Any]] = None) -> None:
        """Drop the request file (what an in-process scheduler calls;
        operators just write the file)."""
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(dict(target or {}), f)
        os.replace(tmp, self.path)

    def consume(self) -> Dict[str, Any]:
        """Read-and-clear: returns the target, removes the file,
        resets the flag — the next run starts unrequested."""
        target = self.target
        try:
            os.remove(self.path)
        except OSError:
            pass
        self._flag.clear()
        return target

    def _handle(self, signum, frame):
        self._flag.set()
        _log().warning(
            "received %s: elastic resize requested — checkpointing at "
            "the next chunk boundary", signal.Signals(signum).name)

    def __enter__(self) -> "ResizeRequest":
        if self.signal_num is not None and \
                threading.current_thread() is threading.main_thread():
            self._old = signal.signal(self.signal_num, self._handle)
            self.installed = True
        return self

    def __exit__(self, *exc):
        if self.installed:
            try:
                signal.signal(self.signal_num, self._old)
            except (ValueError, TypeError):
                pass
            self.installed = False
        return False


# -- NaN/Inf guard policy ----------------------------------------------------


@dataclasses.dataclass
class GuardPolicy:
    """Graceful-degradation policy for non-finite training steps
    (``Trainer(guard=GuardPolicy(...))``).

    The detection itself is a single fused on-device ``all(isfinite)``
    reduction over the gradients and every float fetch output, computed
    INSIDE the compiled step and returned as one extra scalar bitmask in
    the fetch dict — no per-leaf host sync (the old
    ``FLAGS_check_nan_inf`` scan dispatched one blocking reduction per
    leaf from Python). On a non-finite step the update is discarded
    branchlessly (params/opt_state/state keep their pre-step values —
    the on-device last-good snapshot is the step's own donated carry),
    an :class:`Incident` is recorded host-side, and training continues.
    The host readback is deferred by one dispatch (examined while the
    next chunk runs; ``Trainer.drain_guard()`` flushes it, ``fit`` does
    so automatically), so incident records and escalation trail the
    device by at most one chunk while the hot path keeps ZERO added
    synchronization.

    ``max_incidents``/``window``: when MORE than ``max_incidents``
    incidents land within the trailing ``window`` optimizer steps, the
    guard escalates to ``FloatingPointError`` (``max_incidents=0``
    raises on the first incident — the FLAGS_check_nan_inf abort
    semantic, minus the per-leaf syncs). Dynamic loss-scale state is
    NOT rolled back on a guarded step: the scaler's overflow backoff
    must persist or the same overflow recurs forever."""

    max_incidents: int = 8
    window: int = 1000          # in optimizer steps
    # feed digests require holding the previous dispatch's device feed
    # until its bitmask is examined: one extra (super-)batch of HBM
    # resident on every guarded step. Set False for memory-tight runs —
    # incidents then record step + outputs but no batch fingerprint.
    record_feed_digest: bool = True
    # deferred readback (the default) examines the bitmask one dispatch
    # late so the hot path adds no sync; False reads it back immediately
    # after every dispatch — escalation then raises AT the offending
    # step, at the cost of one blocking scalar fetch per dispatch (the
    # check_nan_inf flag route uses this to keep its abort contract for
    # hand-rolled step() loops that never call drain_guard())
    defer_readback: bool = True


@dataclasses.dataclass
class Incident:
    """One discarded non-finite step, recorded by the guard."""

    step: int                   # global_step of the discarded update
    outputs: Tuple[str, ...]    # which checked values were non-finite
    feed_digest: Optional[str]  # crc32 of the offending host batch (or None)
    wall_time: float

    def __str__(self):
        return (f"non-finite step {self.step}: {', '.join(self.outputs)}"
                + (f" (feed crc32 {self.feed_digest})" if self.feed_digest
                   else ""))


def feed_digest(feed: Dict[str, Any], index: Optional[int] = None) -> str:
    """crc32 digest of a feed dict (one batch). ``index`` selects step
    ``i`` of a stacked ``(K, batch, ...)`` super-batch. Only called on
    incidents, so the device→host pull is off the hot path."""
    import numpy as np

    crc = 0
    for k in sorted(feed):
        v = np.asarray(feed[k])
        if index is not None and v.ndim >= 1:
            v = v[index]
        crc = zlib.crc32(k.encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(v).tobytes(), crc)
    return f"{crc & 0xFFFFFFFF:#010x}"


def escalate_if_needed(incidents: List[Incident], policy: GuardPolicy,
                       current_step: int) -> None:
    """Raise ``FloatingPointError`` when more than ``policy.max_incidents``
    incidents fall in the trailing ``policy.window`` steps. Scans the
    (step-ordered) list from the tail only — O(window incidents), not
    O(history)."""
    recent: List[Incident] = []
    for inc in reversed(incidents):
        if inc.step <= current_step - policy.window:
            break
        if inc.step <= current_step:
            recent.append(inc)
    if len(recent) > policy.max_incidents:
        lines = "\n  ".join(str(i) for i in recent[:5])
        raise FloatingPointError(
            f"{len(recent)} non-finite steps within the last "
            f"{policy.window} steps (GuardPolicy.max_incidents="
            f"{policy.max_incidents}); last incidents:\n  {lines}")


# a multi-month run with occasional sub-threshold incidents must not
# grow the log without bound; oldest entries beyond this are dropped
# (escalation only ever looks at the trailing window anyway)
MAX_INCIDENT_LOG = 10_000


def record_incident(incidents: List[Incident], step: int,
                    outputs: Tuple[str, ...],
                    digest: Optional[str]) -> Incident:
    inc = Incident(step=step, outputs=outputs, feed_digest=digest,
                   wall_time=time.time())
    incidents.append(inc)
    if len(incidents) > MAX_INCIDENT_LOG:
        del incidents[:len(incidents) - MAX_INCIDENT_LOG]
    _log().warning("guard: discarded %s", inc)
    # journal the incident so a flight dump taken later (escalation,
    # preemption, watchdog) names the non-finite steps that led up
    from .telemetry import get_journal
    get_journal().emit("guard.incident", step=step,
                       outputs=list(outputs), feed_digest=digest)
    return inc


__all__ = [
    "CheckpointCorrupt", "CheckpointInfo", "GuardPolicy", "Incident",
    "InjectedCrash", "PreemptionHandler", "ReshardError", "ResizeRequest",
    "check_segment",
    "crash_point", "crash_points", "feed_digest", "frame_record",
    "iter_records", "list_checkpoints", "mesh_axes",
    "normalize_mesh_axes", "read_manifest", "reshard_restore",
    "restore_latest", "seal_segment", "sweep_tmp_dirs",
    "trainer_mesh_axes", "validate_checkpoint", "write_manifest",
]
