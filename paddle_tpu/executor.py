"""Executor & Trainer — compile-and-run machinery.

Reference analog (SURVEY §3.1): ``fluid.Executor.run(program, feed,
fetch_list)`` interprets a ProgramDesc op-by-op (executor.cc:359), with
feed/fetch ops moving data in/out; ``ParallelExecutor`` schedules an SSA
graph over devices. Here the program is jit-compiled whole by XLA —
the op-loop, data transforms, and fusion passes all collapse into one
compiled executable per (program, shapes) key, cached like the
reference's program cache (executor.py:256 Executor._program_caches).

``Executor`` owns a :class:`Scope` (params/state/opt_state — the
scope.h:41 analog) so the fluid usage pattern maps 1:1:

    exe = pt.Executor()                      # place chosen like InitDevices
    exe.startup(prog, rng, sample_feed)      # startup-program analog
    out = exe.run(prog, feed={...}, fetch_list=['loss'])

``Trainer`` adds the optimizer loop: value_and_grad + optimizer.update
jitted with buffer donation (the eager-deletion/memory-reuse analog —
donation gives XLA the in-place update the reference's GC achieved).
Mesh-parallel execution plugs in through ``mesh``/``sharding_rules``
(see paddle_tpu.parallel) — the ParallelExecutor/BuildStrategy analog.
"""

from __future__ import annotations

import contextlib
import functools
import time as _time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .core import profiler
from .core.config import enable_compile_cache, get_flag, make_prng_key
from .core.errors import enforce
from .core.place import Place, default_place
from .framework import Program

Feed = Dict[str, Any]


class Scope:
    """Name→value runtime store (scope.h:41 analog)."""

    def __init__(self):
        self.params: Dict[str, jax.Array] = {}
        self.state: Dict[str, jax.Array] = {}
        self.opt_state: Optional[Dict[str, Any]] = None
        self.extra: Dict[str, Any] = {}

    def var_names(self) -> List[str]:
        return sorted(self.params) + sorted(self.state)


def _trainer_log():
    import logging
    return logging.getLogger("paddle_tpu.trainer")


def _check_nan_inf(tree, where: str):
    """Host-side per-leaf scan (FLAGS_check_nan_inf analog) — still used
    on the forward/eval path (Executor.run). The TRAIN path uses the
    fused on-device guard instead (Trainer guard / GuardPolicy): one
    scalar bitmask computed inside the compiled step, no per-leaf host
    sync."""
    flat, _ = jax.tree.flatten(tree)
    for leaf in flat:
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if bool(jnp.any(~jnp.isfinite(leaf))):
                raise FloatingPointError(f"NaN/Inf detected in {where} "
                                         "(FLAGS_check_nan_inf analog)")


def _tree_nonfinite(tree) -> jax.Array:
    """Scalar bool: ANY inexact leaf of ``tree`` holds a NaN/Inf.
    Traced inside the compiled step — the per-leaf partial reductions
    fuse into one on-device scalar, the guard's whole detection cost."""
    leaves = [x for x in jax.tree.leaves(tree)
              if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.inexact)]
    if not leaves:
        return jnp.bool_(False)
    return ~jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves]).all()


# module names of the DONATING compiled step programs — the predicate
# both cache-read gates share (this one and tests/conftest.py's)
DONATING_STEP_MODULE_TAGS = ("train_step", "run_k_steps")

_cpu_cache_gate_installed = False


def _install_cpu_cache_read_gate():
    """On the CPU backend, gate persistent-compile-cache READS away from
    DONATING step executables (train_step / run_k_steps): the CPU
    runtime's disk→executable reload can lose donation alias info and a
    fetched output then reads clobbered memory — observed as sporadic
    garbage/NaN losses right after checkpoint saves (see
    tests/conftest.py, which applies the same quarantine for the test
    suite). Forward/eval/infer programs — the bulk of the cache's win —
    keep reading the cache; the step programs recompile once per
    process. TPU/GPU backends are unaffected and skip this entirely."""
    global _cpu_cache_gate_installed
    if _cpu_cache_gate_installed:
        return
    try:
        if jax.default_backend() != "cpu":
            return
        from jax._src import compiler as _jc
        orig = _jc._cache_read

        def gated(module_name, *args, **kw):
            if any(tag in (module_name or "")
                   for tag in DONATING_STEP_MODULE_TAGS):
                return None, None
            return orig(module_name, *args, **kw)

        _jc._cache_read = gated
        _cpu_cache_gate_installed = True
    except Exception as e:
        # private API drifted: the cache stays fully enabled, which on
        # this backend can silently corrupt reloaded donating steps —
        # say so instead of degrading invisibly
        _trainer_log().warning(
            "could not install the CPU cache-read gate for donating step "
            "executables (%s: %s); persistent-cache reloads of "
            "train_step/run_k_steps may corrupt fetched outputs on this "
            "backend — consider disabling compile_cache_dir on CPU",
            type(e).__name__, e)


class Executor:
    """Forward/eval executor with a held scope (executor.py:256 analog)."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or default_place()
        self.scope = Scope()
        self._jit_cache: Dict[Any, Callable] = {}

    # -- startup ------------------------------------------------------------
    def startup(self, program: Program, rng: Optional[jax.Array] = None, *example_args,
                **example_kwargs) -> Scope:
        """Run the startup-program analog: initialize params/state into
        the scope."""
        if rng is None:
            rng = make_prng_key(get_flag("seed"))
        params, state = program.init(rng, *example_args, **example_kwargs)
        dev = self.place.device()
        self.scope.params = jax.device_put(params, dev)
        self.scope.state = jax.device_put(state, dev)
        return self.scope

    # -- run ----------------------------------------------------------------
    def run(
        self,
        program: Program,
        feed: Optional[Feed] = None,
        fetch_list: Optional[Sequence[str]] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        training: bool = False,
        rng: Optional[jax.Array] = None,
        update_state: bool = False,
    ):
        """Run a program forward (Executor.run analog, executor.py:374).

        ``feed`` maps the program fn's argument names to arrays;
        ``fetch_list`` selects keys of the program's dict output (or
        returns the raw output when None).
        """
        scope = scope or self.scope
        feed = feed or {}
        # key on the Program object itself (not id(): a GC'd Program's id
        # can be reused and hit a stale compiled fn); the strong ref lives
        # until close() like the reference's per-executor program cache
        key = (program, training, tuple(sorted(feed)))
        if key not in self._jit_cache:
            def fwd(params, state, rng_, feed_):
                out, new_state = program.apply(params, state, training=training,
                                               rng=rng_, **feed_)
                return out, new_state
            self._jit_cache[key] = jax.jit(fwd)
        dev = self.place.device()
        feed_dev = {k: jax.device_put(np.asarray(v) if not isinstance(v, jax.Array) else v, dev)
                    for k, v in feed.items()}
        with profiler.record_event(f"exe.run/{program.name}"):
            out, new_state = self._jit_cache[key](scope.params, scope.state, rng, feed_dev)
        if get_flag("check_nan_inf"):
            _check_nan_inf(out, f"outputs of {program.name}")
        if update_state:
            scope.state = new_state
        if fetch_list is None:
            return jax.device_get(out) if return_numpy else out
        enforce(isinstance(out, dict),
                "fetch_list requires the program to return a dict of named outputs")
        vals = [out[name] for name in fetch_list]
        return [np.asarray(v) for v in vals] if return_numpy else vals

    def close(self):
        self._jit_cache.clear()


def _register_trainer_telemetry(trainer) -> int:
    """Register the trainer's scrape-time collector in the process
    registry: step/dispatch counters from the StepTimer, feeder stage
    counters from the PipelineMetrics, plus the trainer-level
    global-step gauge and guard-incident counter — all read from the
    structures the trainer already maintains, so the exported series
    cannot disagree with ``profile_report()``/``pipeline_report()``
    and the hot path pays nothing at publication time. The collector
    is weakly bound to the trainer (dropped when it is collected; the
    registry hands the live trainer back at scrape time)."""
    from .telemetry import get_registry
    from .telemetry.registry import counter_family, gauge_family

    def collect(tr):
        inst = tr.telemetry_inst
        labels = {"inst": inst}
        fams = [
            gauge_family("paddle_tpu_trainer_global_step",
                         "Current optimizer global step",
                         [(labels, tr.global_step)]),
            counter_family(
                "paddle_tpu_trainer_guard_incidents_total",
                "Non-finite steps discarded by the NaN/Inf guard",
                [(labels, tr.guard_incident_total)]),
        ]
        fams.extend(tr.step_timer.telemetry_families(inst))
        fams.extend(tr.pipeline_metrics.telemetry_families(inst))
        return fams

    return get_registry().add_collector(collect, owner=trainer)


class Trainer:
    """Jitted train loop: the Executor+optimizer / ParallelExecutor story.

    Single-device by default; pass ``mesh``+``sharding_rules`` (see
    paddle_tpu.parallel) for SPMD execution — params/opt-state sharded by
    rule, batch sharded over the data axes, gradients all-reduced by XLA
    over ICI (the AllReduceOpHandle analog, with zero scheduler code).
    """

    def __init__(
        self,
        program: Program,
        optimizer,
        loss_name: str = "loss",
        place: Optional[Place] = None,
        mesh=None,
        sharding_rules=None,
        strategy=None,
        donate: bool = True,
        fetch_list: Optional[Sequence[str]] = None,
        guard=None,
        feed_wire=None,
        augment=None,
    ):
        self.program = program
        self.optimizer = optimizer
        self.loss_name = loss_name
        self.place = place or default_place()
        self.mesh = mesh
        # adapt preset rule tables to the declared mesh once, up front:
        # axes the mesh doesn't have are dropped silently here (the
        # user's declared intent) instead of tripping the _validate
        # replication warning on every spec lookup. The pre-adaptation
        # table is kept for the lint's sharding audit — typo'd axes are
        # only visible on the raw table (adapted_to strips them).
        self.sharding_rules_raw = sharding_rules
        if sharding_rules is not None and mesh is not None:
            sharding_rules = sharding_rules.adapted_to(mesh)
        self.sharding_rules = sharding_rules
        enforce(not getattr(strategy, "async_mode", False),
                "DistStrategy.async_mode (DistributeTranspiler sync_mode="
                "False) selects barrier-free parameter-server training — "
                "use parallel.AsyncPSTrainer with a parallel.PServerProcess "
                "instead of the SPMD Trainer")
        self.strategy = strategy
        self.donate = donate
        # fetch_list prunes the per-step outputs INSIDE jit (executor.py
        # fetch-op analog) — unfetched outputs (e.g. full logits) are
        # dead-code-eliminated by XLA instead of materialized.
        self.fetch_list = list(fetch_list) if fetch_list is not None else None
        self.scope = Scope()
        self._step_fn = None
        self._multi_step_fn = None
        self._eval_fn = None
        # python executions of the step body == traces (the body only
        # runs at trace time inside jit/scan); tests pin no-retrace
        # guarantees on this counter staying flat
        self._trace_count = 0
        self._traces_registered = 0  # _trace_count at the last registration
        self.global_step = 0
        self.lint_report = None  # set by startup(lint=...)
        # NaN/Inf guard: guard=True -> default GuardPolicy; None ->
        # defer to the check_nan_inf flag at build time (the check is
        # compiled into the step program); False -> explicit opt-out
        # that also overrides the flag; otherwise a GuardPolicy
        from .resilience import GuardPolicy
        self.guard_policy = (GuardPolicy() if guard is True
                             else (None if not guard else guard))
        self._guard_opt_out = guard is False
        self.guard_incidents: List[Any] = []
        self._guard = None            # resolved policy (build time)
        self._guard_bit_names = ()    # bitmask bit -> checked-value name
        self._guard_pending = None    # (mask, feed, base_step, k) to examine
        # feed wire formats (data/wire.py): host-side encode in
        # _put_feed / the DeviceFeeder fill thread, device-side decode
        # traced into the step program (fused — no extra dispatch).
        # augment (data/augment.py): on-device crop/flip/normalize
        # appended to the decode inside the same traced step, per-step
        # randomness off the step rng (fused K == sequential).
        from .data.augment import FeedAugment
        from .data.feeder import PipelineMetrics
        from .data.wire import FeedWire
        from .profiling.steptime import StepTimer
        from .telemetry import get_journal, get_registry
        self.feed_wire = FeedWire.make(feed_wire)
        self.feed_augment = FeedAugment.make(augment)
        # the HBM dataset cache fit(device_cache=...) binds here, so
        # reload/reshard can invalidate it without knowing about fit
        self.device_cache = None
        self.pipeline_metrics = PipelineMetrics()
        # unified telemetry (paddle_tpu.telemetry): every trainer
        # publishes into the process registry through ONE scrape-time
        # collector (zero hot-path cost; the `inst` label keeps two
        # live trainers' series apart) and journals one correlated
        # event per dispatch through the StepTimer
        self.journal = get_journal()
        self.telemetry_inst = get_registry().next_instance("trainer")
        self.guard_incident_total = 0
        self._telemetry_server = None
        # push shipping: with PDTPU_TELEMETRY_ADDR set, this process
        # streams its journal + registry snapshots to the telemetry
        # collector — zero code beyond the env var (ship_to() is the
        # explicit door); never raises into training
        from .telemetry.shipper import maybe_auto_ship
        maybe_auto_ship()
        # per-dispatch wall-time accounting (profiling.steptime):
        # always-on — two clock reads per dispatch, <2% of step time
        # test-pinned — and merged with pipeline_metrics by
        # profile_report()
        self.step_timer = StepTimer(journal=self.journal,
                                    inst=self.telemetry_inst)
        self._fusion_report = None  # cache: fusion_report(feed) result
        # quantized-exchange state (resolved at _build_step): whether
        # the step signature carries the error-feedback residual, and
        # the static bytes-on-wire attribution of the grad exchange
        self._quant_ef = False
        self.collective_bytes = None
        # ZeRO weight-update sharding (strategy.zero_sharding): set by
        # startup to a parallel.zero.ZeroSpec when active; the step's
        # combine/partition hooks, io checkpointing, and the analysis/
        # advisor stack all key off this attribute
        self._zero = None
        self.loss_scaler = None
        if strategy is not None and (getattr(strategy, "loss_scale", None)
                                     or getattr(strategy, "dynamic_loss_scale", False)):
            from .amp import LossScaler
            self.loss_scaler = LossScaler(
                init_scale=strategy.loss_scale or 2.0 ** 15,
                dynamic=strategy.dynamic_loss_scale,
                growth_interval=strategy.loss_scale_growth_interval)
        # registered LAST: a scrape racing a half-constructed trainer
        # (or an __init__ that raises above) must never see a
        # collector whose attributes don't exist yet
        self._telemetry_cid = _register_trainer_telemetry(self)

    # ------------------------------------------------------------------
    def startup(self, rng: Optional[jax.Array] = None, sample_feed: Optional[Feed] = None,
                lint: str = "off"):
        """Initialize the scope and build the jitted step.

        ``lint`` runs the static program checker (paddle_tpu.analysis)
        over the program + built step before anything compiles:
        ``"warn"`` surfaces findings as :class:`analysis.LintWarning`
        and proceeds; ``"error"`` raises :class:`analysis.LintError` on
        any warning-or-worse finding (collective inside the microbatch
        scan, mis-sharded params, dead weights...); ``"off"`` (default)
        skips it. The report is kept at ``self.lint_report``."""
        with profiler.record_event("trainer.startup",
                                   inst=self.telemetry_inst):
            return self._startup(rng, sample_feed, lint)

    def _startup(self, rng, sample_feed, lint):
        enforce(lint in ("off", "warn", "error"),
                f"Trainer.startup(lint={lint!r}): expected off|warn|error")
        self._setup_compile_cache()
        if rng is None:
            rng = make_prng_key(get_flag("seed"))
        feed = {k: _abstractify(v) for k, v in (sample_feed or {}).items()}
        if self.feed_wire is not None:
            # a wire-typed sample feed (raw uint8 pixels) initializes
            # the model at its LOGICAL dtype — the decode runs before
            # the model ever sees the feed
            feed = self.feed_wire.logical_feed(feed)
        if self.feed_augment is not None:
            # an augmentation normalize likewise casts the feed before
            # the model sees it (shape-preserving by construction)
            feed = self.feed_augment.logical_feed(feed)
        with profiler.record_event("trainer.init_params"):
            params, state = self.program.init(rng, **feed)
        params = self._interleave_stacked_params(params)
        sd = getattr(self.strategy, "opt_state_dtype", None) if self.strategy else None
        if sd is not None:
            self.optimizer.set_state_dtype(sd)
        opt_state = self.optimizer.init(params)
        if self.mesh is not None:
            from .parallel import api as par_api
            params, state, opt_state = par_api.shard_scope(
                self.mesh, self.sharding_rules, params, state, opt_state)
        else:
            dev = self.place.device()
            params = jax.device_put(params, dev)
            state = jax.device_put(state, dev)
            opt_state = jax.device_put(opt_state, dev)
        self.scope.params, self.scope.state, self.scope.opt_state = params, state, opt_state
        if self.loss_scaler is not None:
            ls = self.loss_scaler.init_state()
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                ls = jax.device_put(ls, NamedSharding(self.mesh, PartitionSpec()))
            else:
                ls = jax.device_put(ls, self.place.device())
            self.scope.loss_scale_state = ls
        # error-feedback residual for the quantized exchange: one f32
        # slot per data-parallel rank per param — global shape
        # (dshard,) + param.shape, sharded on the leading axis so each
        # rank owns (and only ever touches) its own slot. Zeros at
        # init/restore: EF telescoping simply restarts, which costs one
        # step of correction and nothing else (deliberately NOT
        # persisted by io.save).
        self.scope.quant_resid = None
        qmode = ((getattr(self.strategy, "quantized_allreduce", "none")
                  if self.strategy else "none") or "none")
        if qmode in ("int8", "int4") and bool(
                getattr(self.strategy, "error_feedback", True)):
            axes = self._local_exchange_axes(
                f"quantized_allreduce={qmode!r}")
            dshard = 1
            for a in axes:
                dshard *= self.mesh.shape[a]
            from jax.sharding import NamedSharding, PartitionSpec
            bshard = axes if len(axes) > 1 else axes[0]
            self.scope.quant_resid = {
                name: jax.device_put(
                    jnp.zeros((dshard,) + tuple(leaf.shape), jnp.float32),
                    NamedSharding(self.mesh, PartitionSpec(
                        bshard, *([None] * len(leaf.shape)))))
                for name, leaf in self.scope.params.items()}
        # ZeRO weight-update sharding: partition params + opt_state into
        # (N, k) rows over the data axes — AFTER the EF residuals above
        # (they are built from LOGICAL shapes) and BEFORE the step
        # traces (its combine/partition hooks key off self._zero). Same
        # preconditions as the shard_map-local gradient paths.
        self._zero = None
        if self.strategy is not None and getattr(self.strategy,
                                                 "zero_sharding", False):
            from .parallel import zero as zero_mod
            zaxes = self._local_exchange_axes("zero_sharding=True")
            zspec = zero_mod.make_spec(self.mesh, zaxes, self.scope.params,
                                       self.scope.state, self.scope.opt_state)
            self.scope.params = zero_mod.partition_params(
                self.scope.params, zspec, self.mesh)
            self.scope.opt_state = zero_mod.partition_opt_state(
                self.scope.opt_state, zspec, self.mesh)
            self._zero = zspec
        with profiler.record_event("trainer.build_step"):
            self._build_step()
        self.lint_report = None
        if lint != "off":
            from . import analysis
            report = analysis.check_trainer(self, sample_feed)
            self.lint_report = report
            if lint == "error":
                report.enforce_clean("warning")
            else:
                report.emit_warnings("warning")
        return self.scope

    # ------------------------------------------------------------------
    def _pp_settings(self):
        pp_m = getattr(self.strategy, "pp_microbatches", 0) if self.strategy else 0
        pp_v = getattr(self.strategy, "pp_interleave", 1) if self.strategy else 1
        return pp_m, max(1, int(pp_v))

    def _interleave_stacked_params(self, params):
        """Megatron rest layout for the interleaved pipeline: permute
        each pp-sharded stacked leaf's layer rows into rank-major chunk
        order ONCE at startup (parallel.pipeline.interleave_perm), so
        the per-step schedule re-chunks with a free local reshape
        instead of an all-to-all over pp of (V-1)/V of the parameter
        bytes. Checkpoints stay in logical order: io.save/load_trainer*
        round-trip through stacked_to_logical/_from_logical."""
        self._pp_perm = {}
        pp_m, pp_v = self._pp_settings()
        if (pp_m <= 0 or pp_v <= 1 or self.mesh is None
                or "pp" not in self.mesh.axis_names
                or self.mesh.shape["pp"] <= 1
                or self.sharding_rules is None):
            return params
        from .parallel.pipeline import interleave_perm
        p = self.mesh.shape["pp"]
        for name, leaf in params.items():
            spec = self.sharding_rules.spec_for(name, leaf.shape, self.mesh)
            lead = spec[0] if len(spec) > 0 else None
            if not (lead == "pp" or (isinstance(lead, tuple) and "pp" in lead)):
                continue
            if leaf.ndim < 1 or leaf.shape[0] % (p * pp_v) != 0:
                continue
            perm = interleave_perm(leaf.shape[0], p, pp_v)
            params[name] = jnp.asarray(leaf)[perm]
            self._pp_perm[name] = perm
        return params

    def _apply_row_perm(self, params, opt_state, index_of):
        """Apply a per-name row permutation (``index_of(perm)`` chooses
        direction) to params and every per-param optimizer-state
        subtree.

        Optimizer-state contract (stated on the Optimizer base class):
        per-param state must live under a dict keyed by the PARAMETER
        NAME, at any depth — ``opt_state['accums'][name][slot]`` for the
        built-ins, but any other name-keyed location works. This walk
        finds every such subtree and permutes the arrays whose leading
        dim matches the permutation length, so interleaved-layout
        checkpoints stay aligned for ANY conforming optimizer (not just
        ones storing state under 'accums'). Never mutates its inputs —
        callers pass live scope trees on the save path."""
        perms = getattr(self, "_pp_perm", None) or {}
        if not perms:
            return params, opt_state
        params = dict(params)
        for name, perm in perms.items():
            if name in params:
                params[name] = jnp.asarray(params[name])[index_of(perm)]

        def permute_rows(sub, perm):
            idx = index_of(perm)
            return jax.tree.map(
                lambda a: (jnp.asarray(a)[idx]
                           if getattr(a, "ndim", 0) >= 1
                           and a.shape[0] == len(perm) else a), sub)

        def walk(tree):
            if not isinstance(tree, dict):
                return tree
            return {k: (permute_rows(v, perms[k]) if k in perms else walk(v))
                    for k, v in tree.items()}

        return params, (walk(opt_state) if opt_state is not None else None)

    def stacked_to_logical(self, params, opt_state=None):
        """Undo the interleaved rest layout (checkpoint/export order)."""
        return self._apply_row_perm(params, opt_state,
                                    lambda perm: np.argsort(perm))

    def stacked_from_logical(self, params, opt_state=None):
        """Re-apply the interleaved rest layout to logical-order arrays
        (checkpoint restore into a running interleaved trainer)."""
        return self._apply_row_perm(params, opt_state, lambda perm: perm)

    def _logical_params(self):
        """The params at their LOGICAL shapes regardless of ZeRO
        sharding — an eager all-gather of the (N, k) rows when
        ``zero_sharding`` is on, ``scope.params`` verbatim otherwise.
        For analysis traces, the advisor, and export paths; never the
        training hot path (the step's in-trace combine covers that)."""
        if getattr(self, "_zero", None) is None:
            return self.scope.params
        from .parallel import zero as zero_mod
        return zero_mod.combine_params(self.scope.params, self._zero,
                                       self.mesh)

    # ------------------------------------------------------------------
    def _ambient_mode(self, flag_desc: str, wanted: bool, axis: str, enter):
        """Strategy-knob → trace-time ambient plumbing shared by the
        parallelism modes: returns (active, context). Warns when the
        knob is set without a usable mesh axis."""
        import contextlib
        import warnings

        on = (wanted and self.mesh is not None
              and axis in self.mesh.axis_names and self.mesh.shape[axis] > 1)
        if wanted and not on:
            warnings.warn(
                f"{flag_desc} is set but the mesh "
                f"{dict(self.mesh.shape) if self.mesh is not None else None} "
                f"has no '{axis}' axis (size>1); training proceeds WITHOUT it")
        return on, (enter() if on else contextlib.nullcontext())

    @staticmethod
    def _warn_unconsumed(flag_desc: str, on: bool, cfg, hint: str):
        """Silent no-op parallelism (knob set, model never read the
        context) was a review finding — surface it."""
        import warnings

        if on and not cfg["consumed"]:
            warnings.warn(f"{flag_desc} is set but the model never consumed "
                          f"the context — {hint}")

    def _loss_and_aux(self, params, state, rng, feed):
        from .framework import mesh_mode, pipeline_mode, remat_mode, sp_mode

        # strategy.remat (memory_optimize analog) flips the ambient
        # trace-time switch; zoo models wrap their repeated blocks in
        # maybe_remat, so jax.checkpoint lands per block
        pp_m, pp_v = self._pp_settings()
        pp_layout = ("interleaved" if getattr(self, "_pp_perm", None)
                     else "stacked")
        pp_on, pp_ctx = self._ambient_mode(
            f"DistStrategy.pp_microbatches={pp_m}", pp_m > 0, "pp",
            lambda: pipeline_mode(self.mesh, pp_m, interleave=pp_v,
                                  param_layout=pp_layout))
        sp_on, sp_ctx = self._ambient_mode(
            "DistStrategy.sequence_parallel",
            bool(getattr(self.strategy, "sequence_parallel", False)), "sp",
            lambda: sp_mode(self.mesh,
                            impl=getattr(self.strategy, "sp_impl", "ring")))
        with remat_mode(bool(getattr(self.strategy, "remat", False)),
                        policy=getattr(self.strategy, "remat_policy", None)), \
                mesh_mode(self.mesh), pp_ctx as pp_cfg, sp_ctx as sp_cfg:
            out, new_state = self.program.apply(params, state, training=True,
                                                rng=rng, **feed)
        self._warn_unconsumed(
            "DistStrategy.pp_microbatches", pp_on, pp_cfg,
            "no stacked block stack routed through the pipeline; every pp "
            "rank redundantly computes the full model. Build the model with "
            "its stacked representation (e.g. TransformerConfig(stacked=True)).")
        self._warn_unconsumed(
            "DistStrategy.sequence_parallel", sp_on, sp_cfg,
            "attention is NOT ring-parallel. Use an sp-aware model "
            "(models/gpt.py).")
        if isinstance(out, dict):
            loss = out[self.loss_name]
        else:
            loss = out
            out = {self.loss_name: loss}
        if self.fetch_list is not None:
            out = {k: out[k] for k in set(self.fetch_list) | {self.loss_name}}
        return loss, (out, new_state)

    def _hoisted_accum_axes(self):
        """Validate and resolve DistStrategy.accum_exchange="hoisted":
        the shard_map-local accumulation that exchanges gradients ONCE
        per optimizer step (under GSPMD the exchange rides inside the
        accumulation loop: tests/test_collective_report.py pins it)."""
        return self._local_exchange_axes("accum_exchange='hoisted'")

    def _local_exchange_axes(self, why: str):
        """Validate and resolve a shard_map-LOCAL gradient path (the
        hoisted exchange and the quantized collective both run the
        model per data shard and exchange explicitly). Only sound when
        the model trace is collective-free per shard, so every
        precondition is enforced loudly rather than silently computing
        something else."""
        enforce(self.mesh is not None,
                f"{why} needs a mesh (it is the cross-shard exchange "
                "policy)")
        axes = tuple(a for a in ("dp", "fsdp") if a in self.mesh.axis_names
                     and self.mesh.shape[a] > 1)
        enforce(axes, f"{why}: mesh has no data axis")
        pp_m, _ = self._pp_settings()
        enforce(pp_m == 0 and not getattr(self.strategy, "sequence_parallel",
                                          False),
                f"{why} composes only with pure data parallelism (no "
                "pp/sp: their shard_map schedules cannot nest inside "
                "the local gradient path)")
        enforce(not self.scope.state,
                f"{why} requires stateless models: per-shard mutable "
                "state (e.g. BN running stats) would silently diverge "
                "across shards")
        from jax.sharding import PartitionSpec
        for name, leaf in self.scope.params.items():
            spec = (self.sharding_rules.spec_for(name, leaf.shape, self.mesh)
                    if self.sharding_rules is not None else PartitionSpec())
            enforce(all(e is None for e in spec),
                    f"{why} requires fully replicated "
                    f"params; {name} is sharded {spec} (use fsdp/tp with "
                    "the default gspmd exchange instead)")
        return axes

    def _collective_bytes_summary(self, quant, axes):
        """Static bytes-on-wire attribution of the per-optimizer-step
        gradient exchange (the ``collective`` line of
        :meth:`profile_report` / ``collective_bytes`` in
        :meth:`fusion_report`): per-device ring-all-reduce bytes summed
        over every gradient leaf and data axis, fp32 baseline vs the
        configured wire format. ``None`` off-mesh or when the mesh has
        no data axis; with ``quantized_allreduce="none"`` the entry is
        still present (reduction 1.0) so dashboards can diff runs.
        Counts ONE exchange per step — the gspmd-accum path's
        per-microbatch exchanges cost ``accum_steps``× this."""
        if self.mesh is None:
            return None
        if axes is None:
            axes = tuple(a for a in ("dp", "fsdp")
                         if a in self.mesh.axis_names
                         and self.mesh.shape[a] > 1)
        if not axes:
            return None
        from .parallel import quantized_collectives as qc
        zero = getattr(self, "_zero", None)
        if zero is not None:
            # scope.params hold (N, k) shard rows under ZeRO; the grad
            # exchange still moves LOGICAL gradient elements
            sizes = [int(np.prod(s)) if s else 1 for s in zero.shapes.values()]
        else:
            sizes = [int(np.prod(p.shape)) if p.shape else 1
                     for p in jax.tree.leaves(self.scope.params)]
        ranks = {a: int(self.mesh.shape[a]) for a in axes}
        fp32 = sum(qc.ring_wire_bytes(n, p)
                   for n in sizes for p in ranks.values())
        wire = fp32 if quant is None else sum(
            qc.ring_wire_bytes(n, p, bits=quant["bits"],
                               block_size=quant["block_size"])
            for n in sizes for p in ranks.values())
        summary = {
            "mode": "none" if quant is None else f"int{quant['bits']}",
            "bits": None if quant is None else quant["bits"],
            "block_size": None if quant is None else quant["block_size"],
            "error_feedback": bool(quant and quant["error_feedback"]),
            "axes": axes,
            "ranks": ranks,
            "grad_elems": int(sum(sizes)),
            "fp32_bytes_per_step": int(fp32),
            "wire_bytes_per_step": int(wire),
            "reduction": (float(fp32) / wire) if wire else 1.0,
        }
        if zero is not None:
            # the ZeRO top-of-step param all-gather rides the same link
            # — attribute it on the collective line next to the grad
            # exchange it complements
            from .parallel import zero as zero_mod
            summary["zero"] = {
                "shards": zero.n,
                "axes": zero.axes,
                "allgather_bytes_per_step":
                    zero_mod.allgather_bytes_per_step(zero),
            }
        return summary

    def _quantized_exchange(self, gsum, accum_steps, axes, dshard, r,
                            res, quant, unscale):
        """The quantized replacement of the hoisted path's pmean,
        traced INSIDE the shard_map body: per gradient leaf, mean over
        microbatches, locally unscale (loss scaling — the residual
        must live in unscaled units or a dynamic-scale change between
        steps corrupts it), add the error-feedback residual, and ring-
        exchange through parallel.quantized_collectives over each data
        axis. With EF the leaf is roundtripped through the wire grid
        FIRST: the exchange then carries the already-quantized value
        (re-encoding is integer-exact — the ring chunk grid is padded
        to the block grid), so ``v - deq`` is exactly the information
        this rank failed to put on the wire, carried to the next step.
        Stochastic rounding keys derive from the shard-folded step rng
        (per-leaf, per-axis folds)."""
        from .parallel import quantized_collectives as qc

        bits, block = quant["bits"], quant["block_size"]
        sr = quant["stochastic_rounding"]
        leaves, treedef = jax.tree.flatten(gsum)
        res_leaves = (jax.tree.leaves(res) if res is not None
                      else [None] * len(leaves))
        qkey = jax.random.fold_in(r, 0x7157) if sr else None
        outg, outres = [], []
        for i, (g, rs) in enumerate(zip(leaves, res_leaves)):
            g = g / accum_steps
            if unscale is not None:
                g = unscale(g)
            key = jax.random.fold_in(qkey, i) if sr else None
            if rs is not None:
                v = g + rs
                x = qc.block_roundtrip(v, bits=bits, block_size=block,
                                       rng=key)
                outres.append(v - x)
                key = None  # the ring re-encodes x exactly; SR is spent
            else:
                x = g
            for j, a in enumerate(axes):
                x = qc.quantized_psum(
                    x, a, bits=bits, block_size=block,
                    rng=(jax.random.fold_in(key, j)
                         if key is not None else None))
            outg.append(x / dshard)
        grads = jax.tree.unflatten(treedef, outg)
        new_res = (jax.tree.unflatten(treedef, outres)
                   if res is not None else None)
        return grads, new_res

    def _hoisted_accum(self, loss_and_aux, axes, accum_steps, params,
                       state, rng, feed, resid=None, quant=None,
                       unscale=None):
        """shard_map-local gradient accumulation: each data shard scans
        its accum_steps microbatches with NO cross-shard traffic, then
        the summed gradients are pmean'd ONCE — the hoisted exchange
        GSPMD will not produce on its own. Params enter
        replicated (enforced), the model trace is collective-free per
        shard, float outputs are pmean'd to match the GSPMD path's
        global means.

        With ``quant`` (DistStrategy.quantized_allreduce) the single
        pmean becomes the block-scaled quantized ring exchange; a
        non-None ``resid`` additionally threads the per-shard error-
        feedback residual — global shape ``(dshard,) + param.shape``,
        sharded on the leading axis so each rank owns its own slot —
        through the shard_map and back out (returned as a 4th value)."""
        import functools

        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        dshard = 1
        for a in axes:
            dshard *= mesh.shape[a]
        b = jax.tree.leaves(feed)[0].shape[0]
        enforce(b % (accum_steps * dshard) == 0,
                f"batch {b} must divide accum_steps*data shards "
                f"({accum_steps}*{dshard}) for hoisted accumulation")
        bshard = axes if len(axes) > 1 else axes[0]

        def body(p, f, r, *res_args):
            # per-shard rng: fold the shard position in so dropout
            # masks decorrelate across shards (same-in-distribution as
            # the GSPMD path's globally-sharded masks)
            for a in axes:
                r = jax.random.fold_in(r, jax.lax.axis_index(a))
            rngs = jax.random.split(r, accum_steps)
            f_m = jax.tree.map(
                lambda x: x.reshape((accum_steps,
                                     x.shape[0] // accum_steps)
                                    + x.shape[1:]), f)
            zero = jax.tree.map(lambda q: jnp.zeros(q.shape, jnp.float32), p)

            def micro(acc, mb):
                (_, (out, _)), grads = jax.value_and_grad(
                    loss_and_aux, has_aux=True)(p, {}, mb["rng"],
                                                mb["feed"])
                return jax.tree.map(jnp.add, acc, grads), out

            gsum, outs = jax.lax.scan(micro, zero,
                                      {"rng": rngs, "feed": f_m})
            pmean_all = functools.partial(
                functools.reduce, lambda v, a: jax.lax.pmean(v, a), axes)
            new_res = None
            if quant is None:
                grads = jax.tree.map(
                    lambda g: pmean_all(g / accum_steps), gsum)
            else:
                # each rank sees its (1, ...) leading slot of the
                # sharded residual
                res = (jax.tree.map(lambda x: x[0], res_args[0])
                       if res_args else None)
                grads, new_res = self._quantized_exchange(
                    gsum, accum_steps, axes, dshard, r, res, quant,
                    unscale)
            # outputs leave the shard_map replicated (out_specs=P()), so
            # only FLOAT SCALARS are sound: a pmean of per-sample arrays
            # (logits) would average across shards' DIFFERENT samples,
            # and non-float leaves have no cross-shard combine at all.
            # Models returning more must prune with Trainer(fetch_list=)
            for path, leaf in jax.tree_util.tree_flatten_with_path(outs)[0]:
                keys = jax.tree_util.keystr(path)
                enforce(jnp.issubdtype(leaf.dtype, jnp.floating)
                        and leaf.ndim == 1,  # (accum_steps,) of scalars
                        f"accum_exchange='hoisted': output {keys} is "
                        f"{leaf.dtype}{leaf.shape[1:]} per microbatch — "
                        "only float scalar outputs (loss/metrics) can be "
                        "replicated across shards; pass fetch_list=[...] "
                        "to prune per-sample or integer outputs")
            out = jax.tree.map(
                lambda x: pmean_all(jnp.mean(x, axis=0)), outs)
            if new_res is not None:
                return grads, out, jax.tree.map(lambda x: x[None], new_res)
            return grads, out

        feed_specs = jax.tree.map(
            lambda x: P(bshard, *([None] * (x.ndim - 1))), feed)
        if resid is not None:
            res_specs = jax.tree.map(
                lambda x: P(bshard, *([None] * (x.ndim - 1))), resid)
            grads, out, new_resid = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), feed_specs, P(), res_specs),
                out_specs=(P(), P(), res_specs), check_vma=False)(
                    params, feed, rng, resid)
            return grads, out, state, new_resid
        grads, out = jax.shard_map(
            body, mesh=mesh, in_specs=(P(), feed_specs, P()),
            out_specs=P(), check_vma=False)(params, feed, rng)
        return grads, out, state

    def _build_step(self):
        accum_steps = getattr(self.strategy, "accum_steps", 1) if self.strategy else 1
        scaler = self.loss_scaler
        # wire-format decode is resolved ONCE here, like the guard: the
        # dequant/cast is traced into the step program and fused by XLA
        # into the first consumers — the feed crosses the link in the
        # wire dtype and costs no extra device launch to decode. Use
        # set_feed_wire() to change it after startup (rebuilds).
        wire = self.feed_wire
        # on-device augmentation rides the same trace, directly after
        # the decode: crop/flip/normalize fuse into the feed's first
        # consumers, keyed off the step rng (fold_in(base, step+i)) so
        # fused K-step augmentation equals sequential exactly
        augment = self.feed_augment
        # validate the exchange mode UNCONDITIONALLY: a typo'd or
        # inapplicable knob must fail loudly, never silently no-op
        # (the _warn_unconsumed lesson)
        mode = (getattr(self.strategy, "accum_exchange", "gspmd")
                if self.strategy else "gspmd")
        enforce(mode in ("gspmd", "hoisted"),
                f"DistStrategy.accum_exchange={mode!r} (gspmd|hoisted)")
        enforce(mode == "gspmd" or accum_steps > 1,
                "accum_exchange='hoisted' without accum_steps>1 is a "
                "misconfiguration (there is no loop to hoist out of)")
        hoist_axes = (self._hoisted_accum_axes() if mode == "hoisted"
                      else None)
        # quantized gradient exchange (EQuARX lineage): resolved ONCE
        # here like the guard — bits/block/EF are compiled into the
        # step program. "none" keeps today's exchange bit-identically
        # (no quant code on the trace at all).
        qmode = ((getattr(self.strategy, "quantized_allreduce", "none")
                  if self.strategy else "none") or "none")
        enforce(qmode in ("none", "int8", "int4"),
                f"DistStrategy.quantized_allreduce={qmode!r} "
                "(none|int8|int4)")
        quant_cfg = quant_axes = None
        if qmode != "none":
            from .parallel import quantized_collectives as qc
            qbits = 8 if qmode == "int8" else 4
            qblock = int(getattr(self.strategy, "quant_block_size", 256))
            qc.wire_block_bytes(1, bits=qbits, block_size=qblock)  # validate
            quant_cfg = {
                "bits": qbits,
                "block_size": qblock,
                "error_feedback": bool(getattr(self.strategy,
                                               "error_feedback", True)),
                "stochastic_rounding": bool(getattr(
                    self.strategy, "quant_stochastic_rounding", False)),
            }
            quant_axes = self._local_exchange_axes(
                f"quantized_allreduce={qmode!r}")
        qef = bool(quant_cfg and quant_cfg["error_feedback"])
        self._quant_ef = qef
        self.collective_bytes = self._collective_bytes_summary(
            quant_cfg, quant_axes)
        # guard resolution happens ONCE here: the detection is compiled
        # into the step program, so the check_nan_inf flag is read at
        # build time (set it before startup). An explicit GuardPolicy
        # degrades gracefully; the bare flag keeps its abort semantics
        # (escalate on the first incident) minus the per-leaf host syncs.
        guard = self.guard_policy
        if guard is None and not self._guard_opt_out \
                and get_flag("check_nan_inf"):
            from .resilience import GuardPolicy
            # eager readback: the legacy flag promises an abort AT the
            # offending step, including for hand-rolled step() loops
            # that never call drain_guard()
            guard = GuardPolicy(max_incidents=0, window=1,
                                record_feed_digest=False,
                                defer_readback=False)
        self._guard = guard
        zspec = getattr(self, "_zero", None)
        if zspec is not None:
            from .parallel import zero as zero_mod

        def _step_impl(params, opt_state, state, rng, feed, ls, qresid):
            self._trace_count += 1  # trace-time only: counts compilations
            if wire is not None:
                feed = wire.decode(feed)
            if augment is not None:
                feed = augment.apply(feed, rng, training=True)
            pshards = None
            if zspec is not None:
                # top-of-step all-gather: fresh logical params from this
                # step's shard rows (GSPMD materializes the gather at
                # the replicated constraint); the rows stay bound for
                # the shard-local update below
                pshards = params
                params = zero_mod.combine_params(pshards, zspec, self.mesh)
            def loss_and_aux(p, st, r, f):
                loss, aux = self._loss_and_aux(p, st, r, f)
                if scaler is not None:
                    loss = scaler.scale_loss(loss, ls)
                return loss, aux

            new_qresid = None
            if quant_cfg is not None:
                # quantized exchange: the model runs shard_map-local
                # (same schedule as the hoisted path, at any
                # accum_steps>=1) so the ONE per-step gradient exchange
                # is the block-scaled quantized ring instead of a GSPMD
                # f32 all-reduce. Loss unscaling happens INSIDE the
                # body, before encode (the EF residual lives in
                # unscaled units).
                unscale = ((lambda g: scaler.unscale(g, ls))
                           if scaler is not None else None)
                if qef:
                    grads, out, new_state, new_qresid = self._hoisted_accum(
                        loss_and_aux, quant_axes, accum_steps, params,
                        state, rng, feed, resid=qresid, quant=quant_cfg,
                        unscale=unscale)
                else:
                    grads, out, new_state = self._hoisted_accum(
                        loss_and_aux, quant_axes, accum_steps, params,
                        state, rng, feed, quant=quant_cfg,
                        unscale=unscale)
            elif accum_steps > 1 and hoist_axes is not None:
                grads, out, new_state = self._hoisted_accum(
                    loss_and_aux, hoist_axes, accum_steps, params, state,
                    rng, feed)
            elif accum_steps > 1:
                # gradient accumulation (multi_batch_merge_pass analog):
                # microbatch over the leading feed axis with lax.scan.
                # NOTE the grad exchange rides inside this loop under
                # GSPMD (tests/test_collective_report.py pins it);
                # accum_exchange="hoisted" is the once-per-step
                # alternative.
                def micro(carry, mb):
                    acc, st = carry
                    (loss, (out, new_st)), grads = jax.value_and_grad(
                        loss_and_aux, has_aux=True)(params, st, mb["rng"], mb["feed"])
                    acc = jax.tree.map(jnp.add, acc, grads)
                    return (acc, new_st), out

                feed_m = jax.tree.map(
                    lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:]),
                    feed)
                rngs = jax.random.split(rng, accum_steps)
                zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (gsum, new_state), outs = jax.lax.scan(
                    micro, (zero, state), {"rng": rngs, "feed": feed_m})
                out = jax.tree.map(lambda x: jnp.mean(x, axis=0), outs)
                grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            else:
                (loss, (out, new_state)), grads = jax.value_and_grad(
                    loss_and_aux, has_aux=True)(params, state, rng, feed)

            # the gradient exchange's tail, clipping and the update, named on
            # the device: operation metadata only
            with jax.named_scope("optimizer"):
                if zspec is not None:
                    # reduce-scatter: the row constraint keeps only this
                    # replica's slice of the exchanged grads; rebinding the
                    # shard rows makes everything below — unscale,
                    # all_finite, optimizer.update, overflow/guard rollback
                    # — shard-local over matching (N, k) trees (grad pads
                    # are exact zeros, so norms and finiteness agree with
                    # the logical grads)
                    grads = zero_mod.partition_grads(grads, zspec, self.mesh)
                    params = pshards

                if scaler is not None:
                    if quant_cfg is None:
                        # the quant path already unscaled inside the
                        # shard_map body (pre-encode)
                        grads = scaler.unscale(grads, ls)
                    finite = scaler.all_finite(grads)
                    new_params, new_opt = self.optimizer.update(
                        grads, opt_state, params, self.program.param_info)
                    # overflow-skip: keep old params/opt/state on non-finite grads
                    new_params = scaler.select(finite, new_params, params)
                    new_opt = scaler.select(finite, new_opt, opt_state)
                    new_state = scaler.select(finite, new_state, state)
                    if new_qresid is not None:
                        # a skipped step must not bank a NaN-poisoned (or
                        # phantom) residual: EF state rolls back with the
                        # rest of the carry
                        new_qresid = scaler.select(finite, new_qresid, qresid)
                    new_ls = scaler.update(ls, finite)
                    out = dict(out)
                    out["loss_scale"] = new_ls["scale"]
                else:
                    new_params, new_opt = self.optimizer.update(
                        grads, opt_state, params, self.program.param_info)
                    new_ls = ls
                if guard is not None:
                    # fused on-device NaN/Inf guard: ONE scalar bitmask over
                    # the gradients and every inexact fetch output, computed
                    # inside the compiled step. On a non-finite step the
                    # update is discarded branchlessly — the pre-step carry
                    # (params/opt_state/state) IS the last-good snapshot,
                    # already on device. Loss-scale state is deliberately
                    # NOT rolled back: the scaler's overflow backoff must
                    # persist or the same overflow recurs forever.
                    from .amp import LossScaler
                    # with a loss scaler, grad overflow is the SCALER's
                    # domain: it already skipped the update and backed the
                    # scale off, and routine calibration overflows must not
                    # count as guard incidents (much less abort the run via
                    # the check_nan_inf route) — the guard then watches the
                    # fetch outputs only
                    names, flags = [], []
                    if scaler is None:
                        names, flags = ["grads"], [_tree_nonfinite(grads)]
                    for kname in sorted(out):
                        v = out[kname]
                        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype,
                                                                  jnp.inexact):
                            names.append(kname)
                            flags.append(_tree_nonfinite(v))
                    if len(flags) > 32:
                        # uint32 mask: shifts past bit 31 are undefined and
                        # would silently drop detection — fold the tail into
                        # one combined bit (detection stays exact, only the
                        # which-output attribution coarsens)
                        rest = flags[31:]
                        flags = flags[:31] + [jnp.stack(rest).any()]
                        names = names[:31] + [
                            f"any-of-{len(rest)}-more:{'/'.join(names[31:34])}…"]
                    mask = jnp.zeros((), jnp.uint32)
                    for i, fl in enumerate(flags):
                        mask = mask | (fl.astype(jnp.uint32) << i)
                    finite = mask == 0
                    new_params = LossScaler.select(finite, new_params, params)
                    new_opt = LossScaler.select(finite, new_opt, opt_state)
                    new_state = LossScaler.select(finite, new_state, state)
                    if new_qresid is not None:
                        new_qresid = LossScaler.select(finite, new_qresid,
                                                       qresid)
                    self._guard_bit_names = tuple(names)  # trace-time capture
                    out = dict(out)
                    out["guard_nonfinite"] = mask
            if qef:
                return (new_params, new_opt, new_state, out, new_ls,
                        new_qresid)
            return new_params, new_opt, new_state, out, new_ls

        # the public step signature only grows the error-feedback
        # residual arg when the knob asks for it — quantized_allreduce=
        # "none" keeps today's 6-arg step (and its donation map)
        # byte-identically
        if qef:
            def train_step(params, opt_state, state, rng, feed, ls, qresid):
                return _step_impl(params, opt_state, state, rng, feed, ls,
                                  qresid)
        else:
            def train_step(params, opt_state, state, rng, feed, ls):
                return _step_impl(params, opt_state, state, rng, feed, ls,
                                  None)

        donate = ((0, 1, 2, 5, 6) if qef else (0, 1, 2, 5)) \
            if self.donate else ()
        # kept for the fused driver and the donation lint: the raw
        # python step body (check_trainer traces it to see input→output
        # passthrough aliasing that the jitted wrapper hides)
        self._train_step_core = train_step
        self._donate_argnums = donate
        if self.mesh is not None:
            from .parallel import api as par_api
            self._step_fn = par_api.jit_sharded_step(
                self.mesh, self.sharding_rules, train_step, donate_argnums=donate,
                scope=self.scope)
        else:
            self._step_fn = jax.jit(train_step, donate_argnums=donate)

        if qef:
            def run_k_steps(params, opt_state, state, base_rng, step0,
                            feed_k, ls, qresid):
                """Fused multi-step driver, error-feedback variant: the
                quantization residual rides the scan carry, so over the
                K fused steps the compression error TELESCOPES (each
                step's encode sees what the last one dropped) while the
                program stays one device launch."""
                k = jax.tree.leaves(feed_k)[0].shape[0]

                def body(carry, x):
                    p, o, s, ls_, qr = carry
                    r = jax.random.fold_in(base_rng, step0 + x["i"])
                    p, o, s, out, ls_, qr = train_step(p, o, s, r,
                                                       x["feed"], ls_, qr)
                    return (p, o, s, ls_, qr), out

                (p, o, s, new_ls, new_qr), outs = jax.lax.scan(
                    body, (params, opt_state, state, ls, qresid),
                    {"i": jnp.arange(k, dtype=jnp.int32), "feed": feed_k})
                return p, o, s, outs, new_ls, new_qr

            kdonate = (0, 1, 2, 6, 7) if self.donate else ()
        else:
            def run_k_steps(params, opt_state, state, base_rng, step0,
                            feed_k, ls):
                """Fused multi-step driver: ONE device launch runs K
                optimizer steps under lax.scan with the full training
                carry (params, opt_state, state, loss-scale state)
                resident on device between updates — per-step rng keys
                reproduce the sequential ``step()`` stream exactly
                (fold_in of the same base key at the same global
                step)."""
                k = jax.tree.leaves(feed_k)[0].shape[0]

                def body(carry, x):
                    p, o, s, ls_ = carry
                    r = jax.random.fold_in(base_rng, step0 + x["i"])
                    p, o, s, out, ls_ = train_step(p, o, s, r, x["feed"],
                                                   ls_)
                    return (p, o, s, ls_), out

                (p, o, s, new_ls), outs = jax.lax.scan(
                    body, (params, opt_state, state, ls),
                    {"i": jnp.arange(k, dtype=jnp.int32), "feed": feed_k})
                return p, o, s, outs, new_ls

            kdonate = (0, 1, 2, 6) if self.donate else ()
        if self.mesh is not None:
            from .parallel import api as par_api
            self._multi_step_fn = par_api.jit_sharded_step(
                self.mesh, self.sharding_rules, run_k_steps,
                donate_argnums=kdonate, scope=self.scope)
        else:
            self._multi_step_fn = jax.jit(run_k_steps, donate_argnums=kdonate)

        def eval_step(params, state, feed):
            if zspec is not None:
                # eval sees the same all-gathered logical params the
                # train step computes with
                params = zero_mod.combine_params(params, zspec, self.mesh)
            if wire is not None:
                feed = wire.decode(feed)
            if augment is not None:
                # deterministic ops only (normalize): eval never flips
                # or crops randomly
                feed = augment.apply(feed, None, training=False)
            # With the interleaved rest layout (pp_interleave>1) the
            # stacked rows are only meaningful through the pipeline
            # schedule, so eval must enter the same pipeline ctx as
            # training (its feeds then share the train step's
            # microbatch-divisibility requirement). Plain-pp trainers
            # keep the old scan-path eval: logical row order is intact
            # and any batch size works.
            from .framework import mesh_mode, pipeline_mode
            pp_m, pp_v = self._pp_settings()
            if getattr(self, "_pp_perm", None):
                b = jax.tree.leaves(feed)[0].shape[0]
                enforce(
                    b % pp_m == 0,
                    f"Trainer.eval with pp_interleave={pp_v}>1 runs the "
                    f"training pipeline schedule, so the eval batch ({b}) "
                    f"must be divisible by pp_microbatches={pp_m} (and its "
                    "microbatches by the dp shard product) — pad or "
                    "re-batch the eval feed; plain-pp trainers keep the "
                    "any-batch scan path")
            ctx = (pipeline_mode(self.mesh, pp_m, interleave=pp_v,
                                 param_layout="interleaved")
                   if getattr(self, "_pp_perm", None)
                   else contextlib.nullcontext())
            with mesh_mode(self.mesh), ctx:
                out, _ = self.program.apply(params, state, training=False,
                                            **feed)
            return out

        self._eval_fn = jax.jit(eval_step)

    # ------------------------------------------------------------------
    def _setup_compile_cache(self):
        """Wire the persistent XLA compilation cache (behind the
        ``compile_cache_dir`` flag / ``PDTPU_COMPILE_CACHE_DIR`` env):
        repeated bench/CI runs then skip recompiles of the (large) fused
        step program. Keyed on the HLO hash, so edited model code can
        never be served a stale executable. Hit/miss is logged on the
        first dispatch (``paddle_tpu.trainer`` logger)."""
        import os

        self._cache_dir = None
        self._cache_logged = False
        if not get_flag("compile_cache_dir"):
            return
        _install_cpu_cache_read_gate()
        # the flag yields to JAX_COMPILATION_CACHE_DIR: one rule, in
        # core/config.compile_cache_dir
        self._cache_dir = d = enable_compile_cache()
        self._cache_entries0 = len(os.listdir(d))
        _trainer_log().info(
            "persistent compilation cache at %s (%d entries)", d,
            self._cache_entries0)

    def _log_compile_cache(self, what: str):
        """After the first dispatch of a compiled fn: did the persistent
        cache serve it (entry count unchanged) or was it a miss (new
        entries written)?"""
        import os

        if self._cache_logged or not getattr(self, "_cache_dir", None):
            return
        self._cache_logged = True
        try:
            now = len(os.listdir(self._cache_dir))
        except OSError:
            return
        new = now - self._cache_entries0
        if new > 0:
            _trainer_log().info(
                "compile cache MISS for %s: %d new entr%s written to %s",
                what, new, "y" if new == 1 else "ies", self._cache_dir)
        else:
            _trainer_log().info(
                "compile cache HIT for %s (served from %s)", what,
                self._cache_dir)

    # ------------------------------------------------------------------
    def step(self, feed: Feed, rng: Optional[jax.Array] = None,
             span: Optional[str] = None) -> Dict[str, Any]:
        """One optimization step; returns the program's fetch dict.
        ``span`` correlates this dispatch's journal event with the
        feeder fill that produced the batch (``fit`` passes the
        DeviceFeeder's chunk span; minted fresh when omitted)."""
        enforce(self._step_fn is not None, "call startup() before step()")
        if rng is None:
            rng = jax.random.fold_in(make_prng_key(get_flag("seed") + 1), self.global_step)
        feed = self._put_feed(feed)
        ls = getattr(self.scope, "loss_scale_state", None) or {}
        base_step = self.global_step
        t0 = _time.perf_counter()
        args = (self.scope.params, self.scope.opt_state, self.scope.state,
                rng, feed, ls)
        with profiler.record_event("trainer.step", step=base_step, steps=1,
                                   inst=self.telemetry_inst):
            if self._quant_ef:
                args += (self.scope.quant_resid,)
                p, o, s, out, new_ls, new_qr = self._step_fn(*args)
                self.scope.quant_resid = new_qr
            else:
                p, o, s, out, new_ls = self._step_fn(*args)
        if self._trace_count != self._traces_registered:
            self._register_program("_step_fn", args)
        self.step_timer.record_dispatch(t0, _time.perf_counter(), 1, "step",
                                        span=span, base_step=base_step)
        self._log_compile_cache("train step")
        self.scope.params, self.scope.opt_state, self.scope.state = p, o, s
        if self.loss_scaler is not None:
            self.scope.loss_scale_state = new_ls
        self.global_step += 1
        if get_flag("benchmark"):
            jax.block_until_ready(out)
        if self._guard is not None:
            self._guard_enqueue(out, feed, self.global_step - 1, 1)
        else:
            self._warn_inert_nan_flag()
        return out

    def run_steps(self, stacked_feed: Feed, k: Optional[int] = None,
                  rng: Optional[jax.Array] = None,
                  span: Optional[str] = None) -> Dict[str, Any]:
        """K optimization steps in ONE device launch (fused multi-step
        dispatch): ``stacked_feed`` carries K per-step batches on a new
        leading axis (``{name: (K, batch, ...)}``), the jitted program
        scans over them with params/opt_state/state/loss-scale donated
        end-to-end, and the fetch dict comes back stacked ``(K, ...)``.

        Per-step rng keys are ``fold_in(base, global_step + i)`` — the
        SAME stream ``step()`` draws — so K fused steps are numerically
        identical to K sequential ``step()`` calls (pinned by
        tests/test_fused_steps.py). ``k`` is validated against the feed's
        leading dim; each distinct K compiles once (remainder batches
        should fall through to :meth:`step`, as ``fit`` does).
        Amortizes the Python→XLA launch overhead that dominates small
        step times (see BENCH ``dispatch_overhead``)."""
        enforce(self._multi_step_fn is not None,
                "call startup() before run_steps()")
        lead = {name: jax.tree.leaves(v)[0].shape[0]
                for name, v in stacked_feed.items()}
        enforce(len(set(lead.values())) == 1,
                f"run_steps: stacked feed leading dims disagree: {lead}")
        feed_k = next(iter(lead.values()))
        if k is None:
            k = feed_k
        enforce(k == feed_k,
                f"run_steps(k={k}): stacked feed carries {feed_k} step "
                "batches on its leading axis")
        if rng is None:
            rng = make_prng_key(get_flag("seed") + 1)
        feed = self._put_feed(stacked_feed, stacked=True)
        ls = getattr(self.scope, "loss_scale_state", None) or {}
        step0 = np.int32(self.global_step)
        t0 = _time.perf_counter()
        args = (self.scope.params, self.scope.opt_state, self.scope.state,
                rng, step0, feed, ls)
        with profiler.record_event("trainer.run_steps", step=int(step0),
                                   steps=k, inst=self.telemetry_inst):
            if self._quant_ef:
                args += (self.scope.quant_resid,)
                p, o, s, outs, new_ls, new_qr = self._multi_step_fn(*args)
                self.scope.quant_resid = new_qr
            else:
                p, o, s, outs, new_ls = self._multi_step_fn(*args)
        if self._trace_count != self._traces_registered:
            self._register_program("_multi_step_fn", args)
        self.step_timer.record_dispatch(t0, _time.perf_counter(), k,
                                        "run_steps", span=span,
                                        base_step=int(step0))
        self._log_compile_cache(f"fused {k}-step program")
        self.scope.params, self.scope.opt_state, self.scope.state = p, o, s
        if self.loss_scaler is not None:
            self.scope.loss_scale_state = new_ls
        self.global_step += k
        if get_flag("benchmark"):
            jax.block_until_ready(outs)
        if self._guard is not None:
            self._guard_enqueue(outs, feed, self.global_step - k, k)
        else:
            self._warn_inert_nan_flag()
        return outs

    # what _build_step, the step it traces and _loss_and_aux read of their
    # Trainer: all a registered program's stand-in is given
    _STEP_READS = ("program", "optimizer", "loss_name", "fetch_list", "mesh",
                   "sharding_rules", "strategy", "donate", "loss_scaler",
                   "guard_policy", "_guard_opt_out", "feed_wire",
                   "feed_augment", "_zero", "_pp_perm")

    def _register_program(self, attr: str, args) -> None:
        """The dispatch just made traced its program (a first ``step`` /
        ``run_steps`` of a feed shape): register it with ``core.profiler``
        so a device trace's operations can be put under this program's
        scopes. The registry outlives this Trainer, and the step's
        closures hold theirs, so what is kept is a stand-in: a bare
        Trainer with the ``_STEP_READS`` of this one and a scope that
        holds the arguments' shapes, dtypes and shardings (donated arrays
        still tell them) in place of arrays; not the dataset cache, a
        pending guard readback, the feeder or the telemetry. Asked for the
        text, the stand-in builds its own step and lowers it at those
        shapes: the program that ran, so the compile cache serves it."""
        self._traces_registered = self._trace_count

        def struct(a):
            # an uncommitted array (the rng key) or a host scalar goes
            # where the program is, as it did in the call
            sh = a.sharding if getattr(a, "_committed", False) else None
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sh)

        structs = jax.tree.map(struct, args)
        shadow = object.__new__(type(self))
        for name in self._STEP_READS:
            setattr(shadow, name, getattr(self, name, None))
        shadow._trace_count = 0
        shadow.scope = Scope()
        (shadow.scope.params, shadow.scope.opt_state,
         shadow.scope.state) = structs[:3]

        def text() -> str:
            shadow._build_step()
            return getattr(shadow, attr).lower(*structs).compile().as_text()

        fn = getattr(self, attr)
        profiler.register_program(
            "jit_" + getattr(fn, "__name__", attr), text,
            mesh_axes=tuple(self.mesh.shape.items()) if self.mesh is not None
            else (),
            key=(attr, jax.tree.structure(structs),
                 tuple(jax.tree.leaves(structs))))

    def _warn_inert_nan_flag(self):
        """The check_nan_inf flag is compiled into the step at
        _build_step — flipping it on AFTER startup() cannot arm the
        guard (the old host-scan read it per step). Warn once instead
        of letting the user believe detection is active."""
        if getattr(self, "_nan_flag_warned", False) or self._guard_opt_out:
            return
        if get_flag("check_nan_inf"):
            import warnings
            self._nan_flag_warned = True
            warnings.warn(
                "check_nan_inf was enabled after Trainer.startup(): the "
                "NaN guard is compiled into the step, so the flag has no "
                "effect on this trainer — set it before startup() (or "
                "pass Trainer(guard=GuardPolicy(...))). Note the guard "
                "raises FloatingPointError (the legacy host scan did "
                "too; Executor.run still uses it).")

    def _guard_enqueue(self, outs, feed, base_step: int, k: int) -> None:
        """Host half of the NaN/Inf guard, DEFERRED by one dispatch:
        the bitmask device array is parked and only examined when the
        NEXT dispatch is already in flight (or at :meth:`drain_guard`),
        so the guard adds NO host synchronization to the hot path —
        the readback overlaps the next chunk's device time. Params are
        protected regardless: the discard-select runs on device inside
        the step; the host side is bookkeeping (incident records +
        escalation, at most one chunk late). With
        ``GuardPolicy(defer_readback=False)`` the mask is examined
        immediately instead (one blocking fetch per dispatch) so
        escalation raises at the offending step."""
        if not self._guard.defer_readback:
            self._guard_examine(
                outs["guard_nonfinite"],
                feed if self._guard.record_feed_digest else None,
                base_step, k)
            return
        prev, self._guard_pending = self._guard_pending, (
            outs["guard_nonfinite"],
            feed if self._guard.record_feed_digest else None,
            base_step, k)
        if prev is not None:
            self._guard_examine(*prev)

    def drain_guard(self) -> None:
        """Examine the last parked guard bitmask (one blocking scalar
        fetch). Call when the step loop pauses — before a checkpoint
        read of ``guard_incidents``, at the end of ``fit``, on
        preemption — so no incident stays unrecorded."""
        prev, self._guard_pending = self._guard_pending, None
        if prev is not None:
            self._guard_examine(*prev)

    def _guard_examine(self, mask_dev, feed, base_step: int, k: int) -> None:
        from . import resilience

        mask = np.asarray(jax.device_get(mask_dev)).reshape(-1)
        if not mask.any():
            return
        names = self._guard_bit_names
        recorded = []
        for i, m in enumerate(mask):
            m = int(m)
            if not m:
                continue
            bad = tuple(n for b, n in enumerate(names) if (m >> b) & 1)
            digest = None
            if feed is not None:
                try:
                    # pull only THIS step's slice of a stacked super-
                    # batch across the link, not all K batches
                    sl = (jax.tree.map(lambda v: v[i], feed) if k > 1
                          else feed)
                    digest = resilience.feed_digest(jax.device_get(sl))
                except Exception:
                    digest = None  # digesting must never mask the incident
            recorded.append(resilience.record_incident(
                self.guard_incidents, base_step + i, bad or ("unknown",),
                digest))
        self.guard_incident_total += len(recorded)
        # escalation is evaluated at each INCIDENT's own step, not the
        # chunk end: with window < K a mid-chunk incident would
        # otherwise fall outside the trailing window by the time the
        # chunk finishes and never escalate (the check_nan_inf route is
        # window=1 — its abort contract must hold under fused dispatch)
        for inc in recorded:
            try:
                resilience.escalate_if_needed(self.guard_incidents,
                                              self._guard, inc.step)
            except FloatingPointError as e:
                # flight-record the escalation BEFORE it unwinds: the
                # ring still holds the incidents/dispatches leading up
                from .telemetry import flight_dump
                self.journal.emit("guard.escalation", step=inc.step,
                                  error=str(e)[:500])
                flight_dump("guard_escalation",
                            detail={"step": inc.step,
                                    "error": str(e)[:500]})
                raise

    def eval(self, feed: Feed) -> Dict[str, Any]:
        """Forward pass without dropout/updates.

        With ``pp_interleave>1`` the stacked parameter rows rest in the
        Megatron interleaved layout, so eval runs the SAME pipeline
        schedule as training and inherits its feed constraints: the
        batch must be divisible by ``DistStrategy.pp_microbatches``
        (and each microbatch by the dp shard product) — enforced at
        trace time with a message naming the knob. Plain-pp (``pp_interleave=1``) and
        non-pipeline trainers evaluate on the scan path, where any
        batch size works. See MIGRATION.md "Deep stacks"."""
        feed = self._put_feed(feed)
        return self._eval_fn(self.scope.params, self.scope.state, feed)

    def set_feed_wire(self, feed_wire) -> None:
        """Install (or change) the feed wire-format table. Before
        ``startup`` this is equivalent to the constructor arg; after,
        the step/eval programs are rebuilt so the decode is traced into
        them (one recompile on the next dispatch)."""
        from .data.wire import FeedWire
        wire = FeedWire.make(feed_wire)
        if wire == self.feed_wire:
            return
        self.feed_wire = wire
        if self._step_fn is not None:
            self._build_step()

    def set_augment(self, augment) -> None:
        """Install (or change) the on-device augmentation table
        (``{name: AugmentSpec}`` or a FeedAugment) — the
        :meth:`set_feed_wire` contract: after ``startup`` the
        step/eval programs rebuild so the augmentation is traced in
        (one recompile on the next dispatch)."""
        from .data.augment import FeedAugment
        aug = FeedAugment.make(augment)
        if aug == self.feed_augment:
            return
        self.feed_augment = aug
        if self._step_fn is not None:
            self._build_step()

    def pipeline_report(self) -> Dict[str, Any]:
        """Input-pipeline stage attribution accumulated since startup
        (or the last ``pipeline_metrics.reset()``): per-stage seconds
        (reader/encode/stack/h2d/dispatch), wire vs logical bytes, the
        effective h2d MB/s estimate, and the bottleneck stage. Fed by
        the DeviceFeeder fill thread under ``fit`` and by ``_put_feed``
        on direct ``step()``/``run_steps()`` calls."""
        return self.pipeline_metrics.report()

    def _put_feed(self, feed: Feed, stacked: bool = False,
                  record: bool = True):
        """Wire-encode (host side) and place a feed on device/mesh.
        ``stacked=True``: the feed is a K-step super-batch
        ``(K, batch, ...)`` — the steps axis stays replicated, the batch
        sharding applies from dim 1. Fields covered by ``feed_wire``
        cross the link in their wire dtype; already-encoded arrays (the
        DeviceFeeder fill thread encodes before stacking) pass through.
        ``record=False`` suppresses the pipeline-metrics accounting —
        used when a DeviceFeeder owns the timing of this call."""
        metrics = self.pipeline_metrics if record else None
        with profiler.record_event("trainer.put_feed"):
            return self._put_feed_impl(feed, stacked, metrics)

    def fusion_report(self, feed: Feed, top_k: int = 8) -> Dict[str, Any]:
        """Fusion-level cost attribution of the compiled train step
        (profiling.fusion): parses the executable's optimized HLO into
        per-fusion units with bytes + analytic FLOPs + source-level op
        names and ranks the top-k by roofline cost. Re-lowers and
        re-compiles the step (same cost as
        ``debugger.collective_report``); the result is cached and rides
        along in :meth:`profile_report`."""
        from .profiling import fusion_report as _fusion_report
        self._fusion_report = _fusion_report(self, feed, top_k=top_k)
        # bytes-on-wire attribution of the grad exchange rides along so
        # one report answers "is the win link bytes or compute"
        self._fusion_report["collective_bytes"] = self.collective_bytes
        return self._fusion_report

    def profile_report(self) -> Dict[str, Any]:
        """The unified step profile (profiling.steptime): per-dispatch
        wall-time totals merged with the input-pipeline stage report
        into a compute / h2d / host-encode / starvation breakdown with
        a named bottleneck, plus the cached fusion table when
        :meth:`fusion_report` has run. Emitted as ``Event.profile`` on
        ``end_epoch``/``preempted``; see MIGRATION.md "Profiling &
        memory advisor" for the schema."""
        from .profiling import profile_report as _profile_report
        return _profile_report(self, fusion=self._fusion_report)

    def export_trace(self, path: str) -> int:
        """Write the retained dispatch spans (and any enabled-profiler
        host spans) as chrome://tracing JSON via the ``core.profiler``
        timeline machinery. Returns the number of events written."""
        from .profiling import export_chrome_trace
        return export_chrome_trace(self, path)

    def reset_profile(self) -> None:
        """Zero the step-timer and pipeline-stage accumulators (e.g.
        between warmup and a measured window)."""
        self.step_timer.reset()
        self.pipeline_metrics.reset()

    def serve_metrics(self, port: int = 0, host: str = "127.0.0.1"):
        """Opt-in scrape endpoint for a TRAINING worker: start the
        stdlib ``GET /metrics`` (Prometheus text of the process
        registry — this trainer's series carry its ``inst`` label) +
        ``GET /healthz`` server; port 0 picks a free port (see
        ``.port``). The same :class:`~paddle_tpu.telemetry.
        TelemetryServer` backs ``PredictorServer.serve_metrics`` —
        trainer and serving fleet look identical to the scraper.
        Idempotent — repeat calls return the same running server
        (ports/threads don't leak); caller owns ``.close()``."""
        from .telemetry import serve_metrics as _serve

        def health():
            return {
                "live": True,
                "role": "trainer",
                "inst": self.telemetry_inst,
                "run": self.journal.run_id,
                "global_step": self.global_step,
                "guard_incidents": self.guard_incident_total,
            }

        srv = self._telemetry_server
        if srv is None or not srv._thread.is_alive():
            # fresh server only when none is running (a closed one may
            # be re-opened later; never two live endpoints per trainer)
            srv = self._telemetry_server = _serve(health_fn=health,
                                                  port=port, host=host)
        return srv

    def ship_to(self, addr, origin=None, **kw):
        """Attach the PROCESS telemetry shipper to a collector at
        ``addr`` (``"host:port"`` or a tuple): journal events + registry
        snapshots stream there in the background — the push mirror of
        :meth:`serve_metrics` (``PDTPU_TELEMETRY_ADDR`` does the same
        with zero code). Returns the :class:`~paddle_tpu.telemetry.
        shipper.Shipper`."""
        from .telemetry.shipper import ship_to as _ship_to

        return _ship_to(addr, origin=origin, **kw)

    def _put_feed_impl(self, feed: Feed, stacked, metrics):
        # device-resident fast path (the cache-served epoch): a feed of
        # nothing but jax.Arrays has no host bytes to encode or move
        # (encode and the byte accounting both skip device arrays), so
        # the single-device put — a no-op device_put per field — can be
        # skipped wholesale. MESH feeds always go through put_batch:
        # its per-array same-sharding passthrough serves cached chunks
        # for free, while a user-staged array with a different layout
        # still gets re-placed to the batch sharding as before.
        if self.mesh is None \
                and all(isinstance(v, jax.Array) for v in feed.values()):
            return feed
        if self.feed_wire is not None:
            t0 = _time.perf_counter()
            encoded = self.feed_wire.encode(feed)
            if metrics is not None:
                host = {k: v for k, v in feed.items()
                        if not isinstance(v, jax.Array)}
                if host:
                    # logical bytes are spec-aware: a reader that
                    # already produces wire-dtype data (raw uint8
                    # pixels) still counts at the decode dtype's width,
                    # so wire_reduction states the true link saving
                    logical = self.feed_wire.logical_nbytes(host)
                    wire_b = sum(np.asarray(encoded[k]).nbytes
                                 for k in host)
                    metrics.record_encode(_time.perf_counter() - t0,
                                          logical, wire_b)
            feed = encoded
        if self.mesh is not None:
            from .parallel import api as par_api
            return par_api.put_batch(self.mesh, self.sharding_rules, feed,
                                     stacked=stacked, metrics=metrics)
        dev = self.place.device()
        host_bytes = 0
        if metrics is not None:
            from .data.feeder import host_feed_nbytes
            host_bytes = host_feed_nbytes(feed)
            t0 = _time.perf_counter()
        out = {k: jax.device_put(np.asarray(v) if not isinstance(v, jax.Array) else v, dev)
               for k, v in feed.items()}
        if metrics is not None and host_bytes:
            metrics.record_h2d(host_bytes, _time.perf_counter() - t0)
        return out


class CheckpointConfig:
    """contrib.trainer CheckpointConfig analog (contrib/trainer.py:100)."""

    def __init__(self, checkpoint_dir: str, epoch_interval: int = 1,
                 step_interval: int = 0, max_num_checkpoints: int = 3):
        self.checkpoint_dir = checkpoint_dir
        self.epoch_interval = epoch_interval
        self.step_interval = step_interval
        self.max_num_checkpoints = max_num_checkpoints


class Event:
    """Training events (contrib.trainer BeginEpochEvent/EndStepEvent…).

    ``num_steps`` > 1 marks a fused-dispatch chunk (``fit(...,
    steps_per_dispatch=K)``): one begin_step/end_step pair covers
    ``num_steps`` optimizer steps and the end_step ``metrics`` arrays
    carry a leading ``(num_steps, ...)`` axis — see MIGRATION.md
    "Fused stepping". A ``"preempted"`` event fires once after the
    boundary checkpoint when fit exits on SIGTERM/SIGINT.

    ``pipeline`` carries the input-pipeline stage report
    (``Trainer.pipeline_report()``) on ``end_epoch``/``preempted``
    events — per-stage time, wire bytes, h2d MB/s, bottleneck stage.
    ``profile`` carries the unified step profile
    (``Trainer.profile_report()``) on the same events — per-dispatch
    wall time, the compute/h2d/host-encode/starvation breakdown with
    its named bottleneck, and the cached fusion table when one was
    computed.

    A ``"profile"`` event fires every time ``global_step`` crosses a
    multiple of ``fit(profile_interval_steps=N)`` (chunk-boundary
    rounded like interval checkpoints), carrying the same
    ``pipeline``/``profile`` payloads — so a long epoch reports
    between boundaries through the same path, with no extra host
    sync."""

    def __init__(self, kind: str, epoch: int, step: int, metrics=None,
                 num_steps: int = 1, pipeline=None, profile=None):
        # begin_epoch | end_epoch | begin_step | end_step | profile
        # | preempted
        self.kind = kind
        self.epoch = epoch
        self.step = step
        self.metrics = metrics or {}
        self.num_steps = num_steps
        self.pipeline = pipeline
        self.profile = profile


def fit(trainer: "Trainer", reader, num_epochs: int, feed_names: Sequence[str],
        dtypes: Optional[Sequence[Any]] = None, event_handler=None,
        checkpoint_config: Optional[CheckpointConfig] = None,
        prefetch: bool = True, steps_per_dispatch: int = 1,
        resume: bool = False, elastic: bool = False,
        preemption: Optional[bool] = None, resize=None,
        feed_wire=None, profile_interval_steps: int = 0,
        device_cache=None, augment=None):
    """High-level train loop (contrib.trainer.Trainer.train analog):
    reader → DataFeeder → (optional double-buffered prefetch) →
    trainer.step, with event callbacks and periodic checkpoints.

    ``profile_interval_steps=N`` fires a ``"profile"`` event (carrying
    ``Event.profile``/``Event.pipeline`` exactly like ``end_epoch``
    does) every time ``global_step`` crosses a multiple of N, so a
    long epoch is not blind between boundaries — same report path,
    host-side accumulators only, no extra device↔host sync.

    **Telemetry** (MIGRATION.md "Telemetry"): checkpoint saves/
    restores, preemption, and guard incidents are journaled; with a
    ``checkpoint_config`` the process flight recorder re-roots to
    ``<checkpoint_dir>/flight`` and dumps the recent-event ring on
    SIGTERM preemption, guard escalation, ``ReshardError``, and any
    unhandled exception that aborts the loop.

    ``steps_per_dispatch=K`` fuses the hot path: the prefetch thread
    stacks K host batches into one super-batch, transfers it in one
    sharded put, and ``trainer.run_steps`` runs the K optimizer steps in
    a single device launch. Events fire once per CHUNK (``Event.num_steps``,
    stacked metrics), ``global_step`` advances by the true step count
    (remainder batches run singly through ``trainer.step``), and
    ``step_interval`` checkpoints round forward to the chunk boundary
    that crossed the interval. See MIGRATION.md "Fused stepping".

    ``feed_wire={name: WireSpec}`` (or a FeedWire) installs feed wire
    formats (MIGRATION.md "Feed wire formats"): the fill thread encodes
    each batch to its wire dtype (uint8/int8 quantized, bf16/f16
    truncated) BEFORE stacking, the transfer carries the shrunk bytes,
    and the compiled step decodes on device with no extra launch.
    Per-stage pipeline metrics (reader/encode/stack/h2d/dispatch wait,
    wire bytes, effective link MB/s) accumulate either way and ride the
    ``end_epoch``/``preempted`` events as ``Event.pipeline``
    (``trainer.pipeline_report()`` at any time).

    **Device-resident data path** (MIGRATION.md section of that name):

    - ``device_cache=True|"auto"|<bytes>|DeviceCache`` arms the HBM
      dataset cache (``data/device_cache.py``): epoch 1 streams
      normally but retains each encoded chunk on device (admission
      budgeted against the advisor's residual-HBM estimate; the
      explicit int budget is for CPU/tests); epoch 2+ feeds the step
      device-to-device — ZERO h2d wire bytes, bit-identical losses.
      Degrades to partial (cache a prefix, stream the rest) or off
      (no budget / dataset too big). Invalidated on resume-restore and
      elastic reshard; assumes an epoch-stable reader (a per-epoch
      shuffle would replay epoch-1 order — don't cache one).
    - ``augment={name: AugmentSpec}`` traces on-device
      crop/flip/normalize into the step right after the wire decode
      (``trainer.set_augment``); per-step randomness follows the
      ``fold_in(base, global_step+i)`` discipline, so fused K-step
      equals sequential and resume reproduces the stream.
    - transfers run through the DeviceFeeder's 2-deep staging ring:
      chunk N+1's h2d overlaps chunk N's K-step scan, with the
      hidden-vs-exposed split reported as ``overlap_hidden_s``.

    **Fault tolerance** (MIGRATION.md "Fault tolerance & resume"):

    - ``resume=True`` restores the newest *valid* checkpoint under
      ``checkpoint_config.checkpoint_dir`` (corrupt ones are skipped
      with a warning, falling back to older), fast-forwards the
      epoch/in-epoch position recorded in the checkpoint meta, and
      continues with exact step/loss continuity — restart reproduces
      the uninterrupted run bit-for-bit for a deterministic reader.
    - ``elastic=True`` (with ``resume=True``) lets the resume ride
      through a WORKER-COUNT change: a checkpoint saved at different
      mesh axes than this trainer's is reshard-restored
      (``resilience.reshard_restore`` — bit-exact re-placement per the
      trainer's target rules) instead of raising. Step accounting needs
      no special casing across the N→M boundary: the reader batch is
      GLOBAL (dp only splits it across devices), so the epoch/
      epoch_step fast-forward and ``steps_per_dispatch`` re-stacking
      (including a different K than the run that saved) hold unchanged
      — one reader batch is one optimizer step at any mesh. Without
      ``elastic``, the mesh mismatch surfaces as a structured
      ``resilience.ReshardError`` at startup, naming saved vs. target
      axes, instead of a ``device_put`` stack trace mid-run.
    - The checkpoint ROTATION list is rebuilt from the directory at
      startup, so ``max_num_checkpoints`` holds across restarts.
    - SIGTERM/SIGINT (``preemption``; default on whenever a
      ``checkpoint_config`` is given, main thread only) requests a
      checkpoint at the next chunk boundary: fit saves
      ``step_<global_step>``, drains async orbax saves, fires a
      ``"preempted"`` event, and returns cleanly.
    - ``resize=`` (a path or a ``resilience.ResizeRequest``) is the
      SCHEDULED elastic grow/shrink — the autoscaler's trainer-side
      analog. When the resize-request file appears (or its optional
      signal arrives), fit exits at the same chunk boundary with the
      same boundary checkpoint, but journals ``fit.resized`` (with the
      request's advisory target) and fires a ``"resized"`` event
      instead: the launcher reads the event, ``consume()``s the
      request, and relaunches at the new worker count with
      ``fit(elastic=True, resume=True)`` — the mesh change rides the
      reshard-restore path above. A concurrent SIGTERM wins: a real
      preemption must never be reported as a planned resize.
    """
    import os

    from . import resilience
    from .telemetry import flight_dump, get_recorder

    if checkpoint_config is not None:
        # crash artifacts live next to the checkpoints they explain
        get_recorder().set_root(
            os.path.join(checkpoint_config.checkpoint_dir, "flight"))
    try:
        return _fit_impl(trainer, reader, num_epochs, feed_names, dtypes,
                         event_handler, checkpoint_config, prefetch,
                         steps_per_dispatch, resume, elastic, preemption,
                         feed_wire, profile_interval_steps, device_cache,
                         augment, resize)
    except resilience.InjectedCrash:
        raise  # models abrupt process death: a real kill -9 dumps nothing
    except FloatingPointError:
        raise  # guard escalation already flight-dumped at the escalate site
    except resilience.ReshardError:
        raise  # already flight-dumped at the raise site (resilience)
    except Exception as e:
        # unhandled abort: capture what the run was doing when it died
        err = f"{type(e).__name__}: {e}"[:500]
        trainer.journal.emit("fit.error", error=err,
                             global_step=trainer.global_step)
        flight_dump("fit_exception",
                    detail={"error": err,
                            "global_step": trainer.global_step})
        raise


def _fit_impl(trainer, reader, num_epochs, feed_names, dtypes,
              event_handler, checkpoint_config, prefetch,
              steps_per_dispatch, resume, elastic, preemption,
              feed_wire, profile_interval_steps, device_cache=None,
              augment=None, resize=None):
    import contextlib as _contextlib
    import os
    import shutil

    from .core.errors import enforce as _enforce
    from . import io as _io
    from . import resilience
    from .data.device_cache import DeviceCache
    from .data.feeder import DataFeeder, DeviceFeeder, iter_chunked
    from .telemetry import flight_dump, get_registry

    ckpt_counter = get_registry().counter(
        "paddle_tpu_trainer_checkpoints_total",
        "Checkpoints committed by fit", ("kind",))

    _enforce(steps_per_dispatch >= 1,
             f"fit(steps_per_dispatch={steps_per_dispatch}): need >= 1")
    _enforce(profile_interval_steps >= 0,
             f"fit(profile_interval_steps={profile_interval_steps}): "
             "need >= 0 (0 disables interval profile events)")
    if feed_wire is not None:
        trainer.set_feed_wire(feed_wire)
    if augment is not None:
        trainer.set_augment(augment)
    # the HBM dataset cache: bound to the trainer so reload/reshard
    # paths can invalidate it without knowing about this loop
    cache = DeviceCache.make(device_cache, trainer=trainer)
    trainer.device_cache = cache
    feeder = DataFeeder(feed_names, dtypes)

    _enforce(resume or not elastic,
             "fit(elastic=True) without resume=True does nothing: elastic "
             "names the resume-across-a-mesh-change behavior")
    start_epoch, skip_steps = 0, 0
    if resume:
        _enforce(checkpoint_config is not None,
                 "fit(resume=True) needs a checkpoint_config to scan")
        sample_feed = None
        if elastic:
            # peek one reader batch so the reshard feasibility check can
            # prove the per-step batch divides the target shards — the
            # infeasible case must be a structured ReshardError HERE,
            # not a raw put_batch ValueError mid-run (readers are
            # re-iterable callables; each epoch calls reader() fresh,
            # so the peek consumes nothing)
            first = next(iter(reader()), None)
            if first is not None:
                sample_feed = feeder.feed(first)
        meta = resilience.restore_latest(checkpoint_config.checkpoint_dir,
                                         trainer, elastic=elastic,
                                         sample_feed=sample_feed)
        if meta is not None:
            start_epoch = int(meta.get("epoch", 0))
            skip_steps = int(meta.get("epoch_step", 0))
            trainer.journal.emit("ckpt.restore",
                                 global_step=trainer.global_step,
                                 epoch=start_epoch, epoch_step=skip_steps)
            if cache is not None:
                # a restore lands mid-epoch / possibly on a new mesh:
                # any cached prefix no longer aligns with what the
                # epoch will consume (reshard_restore invalidates on
                # its own for direct callers)
                cache.invalidate("checkpoint restore")

    # rebuild the rotation list from disk (oldest first) so pre-existing
    # checkpoints rotate out across restarts instead of accumulating,
    # and sweep torn-save tmp leftovers from crashed predecessors
    def _fit_tag(tag: str) -> bool:
        # only fit-OWNED tags enter rotation: a user's hand-saved
        # checkpoint living in the same dir (e.g. "best") must never be
        # rotation-deleted by us
        head, _, num = tag.partition("_")
        return head in ("step", "epoch") and num.isdigit()

    kept: List[str] = []
    if checkpoint_config is not None:
        resilience.sweep_tmp_dirs(checkpoint_config.checkpoint_dir)
        kept = [c.path for c in resilience.list_checkpoints(
            checkpoint_config.checkpoint_dir) if _fit_tag(c.tag)]
        # over-quota pre-existing checkpoints are trimmed by the FIRST
        # save, not here: a startup trim could delete the oldest-but-
        # only-VALID checkpoint that resume just restored from (newer
        # ones corrupt) before this run has committed anything new

    last_saved_step = [None]  # step of the last save THIS run performed

    def save(tag: str, epoch: int, epoch_step: int):
        if checkpoint_config is None:
            return
        d = os.path.join(checkpoint_config.checkpoint_dir, tag)
        t0 = _time.perf_counter()
        _io.save_trainer(d, trainer, extra_meta={"epoch": epoch,
                                                 "epoch_step": epoch_step})
        trainer.journal.emit("ckpt.save", tag=tag, path=d,
                             global_step=trainer.global_step,
                             seconds=round(_time.perf_counter() - t0, 6))
        ckpt_counter.inc(kind=tag.partition("_")[0] or "other")
        last_saved_step[0] = trainer.global_step
        if d in kept:      # re-saved tag (e.g. preempt at an interval
            kept.remove(d)  # boundary): refresh its rotation position
        kept.append(d)
        while len(kept) > checkpoint_config.max_num_checkpoints:
            shutil.rmtree(kept.pop(0), ignore_errors=True)

    use_preempt = (preemption if preemption is not None
                   else checkpoint_config is not None)
    preempt_ctx = (resilience.PreemptionHandler() if use_preempt
                   else _contextlib.nullcontext())
    # scheduled elastic resize: a path becomes a ResizeRequest; an
    # existing handler (caller already holds the signal) is used as-is
    resize_ctx = (resilience.ResizeRequest(resize)
                  if isinstance(resize, (str, os.PathLike)) else resize)
    si = checkpoint_config.step_interval if checkpoint_config else 0
    with preempt_ctx as ph, (resize_ctx if resize_ctx is not None
                             else _contextlib.nullcontext()) as rz:
        for epoch in range(start_epoch, num_epochs):
            # resume lands mid-epoch: fast-forward past the batches the
            # restored checkpoint already consumed (1 batch == 1 step)
            skip = skip_steps if epoch == start_epoch else 0
            steps_in_epoch = skip
            trainer.journal.emit("fit.begin_epoch", epoch=epoch,
                                 global_step=trainer.global_step)
            if event_handler:
                event_handler(Event("begin_epoch", epoch, trainer.global_step))

            # device-cache disposition for THIS epoch. Serving and
            # admission both require the epoch to start at batch 0 (a
            # resume lands mid-epoch — the cached prefix would not
            # align); an invalidated cache re-arms on the next clean
            # epoch start.
            serve_cache = False
            admitting = False
            cached_steps = 0
            if cache is not None and skip == 0:
                if cache.state == "invalid":
                    cache.reset()
                serve_cache = cache.ready
                admitting = (not serve_cache
                             and cache.state in ("cold", "admitting"))
                cached_steps = cache.cached_steps if serve_cache else 0

            def batches(_skip=skip + cached_steps):
                for i, samples in enumerate(reader()):
                    if i < _skip:
                        continue
                    yield feeder.feed(samples)

            device_feeder = None
            if serve_cache and cache.complete:
                # the whole epoch is resident: no reader, no fill
                # thread, zero h2d wire bytes
                iterator = iter(())
            elif prefetch:
                # the feeder owns the stage timing (put_fn record=False
                # so h2d isn't double-counted) and runs the wire encode
                # on the fill thread, per batch, before stacking
                device_feeder = DeviceFeeder(
                    batches,
                    put_fn=functools.partial(trainer._put_feed,
                                             record=False),
                    stack_k=steps_per_dispatch,
                    put_stacked_fn=functools.partial(trainer._put_feed,
                                                     stacked=True,
                                                     record=False),
                    encode_fn=(trainer.feed_wire.encode
                               if trainer.feed_wire is not None else None),
                    metrics=trainer.pipeline_metrics,
                    logical_nbytes_fn=(trainer.feed_wire.logical_nbytes
                                       if trainer.feed_wire is not None
                                       else None),
                    journal=trainer.journal)
                iterator = iter(device_feeder)
            elif steps_per_dispatch > 1:
                iterator = iter_chunked(
                    batches(), steps_per_dispatch, put_fn=trainer._put_feed,
                    put_stacked_fn=functools.partial(trainer._put_feed,
                                                     stacked=True))
            else:
                iterator = map(trainer._put_feed, batches())
            def epoch_items():
                """(n, feed, span, streamed): cache-served chunks first
                (device-to-device, span-less, hit bytes attributed),
                then the streamed remainder."""
                if serve_cache:
                    for n, feed in cache.chunks(
                            metrics=trainer.pipeline_metrics):
                        yield n, feed, None, False
                for item in iterator:
                    n, feed = (item if steps_per_dispatch > 1
                               else (1, item))
                    # the chunk's trace id, minted by the fill thread:
                    # its dispatch event correlates with the
                    # feeder.fill event that produced this batch
                    span = (device_feeder.last_span
                            if device_feeder is not None else None)
                    yield n, feed, span, True

            preempted = False
            resized = False
            try:
                for n, feed, span, streamed in epoch_items():
                    if admitting and streamed:
                        # epoch-1 tee: retain the encoded device chunk
                        # (feeds are never donated, so the buffers
                        # survive the dispatch untouched)
                        cache.offer(n, feed)
                    gs_before = trainer.global_step
                    if event_handler:
                        event_handler(Event("begin_step", epoch, gs_before,
                                            num_steps=n))
                    out = trainer.run_steps(feed, k=n, span=span) if n > 1 \
                        else trainer.step(feed, span=span)
                    steps_in_epoch += n
                    if event_handler:
                        event_handler(Event("end_step", epoch,
                                            trainer.global_step, out,
                                            num_steps=n))
                    # interval profile events: same chunk-boundary
                    # rounding as checkpoints, same report path as
                    # end_epoch (host accumulators only, no host sync)
                    pi = profile_interval_steps
                    if pi and event_handler and \
                            trainer.global_step // pi > gs_before // pi:
                        profile = trainer.profile_report()
                        event_handler(Event("profile", epoch,
                                            trainer.global_step,
                                            num_steps=n,
                                            pipeline=profile["pipeline"],
                                            profile=profile))
                    # chunk-boundary rounding: save whenever this dispatch
                    # crossed a step_interval multiple (== the exact-multiple
                    # check when n == 1)
                    if si and trainer.global_step // si > gs_before // si:
                        save(f"step_{trainer.global_step}", epoch,
                             steps_in_epoch)
                    if ph is not None and ph.requested:
                        preempted = True
                        break
                    if rz is not None and rz.requested:
                        preempted = True
                        resized = True
                        break
            finally:
                # consumer abandoned mid-epoch (exception/early exit): the
                # fill thread must not stay blocked holding device buffers
                if device_feeder is not None:
                    device_feeder.close()
            if admitting:
                if preempted:
                    # a half-observed epoch must not seal: the next fit
                    # resumes mid-epoch and appending its chunks after
                    # this prefix would interleave two epochs
                    cache.invalidate("preempted mid-admission")
                else:
                    cache.seal(steps_in_epoch)
            if preempted:
                # preemption flow: boundary checkpoint, drain the parked
                # guard bitmask and async orbax writes, clean exit (the
                # TPU maintenance-event analog). Skip the save when the
                # interval save that just ran already committed this
                # exact step — a duplicate full gather+write would burn
                # the preemption grace period for bit-identical state.
                # A pending guard ESCALATION must not forfeit the
                # boundary checkpoint (device state is clean — the bad
                # updates were discarded on device): save first, then
                # re-raise.
                guard_err = None
                try:
                    trainer.drain_guard()
                except FloatingPointError as e:
                    guard_err = e
                # "already saved" must mean saved by THIS run — a stale
                # same-tag dir from a previous run (rebuilt into `kept`)
                # holds old params and must not suppress the save
                if last_saved_step[0] != trainer.global_step:
                    save(f"step_{trainer.global_step}", epoch,
                         steps_in_epoch)
                _io.wait_for_checkpoints()
                # journal + flight-record the preemption AFTER the
                # boundary save so the dump's ring contains the
                # ckpt.save event (and any guard incidents drained
                # above) — the black box explains the exit
                if ph is not None and ph.requested:
                    # a SIGTERM that landed after the resize poll wins:
                    # a real preemption is never reported as planned
                    resized = False
                signum = getattr(ph, "signum", None)
                if resized:
                    target = rz.target if rz is not None else {}
                    trainer.journal.emit("fit.resized", epoch=epoch,
                                         global_step=trainer.global_step,
                                         target=target)
                    get_registry().counter(
                        "paddle_tpu_trainer_resizes_total",
                        "Scheduled elastic resizes handled by fit").inc()
                    flight_dump("resized",
                                detail={"global_step": trainer.global_step,
                                        "epoch": epoch, "target": target})
                else:
                    trainer.journal.emit("fit.preempted", epoch=epoch,
                                         global_step=trainer.global_step,
                                         signum=signum)
                    get_registry().counter(
                        "paddle_tpu_trainer_preemptions_total",
                        "SIGTERM/SIGINT preemptions handled by fit").inc()
                    flight_dump("preempted",
                                detail={"global_step": trainer.global_step,
                                        "epoch": epoch, "signum": signum})
                if event_handler:
                    # ONE profile snapshot: Event.pipeline aliases its
                    # pipeline section, so handlers comparing the two
                    # never see the fill thread advance between them
                    profile = trainer.profile_report()
                    event_handler(Event("resized" if resized
                                        else "preempted", epoch,
                                        trainer.global_step,
                                        pipeline=profile["pipeline"],
                                        profile=profile))
                if guard_err is not None:
                    raise guard_err
                return trainer
            if event_handler:
                profile = trainer.profile_report()
                event_handler(Event("end_epoch", epoch, trainer.global_step,
                                    pipeline=profile["pipeline"],
                                    profile=profile))
            if checkpoint_config and checkpoint_config.epoch_interval and \
                    (epoch + 1) % checkpoint_config.epoch_interval == 0:
                save(f"epoch_{epoch}", epoch + 1, 0)
    trainer.drain_guard()
    return trainer


def _abstractify(v):
    if isinstance(v, jax.ShapeDtypeStruct):
        return v
    arr = np.asarray(v)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


_global_scope = Scope()


def global_scope() -> Scope:
    """executor.py global_scope analog: the process-wide name→array
    scope used when no explicit scope is passed."""
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope: Scope):
    """executor.py scope_guard analog: swap the global scope within a
    with-block."""
    global _global_scope
    old, _global_scope = _global_scope, scope
    try:
        yield scope
    finally:
        _global_scope = old


def _switch_scope(scope: Scope) -> Scope:
    """executor.py _switch_scope analog (reference exports it)."""
    global _global_scope
    old, _global_scope = _global_scope, scope
    return old


class Inferencer:
    """High-level inference wrapper (contrib/inferencer.py:31): build the
    inference program fn, load a checkpoint, run batches.

        inf = Inferencer(infer_fn, param_path="ckpt_dir")
        out = inf.infer({"image": batch})

    ``param_path`` may hold either a persistables checkpoint
    (io.save_persistables / save_trainer) or explicit (params, state)."""

    def __init__(self, infer_func: Callable, param_path: Optional[str] = None,
                 params=None, state=None, place: Optional[Place] = None):
        from .framework import build

        self.program = infer_func if isinstance(infer_func, Program) else build(infer_func)
        self.place = place or default_place()
        if param_path is not None:
            from . import io as _io
            params, state, _, _ = _io.load_persistables(param_path)
            enforce(bool(params),
                    f"Inferencer: no parameters found in {param_path!r}")
        enforce(params is not None, "Inferencer: need param_path or params")
        dev = self.place.device()
        self._params = jax.device_put(params, dev)
        self._state = jax.device_put(state or {}, dev)
        self._jit = jax.jit(functools.partial(self.program.apply, training=False))

    def infer(self, inputs: Feed, return_numpy: bool = True):
        out, _ = self._jit(self._params, self._state, **inputs)
        return jax.device_get(out) if return_numpy else out
