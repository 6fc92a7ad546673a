"""Framework core: parameter scope, build context, Program.

This is the TPU-native redesign of the reference's central machinery:

- Reference (SURVEY §1 L1/L4): a protobuf ``ProgramDesc`` built by Python
  layer calls via ``LayerHelper.append_op`` (framework.py:1199), holding
  ``VarDesc``/``OpDesc``; parameters live in a C++ ``Scope``
  (scope.h:41) keyed by name; an Executor interprets the program.

- Here: a *function* is the program. Layer calls inside it request
  parameters by stable unique names from a build-context scope
  (:class:`BuildContext`); ``Program.init`` traces the function once to
  materialize the parameter pytree (startup-program analog), and
  ``Program.apply`` traces it for execution under ``jax.jit`` — the
  jaxpr is the ProgramDesc analog (see :meth:`Program.desc`).

Parameters are a flat ``{name: jax.Array}`` dict — the Scope — so the
reference's name-keyed variable semantics (save/load by name, per-param
attributes, selective trainability) carry over directly, while the whole
thing stays a pytree that jax.grad / pjit understand.

State variables (batch-norm moving stats etc., the reference's
non-trainable persistable vars) live in a separate collection and are
threaded functionally: ``apply`` returns ``(outputs, new_state)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import inspect
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .core import unique_name as _unique_name
from .core.dtypes import DEFAULT_DTYPE, convert_dtype
from .core.errors import EnforceError, NotFoundError, enforce

Params = Dict[str, jax.Array]
State = Dict[str, jax.Array]


# --------------------------------------------------------------------------
# ParamAttr — per-parameter attributes (param_attr.py analog)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ParamAttr:
    """Parameter attributes (python/paddle/fluid/param_attr.py analog).

    ``regularizer`` is an object with ``apply(param, grad) -> grad`` (see
    paddle_tpu.regularizer); ``learning_rate`` is a per-param LR multiplier;
    ``trainable=False`` freezes the parameter (stop_gradient analog).
    """

    name: Optional[str] = None
    initializer: Optional[Any] = None
    learning_rate: float = 1.0
    regularizer: Optional[Any] = None
    trainable: bool = True

    @staticmethod
    def to_attr(attr: Any) -> "ParamAttr":
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if attr is False:
            return ParamAttr(trainable=False)
        raise ValueError(f"Cannot interpret param_attr: {attr!r}")


@dataclasses.dataclass
class ParamInfo:
    """Static metadata recorded at init for each parameter."""

    shape: Tuple[int, ...]
    dtype: Any
    trainable: bool = True
    learning_rate: float = 1.0
    regularizer: Optional[Any] = None
    is_distributed: bool = False  # sharded-embedding marker (distributed lookup table analog)


# --------------------------------------------------------------------------
# BuildContext — the live scope during a trace
# --------------------------------------------------------------------------


class BuildContext:
    """Per-trace context: parameter scope + name generator + RNG + mode.

    Mode 'init' creates parameters (startup program analog); mode 'apply'
    fetches them. Name generation is context-local so init/apply traces
    agree (the determinism requirement Program construction has in the
    reference too).
    """

    def __init__(
        self,
        mode: str,
        params: Params,
        state: State,
        rng: Optional[jax.Array],
        training: bool,
        param_info: Dict[str, ParamInfo],
    ):
        assert mode in ("init", "apply")
        self.mode = mode
        self.params = params
        self.state = state
        self.new_state: State = {}
        self.rng = rng
        self._rng_count = 0
        self.training = training
        self.param_info = param_info
        self.namer = _unique_name.UniqueNameGenerator()
        self.name_stack: List[str] = []

    # -- naming ------------------------------------------------------------
    def unique_name(self, key: str) -> str:
        return self.namer(key)

    def full_name(self, suffix: str) -> str:
        return "/".join(self.name_stack + [suffix]) if self.name_stack else suffix

    # -- rng ---------------------------------------------------------------
    def next_rng_key(self) -> jax.Array:
        enforce(
            self.rng is not None,
            "This program needs an RNG (dropout/random op) but none was passed; "
            "call apply(..., rng=key).",
        )
        self._rng_count += 1
        return jax.random.fold_in(self.rng, self._rng_count)

    def param_rng_key(self, name: str) -> jax.Array:
        # Deterministic per-name key: stable under call-order changes of
        # unrelated layers, mirrors per-var initializer seeds in the
        # reference's startup program (initializer.py).
        h = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")
        return jax.random.fold_in(self.rng, h)


def compute_dtype():
    """Mixed-precision compute dtype (float16_transpiler/contrib float16
    analog, done right for TPU): master params stay float32; layers cast
    matmul/conv operands to this dtype — bfloat16 hits the MXU natively.
    Set via config flag 'default_compute_dtype' or amp_guard."""
    from .core.config import get_flag

    return convert_dtype(get_flag("default_compute_dtype"))


@contextlib.contextmanager
def amp_guard(dtype="bfloat16"):
    """Scoped mixed precision (fluid contrib float16 rewrite analog)."""
    from .core.config import get_flag, set_flag

    prev = get_flag("default_compute_dtype")
    set_flag("default_compute_dtype", dtype)
    try:
        yield
    finally:
        set_flag("default_compute_dtype", prev)


def cast_compute(*arrays):
    """Cast matmul/conv operands to the compute dtype. Float inputs only;
    integer arrays pass through."""
    cd = compute_dtype()
    out = tuple(a.astype(cd) if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
                else a for a in arrays)
    return out if len(out) > 1 else out[0]


_tls = threading.local()


def _ctx() -> BuildContext:
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        raise EnforceError(
            "No build context active: layer functions must run inside "
            "Program.init/apply (pt.build(fn)) — the program_guard analog."
        )
    return ctx


def current_context() -> Optional[BuildContext]:
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def _use_ctx(ctx: BuildContext):
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


@contextlib.contextmanager
def reuse_names():
    """Replay unique-name counters on exit, so a block of layer calls
    invoked repeatedly (e.g. a decode step called once outside lax.scan
    to create params and again inside to reuse them) resolves to the
    SAME parameter names each time — the ParamAttr-name / while_op
    sub-block variable-reuse analog."""
    ctx = _ctx()
    snapshot = dict(ctx.namer.ids)
    try:
        yield
    finally:
        ctx.namer.ids.clear()
        ctx.namer.ids.update(snapshot)


@contextlib.contextmanager
def name_scope(name: str):
    """Hierarchical naming scope (fluid.name_scope analog). Parameters
    created inside are named under it, and so are the operations traced
    inside (``jax.named_scope``: the ``op_name`` a device trace shows)."""
    ctx = _ctx()
    ctx.name_stack.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        ctx.name_stack.pop()


def in_training() -> bool:
    ctx = current_context()
    return bool(ctx and ctx.training)


def next_rng_key() -> jax.Array:
    return _ctx().next_rng_key()


@contextlib.contextmanager
def rng_fold(tag):
    """Fold ``tag`` (python int or traced int32) into the ambient rng
    stream for the duration of the block.

    The per-call counter in :meth:`BuildContext.next_rng_key` is a
    PYTHON int fixed at trace time, so a body traced once and executed
    many times — a ``lax.scan`` over stacked layers — would hand every
    iteration the same dropout keys. Wrapping each iteration in
    ``rng_fold(layer_index)`` decorrelates them (fold_in accepts traced
    operands). No-op when no build context / rng is active, so pure
    inference paths need no guard."""
    ctx = current_context()
    if ctx is None or ctx.rng is None:
        yield
        return
    old = ctx.rng
    ctx.rng = jax.random.fold_in(old, tag)
    try:
        yield
    finally:
        ctx.rng = old


@contextlib.contextmanager
def rng_scope(key):
    """REPLACE the ambient rng stream with ``key`` for the block.

    Where :func:`rng_fold` derives from the ambient key, this installs
    an explicitly-threaded one — the pipeline schedule needs it because
    its body runs under ``shard_map``, where the ambient key must enter
    as a replicated argument and be re-derived per (layer, microbatch,
    data-shard) inside the body. No-op when ``key`` is None or no build
    context is active."""
    ctx = current_context()
    if ctx is None or key is None:
        yield
        return
    old = ctx.rng
    ctx.rng = key
    try:
        yield
    finally:
        ctx.rng = old


# --------------------------------------------------------------------------
# Parameter / variable creation — the LayerHelper primitives
# --------------------------------------------------------------------------


def create_parameter(
    shape,
    dtype=None,
    name: Optional[str] = None,
    attr: Any = None,
    initializer: Optional[Any] = None,
    is_distributed: bool = False,
) -> jax.Array:
    """Create-or-fetch a named parameter (LayerHelper.create_parameter
    analog, layer_helper.py). In init mode runs the initializer; in apply
    mode fetches from the scope."""
    from . import initializer as _init_mod  # local import to avoid cycle

    ctx = _ctx()
    attr = ParamAttr.to_attr(attr)
    shape = tuple(int(s) for s in shape)
    dtype = convert_dtype(dtype) if dtype is not None else DEFAULT_DTYPE
    full = attr.name or ctx.full_name(name or "param")

    if ctx.mode == "init":
        if full not in ctx.params:
            init_fn = attr.initializer or initializer
            if init_fn is None:
                init_fn = _init_mod.Xavier()
            ctx.params[full] = init_fn(ctx.param_rng_key(full), shape, dtype)
            ctx.param_info[full] = ParamInfo(
                shape=shape,
                dtype=dtype,
                trainable=attr.trainable,
                learning_rate=attr.learning_rate,
                regularizer=attr.regularizer,
                is_distributed=is_distributed,
            )
    if full not in ctx.params:
        raise NotFoundError(
            f"Parameter {full!r} not found in scope (have: {sorted(ctx.params)[:20]}...)"
        )
    p = ctx.params[full]
    info = ctx.param_info.get(full)
    if info is not None and not info.trainable:
        p = jax.lax.stop_gradient(p)
    if isinstance(attr, WeightNormParamAttr):
        p = _weight_norm_reparam(p, attr, full, ctx)
    return p


def create_variable(
    shape,
    dtype=None,
    name: Optional[str] = None,
    initializer: Optional[Any] = None,
) -> jax.Array:
    """Create-or-fetch non-trainable persistable state (e.g. BN moving
    mean — the reference's persistable non-parameter vars)."""
    from . import initializer as _init_mod

    ctx = _ctx()
    shape = tuple(int(s) for s in shape)
    dtype = convert_dtype(dtype) if dtype is not None else DEFAULT_DTYPE
    full = ctx.full_name(name or "var")
    if ctx.mode == "init":
        if full not in ctx.state:
            init_fn = initializer or _init_mod.Constant(0.0)
            ctx.state[full] = init_fn(ctx.param_rng_key(full), shape, dtype)
    if full in ctx.new_state:
        return ctx.new_state[full]
    if full not in ctx.state:
        raise NotFoundError(f"State variable {full!r} not found in scope.")
    return ctx.state[full]


def assign_variable(name_suffix_or_full: str, value: jax.Array, full: bool = False) -> None:
    """Functional write to a state variable; new value is returned from
    apply() as part of new_state."""
    ctx = _ctx()
    full_name = name_suffix_or_full if full else ctx.full_name(name_suffix_or_full)
    ctx.new_state[full_name] = value


class LayerHelper:
    """Names a layer instance and scopes its parameters.

    Analog of python/paddle/fluid/layer_helper.py: each call site gets a
    unique instance name ("fc_0"); parameters created under it are
    "fc_0/w" etc.
    """

    def __init__(self, layer_type: str, name: Optional[str] = None):
        ctx = _ctx()
        self.name = name or ctx.unique_name(layer_type)

    def scope(self):
        return name_scope(self.name)

    def create_parameter(self, suffix: str, shape, dtype=None, attr=None, initializer=None,
                         is_distributed: bool = False) -> jax.Array:
        with self.scope():
            return create_parameter(
                shape, dtype=dtype, name=suffix, attr=attr, initializer=initializer,
                is_distributed=is_distributed,
            )

    def create_variable(self, suffix: str, shape, dtype=None, initializer=None) -> jax.Array:
        with self.scope():
            return create_variable(shape, dtype=dtype, name=suffix, initializer=initializer)

    def assign_variable(self, suffix: str, value: jax.Array) -> None:
        with self.scope():
            assign_variable(suffix, value)


# --------------------------------------------------------------------------
# Program — build/init/apply
# --------------------------------------------------------------------------


class Program:
    """A traced program: the ProgramDesc analog (framework.py:1404).

    ``fn`` is a pure-Python function of array inputs using
    paddle_tpu.layers ops; tracing it under init/apply materializes /
    consumes the parameter scope. ``param_info`` (populated by init)
    carries per-parameter attrs the optimizer consults — the OpRole /
    param-attr metadata of the reference.
    """

    def __init__(self, fn: Callable, name: Optional[str] = None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "program")
        self.param_info: Dict[str, ParamInfo] = {}
        # capture the EFFECTIVE image layout at BUILD time and re-enter
        # it for every trace: pt.build(model) under layout_mode("NHWC")
        # pins the whole program to the TPU-native layout even though
        # tracing happens later (init / jitted apply / export). Programs
        # built outside any layout_mode pin NCHW — an ambient context
        # active at trace time must not leak in.
        self.layout = current_layout()

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array, *args, **kwargs) -> Tuple[Params, State]:
        """Run the startup-program analog: trace fn, create params/state.

        ``args``/``kwargs`` are example inputs (concrete or
        jax.ShapeDtypeStruct)."""
        params: Params = {}
        state: State = {}
        self.param_info = {}
        ctx = BuildContext("init", params, state, rng, training=False,
                          param_info=self.param_info)

        def _run(*a, **kw):
            with _use_ctx(ctx), layout_mode(self.layout):
                self.fn(*a, **kw)
            return 0

        args = tuple(_concretize(a) for a in args)
        kwargs = {k: _concretize(v) for k, v in kwargs.items()}
        _run(*args, **kwargs)
        return params, state

    # ------------------------------------------------------------------
    def apply(
        self,
        params: Params,
        state: Optional[State],
        *args,
        training: bool = False,
        rng: Optional[jax.Array] = None,
        **kwargs,
    ) -> Tuple[Any, State]:
        """Execute the program functionally. Returns (outputs, new_state)."""
        ctx = BuildContext(
            "apply", params, state or {}, rng, training, dict(self.param_info)
        )
        with _use_ctx(ctx), layout_mode(self.layout):
            out = self.fn(*args, **kwargs)
        new_state = dict(ctx.state)
        new_state.update(ctx.new_state)
        return out, new_state

    # ------------------------------------------------------------------
    def desc(self, params: Params, state: State, *args, **kwargs):
        """The jaxpr of this program — the ProgramDesc/debugger analog."""
        def f(p, s, *a, **kw):
            return self.apply(p, s, *a, **kw)

        return jax.make_jaxpr(f)(params, state, *args, **kwargs)

    def desc_flat(self, params: Params, state: State, *args,
                  training: bool = False, rng: Optional[jax.Array] = None,
                  **kwargs):
        """The jaxpr with NAMED inputs: returns ``(closed_jaxpr, names)``
        where ``names[i]`` is a ``(kind, name)`` pair for invar i — kind
        one of ``"param" | "state" | "arg" | "kwarg"`` — so analyses
        (paddle_tpu.analysis) can map jaxpr dataflow back to the scope's
        name-keyed variables, the way the reference's passes read
        VarDesc names off the ProgramDesc."""
        import jax.tree_util as jtu

        tree = (params, state or {}, args, kwargs)
        leaves, treedef = jax.tree.flatten(tree)
        keyed, _ = jtu.tree_flatten_with_path(tree)
        kinds = ("param", "state", "arg", "kwarg")

        def name_of(path) -> Tuple[str, str]:
            kind = kinds[path[0].idx]
            parts = []
            for k in path[1:]:
                if hasattr(k, "key"):
                    parts.append(str(k.key))
                elif hasattr(k, "idx"):
                    parts.append(str(k.idx))
                elif hasattr(k, "name"):
                    parts.append(str(k.name))
            return kind, "/".join(parts)

        def f(flat):
            p, s, a, kw = jax.tree.unflatten(treedef, flat)
            out, _ = self.apply(p, s, *a, training=training, rng=rng, **kw)
            return out

        closed = jax.make_jaxpr(f)(leaves)
        return closed, [name_of(path) for path, _ in keyed]

    def arg_names(self) -> List[str]:
        return list(inspect.signature(self.fn).parameters)

    def arg_signature(self, *args, **kwargs) -> Dict[str, Any]:
        """Bind an example call to ``fn``'s signature and return the
        name→value mapping — the traced-argument signature the
        recompilation-hazard lint (paddle_tpu.analysis) inspects before
        values are abstracted into avals."""
        try:
            bound = inspect.signature(self.fn).bind_partial(*args, **kwargs)
            return dict(bound.arguments)
        except TypeError:
            names = self.arg_names()
            out = {(names[i] if i < len(names) else f"arg{i}"): a
                   for i, a in enumerate(args)}
            out.update(kwargs)
            return out


def _concretize(x):
    if isinstance(x, jax.ShapeDtypeStruct):
        # canonicalize first: int64 specs under the default x64-off config
        # would otherwise emit a truncation UserWarning on every trace
        return jnp.zeros(x.shape, jax.dtypes.canonicalize_dtype(x.dtype))
    return x


def build(fn: Callable, name: Optional[str] = None) -> Program:
    """Wrap a layer-composition function into a Program."""
    return Program(fn, name=name)


# --------------------------------------------------------------------------
# default-program registry (framework.py default_main_program:1404 region /
# program_guard). In the traced design a Program is a function, not a
# mutable op list; the "default program" is a module slot driver code can
# swap with program_guard — the structural shape fluid scripts expect.
# --------------------------------------------------------------------------

_remat_mode = threading.local()


_layout_mode = threading.local()


@contextlib.contextmanager
def layout_mode(data_format: str = "NHWC"):
    """Ambient image-layout switch. TPU's MXU wants NHWC convolutions
    (channels on the 128-lane minor axis — NCHW graphs pay XLA
    layout-assignment transposes), but the reference API's default and
    most user model code say NCHW. Under ``layout_mode("NHWC")`` every
    conv/pool/BN layer whose ``data_format`` is left unspecified, and
    every zoo model's channel-axis bookkeeping (via
    :func:`current_layout`), follows the ambient layout — the whole
    model zoo runs TPU-native without per-model threading."""
    assert data_format in ("NCHW", "NHWC"), data_format
    old = getattr(_layout_mode, "fmt", None)
    _layout_mode.fmt = data_format
    try:
        yield
    finally:
        _layout_mode.fmt = old


def current_layout(explicit=None) -> str:
    """Resolve a layer's data_format: explicit argument wins, then the
    ambient :func:`layout_mode`, then the reference default NCHW."""
    if explicit is not None:
        return explicit
    return getattr(_layout_mode, "fmt", None) or "NCHW"


@contextlib.contextmanager
def remat_mode(enabled: bool = True, policy=None):
    """Ambient rematerialization switch (memory_optimization_transpiler
    analog, consumed at trace time). Trainer enters this around
    ``program.apply`` when ``DistStrategy.remat`` is set; zoo models
    check it via :func:`maybe_remat` around their repeated blocks, so
    ``memory_optimize()`` turns on per-block ``jax.checkpoint`` without
    the model config having to opt in.

    ``policy`` (a jax.checkpoint_policies callable or one of the names
    :func:`resolve_remat_policy` knows) tunes WHAT the checkpointed
    blocks keep: e.g. ``"dots"`` saves matmul outputs — skipping their
    MXU recompute in the backward pass while still dropping the cheap
    elementwise intermediates — the standard long-context middle ground
    between full remat and no remat."""
    resolved = resolve_remat_policy(policy)  # may raise: BEFORE any
    old = (getattr(_remat_mode, "on", False),     # thread-local writes
           getattr(_remat_mode, "policy", None))
    _remat_mode.on = bool(enabled)
    _remat_mode.policy = resolved
    try:
        yield
    finally:
        _remat_mode.on, _remat_mode.policy = old


def remat_enabled() -> bool:
    return getattr(_remat_mode, "on", False)


def remat_policy():
    return getattr(_remat_mode, "policy", None)


def resolve_remat_policy(policy):
    """Map a friendly name to a jax.checkpoint_policies callable (pass
    callables through, None means save-nothing — full recompute)."""
    if policy is None or callable(policy):
        return policy
    table = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "everything": jax.checkpoint_policies.everything_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    enforce(policy in table,
            f"unknown remat policy {policy!r}; options: {sorted(table)}"
            " or any jax.checkpoint_policies callable")
    return table[policy]


_pipeline_mode = threading.local()


@contextlib.contextmanager
def pipeline_mode(mesh, microbatches: int, axis: str = "pp",
                  interleave: int = 1, param_layout: str = "stacked"):
    """Ambient pipeline-parallel switch (trace-time, like
    :func:`remat_mode`). Trainer enters this around ``program.apply``
    when ``DistStrategy.pp_microbatches`` is set and the mesh has a
    ``pp`` axis; zoo models route their stacked block stacks through
    ``layers.stacked.apply_stacked``, which consumes it and runs
    ``parallel.pipeline.pipeline_apply`` instead of a sequential scan.
    ``interleave`` selects the Megatron virtual-stage schedule (>1).
    ``param_layout="interleaved"`` declares that stacked param rows are
    ALREADY stored in the rank-major chunk order (Trainer.startup's
    Megatron layout, ``parallel.pipeline.interleave_perm``), so the
    schedule needs no per-step re-layout collective."""
    old = getattr(_pipeline_mode, "cfg", None)
    cfg = {"mesh": mesh, "microbatches": int(microbatches), "axis": axis,
           "interleave": max(1, int(interleave)),
           "param_layout": param_layout, "consumed": False}
    _pipeline_mode.cfg = cfg
    try:
        yield cfg
    finally:
        _pipeline_mode.cfg = old


def pipeline_config() -> Optional[dict]:
    """The active pipeline context, or None. Init-mode builds always see
    None: parameter creation must not run under shard_map."""
    ctx = current_context()
    if ctx is not None and ctx.mode == "init":
        return None
    cfg = getattr(_pipeline_mode, "cfg", None)
    if cfg is not None:
        cfg["consumed"] = True
    return cfg


_mesh_mode = threading.local()


@contextlib.contextmanager
def mesh_mode(mesh):
    """Ambient device mesh of the step being traced (trace-time, like
    :func:`pipeline_mode`). Trainer enters this around ``program.apply``
    when it has a mesh: GSPMD partitions every XLA op from the operand
    shardings, but it cannot partition a Mosaic kernel, so attention
    reads the mesh here and runs the flash kernel per shard under
    ``shard_map`` (layers/attention.flash_sdpa)."""
    old = getattr(_mesh_mode, "mesh", None)
    _mesh_mode.mesh = mesh
    try:
        yield mesh
    finally:
        _mesh_mode.mesh = old


def active_mesh():
    """The mesh of :func:`mesh_mode`, or None."""
    return getattr(_mesh_mode, "mesh", None)


_sp_mode = threading.local()


@contextlib.contextmanager
def sp_mode(mesh, axis: str = "sp", impl: str = "ring"):
    """Ambient sequence-parallel switch (trace-time, like
    :func:`pipeline_mode`). Trainer enters this around ``program.apply``
    when ``DistStrategy.sequence_parallel`` is set and the mesh has an
    ``sp`` axis; sp-aware zoo models (models/gpt.py) route their
    attention through ring attention (``impl="ring"``, zigzag layout) or
    all-to-all head-sharded attention (``impl="ulysses"``)."""
    enforce(impl in ("ring", "ulysses"),
            f"unknown sequence-parallel impl {impl!r} (ring|ulysses)")
    old = getattr(_sp_mode, "cfg", None)
    cfg = {"mesh": mesh, "axis": axis, "impl": impl, "consumed": False}
    _sp_mode.cfg = cfg
    try:
        yield cfg
    finally:
        _sp_mode.cfg = old


def sp_config() -> Optional[dict]:
    """The active sequence-parallel context, or None (always None during
    init-mode builds, mirroring :func:`pipeline_config`)."""
    ctx = current_context()
    if ctx is not None and ctx.mode == "init":
        return None
    cfg = getattr(_sp_mode, "cfg", None)
    if cfg is not None:
        cfg["consumed"] = True
    return cfg


def maybe_remat(fn: Callable, enabled: Optional[bool] = None,
                policy: Optional[Callable] = None) -> Callable:
    """Wrap ``fn`` in ``jax.checkpoint`` when remat is requested — either
    explicitly (``enabled=True``, e.g. a model config flag) or ambiently
    (``enabled=None`` and :func:`remat_enabled`). Activations inside the
    block are recomputed in the backward pass; only the block inputs (and
    anything ``policy`` saves) stay live — the TPU trade of HBM for MXU
    FLOPs that the reference's liveness-based var reuse approximated
    (memory_optimization_transpiler.py:456).

    Never wraps during init-mode builds: jax.checkpoint traces its body,
    and init-mode create_parameter writes eager arrays into the build
    context as a side effect — tracing would leak tracers into params."""
    ctx = current_context()
    if ctx is not None and ctx.mode == "init":
        return fn
    if enabled or (enabled is None and remat_enabled()):
        return jax.checkpoint(
            fn, policy=resolve_remat_policy(policy) or remat_policy())
    return fn


_default_programs: List["Program"] = []


def default_main_program() -> "Program":
    """framework.py default_main_program analog: the innermost
    program_guard program (or None outside any guard)."""
    return _default_programs[-1] if _default_programs else None


def default_startup_program() -> "Program":
    """Startup = init trace of the same Program (double-program
    convention collapses: Program.init IS the startup program)."""
    return default_main_program()


@contextlib.contextmanager
def program_guard(main_program: "Program", startup_program: Optional["Program"] = None):
    """framework.py program_guard analog."""
    _default_programs.append(main_program)
    try:
        yield main_program
    finally:
        _default_programs.pop()


class WeightNormParamAttr(ParamAttr):
    """param_attr.py WeightNormParamAttr: weight-norm reparameterization
    w = g·v/‖v‖ along ``dim`` (Salimans & Kingma). create_parameter
    detects this attr and returns the reparameterized weight; the stored
    trainables are v (under the layer's name) and g ("<name>@wn_g",
    initialized to ‖v_init‖ so the first forward equals plain init)."""

    def __init__(self, dim: Optional[int] = None, **kwargs):
        super().__init__(**kwargs)
        self.dim = dim


def _weight_norm_reparam(p: jax.Array, attr: "WeightNormParamAttr", full: str,
                         ctx: "BuildContext") -> jax.Array:
    # dim=None = norm over ALL axes (scalar g), matching the reference's
    # layer_helper __norm_except_dim; an integer dim keeps a per-slice g
    dim = attr.dim
    if dim is None:
        axes = tuple(range(p.ndim))
        shape = [1] * p.ndim
    else:
        axes = tuple(a for a in range(p.ndim) if a != dim)
        shape = [1] * p.ndim
        shape[dim] = p.shape[dim]
    gname = full + "@wn_g"
    norm = jnp.sqrt(jnp.sum(jnp.square(p), axis=axes) + 1e-12)
    if ctx.mode == "init" and gname not in ctx.params:
        ctx.params[gname] = norm
        ctx.param_info[gname] = ParamInfo(
            shape=tuple(norm.shape), dtype=norm.dtype, trainable=attr.trainable,
            learning_rate=attr.learning_rate, regularizer=None,
            is_distributed=False)
    g = ctx.params[gname]
    return p / norm.reshape(shape) * g.reshape(shape)
