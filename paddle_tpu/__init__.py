"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up JAX/XLA/pallas re-design with the capabilities of the
reference framework (PaddlePaddle Fluid — see SURVEY.md): layer library,
optimizers with in-step regularization/clipping, functional state,
executor-style training, mesh-sharded data/tensor/sequence/pipeline
parallelism, sparse & sharded embeddings, checkpointing, metrics,
profiling, quantization, RecordIO data format (C++ core), beam-search
decoding, and a StableHLO inference/export path.
"""

from . import analysis, backward, clip, core, data, debugger, evaluator, framework, initializer
from . import io, layers, lr_scheduler, metrics, models, nets, optimizer
from . import parallel, quantize, regularizer, resilience, serving, sparse, telemetry, transpiler
from .resilience import (CheckpointCorrupt, GuardPolicy, PreemptionHandler,
                         ReshardError, reshard_restore)
from .serving import PredictorServer
from .core import CPUPlace, CUDAPlace, Place, TPUPlace, default_place
from .core import profiler  # fluid.profiler: pt.profiler.profiler(trace_dir)
from .executor import CheckpointConfig, Event, Executor, Inferencer, Scope, Trainer, fit
from .framework import (
    LayerHelper,
    ParamAttr,
    Program,
    WeightNormParamAttr,
    amp_guard,
    build,
    create_parameter,
    create_variable,
    default_main_program,
    default_startup_program,
    name_scope,
    program_guard,
)
from .backward import append_backward, calc_gradient
from .executor import global_scope, scope_guard
from .transpiler import (
    DistributeTranspiler,
    DistributeTranspilerConfig,
    HashName,
    RoundRobin,
    memory_optimize,
    release_memory,
)
from .parallel import DistStrategy, ShardingRules, make_mesh
from .core.config import enable_determinism

# honor PDTPU_DETERMINISTIC=1 before any backend work happens
if core.config.get_flag("deterministic"):
    enable_determinism()

__version__ = "0.1.0"
