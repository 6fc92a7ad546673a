"""paddle_tpu.profiling — fusion-aware profiler + HBM/remat advisor.

The observability layer over the COMPILED step (the jaxpr-level
``analysis`` lints stop where XLA's fusion passes begin; "Operator
Fusion in XLA", PAPERS.md):

- :mod:`fusion` — parse the executable's optimized HLO into per-fusion
  cost units (bytes + analytic FLOPs + source-level op names) and name
  the top-k by roofline cost; ``fusion_report(trainer, feed)``.
- :mod:`steptime` — per-dispatch wall-time accounting (always-on in
  the Trainer) merged with the input-pipeline stage metrics into
  ``trainer.profile_report()`` (compute / h2d / host-encode /
  starvation), with chrome-trace export via ``core.profiler``.
- :mod:`advisor` — per-device HBM estimate (params + opt state +
  backward-held activations) vs the device budget, emitting
  ``memory:remat-candidate`` findings whose suggested
  ``DistStrategy.remat`` is verified against XLA's ``temp_mb``
  (:func:`advisor.verify_remat`).
"""

from .advisor import advise, device_hbm_bytes, memory_estimate, verify_remat
from .fusion import (fusion_report, fusion_report_from_text, module_units,
                     parse_hlo_module, unit_row)
from .steptime import StepTimer, export_chrome_trace, profile_report

__all__ = [
    "advise", "device_hbm_bytes", "memory_estimate", "verify_remat",
    "fusion_report", "fusion_report_from_text", "module_units",
    "parse_hlo_module", "unit_row",
    "StepTimer", "export_chrome_trace", "profile_report",
]
