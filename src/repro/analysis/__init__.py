"""Static-analysis subsystem: machine-checked packed-BCNN invariants.

Four passes over traced programs and source (see ``docs/analysis.md``):

* :mod:`repro.analysis.packedness` — dataflow proof that activations
  stay bit-packed across every HBM crossing of a forward;
* :mod:`repro.analysis.vmem` — static per-launch VMEM estimation
  (closed-form preflight for ``kernels/ops.py`` + the autotuner cost
  model, and a traced per-launch view for the report);
* :mod:`repro.analysis.collectives` — compiled-HLO collective rules
  for sharded forwards (collective-free data paths, all-gather-only
  model meshes);
* :mod:`repro.analysis.lint` — AST-enforced repo conventions
  (``python -m repro.analysis.lint src/``).

:mod:`repro.analysis.graph` is the shared jaxpr traversal under the
traced passes, and :mod:`repro.analysis.report` merges every pass,
with each forward's launch list, route and largest HBM intermediate,
into the CI-gated baseline (``python -m repro.analysis --check``).
"""
from repro.analysis.graph import (CALL_PRIMITIVES, PallasLaunch,
                                  call_subjaxpr, count_pallas_calls,
                                  iter_eqns, kernel_name,
                                  max_intermediate_bytes, pallas_eqns,
                                  pallas_grids, pallas_launches, subjaxprs)
from repro.analysis.vmem import (LaunchEstimate, VmemBudgetError, VmemTerm,
                                 attention_estimate, bitpack_estimate,
                                 bitplane_dense_estimate,
                                 bn_sign_pack_estimate, conv_estimate,
                                 dense_stack_estimate, estimate_eqn,
                                 estimate_forward, gemm_estimate, preflight,
                                 vmem_budget)

__all__ = [
    "CALL_PRIMITIVES", "PallasLaunch", "call_subjaxpr",
    "count_pallas_calls", "iter_eqns", "kernel_name",
    "max_intermediate_bytes", "pallas_eqns", "pallas_grids",
    "pallas_launches", "subjaxprs",
    "LaunchEstimate", "VmemBudgetError", "VmemTerm",
    "attention_estimate", "bitpack_estimate", "bitplane_dense_estimate",
    "bn_sign_pack_estimate",
    "conv_estimate", "dense_stack_estimate", "estimate_eqn",
    "estimate_forward", "gemm_estimate", "preflight", "vmem_budget",
]
