"""Static kernel-contract analyzer for the fused edge engine.

``python -m repro.analysis`` sweeps every registered operator × backend
× padding × output-mode combination, walks the traced jaxpr / TPU
Mosaic export of each, and verifies the engine's contracts — fusion
purity, contraction fences, dtype ladder, VMEM budget, halo
consistency, determinism — without executing a kernel. See DESIGN.md
§10 for the rule table.
"""

from repro.analysis.rules import (
    RULES,
    AnalysisError,
    check_contraction_fences,
    check_dtype_ladder,
    check_fusion_purity,
    check_halo_window,
    check_kernel_accum_dtype,
    check_kernel_cardinality,
    check_mosaic_program,
    check_static_registration,
    check_vmem_budget,
    find_pallas_eqns,
    tap_accumulation_bounds,
)
from repro.analysis.ast_rules import scan_file, scan_source
from repro.analysis.sweep import MODES, analyze, kernel_math_files
from repro.analysis.violations import Report, Violation, load_baseline, write_baseline

__all__ = [
    "RULES",
    "AnalysisError",
    "Report",
    "Violation",
    "analyze",
    "MODES",
    "kernel_math_files",
    "load_baseline",
    "write_baseline",
    "scan_file",
    "scan_source",
    "check_contraction_fences",
    "check_dtype_ladder",
    "check_fusion_purity",
    "check_kernel_accum_dtype",
    "check_halo_window",
    "check_kernel_cardinality",
    "check_mosaic_program",
    "check_static_registration",
    "check_vmem_budget",
    "find_pallas_eqns",
    "tap_accumulation_bounds",
]
