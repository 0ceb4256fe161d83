# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# SNAP kernel suite (paper Sec. VI): snap_u (Wigner recursion),
# snap_y (adjoint contraction: a VPU walk over the static CG table on
# the half planes, one-hot MXU matmuls on the full ones),
# snap_fused_de[_half] (dual-number dU + force contraction).
# ``ops.snap_force_pipeline`` chains them in one canonical
# [*, natoms_pad] device layout — half-index planes by default
# (layout='half'), full planes kept for A/B (layout='full');
# mxu_dtype=bfloat16 rounds the half walk's U rows, coefficients and
# products to bfloat16, with float32 accumulation.

from .ops import (energy_forces_kernel, half_planes_to_full,
                  snap_dedr_kernel, snap_force_pipeline, snap_ui_kernel,
                  snap_yi_kernel)
from .snap_y import snap_y_half_pallas, snap_y_pallas, y_coef

__all__ = [
    'energy_forces_kernel', 'half_planes_to_full', 'snap_dedr_kernel',
    'snap_force_pipeline', 'snap_ui_kernel', 'snap_yi_kernel',
    'snap_y_half_pallas', 'snap_y_pallas', 'y_coef',
]
