"""The paper's primary contribution: mixed-precision Conjugate Gradient for
the Dirac-Wilson operator, adapted from FPGA dataflow to TPU (see DESIGN.md).

Public surface:
  lattice   — geometry, SU(3) fields, layout packing
  wilson    — the Dirac-Wilson operator (natural + packed layouts)
  operators — the operator registry: site-local terms (wilson,
              twisted-mass) decoupled from the shared hop transport
  solvers   — cg / cgnr / cgnr_eo / mpcg / mpcg_eo / pipecg / bicgstab
  eo        — even-odd (Schur) blocks + eo_context; legacy solve forwarders
  plan      — SolverPlan: THE solve entry point ({operator, backend, batch,
              precision, mesh} resolved to callables; solve_plan runs it)
  precision — (low, high) precision-pair policies
  distributed — shard_map domain decomposition, halo-overlap dslash (full
              AND parity-compressed), psum-fused reductions
"""

from repro.core.lattice import (LatticeShape, complex_to_real_pair,
                                eo_row_offset, field_dot, field_dot_batched,
                                field_norm2, field_norm2_batched,
                                merge_eo, merge_eo_gauge, pack_gauge,
                                pack_half_gauge, pack_half_spinor,
                                pack_spinor, parity_masks, random_gauge,
                                random_spinor, real_pair_to_complex,
                                split_eo, split_eo_gauge, unit_gauge,
                                unpack_gauge, unpack_half_gauge,
                                unpack_half_spinor, unpack_spinor)
from repro.core.operators import (LatticeOperator, SiteTerm, dslash_g,
                                  dslash_dagger_g, get_operator,
                                  normal_op_g, operator_names,
                                  register_operator, schur_dagger_g,
                                  schur_normal_op_g, schur_op_g)
from repro.core.precision import PrecisionPolicy
from repro.core.solvers import (SolveStats, bicgstab, cg, cg_trace, cgnr,
                                cgnr_eo, mpcg, mpcg_eo, pipecg)
from repro.core.wilson import (DSLASH_FLOPS_PER_SITE, apply_gamma5, dslash,
                               dslash_dagger, dslash_dagger_packed,
                               dslash_eo, dslash_flops, dslash_oe,
                               dslash_packed, normal_op, normal_op_packed,
                               schur_dagger, schur_normal_op, schur_op)
from repro.core.eo import (EOContext, EOOperators, eo_context, eo_operators,
                           eo_operators_packed, solve_wilson_eo,
                           solve_wilson_eo_batched, solve_wilson_eo_mp)
from repro.core.plan import SolverPlan
from repro.core.plan import resolve as resolve_plan
from repro.core.plan import solve as solve_plan
