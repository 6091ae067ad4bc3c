"""Stable names for the phases of a solve and for the kernel launches in them.

Each phase of ``plan.solve`` runs under exactly one ``solve.*`` scope, and
each kernel launch under one kernel scope inside it.  A scope is
``jax.named_scope``: it sets the ``op_name`` metadata of the operations
traced under it and nothing else, so the optimized program of a solve is
the same with or without them, apart from that metadata.  A profiler trace
names device operations by their instruction names; reading each
instruction's ``op_name`` in the compiled solve's ``as_text()`` maps them
to these phases.
"""

from __future__ import annotations

import functools

import jax

# the phases of one solve, in the order they run
GAUGE_LAYOUT = "solve.gauge_layout"        # parity split and packing of U
RHS_LAYOUT = "solve.rhs_layout"            # parity split and packing of b
SCHUR_RHS = "solve.schur_rhs"              # D̂†(b_e − D_eo M⁻¹ b_o)
KRYLOV = "solve.krylov"                    # the solver's while loop
BACK_SUBSTITUTE = "solve.back_substitute"  # x_o = M⁻¹(b_o − D_oe x_e)
MERGE = "solve.merge"                      # unpacking and parity merge of x
VERIFY = "solve.verify"                    # the true residual ‖b − D x‖
SOLVE_PHASES = (GAUGE_LAYOUT, RHS_LAYOUT, SCHUR_RHS, KRYLOV, BACK_SUBSTITUTE,
                MERGE, VERIFY)

# the kernel launches, nested inside a phase.  Each wraps a jitted kernel
# entry point from outside: a scope inside the jit would become the last
# component of the launch's name stack, which is what XLA names the
# custom call after (``schur_op.26``), and the trace reductions match those
# names.
HOP_EO = "wilson.hop_eo"      # parity hop, odd in, even out
HOP_OE = "wilson.hop_oe"      # parity hop, even in, odd out
SCHUR = "wilson.schur"        # D̂ or D̂†: a hop_oe and a hop_eo launch
DSLASH = "wilson.dslash"      # full-lattice dslash
CG_UPDATE = "cg.update"       # x += a p, r -= a Ap, |r|^2
CG_XPAY = "cg.xpay"           # p = r + b p
PSUM = "mesh.psum"            # a reduction summed over the device mesh


def scope(name: str):
    """A context manager naming what is traced under it."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: trace every call of the function under ``scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap
