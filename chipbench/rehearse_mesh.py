#!/usr/bin/env python3
"""Compile the sharded even-odd solve for a described v5e 2x2 host, no chip.

    JAX_PLATFORMS=cpu python chipbench/rehearse_mesh.py --dims 64,32,32,32

Compiles, for the four chips of a described ``v5e:2x2`` topology with T
over ``data`` and Z over ``model`` (the layout ``plan.solve`` uses with
``mesh=``), the three programs of a sharded eo-schur Pallas CGNR solve:

* ``prep``   -- the global split and pack of u and b, on one chip, before
  the fields are placed on the mesh;
* ``solve``  -- the ``shard_map`` Krylov solve (halo hops, psum dots);
* ``verify`` -- the post-solve true-residual check inside ``shard_map``.

For each it prints the bytes per chip from ``memory_analysis()`` and
whether that fits one chip's 15.75 GB.  Nothing runs, so nothing here is
a time.  Each program prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CHIP_BYTES = 15.75e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dims", default="64,32,32,32", help="T,Z,Y,X")
    ap.add_argument("--mass", type=float, default=-1.4)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == BENCH_DIR:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.cg_fused.kernel as cg_kernel
    import repro.kernels.cg_fused.ops as cg_ops
    import repro.kernels.wilson_dslash.kernel as dslash_kernel
    from repro.core import distributed as dist
    from repro.core import plan as plan_mod
    from repro.core.lattice import (pack_gauge, pack_spinor, split_eo,
                                    split_eo_gauge)

    # lower with Mosaic for the described chip; a compile for a chip that
    # is not attached can be written to the cache but never read back
    dslash_kernel.resolve_lowering = lambda interpret: "mosaic"
    cg_ops.resolve_lowering = lambda interpret: "mosaic"
    cg_kernel.resolve_interpret = lambda interpret: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    t, z, y, x = (int(v) for v in args.dims.split(","))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = jax.make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    plan = plan_mod.SolverPlan(operator="eo-schur", backend="pallas",
                               solver="cgnr", mesh=mesh)
    psi_spec, gauge_spec, _ = dist.lattice_specs(mesh, plan.axis_map)
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    u_nat = (4, t, z, y, x, 3, 3)
    psi_nat = (t, z, y, x, 4, 3)
    gauge_half = (4, t, z, y, 18, x // 2)
    psi_half = (t, z, y, 24, x // 2)

    def prep(u, b):
        u_e, u_o = split_eo_gauge(u)
        b_e, b_o = split_eo(b)
        return (pack_gauge(u_e), pack_gauge(u_o), pack_spinor(b_e),
                pack_spinor(b_o))

    solver = plan_mod._sharded_eo_solver(plan, args.mass, 1e-6, 1000, 25)

    def verify(u, b, xx):
        return plan_mod._true_residual_mesh(plan, u, b, args.mass, xx,
                                            "natural")

    gs = NamedSharding(mesh, gauge_spec)
    ps = NamedSharding(mesh, psi_spec)
    nat_p = NamedSharding(mesh, P(*psi_spec[:2]))
    nat_g = NamedSharding(mesh, P(*gauge_spec[:3]))
    programs = {
        "prep": (prep, (spec(u_nat, jnp.complex64, one),
                        spec(psi_nat, jnp.complex64, one))),
        "solve": (solver, (spec(gauge_half, jnp.float32, gs),
                           spec(gauge_half, jnp.float32, gs),
                           spec(psi_half, jnp.float32, ps),
                           spec(psi_half, jnp.float32, ps))),
        # u, b and x placed over the mesh as the solve's fields are
        "verify": (verify, (spec(u_nat, jnp.complex64, nat_g),
                            spec(psi_nat, jnp.complex64, nat_p),
                            spec(psi_nat, jnp.complex64, nat_p))),
    }
    for name, (fn, shapes) in programs.items():
        row = {"program": name, "dims": [t, z, y, x]}
        try:
            compiled = jax.jit(fn).lower(*shapes).compile()
            mem = compiled.memory_analysis()
            total = mem.temp_size_in_bytes + mem.argument_size_in_bytes
            row.update(temp_bytes=mem.temp_size_in_bytes,
                       argument_bytes=mem.argument_size_in_bytes,
                       output_bytes=mem.output_size_in_bytes,
                       fits=bool(total + mem.output_size_in_bytes
                                 - mem.alias_size_in_bytes < CHIP_BYTES),
                       kernels=compiled.as_text().count("tpu_custom_call"))
        except Exception as e:  # the compiler's refusal is the finding
            row.update(error=f"{type(e).__name__}: {str(e)[:600]}")
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
