"""Distributed lattice solve: 4D domain decomposition + halo exchange over
a (pod, data, model) mesh, with the pipelined single-reduction CG.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_solve.py

(On a real TPU slice, drop the XLA_FLAGS and the same code distributes
over the physical mesh — the point of the dry-run deliverable.)
"""

import os
import sys

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
from repro.core import LatticeShape                             # noqa: E402
from repro.core import distributed as dist                      # noqa: E402
from repro.core.wilson import dslash_packed                     # noqa: E402
from repro.data import lattice_problem                          # noqa: E402


def main():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    print(f"[dist] devices={len(jax.devices())} mesh={dict(mesh.shape)}")

    lat = LatticeShape(8, 8, 8, 8)
    gauge, b = lattice_problem(lat, mass=0.2, seed=0)
    gauge_d, b_d = dist.shard_lattice_fields(mesh, gauge, b)
    print(f"[dist] lattice {lat} decomposed T->data Z->model Y->pod")

    for solver in ("pipecg", "mpcg"):
        x, st = dist.solve_wilson(mesh, gauge_d, b_d, 0.2, solver=solver,
                                  tol=1e-6, maxiter=1000)
        r = dslash_packed(gauge, jax.device_get(x), 0.2) - b
        rel = float(jnp.linalg.norm(r.ravel()) / jnp.linalg.norm(b.ravel()))
        print(f"[dist] {solver}: iters={int(st.iterations)} "
              f"outer={int(st.outer_iterations)} rel_res={rel:.2e}")
        assert rel < 1e-5
    return 0


if __name__ == "__main__":
    sys.exit(main())
