"""Paper Fig. 2 — transfer/compute overlap, at the inter-chip level.

The FPGA trace shows input DMA / compute / output DMA overlapping until
transfer is "invisible".  The TPU analogue: the halo-exchange dslash's
boundary corrections are independent of the bulk stencil, so the
collective-permutes overlap bulk compute.  This bench runs in a
subprocess on 8 fake devices and reports (a) the HLO structural evidence
(collective-permute count + bytes vs bulk FLOPs), (b) measured step times
for halo vs bulk-only (CPU; the roofline terms give the TPU projection).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
from repro.core import LatticeShape, pack_gauge, pack_spinor
from repro.core import distributed as dist
from repro.data import lattice_problem
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
lat = LatticeShape(8, 8, 8, 8)
up, pp = lattice_problem(lat, mass=0.1)
upd, ppd = dist.shard_lattice_fields(mesh, up, pp)
psi_spec, gauge_spec, sharded = dist.lattice_specs(mesh)

halo = jax.jit(jax.shard_map(lambda u, p: dist.dslash_halo(u, p, 0.1, sharded),
                         mesh=mesh, in_specs=(gauge_spec, psi_spec),
                         out_specs=psi_spec))
from repro.core.wilson import dslash_packed
bulk = jax.jit(jax.shard_map(lambda u, p: dslash_packed(u, p, 0.1),
                         mesh=mesh, in_specs=(gauge_spec, psi_spec),
                         out_specs=psi_spec))

def timeit(f):
    f(upd, ppd).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        out = f(upd, ppd)
    out.block_until_ready()
    return (time.time() - t0) / 5

t_halo, t_bulk = timeit(halo), timeit(bulk)
txt = halo.lower(upd, ppd).compile().as_text()
n_perm = txt.count(" collective-permute(")
print("RESULT" + json.dumps({"t_halo_us": t_halo * 1e6,
                             "t_bulk_us": t_bulk * 1e6,
                             "halo_overhead": t_halo / t_bulk,
                             "collective_permutes": n_perm}))
"""


def run() -> list[tuple[str, float, str]]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    if r.returncode != 0:
        return [("overlap_halo_vs_bulk", -1.0, "FAILED:" + r.stderr[-200:])]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")][-1]
    d = json.loads(line[len("RESULT"):])
    return [("dslash_halo_8dev", d["t_halo_us"],
             f"overhead_vs_bulk={d['halo_overhead']:.2f}x;"
             f"collective_permutes={d['collective_permutes']}"),
            ("dslash_bulk_8dev", d["t_bulk_us"], "no-comm baseline")]
