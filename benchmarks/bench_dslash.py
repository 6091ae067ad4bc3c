"""Paper §5 (sustained GFLOP/s of the dslash-dominated solver).

Timings run under the lowering picked by :mod:`benchmarks.bench_config`
(``--compiled``/``launch_bench.sh`` => compiled; default => the
historical interpret-on-CPU smoke), and EVERY JSON entry carries the
uniform label block (platform/device_kind/compiled/interpret/lowering)
plus the warm-vs-compile-inclusive split (``us_warm``/``us_first``).

Each timing is also scored against the DESIGN.md §6 streaming-traffic
model (``roofline.dslash_intensity``): ``model_bw_gbs`` is the memory
bandwidth the WARM measurement would need if it streamed exactly the
model's ``(144/N + 48)·dtype_bytes`` bytes per site, and ``bw_fraction``
divides that by the platform's roofline bandwidth (measured STREAM triad
on CPU, HBM peak on device — ``bench_config.peak_bandwidth_gbs``).  So a
batched row whose model bandwidth does NOT drop ~(144+48)/(144/N+48)×
versus single-RHS is leaving the gauge-reuse win on the table, and a
``bw_fraction`` near 1 means the lowering is at the paper's
bandwidth-bound operating point.  The JSON (path overridable via
``$BENCH_DSLASH_JSON``) carries one entry per timing.

In compiled mode the kernel rows are timed at N=1 and N=8 beside the
jnp reference on the same backend.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks import bench_config
from benchmarks.roofline import dslash_intensity
from repro.core import LatticeShape, dslash_flops
from repro.core.wilson import dslash_packed
from repro.data import lattice_problem

OUT_JSON = os.environ.get("BENCH_DSLASH_JSON", "BENCH_dslash.json")

BATCH_NRHS = 8  # batched-gauge-reuse timing point (DESIGN.md §6)


def _entry(name, timing, volume, n_rhs=1, dtype_bytes=4, **labels):
    """One JSON row: warm/first split, achieved GFLOP/s, §6-model-implied
    bandwidth and its roofline fraction, uniform labels."""
    model = dslash_intensity(n_rhs=n_rhs, dtype_bytes=dtype_bytes)
    t_s = timing["us_warm"] / 1e6
    flops = dslash_flops(volume) * n_rhs
    model_bytes = model["bytes_per_site"] * volume * n_rhs
    model_bw = model_bytes / t_s / 1e9
    return bench_config.label_entry({
        "name": name,
        "us_per_call": timing["us_warm"],  # back-compat alias
        "us_warm": timing["us_warm"],
        "us_first": timing["us_first"],
        "gflops": flops / t_s / 1e9,
        "sites_rhs_per_s": volume * n_rhs / t_s,
        "model_bytes_per_site": model["bytes_per_site"],
        "model_flops_per_byte": model["flops_per_byte"],
        # bandwidth this timing would need at exactly the model traffic
        "model_bw_gbs": model_bw,
        "bw_fraction": bench_config.bw_fraction(model_bw),
        "n_rhs": n_rhs,
        "dtype_bytes": dtype_bytes,
    }, **labels)


def run() -> list[tuple[str, float, str]]:
    from repro.kernels.wilson_dslash import dslash as dslash_k

    rows, entries = [], []
    compiled = bench_config.is_compiled()
    interp = bench_config.interpret()

    def emit(name, timing, volume, n_rhs=1, dtype_bytes=4, **labels):
        e = _entry(name, timing, volume, n_rhs=n_rhs,
                   dtype_bytes=dtype_bytes, **labels)
        entries.append(e)
        rows.append((name, e["us_warm"],
                     f"{e['gflops']:.3f}GFLOP/s;"
                     f"model_bw={e['model_bw_gbs']:.2f}GB/s"
                     f"({e['bw_fraction']:.3f}xroof)"
                     f"@{e['model_bytes_per_site']:.0f}B/site"))

    m = 0.1
    for dims in ((4, 4, 4, 8), (8, 8, 8, 8), (8, 8, 8, 16)):
        lat = LatticeShape(*dims)
        up, pp = lattice_problem(lat, mass=m)
        jnp_op = jax.jit(lambda u, p: dslash_packed(u, p, m))
        emit(f"dslash_jnp_{lat}",
             bench_config.time_first_warm(jnp_op, up, pp), lat.volume,
             interpret=False, lowering="xla")  # jnp rows are always compiled
        # bf16 storage variant (the paper's low-precision datapath):
        # halves every byte in the §6 model, so the model bandwidth for
        # equal wall-time is half the f32 row's
        up16, pp16 = up.astype(jnp.bfloat16), pp.astype(jnp.bfloat16)
        emit(f"dslash_jnp_bf16_{lat}",
             bench_config.time_first_warm(
                 jax.jit(lambda u, p: dslash_packed(u, p, m)), up16, pp16),
             lat.volume, dtype_bytes=2, interpret=False, lowering="xla")

    # batched N-RHS point: N spinors stream through ONE gauge read, so
    # the §6 per-RHS traffic drops from 192 to 144/N + 48 bytes-reals —
    # this row's model_bw_gbs is the honest amortized number
    lat = LatticeShape(4, 4, 4, 8)
    up, pp = lattice_problem(lat, mass=m)
    pb = jnp.stack([pp] * BATCH_NRHS)
    batched_op = jax.jit(lambda u, p: jax.vmap(
        lambda s: dslash_packed(u, s, m))(p))
    emit(f"dslash_jnp_nrhs{BATCH_NRHS}_{lat}",
         bench_config.time_first_warm(batched_op, up, pb), lat.volume,
         n_rhs=BATCH_NRHS, interpret=False, lowering="xla")

    # Pallas kernel entry point under the configured lowering.  Compiled
    # mode: N=1 and N=8 rows beside the jnp reference above.  Default
    # mode: the historical interpret-mode correctness row (slow by design).
    mode = "compiled" if compiled else "interp"
    kern = jax.jit(lambda u, p: dslash_k(u, p, m, interpret=interp))
    if compiled:
        emit(f"dslash_pallas_{mode}_{lat}",
             bench_config.time_first_warm(kern, up, pp), lat.volume)
        emit(f"dslash_pallas_{mode}_nrhs{BATCH_NRHS}_{lat}",
             bench_config.time_first_warm(kern, up, pb), lat.volume,
             n_rhs=BATCH_NRHS)
    else:
        emit(f"dslash_pallas_{mode}_{lat}",
             bench_config.time_first_warm(kern, up, pp, iters=1, reps=1),
             lat.volume)

    with open(OUT_JSON, "w") as f:
        json.dump({"bench": "dslash", "schema": 2,
                   "model": "DESIGN.md §6: (144/N + 48) * dtype_bytes "
                            "bytes/site, 1320 flops/site",
                   "peak_bw_gbs": bench_config.peak_bandwidth_gbs(),
                   "launch": bench_config.launch_env(),
                   "entries": entries}, f, indent=2, sort_keys=True)
        f.write("\n")
    return rows
