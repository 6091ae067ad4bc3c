"""Shared benchmark configuration: one coherent lowering + uniform labels.

Every bench module historically decided ``interpret`` on its own, so a
single run could mix interpret-mode Pallas rows with compiled jnp rows
and nothing in the JSON said which was which.  This module is the single
source of truth:

* :func:`configure` — called once by ``run.py`` (``--backend``,
  ``--compiled``) or by ``launch_bench.sh`` via the ``BENCH_BACKEND`` /
  ``BENCH_COMPILED`` environment variables; standalone module runs read
  the same env vars, so ``python benchmarks/bench_dslash.py`` under the
  launcher behaves identically to the harness.
* :func:`interpret` — the tri-state ``interpret`` argument every kernel
  call in every bench module must pass through (None = historical
  default = interpret on CPU; False = compiled: Mosaic on device, the
  XLA half-spinor lowering on CPU).
* :func:`labels` — the uniform per-entry label block
  (``platform``/``device_kind``/``compiled``/``interpret``/``lowering``)
  merged into EVERY JSON entry of every bench module.
* :func:`time_first_warm` — the warm-vs-compile-inclusive timing
  protocol (ISSUE: perf trajectory separates ``us_first`` from
  ``us_warm``).
* :func:`peak_bandwidth_gbs` — the roofline denominator: the §6 model
  bandwidth of a timing divided by this is its achieved-vs-roofline
  ``bw_fraction``.  On CPU the peak is *measured* (a big jnp triad, the
  STREAM idiom) rather than assumed; on TPU it is the HBM peak from
  ``roofline.PEAK``.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp

_STATE = {"configured": False, "compiled": False}


def configure(backend: str | None = None, compiled: bool = False) -> None:
    """Pin the JAX platform and the compiled/interpret mode for this
    process.  Must run before the first JAX computation when ``backend``
    is given (the platform cannot change once initialized)."""
    if backend:
        jax.config.update("jax_platform_name", backend)
        os.environ["BENCH_BACKEND"] = backend
    _STATE["configured"] = True
    _STATE["compiled"] = bool(compiled)
    os.environ["BENCH_COMPILED"] = "1" if compiled else "0"


def is_compiled() -> bool:
    if _STATE["configured"]:
        return _STATE["compiled"]
    return os.environ.get("BENCH_COMPILED", "0") in ("1", "true", "on")


def interpret() -> bool | None:
    """The tri-state ``interpret`` argument for kernel entry points."""
    return False if is_compiled() else None


def lowering_name() -> str:
    from repro.kernels.dispatch import resolve_lowering
    return resolve_lowering(interpret())


def labels() -> dict:
    """The uniform label block for every benchmark JSON entry."""
    from repro.kernels.dispatch import device_kind, resolve_interpret
    return {
        "platform": jax.default_backend(),
        "device_kind": device_kind(),
        "compiled": is_compiled(),
        "interpret": resolve_interpret(interpret()),
        "lowering": lowering_name(),
    }


def label_entry(entry: dict, **overrides) -> dict:
    """Merge the uniform labels into one entry (entry's own keys win —
    a module may legitimately pin e.g. ``interpret`` for a row that
    deliberately runs the other lowering, and must then say so)."""
    return {**labels(), **overrides, **entry}


def launch_env() -> dict:
    """The launcher-pinned environment, dumped into each bench JSON so a
    committed number carries its own repro recipe."""
    keys = ("XLA_FLAGS", "LD_PRELOAD", "JAX_DEFAULT_DTYPE_BITS",
            "TF_CPP_MIN_LOG_LEVEL", "BENCH_BACKEND", "BENCH_COMPILED")
    env = {k: os.environ[k] for k in keys if k in os.environ}
    env["jax_version"] = jax.__version__
    return env


def time_first_warm(fn, *args, iters: int = 3, reps: int = 2) -> dict:
    """Compile-inclusive first call + warm steady state (best-of-reps
    mean-of-iters), in microseconds."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    us_first = (time.perf_counter() - t0) * 1e6
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return {"us_first": us_first, "us_warm": best * 1e6}


@functools.lru_cache(maxsize=None)
def peak_bandwidth_gbs() -> float:
    """Roofline bandwidth denominator for the active platform, GB/s.

    CPU: measured — a 64 MiB f32 triad ``a = 2b + c`` (3 streams, the
    STREAM benchmark shape) compiled by XLA, best of 5.  Device backends:
    the HBM peak from ``roofline.PEAK`` (819 GB/s, TPU v4).
    """
    if jax.default_backend() != "cpu":
        from benchmarks.roofline import PEAK
        return PEAK["hbm"] / 1e9
    n = 1 << 24  # 16M f32 per stream = 64 MiB, far past cache
    b = jnp.arange(n, dtype=jnp.float32)
    c = jnp.ones(n, dtype=jnp.float32)
    triad = jax.jit(lambda x, y: 2.0 * x + y)
    jax.block_until_ready(triad(b, c))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        out = triad(b, c)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return 3 * 4 * n / best / 1e9


def bw_fraction(model_bw_gbs: float) -> float:
    """Achieved-vs-roofline fraction: the bandwidth this timing would
    need at exactly the §6 model traffic, over the platform peak."""
    return model_bw_gbs / peak_bandwidth_gbs()
