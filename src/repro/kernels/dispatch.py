"""Backend-aware kernel dispatch: lowering resolution + the tuning cache.

Two concerns live here, shared by every Pallas kernel package:

**Lowering resolution.**  ``interpret=None`` (the default everywhere)
resolves to interpret mode only when JAX is running on CPU — the
validation/debug platform — and to compiled Mosaic kernels on GPU/TPU.
Passing an explicit bool always wins, so tests can force interpret mode
and device runs can force compilation.  ``resolve_lowering`` refines the
same tri-state into the THREE real lowerings:

* ``"interpret"`` — the Pallas interpreter (bitwise reference; slow).
* ``"mosaic"``    — native Pallas compilation (GPU/TPU).
* ``"xla"``       — a compiled-XLA implementation of the same half-spinor
  algorithm (:mod:`repro.kernels.wilson_dslash.xla`).  This is what
  ``interpret=False`` means on CPU, where ``pallas_call`` cannot compile
  ("Only interpret mode is supported on CPU backend"): the honest
  compiled-backend number for this host, labeled as such in benchmarks.

**Tile selection (the tuning cache).**  The dslash launch space — z-block
``bz``, y-block ``by``, RHS-batch placement, gauge streaming mode — is
swept offline by :mod:`repro.kernels.autotune`, and the winner per
``(backend, lattice_shape, nrhs, dtype)`` is checked in at
``kernels/tuning_cache.json``.  Kernel wrappers call :func:`pick_tile`
at trace time; a cache miss (or ``REPRO_TUNING_CACHE=0``) falls back to
the deterministic default (the largest tile that fits the scoped VMEM
budget: ``wilson_dslash.kernel._fit_tile``), so golden/jaxpr tests stay
bitwise with the cache cold or disabled.  All tile choices are
bitwise-neutral by construction (they change data movement, never per-site FMA order) —
the cache can only change *speed*, not results.

Environment overrides (read at trace time):

* ``REPRO_TUNING_CACHE=0``      — disable cache lookups entirely.
* ``REPRO_TUNING_CACHE_PATH``   — read this JSON instead of the default.
* ``REPRO_DSLASH_TILE``         — force a tile, e.g. ``bz=2,by=4,
  batch=grid,stream=db`` (keys may be omitted; beats the cache).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax

DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(__file__),
                                  "tuning_cache.json")

_BATCH_PLACEMENTS = ("block", "grid")
_GAUGE_STREAMS = ("blockspec", "db")


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state ``interpret`` flag against the active backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def resolve_lowering(interpret: bool | None) -> str:
    """Map the tri-state ``interpret`` flag to a lowering name.

    ``None``  -> "interpret" on CPU, "mosaic" on GPU/TPU (the historical
    default behaviour of :func:`resolve_interpret`).
    ``True``  -> "interpret" everywhere.
    ``False`` -> compiled execution: "mosaic" where Pallas can compile,
    "xla" on CPU where it cannot.
    """
    if interpret is None:
        return "interpret" if jax.default_backend() == "cpu" else "mosaic"
    if interpret:
        return "interpret"
    return "xla" if jax.default_backend() == "cpu" else "mosaic"


def device_kind() -> str:
    """Human-readable device model of the default backend ("cpu",
    "TPU v4", "NVIDIA H100", ...) — the per-entry benchmark label."""
    return jax.devices()[0].device_kind


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One point in the dslash launch space (see DESIGN.md §13).

    ``bz``/``by``: z/y planes per block (None = heuristic default:
    largest divisor of Z ≤ 4 for bz, full Y for by, shrunk until the
    launch fits the scoped VMEM budget).  ``batch``: where
    the RHS-batch axis rides — "block" pins the whole batch inside every
    block (one gauge fetch feeds N spinor planes); "grid" makes it the
    trailing (fastest-varying) grid dimension, so consecutive steps
    revisit the same gauge block with a smaller VMEM footprint.
    ``stream``: "blockspec" uses the implicit Pallas pipeline for the
    gauge operands; "db" double-buffers the center gauge planes through
    an explicit 2-slot VMEM scratch with async copies (DESIGN.md §13).

    Every field is bitwise-neutral: per-site FMA order never depends on
    the tile, only HBM->VMEM data movement does.
    """
    bz: int | None = None
    by: int | None = None
    batch: str = "block"
    stream: str = "blockspec"

    def __post_init__(self):
        if self.batch not in _BATCH_PLACEMENTS:
            raise ValueError(
                f"batch placement must be one of {_BATCH_PLACEMENTS}, "
                f"got {self.batch!r}")
        if self.stream not in _GAUGE_STREAMS:
            raise ValueError(
                f"gauge stream must be one of {_GAUGE_STREAMS}, "
                f"got {self.stream!r}")

    def to_entry(self) -> dict:
        return {"bz": self.bz, "by": self.by, "batch": self.batch,
                "stream": self.stream}


DEFAULT_TILE = TileConfig()


def cache_key(backend: str, lattice_shape: tuple[int, ...], nrhs: int,
              dtype) -> str:
    """Tuning-cache key: ``backend|TxZxYxX|nrhsN|dtype``.

    ``lattice_shape`` is the (T, Z, Y, X) extent of the field the kernel
    actually sees — parity kernels key on the compressed X, so full- and
    half-lattice launches tune independently.
    """
    dims = "x".join(str(int(d)) for d in lattice_shape)
    return f"{backend}|{dims}|nrhs{int(nrhs)}|{jax.numpy.dtype(dtype).name}"


def parse_tile(spec: str) -> TileConfig:
    """Parse ``"bz=2,by=4,batch=grid,stream=db"`` (any subset of keys)."""
    kw: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        key, val = key.strip(), val.strip()
        if key in ("bz", "by"):
            kw[key] = None if val in ("", "none", "None") else int(val)
        elif key in ("batch", "stream"):
            kw[key] = val
        else:
            raise ValueError(
                f"unknown tile key {key!r} in REPRO_DSLASH_TILE={spec!r}; "
                "legal keys: bz, by, batch, stream")
    return TileConfig(**kw)


@functools.lru_cache(maxsize=None)
def _load_cache(path: str, mtime: float) -> dict:
    with open(path) as f:
        doc = json.load(f)
    return doc.get("entries", {})


def load_tuning_cache(path: str | None = None) -> dict:
    """Entries of the tuning-cache JSON ({} when absent/disabled)."""
    if os.environ.get("REPRO_TUNING_CACHE", "1") in ("0", "off"):
        return {}
    path = path or os.environ.get("REPRO_TUNING_CACHE_PATH",
                                  DEFAULT_CACHE_PATH)
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    return _load_cache(path, mtime)


def pick_tile(lattice_shape: tuple[int, ...], nrhs: int, dtype,
              backend: str | None = None) -> TileConfig:
    """Tile selection at trace time: env override > cache hit > defaults.

    Deterministic on a cold/disabled cache (returns :data:`DEFAULT_TILE`,
    i.e. the historical heuristics), so tests and goldens never depend on
    which cache file happens to be checked out.
    """
    forced = os.environ.get("REPRO_DSLASH_TILE")
    if forced:
        return parse_tile(forced)
    backend = backend or jax.default_backend()
    entry = load_tuning_cache().get(
        cache_key(backend, lattice_shape, nrhs, dtype))
    if entry is None:
        return DEFAULT_TILE
    return TileConfig(bz=entry.get("bz"), by=entry.get("by"),
                      batch=entry.get("batch", "block"),
                      stream=entry.get("stream", "blockspec"))


def save_tuning_cache(entries: dict, path: str | None = None,
                      meta: dict | None = None) -> str:
    """Write a tuning-cache JSON (autotune.py's persistence hook)."""
    path = path or os.environ.get("REPRO_TUNING_CACHE_PATH",
                                  DEFAULT_CACHE_PATH)
    doc = {"schema": 1,
           "comment": "dslash launch-space winners per (backend, lattice, "
                      "nrhs, dtype); regenerate with python -m "
                      "repro.kernels.autotune",
           "entries": dict(sorted(entries.items()))}
    if meta:
        doc["meta"] = meta
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    _load_cache.cache_clear()
    return path
