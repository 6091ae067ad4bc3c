"""Tuning cache + autotuner: tile selection changes speed, never results.

The launch-space contract of DESIGN.md §13: every tile knob (bz, by,
batch placement, gauge stream) is bitwise-neutral — it steers HBM->VMEM
data movement only, never per-site FMA order — so the checked-in
tuning cache can only change speed.  These tests pin that contract:

* a cache hit visibly changes :func:`pick_tile`'s selection while the
  kernel output stays bitwise identical to the cold-cache default;
* the ``REPRO_DSLASH_TILE`` env override beats the cache;
* the 4-launch jaxpr of ``schur_normal_op`` survives any forced tile;
* illegal bz/by report the legal divisor list in the error message.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LatticeShape, pack_gauge, pack_half_gauge,
                        pack_half_spinor, pack_spinor, random_gauge,
                        random_spinor, split_eo, split_eo_gauge)
from repro.kernels import autotune, dispatch
from repro.kernels.dispatch import (DEFAULT_TILE, TileConfig, cache_key,
                                    parse_tile, pick_tile, save_tuning_cache)
from repro.kernels.wilson_dslash.kernel import (VMEM_BUDGET_BYTES,
                                                _divisors, _fit_tile,
                                                _pick_by,
                                                _pick_bz,
                                                _resolve_parity_tile,
                                                dslash_pallas,
                                                launch_vmem_bytes)
from repro.kernels.wilson_dslash.ops import schur_normal_op
from repro.testing import pallas_call_eqns

# compute tests run interpret-mode kernels — keep the lattice tiny; the
# pure-Python constraint tests use the richer RICH dims below
LAT = LatticeShape(2, 2, 2, 8)
RICH = (2, 4, 4, 8)
MASS = 0.1


@pytest.fixture(scope="module")
def fields():
    key = jax.random.PRNGKey(71)
    ku, kp = jax.random.split(key)
    up = pack_gauge(random_gauge(ku, LAT))
    pp = pack_spinor(random_spinor(kp, LAT))
    ppb = jnp.stack([pack_spinor(random_spinor(
        jax.random.fold_in(kp, i), LAT)) for i in range(2)])
    return up, pp, ppb


@pytest.fixture(autouse=True)
def _clean_tile_env(monkeypatch):
    """Tile selection must come from each test's own setup, not the
    ambient environment or the checked-in cache."""
    monkeypatch.delenv("REPRO_DSLASH_TILE", raising=False)
    monkeypatch.delenv("REPRO_TUNING_CACHE_PATH", raising=False)
    monkeypatch.setenv("REPRO_TUNING_CACHE", "0")


# ---------------------------------------------------------------- knobs


def test_divisors():
    assert _divisors(1) == [1]
    assert _divisors(6) == [1, 2, 3, 6]
    assert _divisors(8) == [1, 2, 4, 8]


def test_pick_bz_defaults():
    # None -> largest divisor <= 4 (the historical heuristic)
    assert _pick_bz(4, None) == 4
    assert _pick_bz(6, None) == 3
    assert _pick_bz(8, None) == 4
    assert _pick_bz(5, None) == 1
    # explicit valid values pass through
    assert _pick_bz(6, 2) == 2


def test_pick_bz_error_lists_legal_values():
    with pytest.raises(ValueError, match=r"bz=3 does not tile the Z extent "
                                         r"4.*legal bz values for Z=4: "
                                         r"\[1, 2, 4\]"):
        _pick_bz(4, 3)
    for bad in (0, -2, 5):
        with pytest.raises(ValueError, match=r"legal bz values for Z=6: "
                                             r"\[1, 2, 3, 6\]"):
            _pick_bz(6, bad)


def test_pick_by_error_lists_legal_values():
    assert _pick_by(4, None) == 4          # None -> full Y
    assert _pick_by(4, 2) == 2
    with pytest.raises(ValueError, match=r"by=3 does not tile the Y extent "
                                         r"4.*\[1, 2, 4\]"):
        _pick_by(4, 3)


def test_fit_tile_shrinks_to_vmem_and_names_the_shape():
    """The full-lattice default tile: the historical default where it
    fits, a smaller block where it does not (24⁴ fields), and an error
    naming the plane when not even bz=1, by=1 fits."""
    f32 = jnp.float32
    assert _fit_tile(2, 2, 4, None, f32, f32) == (2, 2, "block")
    bz, by, batch = _fit_tile(24, 24, 24, None, f32, f32)
    assert (batch, 24 % bz, 24 % by) == ("block", 0, 0)
    assert launch_vmem_bytes(bz, by, 24, 24, 1, f32,
                             f32) <= VMEM_BUDGET_BYTES
    assert launch_vmem_bytes(_pick_bz(24, None), 24, 24, 24, 1, f32,
                             f32) > VMEM_BUDGET_BYTES
    with pytest.raises(ValueError, match=r"\(Z, Y, X\) = \(24, 24, 4096\)"):
        _fit_tile(24, 24, 4096, None, f32, f32)


def test_parity_fit_tile_holds_whole_planes_and_names_the_shape():
    """Parity launches hold whole t-planes: the body chunk is one sublane
    tile (bz = 8) at 24³×48, at 16³×32 and at 32³×64, the whole Z where
    no sublane tile divides it (Z = 20) or the storage is bf16, an
    explicit chunk outside those is refused, and the error names the
    plane when nothing fits."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    fit = lambda dims, dt: _resolve_parity_tile(None, dims, dt,
                                                gauge_dtype=dt, has_acc=True)
    for dims in ((48, 24, 24, 12), (32, 16, 16, 8), (64, 32, 32, 16)):
        assert fit(dims, f32) == 8
    assert fit((48, 24, 24, 12), bf16) == 24
    assert fit((32, 16, 16, 8), bf16) == 16
    assert fit((8, 20, 20, 10), f32) == 20
    assert fit((2, 4, 4, 4), f32) == 4
    assert _resolve_parity_tile(1, (48, 24, 24, 12), f32, gauge_dtype=f32,
                                has_acc=False) == 1
    with pytest.raises(ValueError, match=r"bz=4 is not a parity chunk.*"
                                         r"\[1, 8, 24\]"):
        _resolve_parity_tile(4, (48, 24, 24, 12), f32, gauge_dtype=f32,
                             has_acc=False)
    with pytest.raises(ValueError, match=r"bz=1 is not a parity chunk.*"
                                         r"\[16\]"):
        _resolve_parity_tile(1, (32, 16, 16, 8), bf16, gauge_dtype=bf16,
                             has_acc=False)
    with pytest.raises(ValueError, match=r"\(Z, Y\*Xh\) = \(64, 8192\)"):
        fit((8, 64, 128, 64), f32)


def test_tile_config_validates():
    with pytest.raises(ValueError, match="batch placement"):
        TileConfig(batch="rows")
    with pytest.raises(ValueError, match="gauge stream"):
        TileConfig(stream="prefetch")


def test_parse_tile():
    t = parse_tile("bz=2,by=4,batch=grid,stream=db")
    assert t == TileConfig(bz=2, by=4, batch="grid", stream="db")
    assert parse_tile("bz=2") == TileConfig(bz=2)
    assert parse_tile("bz=none,stream=db") == TileConfig(stream="db")
    with pytest.raises(ValueError, match="legal keys"):
        parse_tile("bx=2")


def test_cache_key_format():
    assert (cache_key("cpu", (4, 4, 4, 8), 8, jnp.float32)
            == "cpu|4x4x4x8|nrhs8|float32")
    assert (cache_key("tpu", (8, 8, 8, 16), 1, jnp.bfloat16)
            == "tpu|8x8x8x16|nrhs1|bfloat16")


# ------------------------------------------------------- cache dispatch


def test_pick_tile_cold_cache_is_default():
    assert pick_tile(LAT.dims, 1, jnp.float32) == DEFAULT_TILE


def test_cache_round_trip(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    tuned = TileConfig(bz=2, by=1, batch="block", stream="blockspec")
    save_tuning_cache(
        {cache_key("cpu", LAT.dims, 1, jnp.float32): tuned.to_entry()},
        path=path)
    monkeypatch.setenv("REPRO_TUNING_CACHE", "1")
    monkeypatch.setenv("REPRO_TUNING_CACHE_PATH", path)
    # hit: the persisted winner comes back
    assert pick_tile(LAT.dims, 1, jnp.float32) == tuned
    # miss (different nrhs): deterministic defaults
    assert pick_tile(LAT.dims, 8, jnp.float32) == DEFAULT_TILE
    # kill switch
    monkeypatch.setenv("REPRO_TUNING_CACHE", "0")
    assert pick_tile(LAT.dims, 1, jnp.float32) == DEFAULT_TILE
    # env override beats the cache
    monkeypatch.setenv("REPRO_TUNING_CACHE", "1")
    monkeypatch.setenv("REPRO_DSLASH_TILE", "bz=1,stream=db")
    assert pick_tile(LAT.dims, 1, jnp.float32) == TileConfig(bz=1,
                                                             stream="db")


def test_cache_hit_changes_tile_not_results(tmp_path, monkeypatch, fields):
    """The acceptance property: a cache hit changes the tile selection
    (visible via pick_tile) without changing the kernel output bitwise."""
    up, pp, _ = fields
    ref = np.asarray(dslash_pallas(up, pp, MASS))       # cache disabled

    path = str(tmp_path / "cache.json")
    tuned = TileConfig(bz=1, by=1, batch="block", stream="blockspec")
    save_tuning_cache(
        {cache_key("cpu", LAT.dims, 1, jnp.float32): tuned.to_entry()},
        path=path)
    monkeypatch.setenv("REPRO_TUNING_CACHE", "1")
    monkeypatch.setenv("REPRO_TUNING_CACHE_PATH", path)
    assert pick_tile(LAT.dims, 1, jnp.float32) == tuned != DEFAULT_TILE
    out = np.asarray(dslash_pallas(up, pp, MASS))       # all-None -> cache
    assert np.array_equal(out, ref)


def test_env_tile_bitwise(monkeypatch, fields):
    up, pp, _ = fields
    ref = np.asarray(dslash_pallas(up, pp, MASS))
    monkeypatch.setenv("REPRO_DSLASH_TILE", "bz=2,stream=db")
    assert np.array_equal(np.asarray(dslash_pallas(up, pp, MASS)), ref)


# ------------------------------------------------ launch-space sweep


def test_candidates_respect_constraints():
    cands = autotune.candidates(RICH, 1, max_bz=8)
    assert cands, "empty candidate list"
    for c in cands:
        assert RICH[1] % c.bz == 0
        assert RICH[2] % c.by == 0
        assert c.batch == "block"                      # nrhs=1: no grid
        if c.stream == "db":                           # db: untiled Y only
            assert c.by == RICH[2]
    batched = autotune.candidates(RICH, 8, max_bz=8)
    assert any(c.batch == "grid" for c in batched)
    assert not any(c.batch == "grid" and c.stream == "db" for c in batched)


# one representative per launch-space knob + the all-knobs composite
# (the full candidate product is swept nightly by the autotuner itself;
# interpret-mode tracing makes each config ~10s, so tier-1 samples)
TILE_SAMPLE = [
    TileConfig(bz=1),                                  # non-default z block
    TileConfig(by=1),                                  # y-tiled splice path
    TileConfig(batch="grid"),                          # trailing batch dim
    TileConfig(stream="db"),                           # explicit dbl-buffer
    TileConfig(bz=1, by=1, batch="grid"),              # composite
]


@pytest.mark.parametrize("tile", TILE_SAMPLE, ids=str)
def test_tile_knobs_bitwise(fields, tile):
    """Each launch-space knob produces bitwise-identical output — the
    property that lets autotune skip accuracy checks."""
    up, _, ppb = fields
    ref = np.asarray(dslash_pallas(up, ppb, MASS))
    out = dslash_pallas(up, ppb, MASS, bz=tile.bz, by=tile.by,
                        batch=tile.batch, stream=tile.stream)
    assert np.array_equal(np.asarray(out), ref), tile


def test_sweep_smoke_and_autotune_roundtrip(tmp_path, monkeypatch):
    """Tiny end-to-end sweep: winner comes from the candidate list, the
    persisted entry round-trips through pick_tile."""
    dims = (2, 2, 2, 8)
    winner, results = autotune.sweep(dims, 1, max_bz=2, sweep_by=False,
                                     iters=1, reps=1)
    assert len(results) == len(autotune.candidates(dims, 1, max_bz=2,
                                                   sweep_by=False))
    assert all(r["us_warm"] > 0 for r in results)
    assert winner in autotune.candidates(dims, 1, max_bz=2, sweep_by=False)

    entries = {cache_key(jax.default_backend(), dims, 1, jnp.float32):
               {**winner.to_entry(), "us_warm": 1.0, "candidates":
                len(results)}}
    path = str(tmp_path / "cache.json")
    save_tuning_cache(entries, path=path,
                      meta={"backend": jax.default_backend()})
    monkeypatch.setenv("REPRO_TUNING_CACHE", "1")
    monkeypatch.setenv("REPRO_TUNING_CACHE_PATH", path)
    assert pick_tile(dims, 1, jnp.float32) == winner


# --------------------------------------------- launch-count invariants


def test_schur_four_launches_under_forced_tile(monkeypatch):
    """A forced tile steers only the full-lattice kernel: schur_normal_op
    stays exactly 4 kernel launches, and bitwise the same, under it."""
    lat = LatticeShape(2, 2, 2, 4)
    ku, kp = jax.random.split(jax.random.PRNGKey(5))
    u_e, u_o = split_eo_gauge(random_gauge(ku, lat))
    p_e, _ = split_eo(random_spinor(kp, lat))
    upe, upo = pack_half_gauge(u_e), pack_half_gauge(u_o)
    ppe = pack_half_spinor(p_e)
    xh = lat.x // 2
    ref = np.asarray(schur_normal_op(upe, upo, ppe, MASS, xh=xh))

    monkeypatch.setenv("REPRO_DSLASH_TILE", "bz=1,stream=db")
    jax.clear_caches()          # trace again, under the override
    jaxpr = jax.make_jaxpr(
        lambda a, b, c: schur_normal_op(a, b, c, MASS, xh=xh))(upe, upo,
                                                              ppe)
    assert len(pallas_call_eqns(jaxpr)) == 4
    assert np.array_equal(
        np.asarray(schur_normal_op(upe, upo, ppe, MASS, xh=xh)), ref)


def test_schur_four_launches_under_forced_chunk():
    """schur_normal_op stays exactly 4 kernel launches under a forced
    one-row chunk, and agrees with the default chunk to f32 rounding.
    The chunk's arithmetic is the same site by site, but the CPU
    interpreter compiles (bz, lanes) arrays of another shape into other
    fusions, which round a multiply-add differently (about 1e-7 of the
    field's scale), so the comparison is not bitwise."""
    lat = LatticeShape(2, 2, 2, 4)
    ku, kp = jax.random.split(jax.random.PRNGKey(5))
    u_e, u_o = split_eo_gauge(random_gauge(ku, lat))
    p_e, _ = split_eo(random_spinor(kp, lat))
    upe, upo = pack_half_gauge(u_e), pack_half_gauge(u_o)
    ppe = pack_half_spinor(p_e)
    xh = lat.x // 2
    ref = np.asarray(schur_normal_op(upe, upo, ppe, MASS, xh=xh))
    jaxpr = jax.make_jaxpr(
        lambda a, b, c: schur_normal_op(a, b, c, MASS, xh=xh, bz=1))(
            upe, upo, ppe)
    assert len(pallas_call_eqns(jaxpr)) == 4
    out = np.asarray(schur_normal_op(upe, upo, ppe, MASS, xh=xh, bz=1))
    np.testing.assert_allclose(out, ref, rtol=0,
                               atol=1e-6 * np.abs(ref).max())


def test_checked_in_cache_is_well_formed():
    """The committed tuning_cache.json parses and every entry is a legal
    TileConfig under its own key's lattice."""
    import json
    with open(dispatch.DEFAULT_CACHE_PATH) as f:
        doc = json.load(f)
    assert doc["schema"] == 1
    assert doc["entries"], "checked-in cache has no entries"
    for key, e in doc["entries"].items():
        backend, dims, nrhs, dtype = key.split("|")
        t, z, y, x = (int(d) for d in dims.split("x"))
        tile = TileConfig(bz=e["bz"], by=e["by"], batch=e["batch"],
                          stream=e["stream"])
        assert z % tile.bz == 0 and y % tile.by == 0, key
        assert nrhs.startswith("nrhs") and int(nrhs[4:]) >= 1
        jnp.dtype(dtype)                       # parses
