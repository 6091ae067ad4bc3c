"""The §6 streaming-traffic model behind every bw_fraction in the bench
JSONs: closed-form values, monotonicity, and input validation.

``benchmarks/roofline.dslash_intensity`` is the denominator of the
achieved-vs-roofline ``bw_fraction`` column of the bench JSONs — a wrong
model silently re-scales every reported bandwidth fraction, so the
closed form is pinned here:

    bytes/site/RHS = (144 / N + 48) · dtype_bytes
    flops/site     = 1320
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.roofline import dslash_intensity  # noqa: E402

from repro.testing import maybe_hypothesis  # noqa: E402

given, settings, st = maybe_hypothesis()


@pytest.mark.parametrize("n_rhs,dtype_bytes,bytes_per_site", [
    (1, 4, (144 + 48) * 4),         # 768: single RHS, f32
    (1, 2, (144 + 48) * 2),         # 384: single RHS, bf16
    (8, 4, (144 / 8 + 48) * 4),     # 264: gauge amortized over 8 RHS
    (8, 2, (144 / 8 + 48) * 2),     # 132
])
def test_closed_form(n_rhs, dtype_bytes, bytes_per_site):
    m = dslash_intensity(n_rhs, dtype_bytes)
    assert m["bytes_per_site"] == pytest.approx(bytes_per_site)
    assert m["flops_per_site"] == 1320.0
    assert m["flops_per_byte"] == pytest.approx(1320.0 / bytes_per_site)
    assert m["n_rhs"] == n_rhs and m["dtype_bytes"] == dtype_bytes


def test_gauge_amortization_limit():
    """As N -> inf only the spinor term survives: 48 reals/site."""
    m = dslash_intensity(10**6, 4)
    assert m["bytes_per_site"] == pytest.approx(48 * 4, rel=1e-3)


def test_invalid_n_rhs():
    for bad in (0, -1):
        with pytest.raises(ValueError, match="n_rhs"):
            dslash_intensity(bad)


@settings(max_examples=50, deadline=None)
@given(st.integers(min_value=1, max_value=4096),
       st.sampled_from([2, 4, 8]))
def test_intensity_monotone_in_n(n, dtype_bytes):
    """Batching strictly increases arithmetic intensity (gauge reads
    amortize; spinor traffic is constant per RHS)."""
    a = dslash_intensity(n, dtype_bytes)
    b = dslash_intensity(n + 1, dtype_bytes)
    assert b["flops_per_byte"] > a["flops_per_byte"]
    assert b["bytes_per_site"] < a["bytes_per_site"]


def test_intensity_monotone_deterministic():
    """Non-hypothesis fallback: monotone over a fixed ladder."""
    vals = [dslash_intensity(n)["flops_per_byte"]
            for n in (1, 2, 4, 8, 16, 32)]
    assert vals == sorted(vals)
    assert all(b > a for a, b in zip(vals, vals[1:]))
