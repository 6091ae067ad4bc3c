"""Test-support utilities.

``collect_eqns``/``pallas_call_eqns`` walk a (Closed)Jaxpr recursively —
through ``pjit``/``while``/``scan``/``cond`` sub-jaxprs but NOT into Pallas
kernel bodies — so tests and benchmarks can assert memory-traffic shapes:
"this operator is exactly N kernel launches and zero other full-field
passes" (the γ5-folding and fused-triad acceptance checks).

``maybe_hypothesis`` lets the property-based tests degrade gracefully on
minimal environments (e.g. the CPU CI job before ``pip install -e .[test]``
has run, or a bare container): when :mod:`hypothesis` is importable it is
returned unchanged; otherwise drop-in stand-ins are returned whose
``@given`` replaces the test with a single ``pytest.skip`` — so the rest
of the module still collects and runs.

Usage in a test module::

    given, settings, st = maybe_hypothesis()

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 100))
    def test_property(n):
        ...
"""

from __future__ import annotations


def collect_eqns(jaxpr, *, into_pallas: bool = False):
    """Yield every equation reachable from ``jaxpr`` (Jaxpr or ClosedJaxpr).

    Recurses through call-like primitives (pjit, while, scan, cond, ...)
    via their jaxpr-valued params; skips the kernel-body jaxpr of
    ``pallas_call`` equations unless ``into_pallas`` — equations inside a
    kernel run from VMEM and must not count as HBM passes.
    """
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for sub in vals:
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    yield from collect_eqns(sub, into_pallas=into_pallas)


def pallas_call_eqns(jaxpr):
    """All ``pallas_call`` equations reachable from ``jaxpr``."""
    return [e for e in collect_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


# Call-like primitives are containers: their outputs are produced by inner
# equations that collect_eqns already walks, so they are not HBM passes
# themselves.
_CONTAINER_PRIMS = frozenset({
    "jit", "pjit", "closed_call", "core_call", "xla_call", "remat", "remat2",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "while", "scan", "cond", "checkpoint", "named_call",
})


def full_field_passes(jaxpr, size: int):
    """Non-pallas compute equations producing an output of ``size`` elements.

    Each such equation materializes a full field outside a kernel — an
    extra HBM round-trip on a real backend.  An operator whose every
    full-field output comes from a ``pallas_call`` returns [] here.
    """
    return [e for e in collect_eqns(jaxpr)
            if e.primitive.name != "pallas_call"
            and e.primitive.name not in _CONTAINER_PRIMS
            and any(getattr(v.aval, "size", 0) == size for v in e.outvars)]


def while_body_psum_counts(jaxpr):
    """Per-``while_loop`` count of ``psum`` collectives in its body.

    Walks every ``while`` equation reachable from ``jaxpr`` (through
    ``shard_map``/``pjit``/... via :func:`collect_eqns`) and counts the
    psum-family equations inside each loop body — the per-iteration
    collective cost of a distributed solver.  The fused-reduction
    contract of DESIGN.md §7 is ``while_body_psum_counts(...) == [1]``
    for the sharded pipelined CGNR: one stacked psum per CG iteration,
    regardless of batch size.
    """
    counts = []
    for eqn in collect_eqns(jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params["body_jaxpr"]
        counts.append(sum(1 for e in collect_eqns(body)
                          if e.primitive.name.startswith("psum")))
    return counts


def maybe_hypothesis():
    """Return (given, settings, st) — real hypothesis or skipping stubs."""
    try:
        from hypothesis import given, settings, strategies as st
        return given, settings, st
    except ImportError:
        pass

    import pytest

    class _AnyStrategy:
        """Accepts any strategy construction; never actually draws."""

        def __getattr__(self, name):
            return lambda *args, **kwargs: None

    def _given(*_args, **_kwargs):
        def deco(fn):
            def _skipped():
                pytest.skip("hypothesis not installed")

            _skipped.__name__ = fn.__name__
            _skipped.__doc__ = fn.__doc__
            return _skipped

        return deco

    def _settings(*_args, **_kwargs):
        return lambda fn: fn

    return _given, _settings, _AnyStrategy()
