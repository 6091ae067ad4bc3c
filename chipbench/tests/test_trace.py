"""The trace reduction, on synthetic events and on a trace recorded on a
TPU v5e chip (``chipbench/record_trace.py``: one eo-schur Pallas CGNR
solve at 16x8x8x8)."""

import os

import pytest

from chipbench import harness, trace

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata", "small_trace", "trace.xplane.pb")


def ev(name, a, b):
    return trace.Event(name, a, b)


def test_merge_takes_the_union():
    got = trace.merge([ev("a", 5, 7), ev("b", 0, 2), ev("c", 1, 3),
                       ev("d", 3, 4), ev("e", 6, 6.5)])
    assert got == [(0, 4), (5, 7)]
    assert trace.busy_ns([ev("a", 0, 2), ev("b", 1, 3)]) == 3


def test_busy_is_averaged_over_chips():
    t = trace.Trace({"/device:TPU:0": [ev("a", 0, 2e9)],
                     "/device:TPU:1": [ev("a", 0, 1e9)]}, [])
    assert trace.mean_busy_s(t) == pytest.approx(1.5)
    assert trace.mean_busy_s(trace.Trace({}, [])) == 0.0


def test_kernel_time_counts_custom_calls_by_pattern():
    t = trace.Trace({"/device:TPU:0": [
        trace.Event("schur_op.1", 0, 10, True),
        trace.Event("fusion.2", 10, 30, False),
        trace.Event("schur_op_fusion", 30, 40, False),
        trace.Event("schur_op.7", 40, 45, True)]}, [])
    assert trace.kernel_time(t, ["schur_op*"]) == (2, 15e-9)
    assert trace.kernel_time(t, ["nothing*"]) == (0, 0.0)


def test_self_time_leaves_out_the_body_of_a_loop():
    t = trace.Trace({"/device:TPU:0": [
        ev("while.3", 0, 100), ev("schur_op.1", 10, 50),
        ev("cg_update.2", 60, 70), ev("copy.1", 120, 130)]}, [])
    assert dict((k, v) for k, v in trace.top_ops(t)) == {
        "while": 50e-9, "schur_op": 40e-9, "copy": 10e-9, "cg_update": 10e-9}
    assert trace.base_name("schur_op.26") == "schur_op"
    assert trace.base_name("while") == "while"


def test_top_ops_and_idle_gaps_named_by_host_spans():
    t = trace.Trace(
        {"/device:TPU:0": [ev("a", 0, 10), ev("b", 20, 50), ev("a", 100, 110)]},
        [ev("outer", 0, 200), ev("chipbench.submit", 55, 95),
         ev("other", 12, 18)])
    assert trace.top_ops(t) == [["b", 30e-9], ["a", 20e-9]]
    gaps = trace.idle_gaps(t)
    assert gaps[0] == ["chipbench.submit", 50e-9]
    assert gaps[1] == ["other", 10e-9]


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def test_recorded_trace_has_one_chip_with_ops(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    ops = recorded.devices["/device:TPU:0"]
    assert len(ops) > 50
    busy = trace.busy_ns(ops)
    span = ops[-1].end_ns - ops[0].start_ns
    assert 0 < busy <= span
    assert any(e.name.startswith("chipbench.") for e in recorded.host)


def test_recorded_trace_names_the_kernels(recorded):
    d = harness.load_module("metrics", "dslash_roofline.py")
    n_d, t_d = trace.kernel_time(recorded, d.PATTERNS)
    n_u, t_u = trace.kernel_time(recorded, ["cg_update*"])
    n_x, t_x = trace.kernel_time(recorded, ["cg_xpay*"])
    # four parity launches an iteration plus the right-hand side's and the
    # back-substitution's; one triad and one xpay an iteration
    assert n_d >= 4 * n_u > 0
    assert n_x >= n_u - 1 > 0
    assert t_d > 0 and t_u > 0 and t_x > 0


def test_recorded_trace_kernel_shares(recorded):
    """At 16x8x8x8 the parity dslash launches read a small share of the
    HBM roofline; the vector kernels' operands sit in VMEM (``S(1)``),
    which is why they are read as a time and not as a roofline share."""
    import types

    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    cell = types.SimpleNamespace(volume=16 * 8 * 8 * 8)
    window = types.SimpleNamespace(records=[{"ok": True, "iterations": 14}])
    view = harness.RunView(cell, window, trace=recorded, window_s=1.0,
                           peak=peak)
    dslash = harness.load_module("metrics", "dslash_roofline.py").read(view)
    cgvec = harness.load_module("metrics", "cgvec_ms_per_iter.py").read(view)
    assert 0 < dslash < 5
    assert 0 < cgvec < 1
