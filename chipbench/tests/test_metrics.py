"""The byte and flop counts of the per-layer metrics, and their readers on
synthetic runs."""

import types

import pytest

from chipbench import harness, trace

V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def metric(name):
    return harness.load_module("metrics", name + ".py")


def view(records=(), trace_=None, window_s=None, peak=V5E,
         server_metrics=None, t0=0.0, t_end=1.0, t_close=1.0):
    cell = types.SimpleNamespace(volume=48 * 24 ** 3)
    window = types.SimpleNamespace(records=list(records), t0=t0, t_end=t_end,
                                   t_close=t_close,
                                   server_metrics=server_metrics)
    return harness.RunView(cell, window, trace=trace_, window_s=window_s,
                           peak=peak)


def test_solve_bytes_per_iteration_is_3936_per_even_site():
    m = metric("solve_hbm_share")
    assert m.ITER_B == 4 * 768 + 9 * 96 == 3936
    v = 48 * 24 * 24 * 24
    assert m.solve_bytes(v, 51) - m.solve_bytes(v, 50) == (v // 2) * 3936
    # 51 iterations at 24^3x48: 66.6 GB of iterations plus the fixed work
    assert m.solve_bytes(v, 51) == pytest.approx(
        (v // 2) * (51 * 3936 + 6 * 768 + 24 * 96))


def test_solve_hbm_share_reads_bytes_over_wall_time():
    m = metric("solve_hbm_share")
    recs = [{"ok": True, "iterations": 51}, {"ok": True, "iterations": 51},
            {"ok": False, "iterations": 1000}]
    v = view(recs, t0=10.0, t_end=18.0)
    want = 100 * 2 * m.solve_bytes(48 * 24 ** 3, 51) / 819e9 / 8.0
    assert m.read(v) == pytest.approx(want)
    assert m.read(view([])) is None


def test_dslash_launch_cost():
    m = metric("dslash_roofline")
    nbytes, flop = m.launch_cost(48 * 24 ** 3)
    assert nbytes == 331776 * 768
    assert flop == 331776 * 1320
    nb4, _ = m.launch_cost(48 * 24 ** 3, nrhs=4)
    assert nb4 == 331776 * (144 + 4 * 48) * 4


def synthetic_trace(events, host=()):
    """Device ops (name, start, end), the Pallas ones as custom calls."""
    ops = [trace.Event(n, a, b, not n.startswith("fusion"))
           for n, a, b in events]
    return trace.Trace({"/device:TPU:0": ops},
                       [trace.Event(*e) for e in host])


def test_kernel_metrics_on_a_synthetic_trace():
    d = metric("dslash_roofline")
    c = metric("cgvec_ms_per_iter")
    vol = 48 * 24 ** 3
    nbytes, _ = d.launch_cost(vol)
    t_dslash = 2 * nbytes / 819e9  # each launch at half the peak
    tr = synthetic_trace([
        ("schur_op.3", 0, t_dslash * 1e9),
        ("cg_update.1", 1e9, 1e9 + 3e6),
        ("cg_xpay.2", 2e9, 2e9 + 1e6),
        ("fusion.3", 3e9, 4e9)])
    recs = [{"ok": True, "iterations": 2}, {"ok": True, "iterations": 2}]
    v = view(recs, trace_=tr, window_s=5.0)
    assert d.read(v) == pytest.approx(50.0)
    assert c.read(v) == pytest.approx(1.0)
    assert c.read(view(recs)) is None
    assert d.read(view(trace_=synthetic_trace([("fusion", 0, 1)]))) is None
    # a fusion that happens to carry a kernel's name is not the kernel
    assert d.read(view(trace_=synthetic_trace([("fusion", 0, 1)]))) is None
    assert d.read(view()) is None


@pytest.mark.parametrize("name", ["idle_share.solve", "idle_share.serve",
                                  "idle_share.overload"])
def test_idle_share(name):
    m = metric(name)
    tr = synthetic_trace([("a", 0, 1e9), ("b", 0.5e9, 2e9), ("c", 3e9, 3.5e9)])
    assert m.read(view(trace_=tr, window_s=5.0)) == pytest.approx(50.0)
    assert m.read(view()) is None


def test_iterations_and_queue_readers():
    recs = [{"ok": True, "iterations": 50, "queue_s": 0.01},
            {"ok": True, "iterations": 52, "queue_s": 0.03},
            {"ok": False, "iterations": 1000}]
    assert metric("iterations").read(view(recs)) == 51
    assert metric("queue_ms.serve").read(view(recs)) == pytest.approx(20.0)
    assert metric("iterations").read(view()) is None


def test_batch_fill():
    m = metric("batch_fill.overload")
    sm = {"batch_hist": {"8": 10, "3": 2}, "rung_hist": {"8": 10, "4": 2}}
    assert m.read(view(server_metrics=sm)) == pytest.approx(100 * 86 / 88)
    assert m.read(view()) is None


def test_s_per_rhs_counts_the_whole_span_over_verified_solves():
    recs = [{"ok": True}, {"ok": True}, {"ok": False}, {"ok": True}]
    m = metric("s_per_rhs")
    assert m.read(view(recs, t0=2.0, t_end=14.0)) == pytest.approx(4.0)
    assert m.read(view([{"ok": False}])) is None


def test_serve_p90_is_nearest_rank_from_the_due_time():
    m = metric("serve_p90_s")
    recs = [{"due": 0.0, "done": 0.1 * (k + 1), "ok": True}
            for k in range(20)]
    assert m.read(view(recs)) == pytest.approx(1.8)
    # a failed request is infinitely late
    for k in range(2):
        recs[k] = {"due": 0.0, "done": None, "ok": False}
    assert m.read(view(recs)) == pytest.approx(2.0)
    recs[2] = {"due": 0.0, "done": None, "ok": False}
    assert m.read(view(recs)) is None


def test_serve_rate_counts_what_was_served_by_the_close():
    m = metric("serve_rhs_per_s")
    recs = [{"ok": True, "done": 5.0}, {"ok": True, "done": 9.0},
            {"ok": True, "done": 11.0}, {"ok": False, "done": 3.0},
            {"ok": False, "done": None}]
    assert m.read(view(recs, t0=0.0, t_close=10.0)) == pytest.approx(0.2)


def test_setup_reader():
    v = view()
    v.setup_s = 12.5
    assert metric("setup_s").read(v) == 12.5
