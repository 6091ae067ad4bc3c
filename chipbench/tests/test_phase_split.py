"""The split of a solve's device time by phase (``chipbench/phases.py``), on
synthetic HLO and events, and on a trace recorded on a TPU v5e chip
(``chipbench/record_scoped_trace.py``: one right-hand side drawn and
solved by eo-schur Pallas CGNR at 16x8x8x8, with both programs' text)."""

import gzip
import json
import os
import types

import pytest

from chipbench import harness, phases, trace

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "testdata", "scoped_trace")
METRICS = ("krylov_ms_per_iter", "fixed_ms_per_solve", "layout_ms_per_solve",
           "verify_ms_per_solve")

HLO = """HloModule jit_f, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %schur_op.5 = f32[8]{0:T(8,128)S(1)} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/solve.krylov/while/body/wilson.schur/jit(schur_op)/pallas_call"}
  ROOT %tuple.6 = (s32[], f32[8]{0}) tuple(%p, %schur_op.5)
}

ENTRY %main.9 (u: c64[8], b: c64[8]) -> f32[8] {
  %u = c64[8]{0} parameter(0), metadata={op_name="u"}
  %custom-call.1 = f32[8]{0:T(8,128)S(1)} custom-call(%u), custom_call_target="X64SplitLow", metadata={op_name="u"}
  %fusion.2 = f32[8]{0:T(8,128)S(1)} fusion(%custom-call.1), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/solve.gauge_layout/mul"}
  %copy.3 = f32[8]{0} copy(%fusion.2)
  %while.4 = (s32[], f32[8]{0}) while(%copy.3), condition=%cond, body=%body, metadata={op_name="jit(f)/solve.krylov/while"}
  %add.7 = f32[8]{0} add(%fusion.2, %fusion.2), metadata={op_name="jit(f)/solve.merge/add;jit(f)/solve.verify/add"}
  ROOT %fusion.8 = f32[8]{0} fusion(%while.4, %add.7), kind=kLoop, calls=%fv, metadata={op_name="jit(f)/solve.verify/sub"}
}
"""


def ev(name, a, b):
    return trace.Event(name, a, b)


def test_each_instruction_takes_its_own_phase_or_its_neighbours():
    got = phases.instruction_phases(HLO)
    assert got["schur_op.5"] == "solve.krylov"
    assert got["fusion.2"] == "solve.gauge_layout"
    assert got["while.4"] == "solve.krylov"
    assert got["fusion.8"] == "solve.verify"
    # the compiler's own: from the nearest consumer that has a phase
    assert got["custom-call.1"] == "solve.gauge_layout"
    assert got["copy.3"] == "solve.krylov"
    # two phases in one op_name: left out, not guessed
    assert "add.7" not in got
    assert "tuple.6" in phases.instruction_names(HLO)


def test_split_counts_self_time_and_leaves_foreign_ops_out():
    t = trace.Trace({"/device:TPU:0": [
        ev("fusion.2", 0, 10), ev("while.4", 10, 110),
        ev("schur_op.5", 20, 60), ev("schur_op.5", 60, 100),
        ev("fusion.8", 120, 130), ev("fusion.1", 130, 135),
        ev("fusion.2", 140, 141)]}, [])
    got = phases.split(t, phases.instruction_phases(HLO), {"fusion.1"})
    assert got == pytest.approx({
        "solve.gauge_layout": 11e-9, "solve.krylov": 100e-9,
        "solve.verify": 10e-9, phases.UNATTRIBUTED: 5e-9})


def _view(monkeypatch, tr, texts, records, workload="recorded",
          dims=(16, 8, 8, 8)):
    monkeypatch.setattr(phases, "_SPLITS", {})
    monkeypatch.setattr(phases, "_compiled_texts", lambda cell: texts)
    cell = types.SimpleNamespace(workload=workload, dims=dims)
    window = types.SimpleNamespace(records=records)
    return harness.RunView(cell, window, trace=tr)


def read(name, view):
    return harness.load_module("metrics", name + ".py").read(view)


def test_metrics_on_a_synthetic_window(monkeypatch):
    t = trace.Trace({"/device:TPU:0": [
        ev("fusion.2", 0, 10e6), ev("while.4", 10e6, 110e6),
        ev("fusion.8", 120e6, 130e6)]}, [])
    v = _view(monkeypatch, t, (HLO, ""), [
        {"iterations": 10, "ok": True}, {"iterations": 30, "ok": False}])
    assert read("krylov_ms_per_iter", v) == pytest.approx(10.0)
    assert read("fixed_ms_per_solve", v) == pytest.approx(20.0)
    assert read("layout_ms_per_solve", v) == pytest.approx(10.0)
    assert read("verify_ms_per_solve", v) == pytest.approx(10.0)


def test_metrics_are_silent_untraced_or_without_scopes(monkeypatch):
    t = trace.Trace({"/device:TPU:0": [ev("fusion.2", 0, 10)]}, [])
    records = [{"iterations": 10, "ok": True}]
    unscoped = HLO.replace("solve.", "other.")
    for tr, text, recs in ((None, HLO, records), (t, unscoped, records),
                           (t, HLO, [])):
        view = _view(monkeypatch, tr, (text, ""), recs)
        assert [read(m, view) for m in METRICS] == [None] * 4


@pytest.fixture(scope="module")
def recorded():
    def text(name):
        with gzip.open(os.path.join(RECORDED, name + ".hlo.txt.gz"),
                       "rt") as f:
            return f.read()

    with open(os.path.join(RECORDED, "record.json")) as f:
        record = json.load(f)
    return (trace.load(os.path.join(RECORDED, "trace.xplane.pb")),
            text("solve"), text("rhs"), record)


def test_recorded_phases_cover_the_solve(recorded):
    tr, solve, rhs, _ = recorded
    seconds = phases.split(tr, phases.instruction_phases(solve),
                           phases.instruction_names(rhs))
    busy = trace.mean_busy_s(tr)
    attributed = sum(s for p, s in seconds.items()
                     if p != phases.UNATTRIBUTED)
    assert attributed >= 0.99 * busy
    assert set(seconds) - {phases.UNATTRIBUTED} == {
        "solve.gauge_layout", "solve.rhs_layout", "solve.schur_rhs",
        "solve.krylov", "solve.back_substitute", "solve.merge",
        "solve.verify"}


def test_recorded_while_body_is_counted_once(recorded):
    tr, solve, rhs, _ = recorded
    events = tr.devices["/device:TPU:0"]
    assert any(e.name.startswith("while") for e in events)
    seconds = phases.split(tr, phases.instruction_phases(solve),
                           phases.instruction_names(rhs))
    assert sum(seconds.values()) == pytest.approx(trace.mean_busy_s(tr),
                                                  rel=1e-9)


def test_recorded_metrics_read_and_add_up(monkeypatch, recorded):
    tr, solve, rhs, record = recorded
    v = _view(monkeypatch, tr, (solve, rhs),
              [{"iterations": record["iterations"], "ok": record["ok"]}])
    got = {m: read(m, v) for m in METRICS}
    assert all(x is not None and x > 0 for x in got.values()), got
    whole = (got["krylov_ms_per_iter"] * record["iterations"]
             + got["fixed_ms_per_solve"])
    assert whole == pytest.approx(1000 * trace.mean_busy_s(tr), rel=0.01)
    assert got["layout_ms_per_solve"] + got["verify_ms_per_solve"] < (
        got["fixed_ms_per_solve"])
