"""The general harness: one cell, one run, driven by the files it names.

A cell is ``{config, traffic}``.  ``configs/<config>.json`` is the
deployment (volume, operator family, tolerance, plan, reference);
``traffic/<mix>.json`` names its driver, ``drivers/<driver>.py``, and that
driver's parameters; ``metrics/<metric>.py`` reads one metric.  A cell is
looked up in ``BENCHMARK.json``, then in ``staged.json`` (cells measured but
not admitted yet).  Nothing here is specific to one cell.

A driver's ``run(jax, cell, seed, seconds, ctx, setup, hooks)`` sets up the
cell's own program and shapes (recording ``compile_s`` and ``warmup_s`` in
``setup``), calls ``ctx["start_window"]()``, drives the program for
``seconds``, calls ``ctx["stop_window"]()``, and returns a :class:`Window`.

Every run checks what the timed path returned against the plain reference
operator (``references/<name>.py``), after the window has closed and the
program's state is freed.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")
DRAIN_S = 60.0


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(BENCH_DIR, *parts)
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """What a run needs to know about its cell, read from the files."""

    workload: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def dims(self) -> tuple[int, int, int, int]:
        lat = self.config["lattice"]
        return (lat["T"], lat["Z"], lat["Y"], lat["X"])

    @property
    def volume(self) -> int:
        t, z, y, x = self.dims
        return t * z * y * x

    @property
    def mass(self) -> float:
        return float(self.traffic.get("mass", self.config["mass"]))

    @property
    def mu(self) -> float:
        return float(self.config.get("mu", 0.0))


def read_cell(bench: dict, workload: str, overrides: dict | None = None
              ) -> Cell:
    """The cell ``workload`` of ``bench`` (BENCHMARK.json), or else of
    ``staged.json``, with ``overrides`` (``key`` for the configuration,
    ``traffic.key`` for the traffic) applied."""
    if workload not in {w["name"] for w in bench["workloads"]}:
        staged = load_json("staged.json")
        bench = {key: bench[key] + staged[key] for key in
                 ("configs", "workloads", "end_to_end", "per_layer")}
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    config = load_json("configs", w["config"] + ".json")
    traffic = load_json("traffic", w["traffic"] + ".json")
    for key, value in (overrides or {}).items():
        target = traffic if key.startswith("traffic.") else config
        target[key.split(".", 1)[-1] if "." in key else key] = value

    def mine(m):
        return workload in m.get("workloads", [workload])

    return Cell(workload, int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def init_jax(chips: int, require_tpu: bool):
    """Import JAX with the checkout's compilation cache; check the chips."""
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, devices[:chips]


class CompileCounter:
    """Counts XLA compilations while ``active``; none belong in a window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1


def make_gauges(jax, cell: Cell, seed: int):
    """(plane-layout gauge fields for the reference, natural ones for the
    program), one pair per gauge field of the deployment."""
    from chipbench import data

    dims = cell.dims

    @jax.jit
    def draw(key):
        planes = data.gauge_planes(key, dims)
        return planes, data.to_natural_gauge(planes, dims)

    n = int(cell.config.get("gauge_fields", 1))
    pairs = [draw(data.seed_key(seed, 0, g)) for g in range(n)]
    jax.block_until_ready(pairs)
    return [p for p, _ in pairs], [q for _, q in pairs]


def rhs_maker(jax, dims):
    """key -> natural-layout spinor, one jitted call on the device."""
    from chipbench import data

    return jax.jit(lambda key: data.to_natural_spinor(
        data.spinor_planes(key, dims), dims))


def rhs_planes_maker(jax, dims):
    from chipbench import data

    return jax.jit(lambda key: data.spinor_planes(key, dims))


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def program_solve(jax, cell: Cell, u, b):
    """The cell's compiled ``plan.solve``: (u, b) -> (x, SolveStats)."""
    from repro.core import plan as plan_mod

    p = cell.config["plan"]
    plan = plan_mod.SolverPlan(
        operator=p["operator"], backend=p["backend"], solver=p["solver"],
        operator_family=cell.config["operator_family"], mu=cell.mu)
    mass, tol = cell.mass, float(cell.config["tol"])
    maxiter = int(cell.config["maxiter"])
    fn = jax.jit(lambda u, b: plan_mod.solve(plan, u, b, mass, tol=tol,
                                             maxiter=maxiter))
    return fn.lower(u, b).compile()


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    """What a driver hands back: records, answers to check, timings."""

    records: list
    answers: list           # (gauge index, rhs key, x) to compare
    t0: float               # window start (host clock)
    t_close: float          # when --seconds had passed
    t_end: float            # when the last timed work finished
    failed: int = 0
    server_metrics: dict | None = None
    extra: dict = dataclasses.field(default_factory=dict)


class Tracer:
    """Starts and stops the profiler around the window when asked to."""

    def __init__(self, jax, enabled: bool):
        self.jax, self.enabled = jax, enabled
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") if enabled \
            else None
        self.span = None

    def start(self):
        if self.enabled:
            self.jax.profiler.start_trace(self.dir)
            self.span = [time.perf_counter(), None]

    def stop(self):
        if self.enabled and self.span and self.span[1] is None:
            self.span[1] = time.perf_counter()
            self.jax.profiler.stop_trace()


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------


def compare(jax, cell: Cell, seed: int, window: Window, gauge_planes):
    """The worst relative residual ||D x - b|| / ||b|| of the checked
    answers, with the reference operator; None when nothing was checked."""
    ref = load_module("references", cell.config["reference"] + ".py")
    from chipbench import data

    planes = rhs_planes_maker(jax, cell.dims)
    worst = None
    for g, key, x in window.answers:
        rel = float(ref.relative_residual(
            gauge_planes[g], data.from_natural_spinor(x), planes(key),
            cell.mass, cell.mu, yx=cell.dims[2:]))
        if not math.isfinite(rel):
            rel = math.inf
        worst = rel if worst is None else max(worst, rel)
    return worst


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunView:
    """What a metric reader (``metrics/<name>.py``, ``read(view)``) gets."""

    cell: Cell
    window: Window
    setup_s: float | None = None
    trace: object | None = None     # trace.Trace of the window, when traced
    window_s: float | None = None   # length of the traced window
    peak: dict | None = None        # peaks.json entry of the device


def read_metrics(view: RunView, metrics: list) -> dict:
    """Each metric's reader by its name; one that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"] + ".py").read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             overrides: dict | None = None, hooks: dict | None = None
             ) -> dict:
    """Run one cell once; returns the result object (last stdout line)."""
    hooks = hooks or {}
    cell = read_cell(bench, workload, overrides)
    setup = {}
    t = time.perf_counter()
    jax, devices = init_jax(cell.chips, require_tpu)
    dev = devices[0]
    peak = None
    if require_tpu:
        from chipbench import peaks

        peak = peaks.peak_for(dev.device_kind)
    setup["init_s"] = time.perf_counter() - t
    say(f"device platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} cache={CACHE_DIR}")

    t = time.perf_counter()
    gauge_planes, gauges = make_gauges(jax, cell, seed)
    setup["data_s"] = time.perf_counter() - t

    counter = CompileCounter()
    tracer = Tracer(jax, trace)
    marks = {}

    def start_window():
        marks["setup_s"] = time.perf_counter() - t_start
        counter.active = True
        tracer.start()

    def stop_window():
        tracer.stop()
        counter.active = False

    ctx = {"gauges": gauges, "start_window": start_window,
           "stop_window": stop_window}
    driver = load_module("drivers", cell.traffic["driver"] + ".py")
    window = driver.run(jax, cell, seed, seconds, ctx, setup, hooks)
    setup_s = marks["setup_s"]
    say("setup " + " ".join(f"{k}={v!r}" for k, v in setup.items())
        + f" setup_s={setup_s!r}")
    say(f"window: records={len(window.records)} failed={window.failed} "
        f"span_s={window.t_end - window.t0!r} "
        f"compiles_in_window={counter.count}")

    stats = dev.memory_stats() or {}
    mem_peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices))
    say(f"memory peak_bytes_in_use={mem_peak} "
        f"bytes_limit={stats.get('bytes_limit')}")

    # the program's state goes before the reference runs
    del gauges, ctx
    gc.collect()
    worst = compare(jax, cell, seed, window, gauge_planes)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": None, "attempted": len(window.records),
              "failed": window.failed, "metrics": {}, "device": device}
    if trace:
        from chipbench import trace as trace_mod

        tr = trace_mod.load(trace_mod.find_xplane(tracer.dir))
        window_s = tracer.span[1] - tracer.span[0]
        device["busy_s"] = trace_mod.mean_busy_s(tr)
        device["window_s"] = window_s
        view = RunView(cell, window, setup_s, tr, window_s, peak)
        result["metrics"] = read_metrics(view, cell.per_layer)
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        result["metrics"] = read_metrics(RunView(cell, window, setup_s),
                                         cell.end_to_end)

    limit = float(cell.config["residual_limit"])
    checks = {
        "max_rel_residual": {"value": worst, "limit": limit},
        "answers_checked": {"value": len(window.answers), "limit": 1},
        "failed_answers": {"value": window.failed, "limit": 0},
    }
    result["correct"] = bool(worst is not None and worst <= limit
                             and len(window.answers) >= 1
                             and window.failed == 0)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"[chipbench] check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
