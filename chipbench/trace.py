"""Reduce a JAX profiler trace to device busy time, idle gaps and kernel time.

Reads the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes, with
``jax.profiler.ProfileData`` and nothing else.  Device planes are named
``/device:<PLATFORM>:<n>``; their operations sit on the line ``XLA Ops``,
each named by its HLO instruction (``%schur_op.26 = f32[...]
custom-call(...)`` on a TPU v5e); a control-flow op such as ``while``
spans the ops of its body.  An event keeps the instruction's name
(``schur_op.26``) and whether it is a custom call, which is how a Pallas
kernel appears.  Host planes (``/host:...``) carry the host threads'
spans, among them the benchmark's own ``chipbench.*`` annotations, which
name the idle gaps.

Importing this module loads no accelerator runtime.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import glob
import os

OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    kernel: bool = False    # a custom call: a Pallas kernel on the TPU

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """Device operations per chip, and host spans, on one clock (ns)."""

    devices: dict[str, list[Event]]
    host: list[Event]


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [Event(e.name.split(" = ", 1)[0].lstrip("%"), e.start_ns,
                         e.end_ns, "custom-call(" in e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            host.extend(Event(e.name, e.start_ns, e.end_ns)
                        for line in plane.lines for e in line.events)
    return Trace(devices, host)


def merge(events: list[Event]) -> list[tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in out]


def busy_ns(events: list[Event]) -> float:
    return sum(b - a for a, b in merge(events))


def mean_busy_s(trace: Trace) -> float:
    """Busy seconds of the device, averaged over the chips that ran ops."""
    if not trace.devices:
        return 0.0
    return sum(busy_ns(ev) for ev in trace.devices.values()) / (
        1e9 * len(trace.devices))


def matches(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def kernel_time(trace: Trace, patterns) -> tuple[int, float]:
    """(launch count, summed device seconds) of the kernels whose name
    matches a pattern, over every chip."""
    n, ns = 0, 0.0
    for events in trace.devices.values():
        for e in events:
            if e.kernel and matches(e.name, patterns):
                n += 1
                ns += e.dur_ns
    return n, ns / 1e9


def base_name(name: str) -> str:
    """``schur_op.26`` -> ``schur_op``: one name for every call site."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def self_times(events: list[Event]):
    """(event, its duration less the events nested in it) pairs: a ``while``
    keeps only what its body's ops do not cover."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end_ns, stack[-1][0].end_ns) - e.start_ns
        stack.append([e, e.dur_ns])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The device operations that took most time, by self time and summed
    over call sites: [[name, seconds], ...]."""
    total: dict[str, float] = {}
    for events in trace.devices.values():
        for e, self_ns in self_times(events):
            key = base_name(e.name)
            total[key] = total.get(key, 0.0) + self_ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def _host_label(host: list[Event], a: float, b: float) -> str:
    """The shortest host span that covers at least half of [a, b], else
    the one that covers most of it."""
    best, best_key = "host: no span", None
    for e in host:
        ov = min(b, e.end_ns) - max(a, e.start_ns)
        if ov <= 0:
            continue
        key = (ov >= (b - a) / 2, -e.dur_ns if ov >= (b - a) / 2 else ov)
        if best_key is None or key > best_key:
            best, best_key = e.name, key
    return best


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The longest gaps between device ops, each named by the host span
    that covers most of it: [[name, seconds], ...]."""
    gaps = []
    for events in trace.devices.values():
        iv = merge(events)
        gaps.extend((b0 - a1, a1, b0) for (_, a1), (b0, _) in zip(iv, iv[1:]))
    gaps.sort(reverse=True)
    return [[_host_label(trace.host, a, b), d / 1e9]
            for d, a, b in gaps[:n]]
