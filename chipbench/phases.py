"""Split the device time of each solve by the phase of the solve it ran in.

The program names the phases of ``plan.solve`` with ``jax.named_scope``
(``solve.gauge_layout``, ``solve.rhs_layout``, ``solve.schur_rhs``,
``solve.krylov``, ``solve.back_substitute``, ``solve.merge``,
``solve.verify``).  A TPU v5e trace names each device op by its HLO
instruction only (``fusion.125``) and carries no ``op_name``, so the phase
of an instruction is read from the compiled solve's optimized HLO
(``compiled.as_text()``), where each instruction's metadata holds the
``op_name`` it was traced under.  An instruction the compiler added with
no scope of its own (a layout copy, a bitcast, the split of a complex
parameter) takes the phase of the nearest instruction that consumes its
result, else of the nearest one it consumes.

The text comes from compiling the cell's solve again from its shapes
(``harness.program_solve``, which the compile cache answers), once per
process and only in a traced run.  An instruction name that also occurs
in the right-hand-side generator's program (``harness.rhs_maker``) is left
unattributed, as is every op of another program.  Each device op counts
its self time (``trace.self_times``), so a ``while`` does not count its
body twice.

Importing this module loads no accelerator runtime.
"""

from __future__ import annotations

import re

from chipbench import harness, trace

KRYLOV = "solve.krylov"
LAYOUT = ("solve.gauge_layout", "solve.rhs_layout", "solve.merge")
VERIFY = "solve.verify"
UNATTRIBUTED = "unattributed"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(solve\.[a-z_]+)(?=/|$)")
_OPERAND = re.compile(r"%([\w.-]+)")


def _group_end(line: str, i: int) -> int:
    """The index of the parenthesis that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(line)):
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        if depth == 0:
            return j
    return len(line)


def _operands(line: str, start: int) -> list[str]:
    """The instruction names among the operands of the instruction whose
    shape starts at ``start`` (``f32[8]{0} add(%a, %b)``, or a tuple
    shape in parentheses)."""
    i = (_group_end(line, start) if line[start] == "("
         else line.find(" ", start))
    i = line.find("(", i)
    return _OPERAND.findall(line, i, _group_end(line, i))


def instruction_phases(hlo_text: str) -> dict[str, str]:
    """{instruction name: its ``solve.*`` phase} over an HLO module's text.

    An instruction whose ``op_name`` names exactly one phase has it; one
    with none takes the phase of the nearest consumer that has one (found
    breadth-first), else of the nearest operand.  Instructions neither
    way reaches are left out, as are those whose ``op_name`` names two.
    """
    own: dict[str, str | None] = {}
    users: dict[str, list[str]] = {}
    operands: dict[str, list[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        found = set(_SCOPE.findall(op.group(1))) if op else set()
        # "" marks two phases in one op_name: no guess
        own[name] = found.pop() if len(found) == 1 else ("" if found
                                                          else None)
        operands[name] = _operands(line, m.end())
        for src in operands[name]:
            users.setdefault(src, []).append(name)

    def nearest(name, edges):
        seen, frontier = {name}, [name]
        while frontier:
            nxt = []
            for n in frontier:
                for e in edges.get(n, ()):
                    if e in seen or e not in own:
                        continue
                    if own[e]:
                        return own[e]
                    seen.add(e)
                    nxt.append(e)
            frontier = nxt
        return None

    out = {}
    for name, phase in own.items():
        if phase is None:
            phase = nearest(name, users) or nearest(name, operands)
        if phase:
            out[name] = phase
    return out


def instruction_names(hlo_text: str) -> set[str]:
    return {m.group(1) for m in map(_INSTR.match, hlo_text.splitlines())
            if m}


def split(tr: trace.Trace, phase_of: dict[str, str],
          foreign: set[str] = frozenset()) -> dict[str, float]:
    """Device self seconds by phase, summed over the chips; ops of other
    programs, and instructions named in ``foreign`` too, under
    ``UNATTRIBUTED``."""
    out: dict[str, float] = {}
    for events in tr.devices.values():
        for e, self_ns in trace.self_times(events):
            phase = (phase_of.get(e.name, UNATTRIBUTED)
                     if e.name not in foreign else UNATTRIBUTED)
            out[phase] = out.get(phase, 0.0) + self_ns / 1e9
    return out


_SPLITS: dict[str, dict | None] = {}


def _compiled_texts(cell) -> tuple[str, str]:
    """(the cell's solve, its right-hand-side generator), compiled from
    their shapes."""
    import jax
    import jax.numpy as jnp

    dims = cell.dims
    u = jax.ShapeDtypeStruct((4,) + dims + (3, 3), jnp.complex64)
    b = jax.ShapeDtypeStruct(dims + (4, 3), jnp.complex64)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    solve = harness.program_solve(jax, cell, u, b).as_text()
    gen = harness.rhs_maker(jax, dims).lower(key).compile().as_text()
    return solve, gen


def window_split(view) -> dict | None:
    """The traced window's device seconds by phase, or None where the run
    was not traced or the program names no phase.  Computed once per
    process."""
    if view.trace is None:
        return None
    key = view.cell.workload
    if key not in _SPLITS:
        solve, gen = _compiled_texts(view.cell)
        phase_of = instruction_phases(solve)
        _SPLITS[key] = (split(view.trace, phase_of, instruction_names(gen))
                        if phase_of else None)
        harness.say(f"phases device_s={_SPLITS[key]!r}")
    return _SPLITS[key]


def solved(view) -> tuple[int, int]:
    """(solves, CG iterations) that converged, verified and finished in
    the window."""
    ok = [r for r in view.window.records if r and r.get("ok")]
    return len(ok), sum(r["iterations"] for r in ok)


def ms_per_solve(view, names) -> float | None:
    """Device milliseconds a solve spends in the phases ``names``."""
    seconds = window_split(view)
    n, _ = solved(view)
    if not seconds or n == 0:
        return None
    return 1000.0 * sum(seconds.get(p, 0.0) for p in names) / n
