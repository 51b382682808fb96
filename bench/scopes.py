"""The program's own spans and named scopes in a profiler trace.

The program marks the host phases of its server with ``repro.``-prefixed
spans (``jax.profiler.TraceAnnotation``) and the parts of its compiled
solver with ``jax.named_scope``.  On a TPU v5e the ops line of a trace
names an op by its HLO instruction alone (``%pad.3 = f32[...] pad(...)``),
with no scope and no framework op name among its stats; the scope is in
the compiled program's ``op_name`` metadata.  So an op's scope is found
from the module that ran it (the plane's ``XLA Modules`` line, on the same
clock) and its instruction's metadata in that module's compiled text.

As in ``trace``, everything after ``read_program`` works on plain lists,
so the reduction is checked on recorded excerpts without a chip.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace

Event = trace.Event

PROGRAM_PREFIX = "repro."
MODULES_LINE = "XLA Modules"
# The solver's named scopes (``core/gmres.py``).
SCOPES = ("operator", "precond", "orthogonalize", "arnoldi_step", "lsq",
          "residual")
STEP = "repro.serve.step"
SYNC = "repro.serve.sync"
TICK = "bench.tick"

_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,}]+)")
_OPERAND = re.compile(r"%([^\s,(){}]+)")


def read_program(log_dir: str) -> dict:
    """The program's host spans and, per TPU plane, the modules it ran:
    ``{"spans": [...], "modules": {plane: [...]}}``, events as in
    ``trace``."""
    import jax

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Event] = []
    modules: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            evs = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    evs.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(PROGRAM_PREFIX))
    return {"spans": spans, "modules": modules}


def module_name(event_name: str) -> str:
    """``jit_solve(1234)`` on the modules line -> ``jit_solve``, the name
    the compiled text gives after ``HloModule``."""
    return _MODULE.match(event_name).group(1)


def instruction(op: str) -> str:
    """The instruction an ops-line event names, suffix kept:
    ``%pad.3 = f32[...] pad(...)`` -> ``pad.3``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction -> ``op_name`` metadata, from a compiled module's text.

    An instruction without metadata of its own takes the first
    ``op_name`` inside the computation it calls (a fusion XLA formed),
    else that of its first operand that has one (a copy or bitcast XLA
    inserted to stage its operand).
    """
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    first_in: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        name, rest = ins.groups()
        meta = _OP_NAME.search(rest)
        if meta:
            own[name] = meta.group(1)
            if comp is not None:
                first_in.setdefault(comp, meta.group(1))
            continue
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        operands[name] = _OPERAND.findall(rest)
    for name, comp in calls.items():
        if comp in first_in:
            own[name] = first_in[comp]

    def inherit(name: str, seen: set) -> Optional[str]:
        if name in own or name in seen:
            return own.get(name)
        seen.add(name)
        for arg in operands.get(name, ()):
            found = inherit(arg, seen)
            if found is not None:
                own[name] = found
                return found
        return None

    for name in operands:
        inherit(name, set())
    return own


def module_of(hlo_text: str) -> str:
    """The module name on a compiled text's ``HloModule`` line."""
    return hlo_text.split(None, 2)[1].rstrip(",")


def scope_of(op_name: str) -> str:
    """The program's scopes on an ``op_name`` path, outermost first and
    each once: ``jit(solve)/while/body/residual/operator/jit(matvec)/pad``
    -> ``residual/operator``; ``""`` where the path holds none."""
    return "/".join(dict.fromkeys(p for p in op_name.split("/")
                                  if p in SCOPES))


def scoped_ops(ops: Dict[str, List[Event]], modules: Dict[str, List[Event]],
               names: Dict[str, Dict[str, str]]) -> List[Event]:
    """Each op that is not control flow as ``(scope, start, duration)``.

    ``names`` maps a module name to its ``op_names``; an op whose module
    or instruction is not there gets the scope ``""``.
    """
    out: List[Event] = []
    for plane, evs in ops.items():
        mods = sorted(modules.get(plane, []), key=lambda e: e[1])
        starts = [s for _, s, _ in mods]
        for op, s, d in evs:
            if trace.encloses(op):
                continue
            i = bisect.bisect_right(starts, s) - 1
            path = ""
            if i >= 0 and s <= mods[i][1] + mods[i][2]:
                table = names.get(module_name(mods[i][0]), {})
                path = scope_of(table.get(instruction(op), ""))
            out.append((path, s, d))
    return out


def scope_s(scoped: Iterable[Event], scope: str,
            exclude: Sequence[str] = ()) -> float:
    """Device seconds of the ops under ``scope`` and under none of
    ``exclude``."""
    total = 0.0
    for path, _, d in scoped:
        parts = path.split("/")
        if scope in parts and not any(x in parts for x in exclude):
            total += d
    return total * 1e-9


def by_scope(scoped: Iterable[Event]) -> List[list]:
    """Device seconds per scope path, most first; ``""`` is no scope."""
    tot: Dict[str, float] = {}
    for path, _, d in scoped:
        tot[path] = tot.get(path, 0.0) + d
    return [[p, t * 1e-9] for p, t in sorted(tot.items(),
                                               key=lambda kv: -kv[1])]


def scoped_share(scoped: Sequence[Event]) -> Optional[float]:
    """The share of op time that falls under a named scope."""
    total = sum(d for _, _, d in scoped)
    if total <= 0:
        return None
    return sum(d for p, _, d in scoped if p) / total


def _inside(spans: Sequence[Event], outer: Event) -> List[Event]:
    _, a, d = outer
    return [s for s in spans if a <= s[1] and s[1] + s[2] <= a + d
            and s is not outer]


def cycle_ticks(host: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each ``repro.serve.step`` span that ran a cycle, with the
    nanoseconds of the ``repro.serve.sync`` spans inside it."""
    spans = [e for e in host if e[0].startswith(PROGRAM_PREFIX)]
    out = []
    for step in (e for e in spans if e[0] == STEP):
        syncs = [d for n, _, d in _inside(spans, step) if n == SYNC]
        if syncs:
            out.append((step, sum(syncs)))
    return out


def tick_host_ms(host: Sequence[Event]) -> Optional[float]:
    """Mean host time of a cycle tick besides waiting for the device:
    the step span minus its sync spans."""
    ticks = cycle_ticks(host)
    if not ticks:
        return None
    return sum(step[2] - sync for step, sync in ticks) / len(ticks) * 1e-6


def tick_sync_ms(host: Sequence[Event]) -> Optional[float]:
    """Mean time a cycle tick waits for the device."""
    ticks = cycle_ticks(host)
    if not ticks:
        return None
    return sum(sync for _, sync in ticks) / len(ticks) * 1e-6


def tick_phases_ms(host: Sequence[Event]) -> Dict[str, float]:
    """Mean milliseconds per cycle tick in each ``repro.serve.*`` phase,
    and in the step's own time outside them (``self``)."""
    spans = [e for e in host if e[0].startswith(PROGRAM_PREFIX)]
    ticks = cycle_ticks(host)
    tot: Dict[str, float] = {}
    for step, _ in ticks:
        kids = [s for s in _inside(spans, step) if s[0] != STEP]
        for n, _, d in kids:
            tot[n] = tot.get(n, 0.0) + d
        tot["self"] = tot.get("self", 0.0) + step[2] - sum(
            d for _, _, d in kids)
    return {n: t / len(ticks) * 1e-6 for n, t in tot.items()} if ticks \
        else {}


def tick_span_ms(host: Sequence[Event]) -> Optional[float]:
    """Mean ``bench.tick`` span around the cycle ticks, for comparison
    with ``tick_host_ms + tick_sync_ms``."""
    ticks = [step for step, _ in cycle_ticks(host)]
    outer = [e for e in host if e[0] == TICK
             and any(e[1] <= s[1] and s[1] + s[2] <= e[1] + e[2]
                     for s in ticks)]
    if not outer:
        return None
    return sum(d for _, _, d in outer) / len(outer) * 1e-6


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    if not values:
        return None
    return sorted(values)[math.ceil(q * len(values)) - 1]
