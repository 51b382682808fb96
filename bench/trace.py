"""Reduction of a profiler trace to device busy time, kernel time, idle
gaps and, on several chips, each chip's busy and collective time.

``read_xplane`` turns the ``.xplane.pb`` file that ``jax.profiler`` writes
into plain event lists; everything else works on those lists, so the
reduction is checked against a recorded excerpt (``testdata``) without a
chip.  Times are in nanoseconds on the profiler's clock, which the host
spans and the device ops share.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"(\.\d+|\.clone)+$")
_ENCLOSING = re.compile(r"^(while|conditional|call)$")
# Ops that move data between chips, synchronous or split in a start and a
# done (``all-reduce-start``, ``collective-permute-done``).
_COLLECTIVE = re.compile(r"^(all-reduce|collective-permute|all-gather"
                         r"|reduce-scatter|all-to-all)")


def base_name(op: str) -> str:
    """The HLO instruction an op event names, without its ``.N`` suffix.

    On the TPU an event of the ops line is named by the instruction's whole
    text, ``%gmres_matvec.10 = f32[...] custom-call(...)``.
    """
    return _SUFFIX.sub("", op.split(" = ", 1)[0].strip().lstrip("%"))


def encloses(op: str) -> bool:
    """Control flow (a ``while`` loop, a conditional) whose event spans the
    ops it runs."""
    return bool(_ENCLOSING.match(base_name(op)))


def read_xplane(log_dir: str) -> dict:
    """Device ops per TPU plane and the benchmark's own host spans."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def window(host: Sequence[Event]) -> Tuple[float, float]:
    """Start and end of the measured window, from its host span."""
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if not spans:
        raise RuntimeError(f"trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def clip(events: Iterable[Event], start: float, end: float) -> List[Event]:
    """The parts of ``events`` that lie inside ``[start, end]``."""
    out = []
    for name, s, d in events:
        a, b = max(s, start), min(s + d, end)
        if b > a:
            out.append((name, a, b - a))
    return out


def is_collective(op: str) -> bool:
    """An op that exchanges data between chips."""
    return bool(_COLLECTIVE.match(base_name(op)))


def intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint pairs."""
    merged: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event]) -> float:
    """Time in which at least one op ran."""
    return sum(b - a for a, b in intervals(events))


def overlap_ns(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Time that two lists of sorted disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def exposed_collective_ns(events: Iterable[Event]) -> float:
    """Time in which a collective ran and no other op did.  Control flow,
    whose events span the ops it runs, hides nothing."""
    events = list(events)
    coll = intervals(e for e in events if is_collective(e[0]))
    other = intervals(e for e in events
                      if not is_collective(e[0]) and not encloses(e[0]))
    return sum(b - a for a, b in coll) - overlap_ns(coll, other)


def kernel_ns(events: Iterable[Event], names: Iterable[str]) -> float:
    """Summed device time of the ops whose base name is in ``names``."""
    want = set(names)
    return sum(d for n, _, d in events if base_name(n) in want)


def top_ops(events: Iterable[Event], k: int = 10) -> List[list]:
    """The ``k`` base names with the most device time, in seconds, not
    counting control flow, whose events span the ops they run."""
    tot: Dict[str, float] = {}
    for n, _, d in events:
        if not encloses(n):
            tot[base_name(n)] = tot.get(base_name(n), 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def idle_gaps(events: Iterable[Event], host: Sequence[Event], start: float,
              end: float, k: int = 10) -> List[list]:
    """Idle device time inside the window, summed by what the host did.

    Each gap between busy intervals takes the name of the shortest
    benchmark span (other than the window's) that covers its midpoint, or
    ``host_other`` where none does.
    """
    spans = sorted(((n, s, s + d) for n, s, d in host if n != WINDOW_SPAN),
                   key=lambda x: x[2] - x[1])
    gaps, t = [], start
    for a, b in intervals(events):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if end > t:
        gaps.append((t, end))
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        label = next((n for n, s, e in spans if s <= mid <= e), "host_other")
        tot[label] = tot.get(label, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


class Summary:
    """A traced window reduced for the metric readers.

    ``ops`` maps each TPU plane that ran an op in the window to its ops,
    clipped to the window; ``host`` holds the benchmark's spans.
    """

    def __init__(self, raw: dict):
        self.host: List[Event] = [tuple(e) for e in raw["host"]]
        self.start, self.end = window(self.host)
        self.ops = {p: clip(evs, self.start, self.end)
                    for p, evs in raw["devices"].items()}
        self.ops = {p: evs for p, evs in self.ops.items() if evs}
        if not self.ops:
            raise RuntimeError("no device op ran inside the traced window")

    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy time, averaged over the planes that ran ops."""
        return (sum(busy_ns(e) for e in self.ops.values())
                / len(self.ops) * 1e-9)

    def kernel_s(self, names: Iterable[str]) -> float:
        """Device time of the named ops, summed over the planes."""
        names = list(names)
        return sum(kernel_ns(e, names) for e in self.ops.values()) * 1e-9

    def busy_by_plane(self) -> Dict[str, float]:
        """Busy seconds of each plane: the busiest chip and the idlest."""
        return {p: busy_ns(e) * 1e-9 for p, e in self.ops.items()}

    def collective_by_plane(self) -> Dict[str, float]:
        """Device seconds of each plane's collective ops."""
        return {p: sum(d for n, _, d in e if is_collective(n)) * 1e-9
                for p, e in self.ops.items()}

    def exposed_collective_by_plane(self) -> Dict[str, float]:
        """Seconds of each plane in which a collective ran and no compute
        op overlapped it."""
        return {p: exposed_collective_ns(e) * 1e-9
                for p, e in self.ops.items()}

    def all_ops(self) -> List[Event]:
        return [e for evs in self.ops.values() for e in evs]

    def breakdown(self) -> dict:
        evs = self.all_ops()
        return {"device_ops": top_ops(evs),
                "idle_gaps": idle_gaps(evs, self.host, self.start, self.end)}
