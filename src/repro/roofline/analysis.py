"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch x shape x mesh), in SECONDS per step:

    compute    = FLOPs_per_chip / peak_FLOPs_per_chip
    memory     = HBM_bytes_per_chip / HBM_bandwidth
    collective = sum over collective ops of ring-model time on the slowest
                 axis the op spans (bytes x (g-1)/g / link_bw, x2 for
                 all-reduce)

FLOPs / bytes come from ``compiled.cost_analysis()`` (per-device: cost
analysis runs on the SPMD-partitioned module).  Collective bytes are NOT in
cost_analysis — we parse the optimized HLO text and sum operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
with replica_groups giving each op's group size.

Hardware constants come from ``PEAKS``, keyed by the ``device_kind`` JAX
reports; a kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import NamedTuple, Optional


class ChipPeaks(NamedTuple):
    flops: float        # dense bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    link_bw: float      # usable bytes/s per ICI link direction


# Per-chip peaks by ``jax.Device.device_kind``.  TPU v5e ("TPU v5 lite"):
# 197 TFLOP/s bf16, 819 GB/s HBM and 1,600 Gbit/s of interconnect per chip
# (Google Cloud documentation, "TPU v5e"); the ring model below takes
# 45 GB/s usable per link direction.
PEAKS = {"TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, link_bw=45e9)}
RING_LINKS = 2               # ring uses both directions of one axis


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of ``device_kind``; ValueError when not in PEAKS."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    """Total bytes of all array shapes appearing in a result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int
    count: int = 1

    def ring_seconds(self, link_bw: float) -> float:
        g = max(self.group_size, 2)
        eff = (g - 1) / g
        bw = link_bw * RING_LINKS
        if self.kind == "all-reduce":
            return 2 * self.result_bytes * eff / bw
        if self.kind == "collective-permute":
            return self.result_bytes / bw
        # all-gather result bytes are the FULL gathered buffer; each chip
        # receives (g-1)/g of it.  reduce-scatter/all-to-all move ~result.
        return self.result_bytes * eff / bw


def _match_collective(line: str) -> Optional[tuple]:
    """(kind, result_bytes, group_size) when the HLO line is a collective."""
    s = line.strip()
    m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\d]+)\s+"
                 r"([\w\-]+)\(", s)
    if not m:
        return None
    result_type, opname = m.group(1), m.group(2)
    kind = None
    for c in _COLLECTIVES:
        if opname == c or opname.startswith(c + "-start") or \
                opname.startswith(c + "."):
            kind = c
            break
    if kind is None:
        return None
    rbytes = _shape_bytes(result_type)
    gm = _GROUPS_RE.search(s)
    if gm:
        gsize = len(gm.group(1).split(","))
    else:
        gi = _GROUPS_IOTA_RE.search(s)
        gsize = int(gi.group(2)) if gi else 2
    return kind, rbytes, gsize


def _collect(lines) -> list[CollectiveOp]:
    ops: dict[tuple, CollectiveOp] = {}
    for line in lines:
        key = _match_collective(line)
        if key is None:
            continue
        if key in ops:
            ops[key].count += 1
        else:
            ops[key] = CollectiveOp(*key)
    return list(ops.values())


def parse_collectives(hlo_text: str) -> list[CollectiveOp]:
    return _collect(hlo_text.splitlines())


def split_computations(hlo_text: str) -> dict[str, str]:
    """HLO computation name -> body text (computations are flat in HLO text:
    a ``%name (...) -> ... {`` header at column 0, closed by ``}``)."""
    comps: dict[str, str] = {}
    name, body = None, []
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
            if m:
                name, body = m.group(1), []
                continue
        if name is not None:
            if line.startswith("}"):
                comps[name] = "\n".join(body)
                name, body = None, []
            else:
                body.append(line)
    return comps


def parse_collectives_by_computation(
        hlo_text: str) -> dict[str, list[CollectiveOp]]:
    return {name: _collect(body.splitlines())
            for name, body in split_computations(hlo_text).items()}


def innermost_loop_collectives(hlo_text: str):
    """Collectives of the hot (innermost collective-bearing) while body.

    Whole-program collective counts dilute per-step schedule differences
    with shared prologue/epilogue work (initial residual, final gather, the
    per-restart true-residual recompute), so per-STEP claims — like the
    pipelined scheme's "one psum per Arnoldi step" — must be read off the
    inner loop body.  HLO while ops name their body computation
    (``body=%name``); a body's OWN ``body=`` references give the loop
    nesting (restart cycle -> Arnoldi step -> Givens helper loops).  This
    picks the deepest-nested body that directly issues collectives (ties
    broken toward more collectives — the Arnoldi body; deeper helper loops
    carry none) and returns ``(name, ops)``; ``(None, [])`` when the
    program has no loop collectives.
    """
    comps = split_computations(hlo_text)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    children = {b: set(re.findall(r"body=%?([\w.\-]+)", comps.get(b, "")))
                for b in bodies}

    def depth(b, seen=frozenset()):
        if b in seen:
            return 0
        parents = [p for p, cs in children.items() if b in cs]
        return 1 + max((depth(p, seen | {b}) for p in parents), default=0)

    best = (0, 0)
    best_name, best_ops = None, []
    for name in bodies:
        body = comps.get(name)
        if body is None:
            continue
        ops = _collect(body.splitlines())
        n = sum(o.count for o in ops)
        if n and (depth(name), n) > best:
            best = (depth(name), n)
            best_name, best_ops = name, ops
    return best_name, best_ops


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float          # from cost_analysis (partitioned module)
    hbm_bytes_per_chip: float
    collective_bytes: float        # summed result bytes of collectives
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float             # 6 * N_active * D tokens (global)
    useful_ratio: float            # model_flops / (flops_per_chip * chips)
    bytes_per_device: Optional[float] = None   # memory_analysis if available
    notes: str = ""

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def analyze(*, arch: str, shape: str, mesh_name: str, chips: int,
            cost: dict, hlo_text: str, model_flops: float, device_kind: str,
            bytes_per_device: Optional[float] = None,
            notes: str = "") -> Roofline:
    peaks = chip_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(hlo_text)
    coll_bytes = sum(c.result_bytes * c.count for c in colls)
    coll_s = sum(c.ring_seconds(peaks.link_bw) * c.count for c in colls)
    compute_s = flops / peaks.flops
    memory_s = hbm / peaks.hbm_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops / max(flops * chips, 1.0)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_chip=flops, hbm_bytes_per_chip=hbm,
        collective_bytes=coll_bytes, compute_s=compute_s, memory_s=memory_s,
        collective_s=coll_s, bottleneck=bottleneck, model_flops=model_flops,
        useful_ratio=useful, bytes_per_device=bytes_per_device, notes=notes)


def model_flops_for(cfg, shape, n_active: int) -> float:
    """6*N*D for train (fwd+bwd), 2*N*D for inference shapes."""
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens
