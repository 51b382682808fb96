"""Peaks of the chip and the least HBM bytes of the solver's work.

The bytes are computed from shapes and from the counts the solver returns
(inner steps, restarts), never from a kernel's own blocking: they count the
work, so a kernel that moves more than the work needs shows a lower share.
All of these kernels are bound by memory, so a share is the least bytes
over the peak bandwidth, over the device time of the ops that do the work.

What is counted:
  operator application   the matrix (or the bands) read once, x read once,
                         y written once;
  preconditioner apply   the factor bands read once, v read once, the
                         result written once;
  orthogonalization      the Krylov basis read at its current width: once
                         per step where a kernel may keep it resident for
                         both passes (the fused Arnoldi step, the batched
                         block Gram-Schmidt), twice per step on the
                         pipelined single-reduce path (the projection must
                         finish before either update), plus the vectors.
A change to how many operator reads a step needs (s-step, matrix powers)
needs these counts redone by a benchmark change first.
"""
from __future__ import annotations

import json
import pathlib
from typing import Iterable, List

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")
F32 = 4


class UnknownDevice(ValueError):
    """A device kind with no published peaks in ``peaks.json``."""


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; UnknownDevice when absent."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def cycle_lengths(inner_steps: int, restarts: int, m: int) -> List[int]:
    """Arnoldi steps of each restart cycle of one solve.

    Every cycle but the last runs ``m`` steps unless it stops early; the
    solver returns only the totals, so the steps are laid out that way.
    """
    cycles = max(int(restarts), 1)
    full = min(cycles - 1, int(inner_steps) // m)
    last = int(inner_steps) - full * m
    return [m] * full + ([last] if last > 0 or cycles > full else [])


def basis_reads(lengths: Iterable[int]) -> int:
    """Sum over the steps of all cycles of the basis width, j + 1."""
    return sum(s * (s + 1) // 2 for s in lengths)


def dense_apply(n: int) -> int:
    return F32 * (n * n + 2 * n)


def banded_apply(n: int, nbands: int) -> int:
    return F32 * n * (nbands + 2)


def gmres_counts(inner_steps: int, restarts: int, m: int,
                 pipelined: bool) -> dict:
    """Operator and preconditioner applications of one ``gmres`` solve.

    Plain cycle: one application per step, and one residual per cycle plus
    the initial one.  Pipelined cycle: also a prologue application per
    cycle.  The preconditioner is applied with each step's operator, in
    the pipelined prologue, and once to the cycle's update.
    """
    cycles = max(int(restarts), 1)
    steps = int(inner_steps)
    pro = cycles if pipelined else 0
    return {"operator": steps + pro + cycles + 1,
            "precond": steps + pro + cycles}


def pipelined_orth_bytes(n: int, m: int, inner_steps: int,
                         restarts: int) -> int:
    """Least bytes of the pipelined single-reduce orthogonalization: two
    reads of the basis at its width per step, and five vectors."""
    lengths = cycle_lengths(inner_steps, restarts, m)
    return F32 * n * (2 * basis_reads(lengths) + 5 * int(inner_steps))


def block_cycle_bytes(n: int, inner: Iterable[int]) -> int:
    """Least bytes of one lockstep cycle of the server over k lanes.

    The matrix is read once per block step, whatever the number of lanes,
    and twice more for the residuals before and after; each active lane's
    basis is read once per step at its width, and its vectors in and out.
    """
    inner = [int(s) for s in inner if int(s) > 0]
    block_steps = max(inner) if inner else 0
    k = len(inner)
    a_reads = block_steps + 2
    basis = sum(s * (s + 1) // 2 for s in inner)
    return F32 * (a_reads * n * n + n * (basis + 2 * sum(inner))
                  + a_reads * 2 * n * k)


def solve_parts(run, solve: dict) -> dict:
    """Least bytes of one closed-loop solve, by the part that does them:
    ``operator``, ``precond``, ``orthogonalize`` (the fused dense step
    counts its basis under ``orthogonalize`` and its matrix under
    ``operator``)."""
    n = int(run.shapes["n"])
    m = int(run.config["solver"]["m"])
    steps, restarts = solve["inner_steps"], solve["restarts"]
    pipelined = run.traffic["gs"] == "cgs2_pipelined"
    c = gmres_counts(steps, restarts, m, pipelined)
    nbands = run.shapes.get("nbands")
    apply = banded_apply(n, nbands) if nbands else dense_apply(n)
    parts = {"operator": c["operator"] * apply, "precond": 0,
             "orthogonalize": 0}
    if run.traffic.get("precond"):
        # ILU(0) keeps the operator's band pattern: its two factors hold
        # as many bands as the operator.
        parts["precond"] = c["precond"] * banded_apply(n, nbands)
    if pipelined:
        parts["orthogonalize"] = pipelined_orth_bytes(n, m, steps, restarts)
    else:
        lengths = cycle_lengths(steps, restarts, m)
        parts["orthogonalize"] = F32 * n * basis_reads(lengths)
    return parts


def share(run, nbytes: float, seconds: float):
    """Percent of the HBM roofline: ``nbytes`` at peak over ``seconds``."""
    if run.peaks is None or seconds <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds


def window_share(run, nbytes: float):
    """Percent of the HBM roofline of all the cell's chips: ``nbytes`` at
    the peak of one chip times the chips, over the traced window."""
    if run.trace is None:
        return None
    pct = share(run, nbytes, run.trace.window_s)
    return None if pct is None else pct / int(run.cell["chips"])


def kernel_share(run, parts, kernels):
    """Roofline share of the traced solves' ``parts`` over the device time
    of the ops named ``kernels`` in the traced window, summed over the
    chips: one chip's peak over the time of all of them."""
    if run.trace is None or not run.solves:
        return None
    t = run.trace.kernel_s(kernels)
    if t <= 0:
        return None
    nbytes = sum(solve_parts(run, s)[p] for s in run.solves if s["traced"]
                 for p in parts)
    return share(run, nbytes, t)
