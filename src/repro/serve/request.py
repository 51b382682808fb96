"""Solve requests and outcomes: the server's wire types.

A request is one right-hand side plus its OWN stopping contract —
``tol`` (relative) and ``max_restarts`` (budget).  Heterogeneous
contracts are the whole point of the serving layer: the batched engine
runs k lanes in lockstep off ONE A stream, and per-lane stopping
(core/gmres.py) lets a loose-tolerance request retire after one restart
while a tight one keeps its lane.

Validation happens HERE, at admission, not in the solver: a NaN/Inf b
poisons every reduction it is batched with (one bad lane's mat-vec is
still one column of the shared block GEMM), so it must never reach a
lane.  Rejected requests get a terminal ``REJECTED`` outcome and never
enter the queue.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

# Terminal / lifecycle states.  Strings, not an Enum: outcomes cross the
# host boundary (JSON metrics, logs) and tests script them literally.
PENDING = "pending"      # admitted, waiting in the queue
RUNNING = "running"      # packed into a lane
DONE = "done"            # converged within its own tol
FAILED = "failed"        # restart budget exhausted before convergence
REJECTED = "rejected"    # refused at admission (invalid b or backpressure)
TIMEOUT = "timeout"      # deadline_ticks expired before convergence

TERMINAL = frozenset({DONE, FAILED, REJECTED, TIMEOUT})


class AdmissionError(ValueError):
    """Request refused at admission; ``.reason`` says why."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def validate_b(b, n: Optional[int] = None, dtype=None) -> np.ndarray:
    """Admission gate for a right-hand side.

    Raises :class:`AdmissionError` on non-finite entries, a shape that
    cannot occupy a lane of the server's (k, n) block, or a dtype that
    cannot represent a real right-hand side of the lane block (complex,
    strings, objects — anything outside real floats/ints; the silent
    jnp cast at pack time would truncate imaginary parts or crash the
    tick loop).  Returns the validated vector as a host ndarray (the
    queue is host-side; device transfer happens at pack time, once, for
    the whole lane block).
    """
    try:
        arr = np.asarray(b)
    except (ValueError, TypeError) as e:
        raise AdmissionError(f"b is not array-like: {e}")
    if not (np.issubdtype(arr.dtype, np.floating)
            or np.issubdtype(arr.dtype, np.integer)):
        raise AdmissionError(
            f"b dtype {arr.dtype} cannot occupy a "
            f"{np.dtype(dtype).name if dtype is not None else 'real'} lane")
    if arr.ndim != 1:
        raise AdmissionError(f"b must be 1-D, got shape {arr.shape}")
    if n is not None and arr.shape[0] != n:
        raise AdmissionError(f"b has n={arr.shape[0]}, server lane n={n}")
    if not np.all(np.isfinite(arr)):
        raise AdmissionError("b contains NaN/Inf")
    return arr


def validate_precond(precond, op) -> None:
    """Admission gate for the server's preconditioner vs its operator.

    A mismatched preconditioner is the one bad parameter that CANNOT be
    caught per-request: it is baked into the compiled cycle, so a wrong-n
    or wrong-format M⁻¹ fails on the first tick INSIDE a lane, poisoning
    every request batched with it.  Validate the pairing once, up front,
    with the field named — the caller sees ``precond`` in the reason, not
    a shape error from the middle of a jitted block GEMM.

    Checks (all metadata-only; plain callables without the
    :class:`~repro.core.preconditioners.Preconditioner` protocol pass
    through — they advertise nothing to check against):

    - ``precond`` is callable at all;
    - ``precond.n`` (if advertised) matches the operator's row count;
    - ``precond.requires_fmt`` (if advertised) matches the operator's
      format tag — e.g. a dense-only block-Jacobi on a banded or sharded
      operator is refused here, not inside a lane.
    """
    if precond is None:
        return
    if not callable(precond):
        raise AdmissionError(
            f"precond is not callable: {type(precond).__name__}")
    name = getattr(precond, "name", type(precond).__name__)
    shape = getattr(op, "shape", None)
    op_n = int(shape[0]) if shape is not None else None
    pc_n = getattr(precond, "n", None)
    if pc_n is not None and op_n is not None and int(pc_n) != op_n:
        raise AdmissionError(
            f"precond '{name}' has n={int(pc_n)}, operator has n={op_n}")
    fmt = getattr(precond, "requires_fmt", None)
    if fmt is not None:
        op_name = type(op).__name__
        op_fmt = (op_name[:-len("Operator")].lower()
                  if op_name.endswith("Operator") else "dense")
        if op_fmt != fmt:
            raise AdmissionError(
                f"precond '{name}' requires a {fmt} operator, "
                f"server operator is {op_fmt}")


def validate_params(tol: float, max_restarts: int,
                    deadline_ticks: Optional[int] = None,
                    *, precond=None, op=None) -> None:
    """Admission gate for the stopping contract itself.

    A non-finite or non-positive ``tol`` can never be met (or is met
    vacuously by garbage), a non-positive ``max_restarts`` lane would
    retire FAILED before its first cycle, and a non-positive deadline
    would TIMEOUT at admission — all of these used to poison a lane or
    wedge the tick loop; now they are REJECTED before touching the queue.

    When ``precond``/``op`` are supplied, the preconditioner/operator
    pairing is validated too (see :func:`validate_precond`) so a server
    constructed with a mismatched M⁻¹ is refused before a handle — and
    its compiled cycle — ever exists.
    """
    tol = float(tol)
    if not np.isfinite(tol) or tol <= 0.0:
        raise AdmissionError(f"tol must be finite and > 0, got {tol}")
    if int(max_restarts) < 1:
        raise AdmissionError(
            f"max_restarts must be >= 1, got {max_restarts}")
    if deadline_ticks is not None and int(deadline_ticks) < 1:
        raise AdmissionError(
            f"deadline_ticks must be >= 1 (or None), got {deadline_ticks}")
    if precond is not None:
        validate_precond(precond, op)


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One admitted solve: rhs + its own stopping contract."""

    rid: int
    b: np.ndarray                 # validated, host-side (n,)
    tol: float = 1e-5             # relative: stop at ||r|| <= tol*||b||
    max_restarts: int = 50        # restart budget before FAILED retirement
    # Wall-tick budget: TIMEOUT retirement after this many scheduler
    # ticks IN A LANE (None = no deadline).  Counted per occupancy, so a
    # retry-on-fresh-lane gets a fresh deadline like it gets a fresh x.
    deadline_ticks: Optional[int] = None
    # Times this request was requeued after a lane fault (quarantine
    # path); bounded by the server's ``fault_retries``.
    retries: int = 0
    # Retirement threshold quantized to the serving handle's compute
    # dtype (server.submit sets it).  Host retirement and the compiled
    # cycle's lane masking MUST compare against the SAME number: a raw
    # float64 tol_abs that rounds differently under the device's float32
    # cast leaves a converged-on-device lane spinning unretired on the
    # host until its budget expires.
    tol_abs_override: Optional[float] = None

    @property
    def tol_abs(self) -> float:
        if self.tol_abs_override is not None:
            return self.tol_abs_override
        return float(self.tol) * float(np.linalg.norm(self.b))


@dataclasses.dataclass(frozen=True)
class SolveOutcome:
    """Terminal record handed back to the submitter."""

    rid: int
    status: str                   # DONE / FAILED / REJECTED / TIMEOUT
    x: Optional[np.ndarray] = None
    residual: float = float("inf")
    restarts: int = 0
    inner_steps: int = 0
    reason: str = ""              # REJECTED: why admission refused it
    # Seconds on the server's clock from submit to the first placement in
    # a lane, and from that placement to retirement.  None where the
    # server did not see the moment: a request refused at admission,
    # failed from the backlog before any placement, or restored from a
    # checkpoint.
    queue_s: Optional[float] = None
    lane_s: Optional[float] = None
