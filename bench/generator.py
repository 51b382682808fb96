"""Traffic from a mix's parameters and a seed.

One generator reads every traffic file under ``bench/traffic``.  A mix is
either a closed loop (one caller, solves back to back, right-hand sides
from a seeded pool) or an open loop (requests due on a schedule, whatever
the server is doing).

Every seed draws the same set of gaps, tolerances and pool rows, in another
order: gaps and tolerances are stratified quantiles of their distributions,
permuted by the seed.  So two seeds ask for the same work, and a run's
spread is the system's, not the sample's.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one run's ``seed``."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def key_int(seed: int, stream: str) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` from a seed of any size."""
    return int(rng(seed, stream).integers(0, 2**31 - 1))


def pool_order(traffic: dict, seed: int) -> np.ndarray:
    """The order in which a run takes the rows of its right-hand-side pool."""
    return rng(seed, "pool").permutation(int(traffic["pool"]))


class Schedule(NamedTuple):
    due: np.ndarray      # seconds after the window opens, ascending
    rhs: np.ndarray      # pool row of each request
    tol: np.ndarray      # relative tolerance of each request


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def open_schedule(traffic: dict, seed: int, seconds: float) -> Schedule:
    """Arrivals of an open-loop mix over a window of ``seconds``.

    Poisson arrivals at the mix's ``rate``: the expected count over the
    window, ``rate * seconds``, is split into stratified exponential gaps,
    permuted by the seed.
    Tolerances are log-uniform over ``tol_loguniform``, stratified and
    permuted alike.
    """
    rate = float(traffic["rate"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-_stratified(n)) * (seconds / n)
    gaps = rng(seed, "arrivals").permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lo, hi = (math.log(float(v)) for v in traffic["tol_loguniform"])
    tol = np.exp(lo + _stratified(n) * (hi - lo))
    tol = rng(seed, "tol").permutation(tol)
    rhs = np.resize(pool_order(traffic, seed), n)
    keep = due < seconds
    return Schedule(due=due[keep], rhs=rhs[keep], tol=tol[keep])
