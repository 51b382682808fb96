"""Block-size autotuner + backend dispatch policy for the solver kernels.

Every Pallas kernel in this package is parameterized by VMEM tile sizes.
The right sizes depend on (shape, dtype) and the per-core VMEM budget:
bigger tiles amortize DMA setup and keep the MXU fed, but the working set
(with double-buffering) must stay inside ~16 MiB/core.  This module is the
single place that arithmetic lives, so the solver layer never hard-codes a
block shape.

Also here: the kernel execution mode policy.  The solver asks
``kernel_mode()`` once per trace and gets

    "compiled"   on TPU — real Pallas lowering,
    "interpret"  on CPU — the Pallas interpreter (slow, bit-accurate; what
                 CI exercises),
    "ref"        anywhere else (or via REPRO_KERNELS=ref) — pure-jnp
                 reference, no Pallas at all.

so ``gmres(gs="cgs2_fused")`` is safe to call on any backend.

Since PR 5 the policy is ALSO axis-aware: a row-sharded solve enters a
``shard_context(axis_name, num_shards)`` (core/distributed.py does this
around the shard_map body) and every dispatch site combines
``kernel_mode()`` with ``shard_axis()``/``shard_size()`` to pick the
per-shard kernels — the split-phase CGS2 pair, the halo-exchange SpMV
variants, the communication-avoiding matrix powers — instead of bailing
to the jnp reference the way pre-PR-5 code did.  The context is
trace-time static (same contract as ``kernel_mode``): shard_map traces
the per-shard program once, with the context set, and the resulting jaxpr
carries the kernel calls with the collectives between them.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Per-core VMEM budget the tuner plans against.  Real cores have ~16 MiB;
# we plan to ~3/4 of it so the compiler keeps double-buffering headroom.
VMEM_BUDGET = 12 * 1024 * 1024

# MXU/VPU native tile: the lane (last) dim is always 128; the sublane dim
# is 8 for f32 and 16 for bf16.
LANE = 128


def sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16) else 8


def itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def vmem_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes Mosaic allocates for a (rows, cols) VMEM buffer: the last dim
    padded to 128 lanes and the one before it to the sublane tile.  A
    32-bit buffer with fewer rows than the tile takes the next power of
    two of them instead (the v5e compiler lays out f32 (1, n) as
    T(1,128), (2, n) as T(2,128) and (5, n) as T(8,128)).  Every fits
    gate below prices its kernel's buffers with this, so a (n, 1) column
    costs 512 bytes per row and a (1, n) f32 row its payload."""
    tile = sublane(dtype)
    if itemsize(dtype) == 4 and rows < tile:
        tile = 1 << (max(rows, 1) - 1).bit_length()
    return (_round_up(max(rows, 1), tile)
            * _round_up(max(cols, 1), LANE) * itemsize(dtype))


# Kernel execution modes: compiled Pallas (TPU), the Pallas interpreter
# (CPU: slow, bit-accurate) and the pure-jnp reference.  The recovery
# layer (core/recovery.py) steps from the backend's own mode straight to
# "ref" — never from "compiled" into the interpreter.
KERNEL_MODES = ("compiled", "interpret", "ref")

_FORCED_MODE: list = []   # stack; trace-time static, like shard_context


@contextlib.contextmanager
def force_kernel_mode(mode: str):
    """Pin ``kernel_mode()`` for code traced inside (trace-time static).

    This is the recovery ladder's kernel-stack rung control: re-tracing a
    cycle under ``force_kernel_mode("ref")`` steps the solve down to the
    kernel-free execution mode WITHOUT touching the
    ``REPRO_KERNELS`` environment (which stays the process-wide default).
    Takes precedence over the env override; nests like ``shard_context``.
    """
    if mode not in KERNEL_MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; "
                         f"options: {list(KERNEL_MODES)}")
    _FORCED_MODE.append(mode)
    try:
        yield
    finally:
        _FORCED_MODE.pop()


def kernel_mode() -> str:
    """Execution mode for kernel-backed solver paths (trace-time static).

    Shard-agnostic on purpose: a row-sharded trace keeps its "compiled" /
    "interpret" mode and dispatch sites consult ``shard_axis()`` to pick
    the per-shard (split-phase / halo) kernel variants — sharding changes
    WHICH kernel runs, not WHETHER kernels run.  An ambient
    ``force_kernel_mode`` context (the recovery ladder) outranks the
    ``REPRO_KERNELS`` env override.
    """
    if _FORCED_MODE:
        return _FORCED_MODE[-1]
    forced = os.environ.get("REPRO_KERNELS")
    if forced in ("ref", "interpret", "compiled"):
        return forced
    backend = jax.default_backend()
    if backend == "tpu":
        return "compiled"
    if backend == "cpu":
        return "interpret"
    return "ref"  # GPU etc.: these kernels are TPU-shaped; use the reference


# Kernels whose body gathers from a VMEM operand (``jnp.take``).  Mosaic
# lowers no such gather ("Shape mismatch in input, indices and output",
# "Only 2D gather is supported"), so they run in the interpreter only and
# their dispatch takes the jnp reference on the chip.
GATHER_KERNELS = ("ell_matvec", "ell_matvec_halo", "sell_matvec",
                  "ell_powers")


def gather_kernel_mode() -> str:
    """``kernel_mode()`` for the ``GATHER_KERNELS``: "ref" where it would
    be "compiled"."""
    mode = kernel_mode()
    return "ref" if mode == "compiled" else mode


class _ShardCtx(NamedTuple):
    axis_name: str
    num_shards: int


_SHARD_CTX: list = []   # stack; trace-time static, like kernel_mode()


@contextlib.contextmanager
def shard_context(axis_name: str, num_shards: int):
    """Declare that code traced inside operates on ROW-LOCAL shards.

    The distributed solvers wrap their shard_map bodies in this context;
    operators and orthogonalization schemes read it back via
    ``shard_axis()`` / ``shard_size()`` to dispatch the per-shard kernels
    (halo-exchange SpMV, split-phase CGS2, CA matrix powers).  The
    ``num_shards`` travels with the context because the halo exchange
    builds its ``ppermute`` permutation as a Python list at trace time.
    """
    _SHARD_CTX.append(_ShardCtx(str(axis_name), int(num_shards)))
    try:
        yield
    finally:
        _SHARD_CTX.pop()


def shard_axis() -> Optional[str]:
    """Mesh axis of the ambient ``shard_context`` (None = single-shard)."""
    return _SHARD_CTX[-1].axis_name if _SHARD_CTX else None


def shard_size() -> int:
    """Shard count of the ambient ``shard_context`` (1 = single-shard)."""
    return _SHARD_CTX[-1].num_shards if _SHARD_CTX else 1


# --------------------------------------------------------------------------
# Persistent autotune cache
# --------------------------------------------------------------------------
# Every ``choose_*`` decision below is deterministic arithmetic today, but
# serving processes re-derive them on every restart and future measured
# tuning (ROADMAP) needs somewhere durable to live.  Tuned choices are
# cached to an on-disk JSON keyed by (function, args, dtype, topology):
#
#     REPRO_TUNE_CACHE=<path>   override the cache file location
#     REPRO_TUNE_CACHE=off      disable persistence (in-memory lru only)
#
# Default: ~/.cache/repro/tuning.json.  All I/O is best-effort — an
# unreadable/unwritable cache silently degrades to the computed value —
# and writes are atomic (tmp + rename) so concurrent processes never see
# a torn file.

_TUNE_CACHE_ENV = "REPRO_TUNE_CACHE"
_DISK_CACHE: Optional[dict] = None   # lazily-loaded {key: value} mirror
_PERSISTENT_FNS: list = []           # for clear_tune_cache()


def tune_cache_path() -> Optional[str]:
    """Resolved cache file path, or None when persistence is disabled."""
    p = os.environ.get(_TUNE_CACHE_ENV)
    if p is not None:
        return None if p.lower() in ("", "0", "off", "none") else p
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "tuning.json")


def _disk_load() -> dict:
    global _DISK_CACHE
    if _DISK_CACHE is None:
        _DISK_CACHE = {}
        path = tune_cache_path()
        if path is not None:
            try:
                import json
                with open(path) as f:
                    data = json.load(f)
                if isinstance(data, dict):
                    _DISK_CACHE.update(data)
            except (OSError, ValueError):
                pass  # missing/corrupt cache: start fresh
    return _DISK_CACHE


def _disk_store(cache: dict) -> None:
    path = tune_cache_path()
    if path is None:
        return
    try:
        import json
        import tempfile
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tune.tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc.: persistence is best-effort


def _decode(v):
    return tuple(v) if isinstance(v, list) else v


def persistent_choice(fn):
    """lru_cache + on-disk JSON persistence for a ``choose_*`` function.

    Disk keys include the ambient topology (``shard_size()``): choices are
    deterministic in their arguments today, so entries recorded under
    different topologies agree — but measured tuning won't, and the key
    schema is what survives restarts.
    """

    @functools.lru_cache(maxsize=256)
    def _lookup(key, args, kwargs):
        disk = _disk_load()
        if key in disk:
            return _decode(disk[key])
        val = fn(*args, **dict(kwargs))
        disk[key] = val
        _disk_store(disk)
        return val

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        kw = tuple(sorted(kwargs.items()))
        key = f"{fn.__name__}|{args}|{kw}|p{shard_size()}"
        return _lookup(key, args, kw)

    wrapper.cache_clear = _lookup.cache_clear
    wrapper.__wrapped__ = fn
    _PERSISTENT_FNS.append(wrapper)
    return wrapper


class LruCache:
    """Bounded LRU with hit/miss/eviction counters.

    ``persistent_choice`` above persists tile CHOICES (cheap arithmetic,
    keyed for restarts); this holds things that cannot go to disk —
    pre-lowered solver handles, jitted callables — and therefore needs an
    eviction bound and observable stats (the serve layer reports them as
    ``solver_serve_*`` metrics).  Not thread-safe by design: the serving
    scheduler is a single tick loop, and dict/OrderedDict mutation under
    the GIL covers the host-ingress read path.
    """

    def __init__(self, maxsize: int = 8):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        from collections import OrderedDict
        self.maxsize = int(maxsize)
        self._d = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d

    def get_or_create(self, key, factory):
        """Return the cached value, building (and possibly evicting) on miss."""
        if key in self._d:
            self.hits += 1
            self._d.move_to_end(key)
            return self._d[key]
        self.misses += 1
        val = factory()
        self._d[key] = val
        if len(self._d) > self.maxsize:
            self._d.popitem(last=False)
            self.evictions += 1
        return val

    def stats(self) -> dict:
        return {"size": len(self._d), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}

    def clear(self) -> None:
        self._d.clear()


def clear_tune_cache(disk: bool = False) -> None:
    """Drop the in-memory tuning caches (and the disk file when ``disk``)."""
    global _DISK_CACHE
    for fn in _PERSISTENT_FNS:
        fn.cache_clear()
    _DISK_CACHE = None
    if disk:
        path = tune_cache_path()
        if path is not None:
            try:
                os.remove(path)
            except OSError:
                pass


def record_tuned(choice_fn, value, *args, **kwargs) -> str:
    """Overwrite the persistent cache entry for ``choice_fn(*args, **kwargs)``.

    This is the WRITE path of autotune-by-measurement (``kernel_bench
    --measure``): ``persistent_choice`` lookups give a disk entry
    precedence over recomputing the VMEM model, so recording a measured
    winner here re-tunes every later call with the same key — in this
    process (the lru shadow is dropped) and in every future one (the JSON
    survives restarts).  The key is built exactly like the read path's,
    including the ambient topology, so record under the same
    ``shard_context`` the kernel will run under.  Returns the key.
    """
    if not hasattr(choice_fn, "__wrapped__"):
        raise TypeError(f"record_tuned wants a @persistent_choice function, "
                        f"got {choice_fn!r}")
    kw = tuple(sorted(kwargs.items()))
    key = f"{choice_fn.__name__}|{args}|{kw}|p{shard_size()}"
    disk = _disk_load()
    # JSON round-trips tuples as lists; store the list form so the entry
    # is identical whether it was written here or by a model lookup that
    # got persisted and re-read (``_decode`` restores tuples either way).
    disk[key] = list(value) if isinstance(value, tuple) else value
    _disk_store(disk)
    choice_fn.cache_clear()
    return key


@persistent_choice
def choose_matvec_blocks(m: int, n: int, dtype_name: str = "float32",
                         k: int = 1, budget: int = VMEM_BUDGET):
    """Pick (block_m, block_n) for the tiled GEMV/GEMM kernel.

    Working set per grid step (double-buffered A tile + operand/output
    columns):  2*bm*bn*s + bn*k*s + bm*k*4  bytes.  We maximize the A tile
    under the budget, preferring a wide ``block_n`` (contiguous HBM stream
    along the reduction dim) over a tall ``block_m``.
    """
    s = itemsize(dtype_name)
    sub = sublane(dtype_name)
    best = (sub, LANE)
    for bm in (128, 256, 512):
        for bn in (128, 256, 512, 1024, 2048):
            bytes_ = 2 * bm * bn * s + bn * k * s + bm * k * 4
            if bytes_ > budget:
                continue
            cur_bm, cur_bn = best
            if (bn, bm * bn) > (cur_bn, cur_bm * cur_bn):
                best = (bm, bn)
    bm, bn = best
    # Clamp to the (sublane/lane-aligned) problem size — a block larger
    # than the array just pads the whole array into one tile.
    bm = min(bm, _round_up(m, sub))
    bn = min(bn, _round_up(n, LANE))
    return bm, bn


def _ell_vmem(n: int, width: int, dtype, k: int, halo: int,
              bm: int) -> int:
    """VMEM of the ELL gather kernel at ``bm`` rows per grid step: the
    (bm, width) values and int32 cols tiles and the (bm, k) output tile,
    double-buffered, plus the whole (n + 2*halo, k) f32 operand."""
    return (2 * vmem_bytes(bm, width, dtype)
            + 2 * vmem_bytes(bm, width, jnp.int32)
            + 2 * vmem_bytes(n + 2 * halo, k, jnp.float32)
            + 2 * vmem_bytes(bm, k, jnp.float32))


@persistent_choice
def choose_spmv_block(n: int, width: int, dtype_name: str = "float32",
                      k: int = 1, halo: int = 0,
                      budget: int = VMEM_BUDGET) -> int:
    """Pick ``block_m`` (rows per grid step) for the ELL SpMV kernel.

    The gather kernel keeps the WHOLE operand x (n, k) resident in VMEM
    (sparse column patterns touch arbitrary rows of x, so tiling x would
    re-stream it once per row block); per grid step it adds the values,
    cols and output tiles (``_ell_vmem``).  We maximize the row block
    under the budget — bigger blocks amortize the gather setup and the
    grid overhead.

    ``halo``: extra resident operand rows on EACH side — the row-sharded
    halo variant gathers from a (n + 2*halo, k) exchanged operand.
    """
    sub = sublane(dtype_name)
    best = sub
    for bm in (128, 256, 512, 1024, 2048):
        if _ell_vmem(n, width, dtype_name, k, halo, bm) <= budget:
            best = bm
    return min(best, _round_up(n, sub))


def spmv_fits(n: int, width: int, dtype, k: int = 1, halo: int = 0,
              budget: int = VMEM_BUDGET) -> bool:
    """Can the gather SpMV kernel keep the full operand x in VMEM?

    This is the kernel's hard requirement (see ``choose_spmv_block``); when
    it fails the operator degrades to the jnp gather reference, which XLA
    streams from HBM.  The resident (n, k) operand pads its lane dim to
    128, so n in the tens of thousands already overflows.  ``halo`` prices
    the row-sharded variant's exchanged (n + 2*halo, k) operand; the
    sharded check runs on the LOCAL n, so sharding P-fold also divides the
    residency requirement P-fold.
    """
    return _ell_vmem(n, width, dtype, k, halo, sublane(dtype)) <= budget


@persistent_choice
def choose_sell_block(n: int, rows: int, width: int,
                      dtype_name: str = "float32", k: int = 1,
                      slice_height: int = 64,
                      budget: int = VMEM_BUDGET) -> int:
    """Pick ``block_m`` for ONE width bin of the sliced-ELL SpMV kernel.

    A bin is just an ELL rectangle — (rows, width) values + int32 cols in
    the sorted-row frame gathering from the GLOBAL (n, k) operand resident
    in VMEM — so the working-set model matches ``choose_spmv_block``.  Two
    differences: the resident-operand term uses the global ``n`` (column
    indices are global; the operand is shared by every bin's launch, not
    sliced per bin), and candidates step in multiples of ``slice_height``
    so a grid step covers whole slices (a block boundary inside a slice
    would split the one rectangle the format guarantees is dense).
    """
    sub = sublane(dtype_name)
    c = max(int(slice_height), sub)
    best = c
    bm = c
    while bm <= 4096:
        if _ell_vmem(n, width, dtype_name, k, 0, bm) <= budget:
            best = bm
        bm *= 2
    return min(best, _round_up(rows, sub))


def sell_fits(n: int, width: int, dtype, k: int = 1,
              budget: int = VMEM_BUDGET) -> bool:
    """Can the sliced-ELL kernel keep the full operand x in VMEM?

    ``width`` is the WIDEST bin's padded width: the per-bin launches share
    one resident (n, k) operand (column indices are global), so the
    binding residency constraint is plain ELL's at the widest bin — which
    is at most plain ELL's own, since bin widths never exceed the global
    max row width.
    """
    return spmv_fits(n, width, dtype, k=k, halo=0, budget=budget)


def _banded_vmem(n: int, nbands: int, dtype, halo: int, k: int,
                 br: int) -> int:
    """VMEM the lane-dense banded kernel allocates at ``br`` rows of 128
    per grid step: the whole (k, rows, 128) f32 operand and the
    (nbands, br, 128) band / (k, br, 128) output tiles, each
    double-buffered by the pipeline, plus the window/accumulator values."""
    f32 = jnp.float32
    rows = _round_up(-(-(_round_up(halo, LANE) + _round_up(n, br * LANE)
                         + halo) // LANE) + 1, 8)
    return (2 * k * vmem_bytes(rows, LANE, f32)
            + 2 * nbands * vmem_bytes(br, LANE, dtype)
            + 2 * k * vmem_bytes(br, LANE, f32)
            + 3 * vmem_bytes(br, LANE, f32))


@persistent_choice
def choose_banded_block(n: int, nbands: int, dtype_name: str = "float32",
                        halo: int = 0, k: int = 1,
                        budget: int = VMEM_BUDGET) -> int:
    """Pick ``block_m`` (rows per grid step) for the banded/stencil kernel.

    The kernel tiles rows as (br, 128) lane-dense blocks; the biggest
    ``br`` whose working set (``_banded_vmem``) fits the budget wins.
    """
    sub = sublane(dtype_name)
    best = sub
    for br in (8, 16, 32, 64, 128, 256, 512):
        if br % sub == 0 and _banded_vmem(n, nbands, dtype_name, halo, k,
                                          br) <= budget:
            best = br
    return min(best, _round_up(-(-n // LANE), sub)) * LANE


def banded_fits(n: int, nbands: int, dtype, halo: int = 0, k: int = 1,
                budget: int = VMEM_BUDGET) -> bool:
    """Can the banded kernel keep the halo-padded operand in VMEM?"""
    return _banded_vmem(n, nbands, dtype, halo, k,
                        sublane(dtype)) <= budget


@persistent_choice
def choose_powers_block(n: int, dtype_name: str = "float32", s: int = 4,
                        budget: int = VMEM_BUDGET) -> int:
    """Square A-tile size for the dense s-step matrix-powers kernel.

    The kernel's resident set is the (s, n) power block plus the current
    operand and the w accumulator (all f32); what's left of the budget goes
    to the double-buffered A tile, biggest MXU-aligned candidate first.
    """
    resident = _round_up(n, LANE) * 4 * (s + 2)
    best = LANE
    for b in (256, 512):
        if b > _round_up(n, LANE):
            break
        if (_round_up(n, b) - n) * 8 > n:
            continue  # same padding-overhead rule as choose_fused_block
        if 2 * b * b * itemsize(dtype_name) + resident <= budget:
            best = b
    return best


def powers_fits(n: int, dtype, s: int, *, nbands: int | None = None,
                halo: int = 0, budget: int = VMEM_BUDGET) -> bool:
    """Can the matrix-powers kernel keep its working set in VMEM?

    Both variants carry the (s, n) power block and the (1, n) operand,
    double-buffered, and (1, n) f32 scratch rows — each row padded to the
    8-row sublane tile; the banded variant (``nbands`` set) additionally
    holds the whole band stack resident (the point of the kernel: ONE HBM
    pass over A for all s powers), the dense variant one double-buffered
    A tile.  Failing the check sends the block step to the jnp reference.
    """
    f32 = jnp.float32
    np_ = _round_up(n, LANE)
    vecs = 2 * vmem_bytes(s, np_, f32) + 2 * vmem_bytes(1, np_, f32)
    if nbands is None:
        b = choose_powers_block(n, jnp.dtype(dtype).name, s=s, budget=budget)
        need = vecs + 2 * vmem_bytes(1, np_, f32) + 2 * vmem_bytes(b, b,
                                                                   dtype)
    else:
        need = (vecs + 2 * vmem_bytes(nbands, np_, dtype)
                + 4 * vmem_bytes(1, np_ + 2 * halo, f32))
    return need <= budget


@persistent_choice
def choose_block_gs(m1: int, n: int, s: int = 1,
                    dtype_name: str = "float32"):
    """Padded residency plan ``(m1_pad, n_pad, s_pad)`` for the block-GS kernel.

    The kernel holds the whole basis as ONE VMEM block (that is its HBM
    win: V streamed once per pass instead of twice), so the only tiling
    decision is the hardware-aligned padding the operands are brought to.
    """
    return (_round_up(m1, sublane(dtype_name)), _round_up(n, LANE),
            _round_up(s, sublane("float32")))


def block_gs_fits(m1: int, n: int, dtype, s: int = 1,
                  budget: int = VMEM_BUDGET) -> bool:
    """Can the block-GS kernel keep the (m1, n) basis block in VMEM?

    Peak working set: the basis in storage ``dtype`` plus its f32 (f64
    under x64) in-register upcast, the (s, n) operand block and its
    orthogonalized copy, and the small C/G outputs.  Per grid step only
    ONE basis block is resident — the batched (k, m1, n) form visits one
    lane per step, so k does not enter the bound.
    """
    sb = itemsize(dtype)
    acc = max(4, sb)
    m1p, np_, sp = choose_block_gs(m1, n, s, jnp.dtype(dtype).name)
    need = (m1p * np_ * (sb + acc)      # resident V + in-kernel upcast
            + 2 * sp * np_ * acc        # W block in + W' out
            + m1p * sp * acc            # C output
            + 2 * sp * sp * acc)        # T in, G out
    return need <= budget


@persistent_choice
def choose_gs_block(m1: int, n: int, dtype_name: str = "float32",
                    budget: int = VMEM_BUDGET):
    """Pick ``block_n`` for the streaming fused Gram-Schmidt kernel.

    Per grid step the kernel holds a (m1, bn) V tile (double-buffered), the
    (bn, 1) w tile, and the (m1, 1) h accumulator.
    """
    s = 4  # the GS kernel accumulates f32
    best = LANE
    for bn in (128, 256, 512, 1024, 2048, 4096):
        if 2 * m1 * bn * s + bn * s + m1 * s <= budget:
            best = bn
    return min(best, _round_up(n, LANE))


def gs_payload_fits(m1: int, n: int, dtype, budget: int = VMEM_BUDGET) -> bool:
    """Can the single-reduce payload/update kernel pair run at (m1, n)?

    The streaming payload kernel tiles V, so the bound is the minimum tile
    working set — a (m1, LANE) V tile double-buffered, the (LANE, 2) W tile
    ([z, v_j]) and the (m1 + 1, 2) payload accumulator, all f32-accumulated.
    This effectively always holds; it exists as the EXPLICIT dispatch gate
    of the ``gs="cgs2_pipelined"`` scheme so overflow (and tests forcing it)
    degrade to the psum-safe jnp reference rather than a kernel failure.
    """
    del dtype  # accumulation is f32 regardless of storage dtype
    s = 4
    need = 2 * m1 * LANE * s + 2 * LANE * s + 2 * (m1 + 1) * s
    return n > 0 and need <= budget


@persistent_choice
def _choose_fused_block(n: int, dtype_name: str, budget: int):
    best = LANE
    for b in (256, 512):
        if b > _round_up(n, LANE):
            break
        if (_round_up(n, b) - n) * 8 > n:
            continue  # >12.5% padded rows/cols — padding traffic beats DMA win
        if 2 * b * b * itemsize(dtype_name) <= budget // 4:
            best = b
    return best


def choose_fused_block(n: int, dtype, budget: int = VMEM_BUDGET) -> int:
    """Square A-tile size for the fused Arnoldi-step kernel.

    One block size for rows and columns (so row/col padding agree on the
    square A), biggest MXU-aligned candidate whose padding overhead and
    double-buffered tile stay sane — the resident basis is the real VMEM
    consumer and is budgeted by ``fused_step_fits``.
    """
    return _choose_fused_block(n, jnp.dtype(dtype).name, budget)


def fused_step_fits(m1: int, n: int, dtype, budget: int = VMEM_BUDGET,
                    a_dtype=None) -> bool:
    """Can the fused Arnoldi-step kernel keep the whole basis V in VMEM?

    The fused kernel's peak working set is the Gram-Schmidt grid step: the
    full (m1, n) basis in storage ``dtype`` (double-buffered) PLUS its
    accumulator-dtype upcast and one basis-sized product, the (1, n) w
    row, and the double-buffered A tile and v_j row — priced in
    ``a_dtype`` (the matrix may be stored wider than the basis, e.g. f32 A
    with a bf16 ``compute_dtype`` basis).
    """
    if a_dtype is None:
        a_dtype = dtype
    acc = jnp.promote_types(a_dtype, jnp.float32)  # f64 under x64
    b = choose_fused_block(n, a_dtype, budget)
    np_ = _round_up(n, b)
    need = (2 * vmem_bytes(m1, np_, dtype)       # resident V
            + 2 * vmem_bytes(m1, np_, acc)       # upcast + one product
            + 2 * vmem_bytes(1, np_, acc)        # w row, in and out
            + 2 * vmem_bytes(b, b, a_dtype)      # A tile
            + 2 * vmem_bytes(1, b, a_dtype))     # v_j row
    return need <= budget


def cheb_fits(n: int, nbands: int, dtype, *, halo: int = 0,
              budget: int = VMEM_BUDGET) -> bool:
    """Can the fused Chebyshev-apply kernel keep its working set in VMEM?

    The kernel is grid-free: the whole band stack, the input v and the
    (1, n) recurrence rows (z, z_old, z_new, the stencil accumulator) stay
    resident for all ``order`` matvecs, plus the halo-padded z scratch —
    each (1, n) row padded to the 8-row sublane tile.  This is the
    EXPLICIT dispatch gate for ``banded_cheb_apply`` — on overflow (and in
    tests forcing it) the preconditioner degrades to the psum-safe
    per-matvec recurrence through the operator.
    """
    f32 = jnp.float32
    np_ = _round_up(n, LANE)
    need = (2 * vmem_bytes(nbands, np_, dtype)     # resident band stack
            + 8 * vmem_bytes(1, np_, f32)          # v, out, recurrence rows
            + vmem_bytes(1, np_ + 2 * halo, f32))  # halo-padded z scratch
    return need <= budget


def _trisweep_vmem(nbands: int, ring: int, blocks: int) -> int:
    """VMEM the line-blocked triangular sweep allocates at ``blocks``
    blocks of 1024 rows per grid step, all lane-dense f32: the (nbands,
    8 blocks, 128) band tile and the (8 blocks, 128) v and z tiles, each
    double-buffered, and the scratch of ``ring + blocks`` solved blocks."""
    f32 = jnp.float32
    return (2 * (nbands + 2) * vmem_bytes(8 * blocks, LANE, f32)
            + vmem_bytes(8 * (ring + blocks), LANE, f32))


@persistent_choice
def choose_trisweep_blocks(nblk: int, nbands: int, ring: int = 1,
                           budget: int = VMEM_BUDGET) -> int:
    """Blocks of 1024 rows per grid step of the triangular sweep.

    The sweep is sequential in blocks, so this only amortizes the grid
    step's overhead: the largest divisor of the ``nblk`` blocks, up to
    128, whose tiles fit the budget.
    """
    for blocks in range(min(nblk, 128), 1, -1):
        if nblk % blocks == 0 and _trisweep_vmem(nbands, ring,
                                                 blocks) <= budget:
            return blocks
    return 1


def trisweep_fits(nbands: int, ring: int = 1,
                  budget: int = VMEM_BUDGET) -> bool:
    """Can the triangular-sweep kernel hold one block and its ring?

    The EXPLICIT VMEM gate for ``kernels/trisolve.banded_trisweep`` —
    overflow (and tests forcing it) degrades to the blocked jnp reference,
    which computes the same recurrence.
    """
    return _trisweep_vmem(nbands, ring, 1) <= budget


def ell_powers_fits(n: int, width: int, dtype, s: int,
                    budget: int = VMEM_BUDGET) -> bool:
    """Can the ELL matrix-powers kernel keep values+cols+powers in VMEM?

    Mirrors ``powers_fits``: the (s, n) normalized power block and the
    (1, n) operand and current rows in f32, plus the WHOLE (n, width)
    values/cols pair resident (the sparse gather may touch any row, and
    one residency pays for all s powers) — its lane dim padded to 128.
    Failing the check sends the s-step block to the jnp reference.
    """
    f32 = jnp.float32
    np_ = _round_up(n, LANE)
    need = (2 * vmem_bytes(s, np_, f32) + 3 * vmem_bytes(1, np_, f32)
            + 2 * vmem_bytes(np_, width, dtype)
            + 2 * vmem_bytes(np_, width, jnp.int32))
    return need <= budget
