"""GF(2^8) arithmetic + Reed-Solomon erasure coding — host tier.

The Reed-Solomon redundancy codec (core/codec.py, DESIGN.md §8) encodes a
parity group's k data shards into m parity blobs such that *any* m concurrent
shard losses per group are recoverable — the multi-failure gap Agullo et al.
(arXiv:2010.13342) identify in single-parity diskless schemes like our XOR
mode.

Construction: the m×k generator is a **Cauchy matrix** over GF(2^8)
(``C[j][i] = 1/(x_j ⊕ y_i)`` with distinct nodes), whose every square
submatrix is invertible — so any e ≤ m surviving parity rows solve for any e
missing data shards (Blömer et al.'s Cauchy-RS; classic Vandermonde systematic
forms lack this guarantee). Field arithmetic runs through log/antilog tables
(primitive polynomial 0x11D, generator α=2); the zero-operand special case is
folded into the tables with a sentinel log and a zero-padded antilog tail, so
the vectorized byte ops are two ``np.take``s and an add with no branches.

The device-tier encode is the Pallas kernel in kernels/rs_encode.py (same
math, constant-folded xtime chains instead of runtime table lookups); this
module is its numerical reference and the engine's host-tier path.

Host-tier backends (DESIGN.md §14): the hot data passes — ``rs_encode``,
``rs_decode``, ``gf_addmul_fast`` and the codec layer's chunked decode — all
dispatch through ONE primitive, :func:`gf_matrix_addmul_into`, with three
interchangeable bit-identical implementations:

  * ``table`` — the per-coefficient 256-entry product-table gather
    (Jerasure-style strength reduction, PR 5). The oracle.
  * ``swar``  — wide-word SWAR over ``uint64`` views: carry-free xtime
    chains process 8 packed GF bytes per numpy op (Horner bit-plane form,
    so the chain amortizes across the whole generator row).
  * ``jax``   — a jitted jax-CPU program reusing the Pallas kernels' xtime
    logic on uint8 lanes; XLA fuses the whole Horner chain into one pass
    over memory, which is why it usually wins the probe outright.

A one-time microbenchmark probe (``_probe_backends``) picks the fastest at
import of the hot path; ``REPRO_GF_BACKEND=table|swar|jax`` or
:func:`set_backend` overrides it. All selection/caching state is
thread-safe and growth-bounded (the engine's async worker pool calls in
concurrently).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

import numpy as np

from repro.utils.logging import get_logger

log = get_logger("core.gf256")

GF_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1, the standard RS(255) polynomial
_ORDER = 255

# Sentinel scheme: LOG32[0] = 512 and EXP_TABLE[510:] = 0, so any product with
# a zero operand indexes into the zero tail (one zero: 512 + 254 = 766; both
# zero: 512 + 512 = 1024 < 2048) while nonzero log sums stay below 509 — no
# masking needed anywhere.
_LOG_ZERO = 512


def _build_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(2048, np.uint8)
    log = np.full(256, _LOG_ZERO, np.int32)
    x = 1
    for i in range(_ORDER):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[_ORDER : 2 * _ORDER] = exp[:_ORDER]  # wrap: α^(i+255) = α^i
    return exp, log


EXP_TABLE, LOG32 = _build_tables()


def gf_mul(a: int, b: int) -> int:
    """Scalar product in GF(2^8)."""
    return int(EXP_TABLE[LOG32[a] + LOG32[b]])


def gf_inv(a: int) -> int:
    assert a != 0, "zero has no inverse in GF(2^8)"
    return int(EXP_TABLE[_ORDER - int(LOG32[a])])


def gf_mul_bytes(c: int, buf: np.ndarray) -> np.ndarray:
    """Vectorized c · buf over GF(2^8): two table gathers + an int add."""
    assert buf.dtype == np.uint8
    if c == 0:
        return np.zeros_like(buf)
    if c == 1:
        return buf.copy()
    return EXP_TABLE[LOG32[buf] + int(LOG32[c])]


def gf_addmul_into(acc: np.ndarray, c: int, buf: np.ndarray) -> None:
    """acc ^= c · buf, XORing over the common prefix only (ragged tails)."""
    n = min(acc.shape[0], buf.shape[0])
    if c == 0 or n == 0:
        return
    if c == 1:
        acc[:n] ^= buf[:n]
    else:
        acc[:n] ^= EXP_TABLE[LOG32[buf[:n]] + int(LOG32[c])]


_MUL_TABLES: dict[int, np.ndarray] = {}
_MUL_TABLES_LOCK = threading.Lock()


def mul_table(c: int) -> np.ndarray:
    """The 256-entry product table of a fixed coefficient: ``T[x] = c·x``.

    Jerasure-style strength reduction for hot decode loops whose coefficients
    are known up front (the precomputed erasure decode matrix): the per-byte
    product becomes ONE gather ``T[buf]`` instead of the log/antilog path's
    two gathers and an int32 add — ~5x faster per pass on large buffers.
    Tables are tiny (256 B) and cached per coefficient; the cache is
    lock-guarded (async-worker threads populate it concurrently) and its
    growth is bounded by the field itself: at most 256 entries, 64 KiB."""
    c = int(c) & 0xFF  # the coefficient is a field element: bounds the cache
    t = _MUL_TABLES.get(c)  # racy read is safe: values are write-once
    if t is None:
        with _MUL_TABLES_LOCK:
            t = _MUL_TABLES.get(c)
            if t is None:
                t = gf_mul_bytes(c, np.arange(256, dtype=np.uint8))
                _MUL_TABLES[c] = t
    return t


def gf_addmul_table_into(acc: np.ndarray, table: np.ndarray, buf: np.ndarray) -> None:
    """acc ^= T[buf] over the common prefix (T from :func:`mul_table`)."""
    n = min(acc.shape[0], buf.shape[0])
    if n:
        np.bitwise_xor(acc[:n], table[buf[:n]], out=acc[:n])


#: below this byte count a backend round-trip (staging + dispatch) cannot
#: beat the direct table gather for a single addmul term — solve_gf's 256-B
#: coefficient rows and similar small passes stay on the table path.
_ADDMUL_BACKEND_MIN = 1 << 15


def gf_addmul_fast(acc: np.ndarray, c: int, buf: np.ndarray) -> None:
    """acc ^= c · buf through the active GF backend (DESIGN.md §14).

    Large buffers route through :func:`gf_matrix_addmul_into` as a 1×1
    product — SWAR xtime chains or the fused jax-CPU program instead of the
    per-coefficient 256-entry gather; small buffers (and the ``table``
    backend) keep the Jerasure-style product-table pass. c ∈ {0, 1} keeps
    the branch-free shortcut paths."""
    if c == 0:
        return
    n = min(acc.shape[0], buf.shape[0])
    if n == 0:
        return
    if c == 1:
        acc[:n] ^= buf[:n]
        return
    backend = _active_backend()
    if backend.name != "table" and n >= _ADDMUL_BACKEND_MIN:
        backend.matrix_into(
            [acc], [buf], ((int(c),),), 0, n, accumulate=True
        )
        return
    gf_addmul_table_into(acc, mul_table(c), buf)


def gf_mul_fast(c: int, buf: np.ndarray) -> np.ndarray:
    """c · buf through the product table (allocating form of
    :func:`gf_addmul_fast`)."""
    if c == 0:
        return np.zeros_like(buf)
    if c == 1:
        return buf.copy()
    return mul_table(c)[buf]


# ---------------------------------------------------------------------------
# Pluggable GF(2^8) backends — one matrix primitive, three implementations
# (DESIGN.md §14). All byte passes above dispatch through here.
# ---------------------------------------------------------------------------

#: SWAR constants: the xtime of 8 packed GF bytes in one uint64 —
#: ``xtime(x) = ((x ^ (x & HIGH)) << 1) ^ (((x & HIGH) >> 7) * POLY)``.
#: Masking the top bit of every byte lane before the shift keeps the shift
#: from carrying across lanes; the reduced top bits come back as 0x00/0x01
#: per lane, and multiplying the whole word by 0x1D scales each lane without
#: cross-lane carries (0x01·0x1D ≤ 0xFF). Byte-lane ops are endian-agnostic.
_SWAR_HIGH = np.uint64(0x8080808080808080)
_SWAR_POLY = np.uint64(0x1D)
_SWAR_ONE = np.uint64(1)
_SWAR_SEVEN = np.uint64(7)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class _Scratch(threading.local):
    """Per-thread staging buffers (async-worker threads decode concurrently;
    sharing scratch across them would race). Grow-only per key, rounded to
    the next power of two — bounded by the largest single request."""

    def __init__(self) -> None:
        self.bufs: dict[str, np.ndarray] = {}

    def u8(self, key: str, nbytes: int) -> np.ndarray:
        buf = self.bufs.get(key)
        if buf is None or buf.nbytes < nbytes:
            buf = np.empty(_next_pow2(max(nbytes, 4096)), np.uint8)
            self.bufs[key] = buf
        return buf[:nbytes]


_SCRATCH = _Scratch()


def _mat_rows(mat) -> tuple[tuple[int, ...], ...]:
    """Normalize a coefficient matrix (ndarray or nested sequence) to a
    hashable tuple-of-tuples of ints — the backend dispatch/compile key."""
    if isinstance(mat, np.ndarray):
        return tuple(tuple(int(c) for c in row) for row in mat)
    return tuple(tuple(int(c) for c in row) for row in mat)


class _TableBackend:
    """The product-table oracle: per-(row, src) 256-entry gathers."""

    name = "table"

    def matrix_into(self, dsts, srcs, rows, lo, hi, accumulate=False):
        for t, dst in enumerate(dsts):
            end = min(hi, dst.nbytes)
            if lo >= end:
                continue
            acc = dst[lo:end]
            if not accumulate:
                acc[:] = 0
            row = rows[t]
            for i, src in enumerate(srcs):
                c = row[i]
                if c == 0 or lo >= src.nbytes:
                    continue
                seg = src[lo : min(end, src.nbytes)]
                w = seg.shape[0]
                if c == 1:
                    np.bitwise_xor(acc[:w], seg, out=acc[:w])
                else:
                    np.bitwise_xor(acc[:w], mul_table(c)[seg], out=acc[:w])


class _SwarBackend:
    """Wide-word SWAR over uint64 views, Horner bit-plane form.

    Per output row: walk the coefficient bits high→low; before each step
    xtime the accumulator ONCE (6 uint64 ops on 8 packed bytes), then XOR in
    every source whose coefficient has that bit set. The expensive carry-free
    chain thus amortizes across the whole generator row instead of running
    per (row, src) term. Misaligned / ragged source segments (lengths not a
    multiple of 8, short prefixes) are staged into zero-padded aligned
    scratch first — zero padding is a GF no-op, so the result is exact."""

    name = "swar"

    @staticmethod
    def _xtime_inplace(x: np.ndarray, tmp: np.ndarray) -> None:
        np.bitwise_and(x, _SWAR_HIGH, out=tmp)
        np.bitwise_xor(x, tmp, out=x)
        np.left_shift(x, _SWAR_ONE, out=x)
        np.right_shift(tmp, _SWAR_SEVEN, out=tmp)
        np.multiply(tmp, _SWAR_POLY, out=tmp)
        np.bitwise_xor(x, tmp, out=x)

    def matrix_into(self, dsts, srcs, rows, lo, hi, accumulate=False):
        end = min(hi, max(d.nbytes for d in dsts))
        L = end - lo
        if L <= 0:
            return
        W = (L + 7) // 8
        # Stage each source's [lo, end) segment as W aligned uint64 words.
        # Full-length segments are viewed in place (numpy tolerates any byte
        # offset on x86); ragged tails are zero-padded into scratch.
        words: list[np.ndarray | None] = []
        for i, src in enumerate(srcs):
            if lo >= src.nbytes:
                words.append(None)
                continue
            seg = src[lo : min(end, src.nbytes)]
            if seg.nbytes == 8 * W:
                words.append(seg.view(np.uint64))
            else:
                row8 = _SCRATCH.u8(f"swar_src{i}", 8 * W)
                row8[: seg.nbytes] = seg
                row8[seg.nbytes :] = 0
                words.append(row8.view(np.uint64))
        acc8 = _SCRATCH.u8("swar_acc", 8 * W)
        tmp8 = _SCRATCH.u8("swar_tmp", 8 * W)
        acc64, tmp64 = acc8.view(np.uint64), tmp8.view(np.uint64)
        for t, dst in enumerate(dsts):
            dend = min(end, dst.nbytes)
            if lo >= dend:
                continue
            row = rows[t]
            acc: np.ndarray | None = None
            for bit in range(7, -1, -1):
                if acc is not None:
                    self._xtime_inplace(acc, tmp64)
                for i, w in enumerate(words):
                    if w is None or not row[i] >> bit & 1:
                        continue
                    if acc is None:
                        np.copyto(acc64, w)
                        acc = acc64
                    else:
                        np.bitwise_xor(acc, w, out=acc)
            dL = dend - lo
            if acc is None:  # all-zero row
                if not accumulate:
                    dst[lo:dend] = 0
            elif accumulate:
                np.bitwise_xor(dst[lo:dend], acc8[:dL], out=dst[lo:dend])
            else:
                dst[lo:dend] = acc8[:dL]


class _JaxBackend:
    """Jitted Horner bit-plane product on uint8 lanes, pinned to the host's
    CPU device whatever the default backend is (on a TPU host the default
    device is the chip, and host encode chunks must not cross PCIe) — the same
    xtime recurrence as the Pallas kernels (kernels/rs_encode.py
    ``_xtime_u32``), restated per byte lane so arbitrary lengths and
    alignments need no packing. XLA fuses the whole chain into a single
    vectorized pass over memory, which is why this path typically probes
    ~15-20x faster than the table gather.

    Compiled programs are cached per (coefficient rows, k, padded length);
    lengths are bucketed to powers of two so the cache stays small, and an
    LRU bound + lock keep it safe under the async worker pool. Where JAX
    has no CPU backend the first call raises, and the probe drops it."""

    name = "jax"
    _MAX_FNS = 64
    _MIN_BUCKET = 4096

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._fns: OrderedDict[tuple, object] = OrderedDict()
        self._cpu = None

    def _device(self):
        if self._cpu is None:
            import jax

            self._cpu = jax.local_devices(backend="cpu")[0]
        return self._cpu

    def _compiled(self, rows: tuple[tuple[int, ...], ...], k: int, nb: int):
        key = (rows, k, nb)
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                return fn
        import jax
        import jax.numpy as jnp

        def _xtime8(x):
            # uint8-lane restatement of kernels/rs_encode._xtime_u32
            return ((x & jnp.uint8(0x7F)) << jnp.uint8(1)) ^ (
                (x >> jnp.uint8(7)) * jnp.uint8(0x1D)
            )

        def _product(stacked):  # (k, nb) uint8
            outs = []
            for row in rows:
                acc = None
                for bit in range(7, -1, -1):
                    if acc is not None:
                        acc = _xtime8(acc)
                    for i, c in enumerate(row):
                        if c >> bit & 1:
                            x = stacked[i]
                            acc = x if acc is None else acc ^ x
                if acc is None:
                    acc = jnp.zeros(nb, jnp.uint8)
                outs.append(acc)
            return jnp.stack(outs)

        fn = jax.jit(_product)
        with self._lock:
            self._fns[key] = fn
            while len(self._fns) > self._MAX_FNS:
                self._fns.popitem(last=False)
        return fn

    def matrix_into(self, dsts, srcs, rows, lo, hi, accumulate=False):
        end = min(hi, max(d.nbytes for d in dsts))
        L = end - lo
        if L <= 0:
            return
        k = len(srcs)
        nb = _next_pow2(max(L, self._MIN_BUCKET))
        stack = _SCRATCH.u8("jax_stack", k * nb).reshape(k, nb)
        for i, src in enumerate(srcs):
            seg = src[lo : min(end, src.nbytes)] if lo < src.nbytes else src[:0]
            stack[i, : seg.nbytes] = seg
            stack[i, seg.nbytes :] = 0  # zero padding is a GF no-op
        fn = self._compiled(rows, k, nb)
        import jax

        # Committed to the CPU device, so the jitted product runs there.
        res = np.asarray(fn(jax.device_put(stack, self._device())))
        for t, dst in enumerate(dsts):
            dend = min(end, dst.nbytes)
            if lo >= dend:
                continue
            dL = dend - lo
            if accumulate:
                np.bitwise_xor(dst[lo:dend], res[t, :dL], out=dst[lo:dend])
            else:
                dst[lo:dend] = res[t, :dL]


_TABLE_BACKEND = _TableBackend()
_BACKENDS: dict[str, object] = {"table": _TABLE_BACKEND, "swar": _SwarBackend()}
try:  # the jax backend registers only when jax imports (CI stubs may lack it)
    import jax as _jax  # noqa: F401

    _BACKENDS["jax"] = _JaxBackend()
except Exception:  # pragma: no cover - exercised only on jax-less installs
    pass

#: probe/selection state — guarded by _BACKEND_LOCK, written once per
#: process (or on set_backend); _PROBE_GBPS additionally feeds the restore
#: chunk planner's first-restore rate estimate (core/checkpoint.py).
_BACKEND_LOCK = threading.Lock()
_SELECTED: list = [None]  # [name | None]; list cell so tests can reset
_FORCED: list = [None]
_PROBE_GBPS: dict[str, float] = {}


def available_backends() -> list[str]:
    return sorted(_BACKENDS)


def get_backend(name: str):
    """A backend implementation by name (tests drive all of them directly)."""
    return _BACKENDS[name]


def set_backend(name: str | None) -> None:
    """Force a backend (config override); ``None`` returns to probe/env
    selection. Unknown names raise KeyError immediately."""
    if name is not None and name not in _BACKENDS:
        raise KeyError(f"unknown GF backend {name!r}; have {available_backends()}")
    with _BACKEND_LOCK:
        _FORCED[0] = name


def _probe_backends() -> str:
    """One-time microbenchmark: time a k=4, m=2 encode-shaped product on
    256 KiB buffers (the smoke/chunk size class) per backend, keep the
    fastest. Cost is a few ms per numpy backend + one jax compile; runs
    once per process, under the selection lock."""
    r = np.random.default_rng(0)
    k, m, L = 4, 2, 1 << 18
    srcs = [r.integers(0, 256, size=L, dtype=np.uint8) for _ in range(k)]
    dsts = [np.empty(L, np.uint8) for _ in range(m)]
    rows = _mat_rows(cauchy_matrix(m, k))
    best_name, best_gbps = "table", 0.0
    for name, backend in _BACKENDS.items():
        try:
            backend.matrix_into(dsts, srcs, rows, 0, L)  # warm (jax: compile)
            dt = float("inf")  # best-of-k: dispatch jitter would misrank
            for _ in range(5):
                t0 = time.perf_counter()
                backend.matrix_into(dsts, srcs, rows, 0, L)
                dt = min(dt, time.perf_counter() - t0)
        except Exception as e:  # a broken backend loses the probe, loudly
            log.warning("GF backend %r dropped from the probe: %r", name, e)
            continue
        gbps = k * L / max(dt, 1e-9) / 1e9
        _PROBE_GBPS[name] = gbps
        if gbps > best_gbps:
            best_name, best_gbps = name, gbps
    return best_name


def active_backend_name() -> str:
    """The selection order: set_backend > REPRO_GF_BACKEND > probe winner."""
    forced = _FORCED[0]
    if forced is not None:
        return forced
    env = os.environ.get("REPRO_GF_BACKEND", "").strip().lower()
    if env and env in _BACKENDS:
        return env
    if _SELECTED[0] is None:
        with _BACKEND_LOCK:
            if _SELECTED[0] is None:
                _SELECTED[0] = _probe_backends()
    return _SELECTED[0]


def _active_backend():
    return _BACKENDS[active_backend_name()]


def probed_gbps(name: str | None = None, default: float = 1.0) -> float:
    """Measured GB/s of a backend's probe pass (the active backend when
    ``name`` is None) — the restore chunk planner's decode-rate seed before
    any real restore has been measured."""
    name = name or active_backend_name()
    if name not in _PROBE_GBPS:
        with _BACKEND_LOCK:
            if _SELECTED[0] is None:
                _SELECTED[0] = _probe_backends()
    return _PROBE_GBPS.get(name, default)


def gf_matrix_addmul_into(
    dsts: list[np.ndarray],
    srcs: list[np.ndarray],
    mat,
    lo: int = 0,
    hi: int | None = None,
    accumulate: bool = False,
    backend: str | None = None,
) -> None:
    """The backend primitive: ``dsts[t][lo:hi] (^)= ⊕_i mat[t,i]·srcs[i]``.

    All buffers are 1-D uint8. Sources may be ragged: a source shorter than
    ``hi`` contributes only its prefix (implicit zero padding — a GF no-op),
    exactly matching the legacy accumulate loops. ``accumulate=False``
    overwrites the destination range, ``True`` XOR-accumulates into it.
    ``backend`` pins an implementation (tests; bit-identity asserts);
    ``None`` dispatches to the probed/forced selection."""
    if not dsts or hi is not None and hi <= lo:
        return
    if hi is None:
        hi = max(d.nbytes for d in dsts)
    impl = _BACKENDS[backend] if backend is not None else _active_backend()
    impl.matrix_into(dsts, srcs, _mat_rows(mat), lo, hi, accumulate)


def cauchy_matrix(m: int, k: int) -> np.ndarray:
    """(m, k) Cauchy generator: C[j][i] = (x_j ⊕ y_i)^-1, x_j = j, y_i = m+i.

    Node sets {0..m-1} and {m..m+k-1} are disjoint, so every entry is the
    inverse of a nonzero element; every square submatrix of C — and of the
    systematic stack [I_k ; C] — is invertible, which is exactly the
    any-m-erasures guarantee.
    """
    assert m >= 1 and k >= 1 and m + k <= 256, (m, k)
    out = np.zeros((m, k), np.uint8)
    for j in range(m):
        for i in range(k):
            out[j, i] = gf_inv(j ^ (m + i))
    return out


def solve_gf(A: np.ndarray, rhs: list[np.ndarray]) -> list[np.ndarray]:
    """Solve A·x = rhs over GF(2^8) by Gaussian elimination.

    A is (e, e) uint8 and invertible (a Cauchy submatrix); rhs is e byte
    buffers (the syndromes). Row ops are vectorized over the buffers — the
    e ≤ m pivot loop is tiny, the data passes are the cost.
    """
    e = A.shape[0]
    A = A.astype(np.uint8).copy()
    rhs = [r.copy() for r in rhs]
    for col in range(e):
        piv = next((r for r in range(col, e) if A[r, col]), -1)
        if piv < 0:
            # Singular: LRC row selection probes candidate row sets with
            # gf_matrix_inverse and skips the non-invertible ones.
            raise ValueError(f"singular GF(2^8) system (pivot column {col})")
        if piv != col:
            A[[col, piv]] = A[[piv, col]]
            rhs[col], rhs[piv] = rhs[piv], rhs[col]
        inv = gf_inv(int(A[col, col]))
        if inv != 1:
            A[col] = EXP_TABLE[LOG32[A[col]] + int(LOG32[inv])]
            rhs[col] = gf_mul_fast(inv, rhs[col])
        for r in range(e):
            c = int(A[r, col])
            if r == col or c == 0:
                continue
            A[r] ^= EXP_TABLE[LOG32[A[col]] + int(LOG32[c])]
            gf_addmul_fast(rhs[r], c, rhs[col])
    return rhs


def gf_matrix_inverse(A: np.ndarray) -> np.ndarray:
    """Inverse of an invertible (e, e) GF(2^8) matrix (a Cauchy submatrix):
    solve A·X = I column set via the same elimination as the data path."""
    e = A.shape[0]
    eye = np.eye(e, dtype=np.uint8)
    return np.stack(solve_gf(A, [eye[r] for r in range(e)]))


def erasure_decode_matrix(
    k: int,
    coef: np.ndarray,
    present_idx: list[int],
    blob_rows: list[int],
    missing: list[int],
) -> np.ndarray:
    """Fold the erasure solve into ONE GF(2^8) generator row per lost shard.

    For e = len(missing) losses with e surviving parity rows ``blob_rows``,
    the Gaussian solve ``A·x = syndromes`` (A the e×e submatrix
    ``coef[blob_rows][:, missing]``) collapses — since the syndromes are
    themselves linear in the inputs — into a *precomputed* decode matrix D of
    shape ``(e, k + m)`` over the concatenated input rows
    ``[data_0..data_{k-1}, blob_0..blob_{m-1}]``:

        rebuilt[t] = ⊕_{s ∈ present} D[t, s] · data_s
                     ⊕_{j ∈ blob_rows} D[t, k + j] · blob_j

    with D[t, s] = ⊕_j W[t, j]·coef[j, s] and D[t, k+j] = W[t, j] where
    W = A^{-1}. Columns for missing data shards and unused parity rows are
    zero. This is what turns decode into the exact mirror of encode: one
    coefficient matmul, chunkable over byte ranges on the host and executable
    by the (runtime-coefficient) Pallas kernel on device — no per-buffer
    Gaussian passes on the recovery path.
    """
    e = len(missing)
    m = coef.shape[0]
    assert len(blob_rows) == e, (blob_rows, missing)
    D = np.zeros((e, k + m), np.uint8)
    if e == 0:
        return D
    A = coef[np.ix_(blob_rows, missing)].astype(np.uint8)
    W = gf_matrix_inverse(A)
    for t in range(e):
        for jj, j in enumerate(blob_rows):
            w = int(W[t, jj])
            D[t, k + j] = w
            for s in present_idx:
                D[t, s] ^= gf_mul(w, int(coef[j, s]))
    return D


# ---------------------------------------------------------------------------
# Reed-Solomon encode / decode over byte buffers
# ---------------------------------------------------------------------------

def padded_len(bufs: list[np.ndarray]) -> int:
    """Blob length ``rs_encode`` produces: the 4-aligned max buffer size
    (uint32 stripe views, matching XOR parity)."""
    n = max(b.nbytes for b in bufs)
    return n + (-n) % 4


_padded_len = padded_len  # internal alias


def rs_encode(
    bufs: list[np.ndarray],
    m: int,
    coef: np.ndarray | None = None,
    out: list[np.ndarray] | None = None,
) -> list[np.ndarray]:
    """k data buffers (ragged lengths ok) -> m parity blobs of the padded size.

    blob_j = ⊕_i C[j][i] · data_i, accumulated over each buffer's prefix —
    the implicit zero padding contributes nothing, so no buffer is copied.

    ``out`` (optional) supplies m reusable uint8 accumulators of the padded
    length (``_padded_len``) — arena-leased by the engine so steady-state
    encodes allocate nothing.

    The whole m×k product runs as ONE :func:`gf_matrix_addmul_into` call
    through the active GF backend (DESIGN.md §14) — SWAR xtime chains or
    the fused jax-CPU Horner program; the ``table`` backend reproduces the
    PR 5 per-coefficient gather loop bit for bit.
    """
    k = len(bufs)
    C = cauchy_matrix(m, k) if coef is None else coef[:, :k]
    n = _padded_len(bufs)
    blobs = []
    for j in range(m):
        if out is None:
            acc = np.empty(n, np.uint8)
        else:
            acc = out[j]
            assert acc.dtype == np.uint8 and acc.nbytes == n, (acc.nbytes, n)
        blobs.append(acc)
    gf_matrix_addmul_into(blobs, [b.reshape(-1) for b in bufs], C, 0, n)
    return blobs


def rs_decode(
    present: dict[int, np.ndarray],
    blobs: dict[int, np.ndarray],
    missing: list[int],
    k: int,
    coef: np.ndarray | None = None,
    m: int | None = None,
) -> dict[int, np.ndarray]:
    """Rebuild ``missing`` data shards (group-local indices) from survivors.

    present: index -> surviving data buffer (ragged lengths ok)
    blobs:   parity index -> intact parity blob (any e of them suffice)
    Decoding needs the encode-time generator: pass the same ``coef`` matrix,
    or the same ``m`` to rebuild it (Cauchy entries depend on m, so it cannot
    be inferred from the surviving blob indices).
    Returns index -> rebuilt padded buffer; callers truncate via manifests.
    Raises ValueError if fewer than len(missing) parity blobs survive.
    """
    e = len(missing)
    if e == 0:
        return {}
    if len(blobs) < e:
        raise ValueError(
            f"need {e} parity blobs to rebuild {e} shards, only {len(blobs)} survive"
        )
    if coef is None:
        assert m is not None, "rs_decode needs the encode-time coef matrix or m"
        coef = cauchy_matrix(m, k)
    C = coef
    rows = sorted(blobs)[:e]
    # Fold the Gaussian solve into the precomputed erasure decode matrix
    # (``erasure_decode_matrix``): the e×e elimination runs once on the tiny
    # coefficient submatrix, then every data pass is one backend matrix
    # product over [survivors ‖ intact blobs] — the same shape the chunked
    # pipeline uses, dispatched through the active GF backend. Bit-identical
    # to the legacy syndromes+solve path (the GF solution is unique).
    present_idx = sorted(present)
    D = erasure_decode_matrix(k, C, present_idx, rows, missing)
    srcs = [present[i].reshape(-1) for i in present_idx] + [
        blobs[j].reshape(-1) for j in rows
    ]
    mat = [
        [int(D[t, s]) for s in present_idx] + [int(D[t, k + j]) for j in rows]
        for t in range(e)
    ]
    n = max(blobs[j].nbytes for j in rows)
    outs = [np.empty(n, np.uint8) for _ in missing]
    gf_matrix_addmul_into(outs, srcs, mat, 0, n)
    return {i: buf for i, buf in zip(missing, outs)}


def device_rs_encode(arrays: list, coef: np.ndarray) -> list[np.ndarray]:
    """Device-tier RS encode via the Pallas GF(2^8) kernel (kernels/rs_encode)."""
    from repro.kernels import ops

    out_u32 = ops.rs_encode_arrays(list(arrays), tuple(tuple(int(c) for c in row) for row in coef))
    return [np.asarray(row).view(np.uint8) for row in out_u32]
