"""The distributed checkpoint engine — paper §5.2 end to end.

Implements the coordinated, application-level, diskless scheme over a set of
per-rank host stores:

  Algorithm 2 (``checkpoint``): create snapshots into writable buffers →
  distribute redundancy per the registered codec → handshake (liveness +
  checksum validation) → pointer-swap all double buffers. A fault at any point
  before the swap leaves every read-only buffer untouched.

  Algorithm 4 (``restore``): a pure recovery plan maps every pre-fault rank to
  the store holding its data; survivors restore their own shards with zero
  communication, lost shards are rebuilt by the codec (adopted whole copies,
  XOR reconstruction, or Reed-Solomon multi-erasure decode).

Recovery is the **mirror image** of creation (DESIGN.md §10): under the
default ``restore_mode="pipelined"`` each failure group's reconstruction
drains a chunked TRANSFER i ‖ DECODE i−1 ‖ VERIFY i−2 pipeline — stripe
segments copy into arena-leased blob buffers, the codec's precomputed-matrix
``decode_into`` rebuilds byte ranges in place, and Fletcher partials of the
rebuilt bytes are checked against capture-time checksums replicated with the
manifests. Independent groups (and chunks) reconstruct in parallel across
``async_workers``; entities are mutated only after every shard is recovered.
``restore_mode="sync"`` keeps the serial per-origin ``codec.decode`` path —
bit-identical, and the benchmark baseline.

Creation is a **zero-copy, chunked pipeline** (DESIGN.md §9):

  * Phase A (``checkpoint_async``) captures every entity's shards straight
    into per-rank host-store **arenas** (``HostStore.lease`` +
    ``pack_bytes(out=...)``) — one memcpy per leaf, zero steady-state
    allocation, read-only buffers untouched.
  * Phase B (``finalize_async`` / a background worker) drains a three-stage
    software pipeline over (parity-group, entity) units: unit *g* ENCODEs
    (codec ``encode_into`` over arena views) while unit *g−1*'s stripes
    TRANSFER into their holder stores and unit *g−2* runs its VERIFY
    checksum — the encode/DMA/handshake overlap that makes creation cost
    independent of the validation pass.
  * The pointer swap at the end of ``finalize_async`` is the **single commit
    point**: every stage before it writes only writable-bank arenas, so a
    fault anywhere in the pipeline aborts back to the previous checkpoint.

All redundancy math and placement lives behind the ``RedundancyCodec``
interface (core/codec.py, DESIGN.md §8) — the engine encodes/decodes through
``self.codec`` and has no scheme-specific branches.

Below the diskless tier sits the **storage-tier ladder** (core/storage.py,
DESIGN.md §12): ``EngineConfig.tiers`` names persistent rungs (local disk,
shared directory) that a committed generation flushes to in the background —
on the same ``async_workers`` drain pool, after the pointer swap, so a flush
never extends the blocked capture window — and recovery **escalates** down
the ladder: codec reconstruction first, and only when the failure set
exceeds tolerance (or nothing survives a cold start) is the newest valid
on-disk generation rehydrated and recovery re-run against it.

The engine is single-controller (it simulates the SPMD host set — see
runtime.cluster); the device-tier collective program used on real pods is in
core/device_tier.py and shares the distribution schedules.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Protocol

import numpy as np

from repro.core import codec as codec_mod
from repro.core import distribution as dist
from repro.core import gf256
from repro.core import parity as parity_mod
from repro.core import storage as storage_mod
from repro.core.hoststore import HostStore, StorePayload
from repro.core.integrity import IntegrityError, np_checksum, payload_checksum
from repro.core.serialization import Manifest, dtype_from_name, pack_bytes, unpack_bytes
from repro.core.snapshot import SnapshotRegistry, Snapshottable
from repro.obs.journal import EventJournal
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import tracer
from repro.utils.logging import get_logger

log = get_logger("core.checkpoint")

_TR = tracer()  # process-global span tracer (no-op spans while disabled)

# Engines number themselves so multi-engine traces (benchmark A/B runs,
# server + trainer in one process) stay attributable per engine.
_ENGINE_SEQ = itertools.count()

#: Process-wide decode-rate record (range bytes/s EWMA per codec name): the
#: adaptive restore planner persists measurements here across engine
#: generations, so a fresh engine sizes its first restore's chunks from the
#: last engine's measured rate instead of the cold GF-probe estimate.
_DECODE_RATE: dict[str, float] = {}
_DECODE_RATE_LOCK = threading.Lock()

#: Process-wide encode-rate record (range bytes/s per codec name) — the
#: create-side twin of ``_DECODE_RATE``, feeding the adaptive encode-chunk
#: planner (ROADMAP item 1 stretch: the create-side host encode runs through
#: the same measured-rate / pow2-bucket / cpu-aware plan as restore).
_ENCODE_RATE: dict[str, float] = {}
_ENCODE_RATE_LOCK = threading.Lock()


class DistributedEntity(Protocol):
    """An entity whose snapshot is sharded across failure-domain ranks."""

    def snapshot_shards(self, n_ranks: int) -> list[Any]: ...

    def restore_shards(self, shards: dict[int, Any]) -> None: ...


class _ReplicatedAdapter:
    """Wraps a plain Snapshottable: same payload stored on every rank (small
    entities — timers, counters, RNG seeds)."""

    def __init__(self, entity: Snapshottable) -> None:
        self.entity = entity

    def snapshot_shards(self, n_ranks: int) -> list[Any]:
        payload = self.entity.snapshot()
        return [payload for _ in range(n_ranks)]

    def restore_shards(self, shards: dict[int, Any]) -> None:
        # Any surviving replica works; pick the lowest rank deterministically.
        self.entity.restore(shards[min(shards)])


@dataclass(frozen=True)
class EngineConfig:
    scheme: str = "pairwise"       # pairwise | neighbor (distribution callbacks)
    n_copies: int = 1              # R remote copies (eq. 2: MEM = S(1+2R'), R' = 1+n_copies)
    parity_group: int = 0          # >0: erasure-coded group size (k for xor/rs)
    compress: bool = False         # int8-compress partner payloads (beyond-paper)
    validate: bool = True          # checksum handshake
    # Redundancy codec (DESIGN.md §8): "copy" | "xor" | "rs" | any registered
    # name. Empty keeps the legacy inference — parity_group>0 selects "xor",
    # otherwise the full-copy scheme — so existing configs are bit-identical.
    codec: str = ""
    rs_parity: int = 2             # m parity blobs per group for codec="rs"
    # Local groups for codec="lrc" (Azure-style local reconstruction,
    # DESIGN.md §16): l local XOR parities over subgroups of ceil(k/l)
    # members plus rs_parity global Cauchy parities. Single-failure repair
    # reads only its subgroup; tolerance stays rs_parity.
    lrc_locals: int = 2
    # Failure-domain topology (core/topology.py, DESIGN.md §16): when set,
    # parity groups are placed so no group has two members in one domain at
    # topology.placement_level — a whole-rack loss costs each group at most
    # one member. None keeps the legacy contiguous rank-order groups
    # bit-identical.
    topology: object = None
    # Background workers draining the phase-B pipeline of an explicit
    # ``checkpoint_async`` (0 = drain synchronously inside finalize_async;
    # the blocking ``checkpoint`` path never spawns a thread either way).
    # With > 1, (group, entity) units shard across the workers — both the
    # create drain and the restore pipeline's parallel group reconstruction.
    async_workers: int = 1
    # Restore path (DESIGN.md §10): "pipelined" drains the chunked
    # TRANSFER/DECODE/VERIFY recovery pipeline (codec.decode_into over
    # arena-leased buffers, failure groups in parallel across async_workers);
    # "sync" keeps the serial per-origin codec.decode path (the A/B baseline
    # — both produce bit-identical restores).
    restore_mode: str = "pipelined"
    # Byte granularity of the restore pipeline's chunks (4-aligned). 0 — the
    # default — turns on the adaptive planner (DESIGN.md §14): chunks are
    # sized from the measured per-codec decode rate so fixed per-chunk
    # overhead stays a bounded fraction of decode time, and payloads below
    # the pipelining crossover collapse to the serial sync path. An explicit
    # nonzero value pins legacy fixed-size chunks and disables both
    # adaptations (tests pin tiny values to force multi-chunk coverage).
    restore_chunk_bytes: int = 0
    # CREATE-side encode chunking (the restore planner's twin, ROADMAP item
    # 1 stretch). 0 — the default — sizes encode ranges from the measured
    # per-codec encode rate (pow2 buckets, cpu-aware: with no realizable
    # parallelism the whole unit encodes as a single range, i.e. exactly the
    # legacy one-call shape). >0 pins fixed-size ranges (4-aligned); -1
    # disables chunking and always calls ``codec.encode_into`` whole.
    encode_chunk_bytes: int = 0
    # Differential checkpointing (DESIGN.md §17). When on, the encode stage
    # computes each member's exchange checksum per chunk of a fixed grid
    # (partials recombine to the exact monolithic Fletcher sums) and
    # replicates the chunk table with the manifests; the next capture diffs
    # against the committed table to (a) patch parity incrementally —
    # ``parity ^= G · (new ^ old)`` over merged dirty ranges only, exact by
    # GF(2^8) linearity — when the dirty fraction is under
    # ``delta_crossover``, and (b) skip re-copying stripe chunks the holder
    # arena already holds. Dedup-enabled persistent tiers (TierSpec.dedup)
    # additionally flush only content-new chunks to a shared chunk store.
    delta: bool = False
    delta_chunk_bytes: int = 1 << 20   # dirty-map chunk grid (4-aligned)
    delta_crossover: float = 0.6       # dirty fraction beyond which full re-encode wins
    # GF(2^8) host backend override: "table" | "swar" | "jax" forces that
    # backend process-wide (gf256.set_backend); "" keeps the microbenchmark
    # probe's winner (overridable again via env REPRO_GF_BACKEND).
    gf_backend: str = ""
    # Storage-tier ladder below the diskless HostStore tier (DESIGN.md §12):
    # persistent TierSpec rungs from core/storage.py, e.g.
    # ``(storage.disk("/ckpt", every=4),)`` — flushed in the background every
    # k-th commit, escalated to when failures exceed codec tolerance or the
    # whole job cold-starts. Empty keeps the engine purely diskless.
    tiers: tuple = ()


#: ``CheckpointStats`` attribute -> (metric kind, metric name, python type,
#: help). The flat legacy fields are *views* over these registry cells
#: (DESIGN.md §13): reading an attribute reads the cell, writing / ``+=``
#: writes it — so the Prometheus endpoint and the legacy fields can never
#: disagree. Naming follows the ``ckpt_* / restore_* / tier_*`` conventions.
_STATS_METRICS: dict[str, tuple[str, str, type, str]] = {
    "created": ("counter", "ckpt_created_total", int,
                "Checkpoints committed (pointer swaps)."),
    "aborted": ("counter", "ckpt_aborted_total", int,
                "Checkpoints aborted before the commit point."),
    "restored": ("counter", "restore_total", int,
                 "Successful restores (incl. elastic)."),
    "last_create_s": ("gauge", "ckpt_last_create_seconds", float,
                      "Wall time of the last checkpoint, capture to commit."),
    "last_restore_s": ("gauge", "restore_last_seconds", float,
                       "Wall time of the last restore."),
    "last_bytes_exchanged": ("gauge", "ckpt_last_bytes_exchanged", int,
                             "Redundancy bytes the last checkpoint moved."),
    "last_bytes_per_rank": ("gauge", "ckpt_last_bytes_per_rank", int,
                            "Redundancy bytes per rank, last checkpoint."),
    "zero_comm_restores": ("counter", "restore_zero_comm_shards_total", int,
                           "Shards restored from local memory."),
    "adopted_restores": ("counter", "restore_adopted_shards_total", int,
                         "Shards adopted from partner copies."),
    "reconstructed_restores": ("counter", "restore_reconstructed_shards_total",
                               int, "Shards rebuilt from parity."),
    # Pipeline accounting (DESIGN.md §9):
    "last_capture_s": ("gauge", "ckpt_last_capture_seconds", float,
                       "Phase A: arena-staged snapshot capture."),
    "last_finalize_wait_s": ("gauge", "ckpt_last_finalize_wait_seconds", float,
                             "Time finalize_async blocked on phase B."),
    "last_blocked_s": ("gauge", "ckpt_last_blocked_seconds", float,
                       "Capture + finalize wait = blocked critical path."),
    "last_bytes_staged": ("gauge", "ckpt_last_bytes_staged", int,
                          "Own + exchange bytes staged (host DMA)."),
    "last_pipeline_chunks": ("gauge", "ckpt_last_pipeline_chunks", int,
                             "(group, entity) units the last drain ran."),
    # Restore pipeline accounting (DESIGN.md §10):
    "last_restore_decode_s": ("gauge", "restore_last_decode_seconds", float,
                              "Wall time of the last recovery drain."),
    "last_restore_bytes_rebuilt": ("gauge", "restore_last_bytes_rebuilt", int,
                                   "Padded bytes codecs reconstructed."),
    "last_restore_chunks": ("gauge", "restore_last_chunks", int,
                            "TRANSFER/DECODE/VERIFY chunks drained."),
    "last_restore_decompressed_bytes": (
        "gauge", "restore_last_decompressed_bytes", int,
        "Bytes expanded by the chunked DEQ stage."),
    "restore_plan_reuses": ("counter", "restore_plan_reuse_total", int,
                            "Restore units served from the generation-keyed "
                            "plan cache (prep/TRANSFER/VERIFY amortized)."),
    # Storage-tier ladder accounting (DESIGN.md §12):
    "tier_flushes": ("counter", "tier_flush_total", int,
                     "Persistent-tier generations committed."),
    "tier_flush_skipped": ("counter", "tier_flush_skipped_total", int,
                           "Flush cadence points dropped under back-pressure."),
    "tier_flush_queued": ("counter", "tier_flush_queued_total", int,
                          "Flush cadence points deferred into the queue slot."),
    "tier_escalations": ("counter", "tier_escalation_total", int,
                         "Recoveries that fell back to a persistent tier."),
    "last_flush_s": ("gauge", "tier_last_flush_seconds", float,
                     "Wall time of the last background flush."),
    "last_flush_bytes": ("gauge", "tier_last_flush_bytes", int,
                         "Bytes the last flush wrote."),
    "last_flush_wait_s": ("gauge", "tier_last_flush_wait_seconds", float,
                          "Capture time spent joining a flush (bank conflict)."),
    # Differential checkpointing (DESIGN.md §17):
    "last_dirty_fraction": ("gauge", "ckpt_last_dirty_fraction", float,
                            "Dirty-chunk byte fraction of the last delta capture."),
    "delta_encodes": ("counter", "ckpt_delta_encode_total", int,
                      "Units whose parity was patched incrementally."),
    "full_encodes": ("counter", "ckpt_full_encode_total", int,
                     "Units re-encoded in full under delta mode."),
    "last_transfer_bytes_skipped": (
        "gauge", "ckpt_last_transfer_bytes_skipped", int,
        "Stripe bytes the last transfer left in place (unchanged chunks)."),
    "last_flush_chunks_written": (
        "gauge", "tier_last_flush_chunks_written", int,
        "New chunk objects the last dedup flush stored."),
    "last_flush_chunks_reused": (
        "gauge", "tier_last_flush_chunks_reused", int,
        "Chunk references the last dedup flush served from the store."),
    "last_dedup_ratio": ("gauge", "tier_last_dedup_ratio", float,
                         "Stored/logical byte ratio of the last dedup flush "
                         "(lower = more dedup)."),
}


class CheckpointStats:
    """Flat engine statistics, kept as a backwards-compatible *view* over a
    :class:`~repro.obs.metrics.MetricsRegistry`: every attribute maps to a
    typed counter/gauge cell (``_STATS_METRICS``), so ``stats.created += 1``
    and ``registry.counter("ckpt_created_total")`` are the same number by
    construction. Int-typed fields round-trip through ``int`` on read, so
    ``%d`` formatting and exact comparisons behave like the old dataclass."""

    __slots__ = ("registry", "_cells")

    def __init__(self, registry: MetricsRegistry | None = None) -> None:
        reg = registry if registry is not None else MetricsRegistry()
        cells: dict[str, tuple[Any, type]] = {}
        for attr, (kind, name, typ, help_) in _STATS_METRICS.items():
            cells[attr] = (getattr(reg, kind)(name, help_), typ)
        object.__setattr__(self, "registry", reg)
        object.__setattr__(self, "_cells", cells)

    def __getattr__(self, attr: str) -> Any:
        try:
            metric, typ = object.__getattribute__(self, "_cells")[attr]
        except KeyError:
            raise AttributeError(attr) from None
        return typ(metric.value())

    def __setattr__(self, attr: str, value: Any) -> None:
        try:
            metric, _ = self._cells[attr]
        except KeyError:
            raise AttributeError(
                f"CheckpointStats has no field {attr!r}"
            ) from None
        metric.set(value)

    def __repr__(self) -> str:
        body = ", ".join(f"{a}={getattr(self, a)!r}" for a in _STATS_METRICS)
        return f"CheckpointStats({body})"

    def as_dict(self) -> dict[str, Any]:
        return {a: getattr(self, a) for a in _STATS_METRICS}


class FaultDuringCheckpoint(RuntimeError):
    """Raised into the engine by the failure injector mid-checkpoint."""


@dataclass
class _RestoreUnit:
    """One failure group's reconstruction of one entity — the unit of the
    restore pipeline (DESIGN.md §10). Prepared up front (references to the
    surviving stripes/shards captured, arenas leased, the erasure-solve
    coefficients precomputed inside ``codec.decode_into``), then drained in
    4-aligned byte chunks: TRANSFER copies stripe segments into the blob
    arenas, DECODE applies the codec's chunk function, VERIFY accumulates
    the rebuilt Fletcher sums against the replicated capture-time checksums.
    Chunks of one unit touch disjoint byte ranges, so independent chunks —
    and independent units — reconstruct in parallel across workers."""

    gi: int
    grp: Any
    name: str
    missing_idx: list[int]
    stripe_srcs: dict[int, list[np.ndarray]]   # blob -> stripes to join (multi-stripe only)
    blobs: dict[int, np.ndarray]               # blob -> arena (or adopted single stripe)
    rebuilt: dict[int, np.ndarray]             # missing idx -> leased output buffer
    decode_chunk: Any                          # codec chunk fn (lo, hi) -> None
    bounds: list[tuple[int, int]]              # 4-aligned chunk byte ranges
    manifests: dict[int, Any]                  # missing idx -> origin manifest
    ref_sums: dict[int, Any]                   # missing idx -> capture checksum | None
    sums: dict[int, list]                      # missing idx -> per-chunk partials
    # Chunked decompression plans for compressed origins (missing idx ->
    # per-quantized-leaf _DeqLeaf): the int8 -> f32 blockwise dequantization
    # runs per chunk inside the drain instead of one monolithic pass at
    # finalize. None when no origin in the unit is compressed.
    decomp: dict[int, list] | None = None
    # Set after a fully-successful restore when the unit enters the
    # generation-keyed restore-plan cache (DESIGN.md §14): committed stripes
    # are immutable, so a repeat restore of the same (generation, alive,
    # failed) topology skips re-joining stripe bytes into the blob arenas
    # (``staged``) and re-deriving the already-clean checksum verdict
    # (``verified``) — the DECODE stage always re-runs.
    staged: bool = False
    verified: bool = False


@dataclass
class _DeqLeaf:
    """One quantized leaf of a compressed origin, dequantized chunk-by-chunk:
    byte range [q_off, q_off+q_n) of the rebuilt compressed flat holds the
    int8 codes; ``scales`` (one f32 per ``block`` codes) is resolved at prep
    (the compressed blob is adopted by reference, so scale bytes exist before
    the drain); ``out`` is the arena-leased f32 destination."""

    q_off: int
    q_n: int
    block: int
    scales: np.ndarray
    out: np.ndarray


@dataclass
class _PendingCheckpoint:
    """An un-committed snapshot between phase A (capture) and the swap."""

    packed: dict[str, list[tuple[Any, Manifest]]]   # exchange/partner buffers
    manifests: dict[tuple[int, str], Any]
    alive0: set[int]
    t0: float
    future: Any = None          # background drain future (None = sync drain)
    bytes_exchanged: int = 0
    verified: set = field(default_factory=set)      # (rank, entity) chunk-verified
    # Replicated with every store's meta (shared reference, like the
    # manifests) and FILLED BY THE DRAIN's encode stage — capture-time
    # exchange checksums for the restore pipeline's VERIFY, computed off
    # the blocking capture window. Keys are (rank, entity).
    exch_sums: dict = field(default_factory=dict)
    # Generation this snapshot becomes when it commits (stats.created + 1 at
    # capture) — the label that ties every span of one checkpoint together.
    gen: int = 0
    # Differential bookkeeping (cfg.delta, DESIGN.md §17), all filled by the
    # drain like exch_sums: the per-(rank, entity) chunk-grid Fletcher
    # partials of this capture's exchange payloads (replicated in meta — the
    # next capture's dirty-map baseline), the scratch-parity validity
    # entries staged for commit, and the capture's dirty/skip byte tally.
    chunk_sums: dict = field(default_factory=dict)
    delta_enc: dict = field(default_factory=dict)
    dirty_bytes: int = 0
    logical_bytes: int = 0
    skipped_bytes: int = 0


def _chunk_checksums(flat: np.ndarray, step: int) -> tuple:
    """Per-chunk Fletcher partials over the ``step``-grid (step 4-aligned,
    only the last chunk ragged). Linearity makes them recombinable: the
    chunk at word offset ``o`` contributes ``s1 += c1; s2 += c2 + o·c1``,
    so the combined sums equal a monolithic ``np_checksum``."""
    return tuple(
        np_checksum(flat[lo : lo + step]) for lo in range(0, flat.nbytes, step)
    )


def _combine_checksums(parts: tuple, step: int) -> tuple[int, int]:
    s1 = s2 = 0
    words = step // 4
    for ci, (c1, c2) in enumerate(parts):
        s1 = (s1 + c1) & 0xFFFFFFFF
        s2 = (s2 + c2 + ci * words * c1) & 0xFFFFFFFF
    return s1, s2


def _merge_chunk_ranges(idx: list[int], step: int, nbytes: int) -> list:
    """Dirty chunk indices -> merged, clipped [lo, hi) byte ranges."""
    ranges: list[list[int]] = []
    for ci in idx:
        lo, hi = ci * step, min(ci * step + step, nbytes)
        if ranges and ranges[-1][1] == lo:
            ranges[-1][1] = hi
        else:
            ranges.append([lo, hi])
    return [(lo, hi) for lo, hi in ranges]


def _copy_dirty(dst: np.ndarray, src: np.ndarray, step: int) -> int:
    """Copy only the step-grid chunks of ``src`` that differ from what
    ``dst`` (the holder arena's previous content) already holds; returns the
    bytes left in place. Exact — it compares the actual bytes, so a freshly
    allocated (garbage) arena simply copies everything."""
    skipped = 0
    for lo in range(0, src.nbytes, step):
        hi = min(lo + step, src.nbytes)
        if np.array_equal(dst[lo:hi], src[lo:hi]):
            skipped += hi - lo
        else:
            np.copyto(dst[lo:hi], src[lo:hi])
    return skipped


class CheckpointEngine:
    def __init__(
        self,
        n_ranks: int,
        cfg: EngineConfig = EngineConfig(),
        alive_fn: Callable[[], set[int]] | None = None,
        fault_hook: Callable[[str], None] | None = None,
    ) -> None:
        self.n_ranks = n_ranks
        self.cfg = cfg
        self.stores: dict[int, HostStore] = {r: HostStore(r) for r in range(n_ranks)}
        self._entities: dict[str, DistributedEntity] = {}
        # Entities whose payload is identical on every rank need no partner
        # exchange (paper §5.2.1: "no exchange is needed for instance if the
        # entity's data is equal on all processes") — any survivor restores them.
        self._replicated: set[str] = set()
        self._alive_fn = alive_fn or (lambda: {r for r, s in self.stores.items() if s.alive})
        # fault_hook(phase) lets the failure injector strike at precise points
        # inside the checkpoint procedure (tests for Algorithm 2's guarantee).
        self._fault_hook = fault_hook or (lambda phase: None)
        self._pending: _PendingCheckpoint | None = None  # un-finalized async snapshot
        self._pool: Any = None               # lazy ThreadPoolExecutor (async drain)
        # Single-slot restore-plan cache (DESIGN.md §14): key -> prepped
        # units of the last fully-successful pipelined restore. One slot is
        # a correctness requirement, not thrift — restore arenas are leased
        # by (gi, entity, ...) key, so plans from two different generations
        # would alias the same buffers.
        self._restore_plan_cache: tuple[Any, dict[tuple[int, str], Any]] | None = None
        self._enc_scratch: dict[Any, np.ndarray] = {}  # transient blob accumulators
        # Differential checkpointing (DESIGN.md §17): which (group, entity)
        # scratch arenas still hold the COMMITTED generation's parity, and
        # for which codec/member layout — the baseline incremental patching
        # requires. Invalidated wholesale on aborts/discards/escalations:
        # a full re-encode is always correct, a stale baseline never is.
        self._delta_enc: dict[tuple[int, str], tuple] = {}
        self._delta_lock = threading.Lock()  # pending dirty/skip tallies
        # Storage-tier ladder (DESIGN.md §12): rung 0 is the diskless
        # HostStore set above; persistent rungs flush committed generations
        # in the background and feed escalating recovery.
        self.tiers = storage_mod.build_tiers(cfg.tiers)
        self._flush_future: Any = None       # at most one in-flight flush
        self._flush_created: int = -1        # commit counter when it started
        self._flush_pending: Any = None      # queued (due, snapshot): one slot
        # Guards the _flush_pending hand-off between the caller and the flush
        # worker (the worker chains the queued flush inline — back-pressure
        # defers a cadence point instead of dropping it).
        self._flush_lock = threading.Lock()
        # Observability (DESIGN.md §13): an engine-local metrics registry —
        # CheckpointStats is a view over it — per-stage histograms for the
        # adaptive chunk planner, and a durable event journal placed inside
        # the first persistent tier's directory so the failure/recovery
        # record survives cold restarts alongside the checkpoint data.
        self._obs_id = next(_ENGINE_SEQ)
        self.stats = CheckpointStats()
        self.registry = self.stats.registry
        self._h_stage = self.registry.histogram(
            "ckpt_stage_seconds", "Create-pipeline stage seconds per unit.",
            labelnames=("phase",),
        )
        self._h_restore = self.registry.histogram(
            "restore_stage_seconds", "Restore-pipeline stage seconds per chunk.",
            labelnames=("phase",),
        )
        # Pre-bound label children for the chunk hot loop: the disabled-tracer
        # fast path must not build kwargs dicts per chunk (DESIGN.md §14).
        self._hr_transfer = self._h_restore.labels(phase="r_transfer")
        self._hr_decode = self._h_restore.labels(phase="decode")
        self._hr_verify = self._h_restore.labels(phase="r_verify")
        self._hr_deq = self._h_restore.labels(phase="deq")
        # Measured chunk-decode throughput (range bytes/s) feeding the
        # adaptive restore planner; also mirrored into the process-wide
        # _DECODE_RATE record so later engine generations inherit it.
        self._h_restore_rate = self.registry.histogram(
            "restore_decode_bytes_per_second",
            "Chunk-decode throughput driving the adaptive restore planner.",
            labelnames=("codec",),
        )
        journal_path = next(
            (
                os.path.join(t.path, "journal.jsonl")
                for t in self.tiers
                if t.persistent and getattr(t, "path", None)
            ),
            None,
        )
        self.journal = EventJournal(journal_path, self.registry)
        self.last_elastic_report: Any = None  # ElasticReport of the last N-to-M restore
        if cfg.parity_group:
            # Non-dividing world sizes get a short last group (parity_groups):
            # the elastic N-to-M path lands on arbitrary M. Group size 1 is
            # the degenerate neighbor-copy scheme (a singleton's parity is
            # its snapshot, stored on the next group) and stays allowed.
            assert cfg.parity_group >= 1, cfg.parity_group
        # All redundancy math + placement dispatches through the codec
        # (DESIGN.md §8); the engine itself is scheme-agnostic.
        self.codec = codec_mod.make_codec(cfg)
        # Per-entity codec overrides (DESIGN.md §16): the adaptive protection
        # policy upgrades hot entities (e.g. optimizer state) to a stronger
        # or cheaper-to-repair codec at the SAME group size — every override
        # shares the engine's group layout, so only the blob math differs.
        # Restores resolve codecs from the captured payload's codec record,
        # so a policy change between capture and restore cannot desync.
        self.entity_codecs: dict[str, codec_mod.RedundancyCodec] = {}
        self._spec_codecs: dict[str, codec_mod.RedundancyCodec] = {}
        # Failure-domain topology (DESIGN.md §16): sized to this world;
        # resized alongside the engine by the elastic path.
        self.topology = (
            cfg.topology.resized(n_ranks) if cfg.topology is not None else None
        )
        self._groups_cache: tuple[tuple, list] | None = None
        # Commit-point hooks (the adaptive policy re-evaluates here).
        self._commit_hooks: list = []
        if cfg.gf_backend:
            gf256.set_backend(cfg.gf_backend)

    # ------------------------------------------------------------------ #
    # per-entity protection (adaptive policy surface, DESIGN.md §16)
    # ------------------------------------------------------------------ #
    def set_entity_codec(self, name: str, codec: str, m: int | None = None) -> None:
        """Override the redundancy codec for one entity from the NEXT
        checkpoint on. The override keeps the engine's group size (layout,
        placement, and recovery plans stay shared); only blob count and
        decode math change. ``m`` sets rs_parity for "rs"/"lrc"."""
        import dataclasses as _dc

        base = self.cfg
        cand = _dc.replace(
            base,
            codec=codec,
            rs_parity=m if m is not None else base.rs_parity,
        )
        new = codec_mod.make_codec(cand)
        assert new.group_size(self.n_ranks) == self.codec.group_size(self.n_ranks), (
            f"entity codec {codec!r} changes the group size; per-entity "
            f"overrides must keep the engine layout"
        )
        self.entity_codecs[name] = new

    def clear_entity_codec(self, name: str) -> None:
        self.entity_codecs.pop(name, None)

    def _codec_for(self, name: str) -> codec_mod.RedundancyCodec:
        return self.entity_codecs.get(name, self.codec)

    def _codec_spec(self, c: codec_mod.RedundancyCodec) -> str:
        """Compact codec descriptor recorded per entity in every payload
        (restore resolves codecs from this, never from live policy state)."""
        m = getattr(c, "m", getattr(c, "global_parity", 0))
        l = getattr(c, "local", 0)
        return f"{c.name}:{m}:{l}"

    def _codec_from_spec(self, spec: str) -> codec_mod.RedundancyCodec:
        import dataclasses as _dc

        name, m, l = spec.split(":")
        if self._codec_spec(self.codec) == spec:
            return self.codec
        cached = self._spec_codecs.get(spec)
        if cached is None:
            cand = _dc.replace(
                self.cfg,
                codec=name,
                rs_parity=max(int(m), 1),
                lrc_locals=int(l) if int(l) else self.cfg.lrc_locals,
            )
            cached = self._spec_codecs[spec] = codec_mod.make_codec(cand)
        return cached

    def _restore_codec(self, name: str) -> codec_mod.RedundancyCodec:
        """Codec for restoring entity ``name``: resolved from the codec
        record captured WITH the payload (any valid store carries it), so a
        policy override between capture and restore decodes with the codec
        that actually encoded. Falls back to the live override map for
        pre-§16 payloads."""
        for st in self.stores.values():
            if st.alive and st.buffer.valid:
                spec = st.buffer.read_only.meta.get("codecs", {}).get(name)
                if spec:
                    return self._codec_from_spec(spec)
        return self._codec_for(name)

    def add_commit_hook(self, fn) -> None:
        """``fn(engine)`` runs after every successful commit (pointer swap +
        tier-flush scheduling) — the adaptive policy's re-evaluation point."""
        self._commit_hooks.append(fn)

    # ------------------------------------------------------------------ #
    # registration
    # ------------------------------------------------------------------ #
    def register(self, name: str, entity: Snapshottable | DistributedEntity) -> None:
        if name in self._entities:
            raise KeyError(f"entity {name!r} already registered")
        if hasattr(entity, "snapshot_shards"):
            self._entities[name] = entity  # type: ignore[assignment]
        else:
            self._entities[name] = _ReplicatedAdapter(entity)  # type: ignore[arg-type]
            self._replicated.add(name)

    def register_registry(self, registry: SnapshotRegistry) -> None:
        """Adopt all entities of a plain SnapshotRegistry as replicated ones."""
        for name in registry.names():
            create = registry._entries[name].create
            restore = registry._entries[name].restore
            self.register(name, _FnEntity(create, restore))  # type: ignore[arg-type]

    # ------------------------------------------------------------------ #
    # Algorithm 2: resilient checkpoint creation
    # ------------------------------------------------------------------ #
    def checkpoint(self, meta: dict[str, Any] | None = None) -> bool:
        """Create + distribute + handshake + swap. Returns True on success;
        False if a fault struck before the swap (read-only buffers intact).
        Fully synchronous and deterministic (no background worker)."""
        if self.checkpoint_async(meta, background=False):
            return self.finalize_async() is True
        return False

    def checkpoint_async(
        self, meta: dict[str, Any] | None = None, background: bool | None = None
    ) -> bool:
        """Phase A (synchronous): capture a consistent snapshot of every
        entity straight into the writable-bank arenas. The expensive encode +
        stripe transfer + verify pipeline is deferred — to a background
        worker when ``background`` (default: ``cfg.async_workers > 0``), else
        to ``finalize_async`` — so it overlaps with subsequent train steps
        (compute/comm overlap; on TPU this is the device→host DMA followed by
        background ICI/DCN traffic). Algorithm 2's guarantee is preserved:
        nothing touches the read-only buffers until the deferred handshake
        succeeds and the buffers swap."""
        if self._pending is not None:
            # Two captures without a finalize: the first snapshot was never
            # committed — drain + drop it before its arenas are re-leased.
            self.discard_pending()
        self.kick_tier_flush()  # staged flush runs behind this capture (disjoint banks)
        queued = self._flush_pending  # local ref: the flush worker may take it
        if (
            self._flush_future is not None
            and self.stats.created > self._flush_created
        ) or (queued is not None and self.stats.created > queued[1].created):
            # A commit happened since the in-flight tier flush started (or
            # since a queued flush captured its snapshot), so the bank this
            # capture is about to stage into is the bank that flush still
            # reads (generation-parity rule): join it before the arenas are
            # re-leased. The flush had a full checkpoint interval to finish,
            # so this wait is the rare stall, not the steady state —
            # recorded in last_flush_wait_s either way.
            t_w = time.perf_counter()
            with _TR.span("flush_wait", eng=self._obs_id, gen=self.stats.created + 1):
                self._join_flush()
            self.stats.last_flush_wait_s = time.perf_counter() - t_w
        else:
            self.stats.last_flush_wait_s = 0.0
        gen = self.stats.created + 1  # generation this capture becomes on commit
        t0 = time.perf_counter()
        alive0 = self._alive_fn()
        try:
            with _TR.span("capture", eng=self._obs_id, gen=gen):
                self._fault_hook("before_create")
                packed_partner, manifests, exch_sums, chunk_sums = self._capture(
                    alive0, meta, gen
                )
                self._fault_hook("after_create")
        except FaultDuringCheckpoint as e:
            log.warning("checkpoint aborted during create: %s", e)
            for s in self.stores.values():
                s.buffer.discard_writable()
            self.stats.aborted += 1
            self.journal.record("abort", phase="capture", gen=gen, cause=str(e))
            return False

        self.stats.last_capture_s = time.perf_counter() - t0
        self._h_stage.observe(self.stats.last_capture_s, phase="capture")
        pending = _PendingCheckpoint(
            packed_partner, manifests, alive0, t0, exch_sums=exch_sums,
            chunk_sums=chunk_sums, gen=gen,
        )
        self._pending = pending
        if background is None:
            background = self.cfg.async_workers > 0
        if background:
            pending.future = self._executor().submit(self._drain, pending)
        return True

    def _capture(
        self, alive0: set[int], meta: dict[str, Any] | None, gen: int
    ) -> tuple[
        dict[str, list[tuple[Any, Manifest]]], dict[tuple[int, str], Any], dict, dict
    ]:
        """Serialize every entity's per-rank shards directly into host-store
        arenas (one memcpy per leaf, zero steady-state allocation) and stage
        the writable payloads. Returns the exchange buffers the pipeline
        encodes plus the replicated manifest table."""
        packed: dict[str, list[tuple[Any, Manifest]]] = {}
        packed_partner: dict[str, list[tuple[Any, Manifest]]] = {}
        coords_tables: dict[str, Any] = {}
        bytes_staged = 0
        bytes_replicated = 0  # leaves every rank holds whole, per rank copy
        eng = self._obs_id

        def _lease_for(r: int, key: tuple):
            """HostStore.lease bound for pack_bytes's callback form (sizing
            happens inside pack_bytes's single traversal); None for ranks
            with no live store — those pack into fresh buffers."""
            store = self.stores.get(r)
            if r not in alive0 or store is None or not store.alive:
                return None
            return lambda nbytes: store.lease(key, nbytes)

        # Every entity's shards first (a sharded state's D2H fetch is timed
        # inside it), then all packing under one span. An entity on the
        # device also hands over its pieces' checksums, summed there before
        # the D2H (``capture_shards``).
        snaps, piece_sums = {}, {}
        for name, ent in self._entities.items():
            if self.cfg.validate and hasattr(ent, "capture_shards"):
                snaps[name], piece_sums[name] = ent.capture_shards(self.n_ranks)
            else:
                snaps[name] = ent.snapshot_shards(self.n_ranks)
        with _TR.span("capture_pack", eng=eng, gen=gen) as sp:
            for name, ent in self._entities.items():
                shards = snaps.pop(name)
                rows: list[tuple[Any, Manifest]] = []
                for r, shard in enumerate(shards):
                    rows.append(pack_bytes(shard, lease=_lease_for(r, ("own", name))))
                    bytes_staged += rows[-1][0].nbytes
                packed[name] = rows
                if hasattr(ent, "replicated_nbytes"):
                    bytes_replicated += sum(
                        ent.replicated_nbytes(shard, self.n_ranks) for shard in shards
                    )
                if hasattr(ent, "shard_coords"):
                    # Global-coordinate manifest: each shard records its slice
                    # of the logical entity, the layer elastic N-to-M restore
                    # repartitions on. The full table is tiny and replicated
                    # with every store's meta (like the parity manifests).
                    table = ent.shard_coords(self.n_ranks)
                    for r, (_, man) in enumerate(packed[name]):
                        man.coords = table[r]
                    coords_tables[name] = table
                if hasattr(ent, "partner_payload"):
                    # Exchange only the uniquely-owned subset (replicated
                    # leaves exist on every rank already — paper §5.2.1).
                    sub_rows: list[tuple[Any, Manifest]] = []
                    for r, shard in enumerate(shards):
                        subset = ent.partner_payload(shard, self.n_ranks)
                        sub_rows.append(
                            pack_bytes(subset, lease=_lease_for(r, ("exch", name)))
                        )
                        bytes_staged += sub_rows[-1][0].nbytes
                    packed_partner[name] = sub_rows
                else:
                    packed_partner[name] = packed[name]
            sp.label(bytes=bytes_staged, replicated_bytes=bytes_replicated)

        # Manifests are tiny: replicate all of them with every store's meta so
        # any survivor can unpack any origin's rebuilt bytes. (Compression in
        # the encode stage swaps in the tagged compressed manifest per origin
        # — the dict is shared, mutated only before the commit point.)
        manifests = {
            (r, name): rows[r][1]
            for name, rows in packed_partner.items()
            for r in range(self.n_ranks)
        }

        # Checksums of every origin's EXCHANGE payload, replicated like the
        # manifests: the restore pipeline's VERIFY stage recomputes them over
        # codec-rebuilt bytes, so a corrupt reconstruction is caught before
        # it reaches an entity. The shared dict is attached EMPTY here and
        # filled by the drain's encode stage (off the blocking capture
        # window — phase A stays one-memcpy-per-leaf); it is complete before
        # the commit because the swap always follows the drain.
        exch_sums: dict[tuple[int, str], Any] = {}

        # Per-chunk Fletcher partials of the same exchange payloads
        # (cfg.delta, DESIGN.md §17), replicated exactly like exch_sums and
        # also filled by the drain's encode stage: the NEXT capture's
        # dirty-map baseline — any survivor carries it, so the diff works
        # after failures just like restore verification does.
        chunk_sums: dict[tuple[int, str], Any] = {}

        # Per-entity codec record (DESIGN.md §16): replicated with every
        # store's meta like the manifests, so restore decodes with the codec
        # that encoded even if the policy has since changed its mind.
        codec_specs = {
            name: self._codec_spec(self._codec_for(name)) for name in packed
        }
        with _TR.span("capture_checksum", eng=eng, gen=gen) as sp:
            # The reference sums of each own payload: the device's piece sums
            # (one fetch per entity) placed at their offsets, the rest summed
            # over the packed bytes here.
            pieces = {
                name: ps.per_rank(self.n_ranks)
                for name, ps in piece_sums.items() if ps is not None
            }
            device_bytes = host_bytes = 0
            for r in alive0:
                payload = StorePayload(meta=dict(meta or {}))
                if coords_tables:
                    payload.meta["coords"] = dict(coords_tables)
                payload.meta["manifests"] = manifests
                payload.meta["codecs"] = codec_specs
                for name, rows in packed.items():
                    flat, man = rows[r]
                    payload.own[name] = (flat, man)
                    if (
                        self._codec_for(name).striped
                        and packed_partner[name] is not packed[name]
                    ):
                        payload.own_exch[name] = packed_partner[name][r]
                    if self.cfg.validate:
                        sums, known = payload_checksum(
                            flat, man.offsets, pieces[name][r] if name in pieces else None
                        )
                        payload.meta.setdefault("checksums", {})[name] = sums
                        device_bytes += known
                        host_bytes += flat.nbytes - known
                if self.cfg.validate:
                    payload.meta["exch_checksums"] = exch_sums
                if self.cfg.delta:
                    payload.meta["exch_chunk_sums"] = chunk_sums
                self.stores[r].buffer.write(payload)
            sp.label(device_bytes=device_bytes, host_bytes=host_bytes)
        self.stats.last_bytes_staged = bytes_staged
        return packed_partner, manifests, exch_sums, chunk_sums

    # ------------------------------------------------------------------ #
    # phase B: the chunked encode/transfer/verify pipeline
    # ------------------------------------------------------------------ #
    def _executor(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=max(1, self.cfg.async_workers),
                thread_name_prefix="ckpt-pipeline",
            )
        return self._pool

    def _pipeline_units(self, packed) -> list[tuple]:
        """One work unit per (parity group, entity): the granularity at which
        encode, stripe transfer, and verification are pipelined. Placement is
        per entity — policy overrides change blob counts (rs m, lrc l+g)
        while the shared group layout keeps holders aligned."""
        groups = self._groups()
        units = []
        for gi, grp in enumerate(groups):
            for name in packed:
                if name in self._replicated:
                    continue  # equal on all ranks: no redundancy needed
                placements = self._codec_for(name).placement(
                    groups, gi, self.n_ranks
                )
                if not placements:
                    continue
                units.append((gi, grp, placements, name))
        return units

    def _drain(self, pending: _PendingCheckpoint) -> tuple[int, set]:
        """Run the three-stage software pipeline to completion: unit *i*
        ENCODEs while unit *i−1*'s stripes TRANSFER to their host stores and
        unit *i−2* VERIFYs its members' staged checksums. Nothing here ever
        touches a read-only buffer; a fault at any chunk raises
        ``FaultDuringCheckpoint`` and the whole snapshot aborts.

        With ``async_workers > 1`` the (group, entity) units shard across the
        worker pool — each worker drains its own three-stage sub-pipeline;
        units touching the same holder store synchronize through the store's
        lock (arena growth + payload-dict writes), while the byte copies land
        in disjoint arenas and run lock-free. This thread keeps one shard for
        itself, so the pool (sized ``async_workers``) never deadlocks when
        the drain itself runs as a background submission."""
        units = self._pipeline_units(pending.packed)
        n = len(units)
        n_shards = max(1, min(self.cfg.async_workers, n))
        if n_shards == 1:
            total, verified = self._drain_shard(units, pending)
        else:
            shards = [units[w::n_shards] for w in range(n_shards)]
            futures = [
                self._executor().submit(self._drain_shard, shard, pending)
                for shard in shards[1:]
            ]
            # Join EVERY sibling shard before propagating any failure: an
            # abandoned worker would keep writing into staging arenas after
            # finalize_async discards them (and races the next lease).
            err: BaseException | None = None
            total, verified = 0, set()
            try:
                total, verified = self._drain_shard(shards[0], pending)
            except BaseException as e:
                err = e
            for f in futures:
                try:
                    sub_total, sub_verified = f.result()
                    total += sub_total
                    verified |= sub_verified
                except BaseException as e:
                    err = err or e
            if err is not None:
                raise err
        self.stats.last_pipeline_chunks = n
        return total, verified

    def _drain_shard(
        self, units: list[tuple], pending: _PendingCheckpoint
    ) -> tuple[int, set]:
        """One worker's share of the drain, in pipeline order."""
        n = len(units)
        total = 0
        verified: set = set()
        encoded: dict[int, list[np.ndarray]] = {}
        eng, gen = self._obs_id, pending.gen
        for i in range(n + 2):
            if i < n:
                u = units[i]
                with _TR.span("encode", eng=eng, gen=gen, group=u[0], entity=u[3]):
                    t = time.perf_counter()
                    encoded[i] = self._encode_unit(u, pending)
                    self._h_stage.observe(time.perf_counter() - t, phase="encode")
            if 0 <= i - 1 < n:
                u = units[i - 1]
                with _TR.span("transfer", eng=eng, gen=gen, group=u[0], entity=u[3]):
                    t = time.perf_counter()
                    nb = self._transfer_unit(u, encoded.pop(i - 1), pending)
                    self._h_stage.observe(time.perf_counter() - t, phase="transfer")
                    total += nb
            if 0 <= i - 2 < n:
                u = units[i - 2]
                with _TR.span("verify", eng=eng, gen=gen, group=u[0], entity=u[3]):
                    t = time.perf_counter()
                    self._verify_unit(u, verified)
                    self._h_stage.observe(time.perf_counter() - t, phase="verify")
            self._fault_hook("pipeline_chunk")
        return total, verified

    def _encode_unit(self, unit, pending: _PendingCheckpoint) -> list[np.ndarray]:
        """ENCODE stage: codec-encode one group's shards of one entity into
        redundancy blobs, accumulated in reusable scratch arenas (transient —
        the transfer stage copies stripes out before scratch is re-leased).
        Also records each member's exchange checksum into the replicated
        ``exch_sums`` table (the restore VERIFY reference) — every (rank,
        entity) belongs to exactly one unit, so multi-worker shards never
        write the same key.

        Under ``cfg.delta`` the member checksums are computed per chunk of
        the dirty-map grid (the partials recombine to the exact monolithic
        Fletcher sums — one pass serves both tables) and diffed against the
        committed generation's replicated chunk table; when the scratch
        arenas still hold the committed parity and the dirty fraction is
        under the crossover, the blobs are patched in place over the merged
        dirty ranges instead of re-encoded (DESIGN.md §17)."""
        gi, grp, placements, name = unit
        codec = self._codec_for(name)
        n_out = len(placements)
        delta_on = (
            self.cfg.delta
            and codec.striped
            and not (self.cfg.compress and codec.compressible)
        )
        step = self._delta_step()
        prev_chunks = self._committed_chunk_sums() if delta_on else {}
        bufs = []
        # Per member: merged dirty [lo, hi) ranges, or None = no usable
        # baseline (first capture, layout change) — treated as fully dirty.
        dirty: list[Any] = []
        dirty_bytes = logical = 0
        for m in grp.members:
            flat, man = pending.packed[name][m]
            if self.cfg.compress and codec.compressible:
                flat, man = self._compress(flat, man)
                pending.manifests[(m, name)] = man
                if codec.striped:
                    # Parity of lossy-compressed buffers only decodes against
                    # the exact compressed bytes, so each member must PRESENT
                    # them at restore time: store the compressed exchange set
                    # in own_exch (every entity — even full-shard ones whose
                    # uncompressed exchange would have aliased ``own``). The
                    # restore paths already prefer own_exch over own.
                    st = self.stores.get(m)
                    payload = st.buffer.writable if st is not None and st.alive else None
                    if payload is not None:
                        with st.lock:
                            payload.own_exch[name] = (flat, man)
            elif delta_on:
                parts = _chunk_checksums(flat, step)
                pending.chunk_sums[(m, name)] = (step, flat.nbytes, parts)
                if self.cfg.validate:
                    # Same reference np_checksum(flat) would produce, from
                    # the partials already in hand (linearity — no 2nd pass).
                    pending.exch_sums[(m, name)] = _combine_checksums(parts, step)
                prev = prev_chunks.get((m, name))
                if prev is not None and prev[0] == step and prev[1] == flat.nbytes:
                    idx = [
                        ci for ci, (a, b) in enumerate(zip(parts, prev[2])) if a != b
                    ]
                    ranges = _merge_chunk_ranges(idx, step, flat.nbytes)
                    dirty.append(ranges)
                    dirty_bytes += sum(hi - lo for lo, hi in ranges)
                else:
                    dirty.append(None)
                    dirty_bytes += flat.nbytes
                logical += flat.nbytes
            elif self.cfg.validate:
                # Compressed blobs skip restore-verify (their manifest is
                # tagged); everything else gets a capture-state reference.
                pending.exch_sums[(m, name)] = np_checksum(flat)
            bufs.append(flat)
        if delta_on:
            with self._delta_lock:
                pending.dirty_bytes += dirty_bytes
                pending.logical_bytes += logical
        scratch_key = (gi, name)

        def lease(b: int, nbytes: int) -> np.ndarray:
            buf = self._enc_scratch.get((scratch_key, b))
            if buf is None or buf.nbytes < nbytes:
                buf = np.empty(nbytes, np.uint8)
                self._enc_scratch[(scratch_key, b)] = buf
            return buf[:nbytes]

        G = (
            codec.encode_matrix(len(bufs))
            if self.cfg.encode_chunk_bytes >= 0
            else None
        )
        if G is not None and G.shape[0] < n_out:
            G = None  # matrix can't cover this layout: defensive fallback
        if delta_on and G is not None:
            # Scratch arenas holding the committed parity under this exact
            # codec/member layout license incremental patching; the staged
            # validity entry commits with the snapshot (finalize_async).
            entry = (self._codec_spec(codec), tuple(b.nbytes for b in bufs), n_out)
            blobs = self._try_delta_encode(
                gi, name, grp, bufs, dirty, dirty_bytes, logical,
                G[:n_out], n_out, lease, entry, pending,
            )
            pending.delta_enc[scratch_key] = (pending.gen,) + entry
            if blobs is not None:
                self.stats.delta_encodes += 1
                return blobs
            self.stats.full_encodes += 1
        elif delta_on:
            self.stats.full_encodes += 1
        if G is not None and codec.striped:
            return self._encode_blobs_chunked(G[:n_out], bufs, n_out, lease)
        return codec.encode_into(bufs, n_out, lease)

    def _try_delta_encode(
        self, gi, name, grp, bufs, dirty, dirty_bytes, logical,
        G, n_out, lease, entry, pending,
    ) -> list[np.ndarray] | None:
        """Incremental parity patch (DESIGN.md §17): ``parity ^= G·(new^old)``
        over the merged dirty ranges — exact by GF(2^8) linearity (addition
        IS xor), bit-identical to a full re-encode of the new members.
        Returns None when any precondition fails (the caller re-encodes in
        full, which is always correct): no committed baseline for the scratch
        parity, a member store without its committed payload, a changed
        payload length, a member with no chunk-table baseline, or a dirty
        fraction past the crossover where patching stops paying."""
        if logical == 0 or dirty_bytes > self.cfg.delta_crossover * logical:
            return None
        if self._delta_enc.get((gi, name)) != (pending.gen - 1,) + entry:
            return None
        if any(r is None for r in dirty):
            return None
        olds = []
        for i, m in enumerate(grp.members):
            st = self.stores.get(m)
            if st is None or not st.alive or not st.buffer.valid:
                return None
            ro = st.buffer.read_only
            old = ro.own_exch.get(name, ro.own.get(name))
            if old is None or old[0].nbytes != bufs[i].nbytes:
                return None
            olds.append(old[0])
        n = gf256.padded_len(bufs)
        blobs = [lease(b, n) for b in range(n_out)]
        if any(blob.nbytes != n for blob in blobs):
            return None  # lease shrank/grew unexpectedly (defensive)
        t = time.perf_counter()
        patched = 0
        for i, ranges in enumerate(dirty):
            col = G[:, i : i + 1]
            for lo, hi in ranges:
                diff = np.bitwise_xor(bufs[i][lo:hi], olds[i][lo:hi])
                gf256.gf_matrix_addmul_into(
                    [blob[lo:hi] for blob in blobs], [diff], col,
                    0, hi - lo, accumulate=True,
                )
                patched += hi - lo
        if patched:
            self._observe_encode_rate(patched, time.perf_counter() - t)
        return blobs

    # -- adaptive encode-chunk planner (create-side twin of DESIGN.md §14) - #
    def _encode_rate(self) -> float:
        """Sustained encode rate (range bytes/s) for the active codec: this
        process's peak-with-decay record, else the GF probe (same /4 seed as
        the decode planner — both sides run the same matrix primitive)."""
        with _ENCODE_RATE_LOCK:
            prior = _ENCODE_RATE.get(self.codec.name)
        if prior is not None:
            return prior
        return max(gf256.probed_gbps() * 1e9 / 4.0, 1e6)

    def _observe_encode_rate(self, nbytes: int, dt: float) -> None:
        if nbytes <= 0 or dt <= 0.0:
            return
        rate = nbytes / dt
        with _ENCODE_RATE_LOCK:
            prev = _ENCODE_RATE.get(self.codec.name)
            _ENCODE_RATE[self.codec.name] = (
                rate if prev is None else max(rate, 0.98 * prev)
            )

    def _plan_encode_step(self) -> int:
        """Create-side chunk size: the restore planner's rule verbatim —
        measured rate × overhead budget, pow2-bucketed, clamped; with no
        realizable parallelism chunking is pure overhead, so one range."""
        cb = self.cfg.encode_chunk_bytes
        if cb > 0:
            return max(4, cb) & ~3
        if self._effective_workers() <= 1:
            return self._CHUNK_MAX
        step = int(self._encode_rate() * self._CHUNK_OVERHEAD_S
                   / self._CHUNK_OVERHEAD_FRAC)
        step = max(self._CHUNK_MIN, min(self._CHUNK_MAX, step))
        return 1 << (step - 1).bit_length()

    def _encode_blobs_chunked(self, G, bufs, n_out, lease) -> list[np.ndarray]:
        """One unit's blobs encoded as planned [lo, hi) ranges through the
        same GF matrix primitive the monolithic ``rs_encode`` runs — per-byte
        math, so the assembled blobs are bit-identical — feeding the measured
        encode rate back to the planner per range (ROADMAP item 1 stretch)."""
        n = gf256.padded_len(bufs)
        blobs = [lease(b, n) for b in range(n_out)]
        step = self._plan_encode_step()
        for lo in range(0, n, step):
            hi = min(lo + step, n)
            t = time.perf_counter()
            gf256.gf_matrix_addmul_into(blobs, bufs, G, lo, hi, accumulate=False)
            self._observe_encode_rate(hi - lo, time.perf_counter() - t)
        return blobs

    def _delta_step(self) -> int:
        """Dirty-map chunk grid (4-aligned — Fletcher partials only
        recombine on word boundaries; floored so tiny configs can't explode
        the table)."""
        return max(4096, self.cfg.delta_chunk_bytes) & ~3

    def _committed_chunk_sums(self) -> dict:
        """The committed generation's replicated chunk-digest table (empty
        for the first capture or a pre-§17 checkpoint — everything dirty)."""
        for st in self.stores.values():
            if st.alive and st.buffer.valid:
                table = st.buffer.read_only.meta.get("exch_chunk_sums")
                if table:
                    return table
        return {}

    def _transfer_unit(
        self, unit, blobs: list[np.ndarray], pending: _PendingCheckpoint
    ) -> int:
        """TRANSFER stage: stripe the blobs onto their holder stores. Striped
        codecs copy each stripe into a holder-owned arena (the simulated
        network hop; blobs live in transient scratch). Full-copy codecs store
        by reference — whole copies stay memcpy-free, and the referenced flat
        is the origin's arena view from the same staging bank, so it commits
        and retires together with the rest of the snapshot.

        Under ``cfg.delta`` each stripe copies only the dirty-grid chunks
        that differ from the holder arena's current content (exact byte
        comparison — the arena holds whatever the last lease of the same
        staging bank left, so garbage or a stale generation simply copies).
        The arena keys and sizes are untouched either way: steady-state
        leases return the identical base pointers delta on or off."""
        gi, grp, placements, name = unit
        total = 0
        skipped = 0
        step = self._delta_step()
        by_ref = not self._codec_for(name).striped
        for b, (blob, holders) in enumerate(zip(blobs, placements)):
            blob = np.asarray(blob).reshape(-1)
            if by_ref:
                stripes = [blob] * len(holders)
            else:
                # Stripe over however many members the *target* group has
                # (ragged last groups appear at elastic world sizes); bounds
                # shared with split/join_stripes so writer and decoder agree.
                stripes = [
                    blob[lo:hi]
                    for lo, hi in parity_mod.stripe_bounds(blob.nbytes, len(holders))
                ]
            for j, member in enumerate(holders):
                st = self.stores[member]
                # Capture the payload reference ONCE: a concurrent kill from
                # the main thread (wipe() swaps st.buffer out under the
                # background drain) must degrade to writes into an orphaned
                # payload — the handshake aborts the snapshot later — never
                # to a None dereference.
                payload = st.buffer.writable if st.alive else None
                if payload is None:
                    continue
                piece = stripes[j]
                if not by_ref:
                    dst = st.lease(("parity", gi, name, b, j), piece.nbytes)
                    if self.cfg.delta:
                        skipped += _copy_dirty(dst, piece, step)
                    else:
                        np.copyto(dst, piece)
                    piece = dst
                # Holder stores are shared across units: when the drain runs
                # on several workers, the payload-dict write synchronizes on
                # the store lock (the memcpy above stays lock-free — every
                # unit's stripes land in distinct arenas).
                with st.lock:
                    payload.parity.setdefault(gi, {})[(name, b, j)] = piece
                total += piece.nbytes
        if skipped:
            with self._delta_lock:
                pending.skipped_bytes += skipped
        return total

    def _verify_unit(self, unit, verified: set) -> None:
        """VERIFY stage: recompute each member's staged checksum for this
        entity (detects corruption during staging/DMA chunk-by-chunk, instead
        of one monolithic validation pass after all transfers)."""
        gi, grp, placements, name = unit
        if not self.cfg.validate:
            return
        for m in grp.members:
            st = self.stores.get(m)
            # Single capture of the payload reference (see _transfer_unit:
            # concurrent wipe() must not turn into a None dereference).
            payload = st.buffer.writable if st is not None and st.alive else None
            if payload is None:
                continue  # dead rank: the handshake aborts the snapshot
            sums = payload.meta.get("checksums", {})
            if name in sums and name in payload.own:
                if np_checksum(payload.own[name][0]) != sums[name]:
                    raise FaultDuringCheckpoint(
                        f"checksum mismatch rank {m} entity {name}"
                    )
                verified.add((m, name))

    def finalize_async(self) -> bool | None:
        """Drain the pipeline (or join the background worker), handshake, and
        **commit via the pointer swap** — the single commit point. Returns
        True on success, False on abort, None if nothing pending."""
        if self._pending is None:
            return None
        pending = self._pending
        self._pending = None
        eng, gen = self._obs_id, pending.gen
        t_wait0 = time.perf_counter()
        try:
            with _TR.span("finalize_wait", eng=eng, gen=gen):
                if pending.future is not None:
                    pending.bytes_exchanged, pending.verified = pending.future.result()
                else:
                    pending.bytes_exchanged, pending.verified = self._drain(pending)
            self.stats.last_finalize_wait_s = time.perf_counter() - t_wait0

            self._fault_hook("after_distribute")

            # -- handshake ----------------------------------------------------
            with _TR.span("handshake", eng=eng, gen=gen):
                alive1 = self._alive_fn()
                if alive1 != pending.alive0 or len(alive1) < self.n_ranks:
                    raise FaultDuringCheckpoint(
                        f"rank set changed during checkpoint: "
                        f"{sorted(pending.alive0 - alive1)} died"
                    )
                if self.cfg.validate:
                    self._validate(alive1, skip=pending.verified)

        except FaultDuringCheckpoint as e:
            # Read-only buffers were never touched; discard in-flight writes.
            log.warning("checkpoint aborted: %s", e)
            for s in self.stores.values():
                s.buffer.discard_writable()
            # The drain may have overwritten scratch with the aborted
            # generation's parity: no committed baseline survives it.
            self._delta_enc.clear()
            self.stats.aborted += 1
            self.journal.record("abort", phase="finalize", gen=gen, cause=str(e))
            return False

        # -- swap: pointer swap, no communication — cannot be interrupted ----
        with _TR.span("commit", eng=eng, gen=gen):
            for r in pending.alive0:
                self.stores[r].buffer.swap()
        self.stats.created += 1
        self.stats.last_create_s = time.perf_counter() - pending.t0
        self.stats.last_blocked_s = (
            self.stats.last_capture_s + self.stats.last_finalize_wait_s
        )
        self.stats.last_bytes_exchanged = pending.bytes_exchanged
        self.stats.last_bytes_per_rank = pending.bytes_exchanged // max(
            len(pending.alive0), 1
        )
        if self.cfg.delta:
            # The scratch arenas now hold THIS committed generation's parity:
            # the staged validity entries become the next capture's baseline.
            self._delta_enc.update(pending.delta_enc)
            self.stats.last_dirty_fraction = (
                pending.dirty_bytes / pending.logical_bytes
                if pending.logical_bytes else 0.0
            )
            self.stats.last_transfer_bytes_skipped = pending.skipped_bytes
        self._maybe_flush_tiers()
        # Commit-point hooks: the adaptive protection policy re-evaluates
        # here (DESIGN.md §16) — after the swap, so a policy flip can never
        # tear a snapshot, and its overrides apply from the NEXT capture.
        for hook in self._commit_hooks:
            hook(self)
        return True

    # ------------------------------------------------------------------ #
    # storage-tier ladder: background flush of committed generations
    # ------------------------------------------------------------------ #
    @property
    def persistent_tiers(self) -> list:
        return [t for t in self.tiers if t.persistent]

    def _maybe_flush_tiers(self) -> None:
        """Stage a background flush of the just-committed generation for
        every due persistent tier. The payload refs are captured HERE,
        synchronously at the commit point — a concurrent kill or the next
        capture's arena re-lease can never tear the flush's source bytes —
        but the executor submission is deferred to ``kick_tier_flush`` (the
        overlap window: the next ``drain_done`` poll, the next capture, or
        any join point), so not even the worker wake-up lands on the blocked
        capture+finalize path. At most one flush is in flight plus at most
        one *queued* in the single-slot ``_flush_pending``: a cadence point
        arriving while a flush is still running is chained behind it (counted
        in ``tier_flush_queued``), and only when the slot is already
        occupied is the older staged snapshot *dropped* in favor of the
        newer one (counted in ``tier_flush_skipped``) — back-pressure
        degrades the disk frequency, it never blocks training."""
        due = [t for t in self.persistent_tiers if t.due(self.stats.created)]
        if not due:
            return
        with self._flush_lock:
            in_flight = (
                self._flush_future is not None and not self._flush_future.done()
            )
            if self._flush_pending is not None:
                # The single queue slot is taken: drop the OLDER staged
                # snapshot (the newer generation supersedes it on disk).
                old_due, old_snap = self._flush_pending
                self.stats.tier_flush_skipped += len(old_due)
                self.journal.record(
                    "flush_skipped", gen=old_snap.created,
                    superseded_by=self.stats.created,
                )
                log.warning(
                    "tier flush of commit %d dropped: superseded by commit %d "
                    "while a flush is still in flight",
                    old_snap.created, self.stats.created,
                )
            self._flush_pending = (due, storage_mod.capture_snapshot(self))
            if in_flight:
                self.stats.tier_flush_queued += len(due)
                self.journal.record(
                    "flush_queued", gen=self.stats.created,
                    tiers=",".join(t.name for t in due),
                )

    def kick_tier_flush(self) -> None:
        """Submit a staged tier flush to the drain pool. Public overlap-
        window probe: callers (trainer/server step loops, ``drain_done``
        polls) invoke it between the commit and the next blocked window so
        the executor wake-up happens off the critical path; every join point
        (``_join_flush``/``close``/escalation) kicks first, so a staged
        generation is never lost. While a flush is in flight the staged one
        stays queued — the worker chains it (``_run_flush``) the moment the
        running flush finishes, so the cadence point is deferred, not
        dropped."""
        submit = None
        with self._flush_lock:
            if self._flush_pending is None:
                return
            if self._flush_future is not None:
                if not self._flush_future.done():
                    return  # stays queued; the flush worker will chain it
                self._reap_flush_future()
            submit, self._flush_pending = self._flush_pending, None
            self._flush_created = submit[1].created
        self._flush_future = self._executor().submit(self._run_flush, *submit)

    def _reap_flush_future(self) -> None:
        """Clear a finished flush future, logging (never raising) a failure —
        losing one disk generation must not kill the job; the previous
        generation stays valid by the commit protocol."""
        future, self._flush_future = self._flush_future, None
        if future is not None:
            try:
                future.result()
            except Exception as e:  # noqa: BLE001 - flush failure is non-fatal
                log.warning("tier flush failed (previous generation intact): %s", e)

    def _run_flush(self, tiers: list, snap) -> int:
        """Flush worker: write one staged generation to every due tier, then
        chain any flush that was queued behind this one (under the lock, so
        a hand-off races neither ``kick_tier_flush`` nor a new staging)."""
        grand_total = 0
        while True:
            t0 = time.perf_counter()
            total = 0
            try:
                for tier in tiers:
                    with _TR.span(
                        "flush", eng=self._obs_id, gen=snap.created, tier=tier.name
                    ):
                        total += tier.flush(snap)
            except Exception as e:
                self.journal.record(
                    "flush", ok=False, gen=snap.created, cause=str(e),
                )
                raise
            self.stats.tier_flushes += len(tiers)
            self.stats.last_flush_s = time.perf_counter() - t0
            self.stats.last_flush_bytes = total
            dedup = next(
                (t.last_dedup for t in tiers if getattr(t, "last_dedup", None)),
                None,
            )
            if dedup is not None:
                self.stats.last_flush_chunks_written = dedup["chunks_written"]
                self.stats.last_flush_chunks_reused = dedup["chunks_reused"]
                self.stats.last_dedup_ratio = (
                    dedup["stored_bytes"] / dedup["logical_bytes"]
                    if dedup["logical_bytes"] else 0.0
                )
            self.journal.record(
                "flush", ok=True, gen=snap.created, bytes=total,
                duration_s=self.stats.last_flush_s, n_ranks=snap.n_ranks,
                tiers=",".join(t.name for t in tiers),
            )
            grand_total += total
            with self._flush_lock:
                if self._flush_pending is None:
                    return grand_total
                (tiers, snap), self._flush_pending = self._flush_pending, None
                self._flush_created = snap.created

    def _join_flush(self) -> None:
        """Kick any staged flush, then join (and clear) the in-flight one —
        looping, because the worker may chain a flush that was queued after
        its last hand-off check. Returns with no flush staged, queued, or
        running."""
        while True:
            self.kick_tier_flush()
            if self._flush_future is None:
                with self._flush_lock:
                    if self._flush_pending is None:
                        return
                continue  # a late staging slipped in: kick it too
            self._reap_flush_future()

    def has_tier_data(self) -> bool:
        """True when some persistent tier holds at least one committed
        generation (or one is staged/in flight — escalation joins it first)
        — i.e. escalation has somewhere to go."""
        if self._flush_pending is not None or self._flush_future is not None:
            return True
        return any(t.has_data() for t in self.persistent_tiers)

    def _store_alive(self) -> set[int]:
        """Liveness as the stores see it (used after a tier load, when the
        cluster's view predates the rehydration)."""
        return {r for r, s in self.stores.items() if s.alive and s.buffer.valid}

    def escalate_from_tiers(self) -> None:
        """Load the newest valid persistent-tier generation into the
        in-memory stores (cold start, or a burst beyond codec tolerance).
        Tiers are tried in ladder order; each tier internally escalates to
        older generations when its newest fails validation. Raises
        ``distribution.DataLostError`` when no rung holds a loadable
        generation. May resize the engine to the stored world size — the
        elastic path maps it back onto the caller's world."""
        self._join_flush()  # an in-flight flush may be committing the newest gen
        # Rehydration replaces the committed payloads: scratch parity no
        # longer corresponds to them, so delta baselines die here.
        self._delta_enc.clear()
        errors: list[str] = []
        for tier in self.persistent_tiers:
            try:
                t0 = time.perf_counter()
                with _TR.span("escalate", eng=self._obs_id, tier=tier.name):
                    gen = tier.load(self)
            except dist.DataLostError as e:
                errors.append(str(e))
                continue
            self.stats.tier_escalations += 1
            self.journal.record(
                "escalation", tier=tier.name, gen=gen, n_ranks=self.n_ranks,
                duration_s=time.perf_counter() - t0,
            )
            log.warning(
                "recovery escalated to the %s tier (generation %s, %d ranks)",
                tier.name, gen, self.n_ranks,
            )
            return
        raise dist.DataLostError(
            "no persistent tier holds a loadable generation"
            + (f": {'; '.join(errors)}" if errors else " (none configured)")
        )

    def discard_pending(self) -> None:
        """Drop an un-finalized async snapshot (e.g. before a restore) — it
        counts as an aborted checkpoint (captured but never committed). Joins
        a still-running background drain first so no worker writes into
        buffers after they are discarded."""
        if self._pending is not None:
            pending, self._pending = self._pending, None
            if pending.future is not None:
                try:
                    pending.future.result()
                except FaultDuringCheckpoint:
                    pass
            for s in self.stores.values():
                s.buffer.discard_writable()
            # The discarded drain may have left its parity in scratch.
            self._delta_enc.clear()
            self.stats.aborted += 1

    def drain_done(self) -> bool:
        """True when there is nothing left to wait on before finalize_async
        can run without blocking on a worker: no pending snapshot, a pending
        whose background drain already finished, or a synchronous-drain
        pending (finalize does the work itself). Public poll point for
        callers sizing their overlap window (benchmarks, servers deciding
        when to finalize early) — which makes it a natural overlap-window
        probe to kick a staged tier flush from."""
        self.kick_tier_flush()
        pending = self._pending
        if pending is None or pending.future is None:
            return True
        return pending.future.done()

    def close(self) -> None:
        """Release background resources: joins + drops any pending snapshot
        (and any in-flight tier flush) and shuts the pipeline worker pool
        down. The engine stays usable for synchronous checkpoints afterward
        (the pool re-creates lazily)."""
        self.discard_pending()
        self._join_flush()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def _groups(self) -> list[dist.ParityGroup]:
        """The engine's group layout. No topology: the legacy contiguous
        rank-order partition, bit-identical to every pre-§16 config. With a
        topology: domain-aware placement (no group holds two members of one
        failure domain), cached per (world, k, topology) since the greedy
        packer is O(n log n) and every capture/restore asks."""
        k = self.codec.group_size(self.n_ranks)
        if self.topology is None:
            return dist.parity_groups(self.n_ranks, k)
        key = (self.n_ranks, k, self.topology.labels, self.topology.placement_level)
        if self._groups_cache is None or self._groups_cache[0] != key:
            groups = dist.domain_parity_groups(self.n_ranks, k, self.topology)
            self._groups_cache = (key, groups, dist.rank_group_map(groups))
        return self._groups_cache[1]

    def _group_of(self, rank: int) -> int:
        """Group index of ``rank`` under the engine layout — replaces the
        ``rank // k`` identity, which only holds for contiguous groups."""
        if self.topology is None:
            return dist.group_of(rank, self.codec.group_size(self.n_ranks))
        self._groups()
        return self._groups_cache[2][rank]

    def _compress(self, flat, man):
        # Compress per-leaf floats through the manifest (int8 blockwise); raw
        # bytes are not quantizable, the tree's float leaves are.
        from repro.optim.grad_compress import compress_tree

        tree = unpack_bytes(flat, man)
        packed = compress_tree(tree)
        cflat, cman = pack_bytes(packed)
        return cflat, ("compressed", cman)

    def _decompress(self, flat, man):
        from repro.optim.grad_compress import decompress_tree

        _, cman = man
        packed = unpack_bytes(flat, cman)
        return decompress_tree(packed)

    def _validate(self, alive: set[int], skip: set | None = None) -> None:
        """Handshake-time checksum validation over whatever the pipeline's
        chunked VERIFY stage did not already cover (replicated entities, and
        every entity when the codec places no redundancy)."""
        skip = skip or set()
        for r in alive:
            payload = self.stores[r].buffer.writable
            sums = payload.meta.get("checksums", {})
            for name, (flat, _) in payload.own.items():
                if (r, name) in skip:
                    continue
                if name in sums and np_checksum(flat) != sums[name]:
                    raise FaultDuringCheckpoint(f"checksum mismatch rank {r} entity {name}")

    # ------------------------------------------------------------------ #
    # Algorithm 4 + restore
    # ------------------------------------------------------------------ #
    @property
    def has_valid_checkpoint(self) -> bool:
        alive = self._alive_fn()
        return any(self.stores[r].buffer.valid for r in alive)

    def checkpoint_step(self) -> Any:
        """Meta recorded with the last valid checkpoint (e.g. the step).
        Scans the stores directly (any alive store's valid buffer): after a
        tier escalation the rehydrated stores are authoritative even while
        the cluster's liveness view is still being realigned."""
        for r in sorted(self.stores):
            store = self.stores[r]
            if store.alive and store.buffer.valid:
                return store.buffer.read_only.meta
        raise RuntimeError("no valid checkpoint")

    def restore(self) -> dict[str, Any]:
        """Recover every entity from the last valid checkpoint. Returns the
        checkpoint meta. Survivor shards restore with zero communication.

        Under ``cfg.restore_mode="pipelined"`` (the default) recovery drains
        the chunked TRANSFER/DECODE/VERIFY pipeline of DESIGN.md §10 —
        bit-identical to the serial ``"sync"`` path. Entities are only
        mutated after EVERY shard has been recovered, so a failure anywhere
        in recovery leaves both the entities and the committed checkpoint
        untouched (the restore can be retried against the survivors)."""
        self.discard_pending()
        t0 = time.perf_counter()
        alive = self._alive_fn()
        failed = set(range(self.n_ranks)) - alive

        with _TR.span(
            "restore", eng=self._obs_id, failed=len(failed), mode=self.cfg.restore_mode
        ):
            with _TR.span("restore_rebuild", eng=self._obs_id):
                recovered = self._recover_all(alive, failed)
            for name, ent in self._entities.items():
                ent.restore_shards(recovered[name])

        meta = self.checkpoint_step()
        self.stats.restored += 1
        self.stats.last_restore_s = time.perf_counter() - t0
        # Domain labels on the failure set (DESIGN.md §16): lets
        # fit_failure_stats cluster recoveries by rack/pod, the signal the
        # adaptive protection policy reads.
        domains = (
            ",".join(sorted({self.topology.domain_label(r) for r in failed}))
            if self.topology is not None and failed
            else ""
        )
        self.journal.record(
            "recovery", mode=self.cfg.restore_mode, failed=len(failed),
            n_ranks=self.n_ranks, duration_s=self.stats.last_restore_s,
            bytes_rebuilt=self.stats.last_restore_bytes_rebuilt,
            escalations=self.stats.tier_escalations,
            step=meta.get("step") if isinstance(meta, dict) else None,
            domains=domains,
        )
        return meta

    def _recover_all(
        self, alive: set[int], failed: set[int]
    ) -> dict[str, dict[int, Any]]:
        """Recover every entity's every shard (no entity mutation), with
        **escalating recovery** (DESIGN.md §12): the in-memory codec path is
        always tried first — failures within tolerance never touch disk —
        and only when it is provably insufficient (``DataLostError``: a burst
        beyond ``m``, destroyed blob holders, or a cold start with nothing in
        memory) does recovery fall down the storage-tier ladder, rehydrate
        the stores from the newest valid generation, and re-run against the
        loaded world (where every rank is a zero-comm survivor, minus any
        ranks the flushed generation itself was missing — those re-enter the
        codec path against the loaded stripes)."""
        try:
            return self._recover_all_memory(alive, failed)
        except dist.DataLostError as e:
            if not self.has_tier_data():
                raise
            log.warning(
                "in-memory recovery impossible (%s); escalating down the "
                "storage-tier ladder", e,
            )
            self.escalate_from_tiers()
            alive = self._store_alive()
            return self._recover_all_memory(
                alive, set(range(self.n_ranks)) - alive
            )

    def _recover_all_memory(
        self, alive: set[int], failed: set[int]
    ) -> dict[str, dict[int, Any]]:
        """One recovery attempt against the in-memory stores: the
        restore-mode dispatch point shared by ``restore`` and
        ``restore_elastic``."""
        if self.cfg.restore_mode == "sync" or (
            self.cfg.restore_chunk_bytes <= 0
            and self._estimate_restore_bytes() <= self._sync_crossover_bytes()
        ):
            # Below the crossover the pipelined path's fixed setup (unit
            # prep, arena leases, pool fan-out) outweighs its overlap win —
            # collapse to the serial sync path (bit-identical result).
            return {
                name: self._recover_entity_shards(name, ent, alive, failed)
                for name, ent in self._entities.items()
            }
        return self._recover_all_pipelined(alive, failed)

    def _recover_entity_shards(
        self, name: str, ent: DistributedEntity, alive: set[int], failed: set[int]
    ) -> dict[int, Any]:
        """Recover every origin's shard of one entity (Algorithm 4 inner loop)."""
        shards: dict[int, Any] = {}
        partials: dict[int, Any] = {}
        # codec.decode solves ALL of a group's missing shards at once (an RS
        # burst is one Gaussian solve); cache per group so co-failed origins
        # share it instead of re-decoding per origin.
        decode_cache: dict[int, dict[int, Any]] = {}
        for origin in range(self.n_ranks):
            kind, payload = self._recover_shard(origin, name, alive, failed, decode_cache)
            if kind == "full":
                shards[origin] = payload
            elif kind == "partial":
                partials[origin] = payload
        if not shards:
            raise dist.DataLostError(f"no shard of entity {name!r} recoverable")
        if partials:
            # Adopted copies hold only the uniquely-owned subset; merge in
            # the replicated leaves from any survivor's full payload.
            ref = shards[min(shards)]
            for origin, subset in partials.items():
                shards[origin] = ent.merge_payload(subset, ref, self.n_ranks)
        return shards

    # ------------------------------------------------------------------ #
    # The pipelined recovery path (DESIGN.md §10) — restore as the mirror
    # image of the create pipeline: plan, then drain chunked
    # TRANSFER i ‖ DECODE i−1 ‖ VERIFY i−2 per (group, entity) unit, with
    # independent units (and independent chunks of one unit) reconstructed
    # in parallel across the async worker pool.
    # ------------------------------------------------------------------ #
    def _recover_all_pipelined(
        self, alive: set[int], failed: set[int]
    ) -> dict[str, dict[int, Any]]:
        t0 = time.perf_counter()
        groups = self._groups()
        shards: dict[str, dict[int, Any]] = {n: {} for n in self._entities}
        partials: dict[str, dict[int, Any]] = {n: {} for n in self._entities}

        # -- plan: survivor unpacks are local jobs, every failed origin's
        # (group, entity) becomes one reconstruction unit ------------------
        local_jobs: list[tuple[str, int, Any, Any]] = []  # (name, origin, flat, man)
        units: list[_RestoreUnit] = []
        seen_units: set[tuple[int, str]] = set()
        ref_table = self._restore_ref_sums()  # one scan for the whole restore
        # Committed stripes are immutable, so a repeat restore of the exact
        # same topology — same survivors, same failures, same per-store
        # buffer generations — can reuse the previous restore's prepped
        # units: erasure solve, arena leases, staged blob bytes and clean
        # checksum verdicts all still hold (decode re-runs regardless).
        plan_key = (
            frozenset(alive),
            frozenset(failed),
            tuple(
                (r, self.stores[r].buffer.generation)
                for r in sorted(self.stores)
                if self.stores[r].alive and self.stores[r].buffer.valid
            ),
        )
        cached_units = (
            self._restore_plan_cache[1]
            if self._restore_plan_cache and self._restore_plan_cache[0] == plan_key
            else None
        )
        for name in self._entities:
            if name in self._replicated:
                donor = next(
                    (r for r in sorted(alive) if self.stores[r].buffer.valid), None
                )
                if donor is None:
                    raise dist.DataLostError(
                        f"replicated entity {name!r} lost everywhere"
                    )
                flat, man = self.stores[donor].buffer.read_only.own[name]
                local_jobs.append((name, -1, flat, man))  # -1: fan out to all
                self.stats.zero_comm_restores += self.n_ranks
                continue
            for origin in range(self.n_ranks):
                if origin in alive and self.stores[origin].buffer.valid:
                    flat, man = self.stores[origin].buffer.read_only.own[name]
                    local_jobs.append((name, origin, flat, man))
                    self.stats.zero_comm_restores += 1
                else:
                    gi = self._group_of(origin)
                    if (gi, name) not in seen_units:
                        seen_units.add((gi, name))
                        u = cached_units.get((gi, name)) if cached_units else None
                        if u is not None:
                            self.stats.restore_plan_reuses += 1
                        else:
                            u = self._prep_restore_unit(
                                gi, groups, name, alive, ref_table
                            )
                        units.append(u)

        # -- drain: chunk tasks + survivor unpacks across the worker pool --
        chunk_tasks = [(u, ci) for u in units for ci in range(len(u.bounds))]
        results: dict[tuple[str, int], Any] = {}
        workers = max(1, min(self.cfg.async_workers, len(chunk_tasks) + len(local_jobs)))
        if self.cfg.restore_chunk_bytes <= 0:
            # Adaptive mode also right-sizes the drain itself: more threads
            # than cores just contend on the CPU-bound decode (an explicit
            # restore_chunk_bytes keeps the legacy fan-out untouched).
            workers = min(workers, self._effective_workers())
        if workers > 1:
            futures = [
                self._executor().submit(self._restore_chunk_task, u, ci)
                for u, ci in chunk_tasks
            ]
            futures += [
                self._executor().submit(unpack_bytes, flat, man)
                for _, _, flat, man in local_jobs
            ]
            # Join EVERY future before propagating a failure (same rule as
            # the create drain): an abandoned chunk task would keep writing
            # into restore arenas that a retrying restore re-leases.
            err: BaseException | None = None
            for f, task in zip(futures, chunk_tasks + local_jobs):
                try:
                    out = f.result()
                    if len(task) == 4:  # a local unpack job
                        results[(task[0], task[1])] = out
                except BaseException as e:
                    err = err or e
            if err is not None:
                raise err
        else:
            # Serial drain: the literal three-stage pipeline per unit, then
            # the local unpacks — same bytes, deterministic chunk order (the
            # form the mid-restore fault-injection tests kill at).
            eng = self._obs_id
            enabled = _TR.enabled
            for u in units:
                nc = len(u.bounds)
                for i in range(nc + 2):
                    if i < nc:
                        t = time.perf_counter()
                        if enabled:
                            with _TR.span(
                                "r_transfer", eng=eng, group=u.gi,
                                entity=u.name, chunk=i,
                            ):
                                self._restore_transfer_chunk(u, *u.bounds[i])
                        else:
                            self._restore_transfer_chunk(u, *u.bounds[i])
                        self._hr_transfer.observe(time.perf_counter() - t)
                    if 0 <= i - 1 < nc:
                        t = time.perf_counter()
                        if enabled:
                            with _TR.span(
                                "decode", eng=eng, group=u.gi,
                                entity=u.name, chunk=i - 1,
                            ):
                                u.decode_chunk(*u.bounds[i - 1])
                        else:
                            u.decode_chunk(*u.bounds[i - 1])
                        dt = time.perf_counter() - t
                        self._hr_decode.observe(dt)
                        lo, hi = u.bounds[i - 1]
                        self._observe_decode_rate(hi - lo, dt)
                    if 0 <= i - 2 < nc:
                        t = time.perf_counter()
                        if enabled:
                            with _TR.span(
                                "r_verify", eng=eng, group=u.gi,
                                entity=u.name, chunk=i - 2,
                            ):
                                self._restore_verify_chunk(u, i - 2)
                        else:
                            self._restore_verify_chunk(u, i - 2)
                        self._hr_verify.observe(time.perf_counter() - t)
                        t = time.perf_counter()
                        if enabled:
                            with _TR.span(
                                "deq", eng=eng, group=u.gi,
                                entity=u.name, chunk=i - 2,
                            ):
                                self._restore_decompress_chunk(u, i - 2)
                        else:
                            self._restore_decompress_chunk(u, i - 2)
                        self._hr_deq.observe(time.perf_counter() - t)
                    self._fault_hook("restore_chunk")
            for name, origin, flat, man in local_jobs:
                results[(name, origin)] = unpack_bytes(flat, man)

        # -- finalize: checksum verdicts, unpack rebuilt shards, merge -----
        for name, origin, _, _ in local_jobs:
            payload = results[(name, origin)]
            if origin < 0:
                shards[name] = {r: payload for r in range(self.n_ranks)}
            else:
                shards[name][origin] = payload
        for u in units:
            self._finalize_restore_unit(u, shards, partials)

        for name, ent in self._entities.items():
            if name in self._replicated:
                continue
            if not shards[name]:
                raise dist.DataLostError(f"no shard of entity {name!r} recoverable")
            if partials[name]:
                ref = shards[name][min(shards[name])]
                for origin, subset in partials[name].items():
                    shards[name][origin] = ent.merge_payload(subset, ref, self.n_ranks)

        self.stats.last_restore_decode_s = time.perf_counter() - t0
        self.stats.last_restore_chunks = len(chunk_tasks)
        self.stats.last_restore_bytes_rebuilt = sum(
            buf.nbytes for u in units for buf in u.rebuilt.values()
        )
        self.stats.last_restore_decompressed_bytes = sum(
            leaf.out.nbytes
            for u in units if u.decomp
            for plan in u.decomp.values()
            for leaf in plan
        )
        # Every unit finalized clean (an IntegrityError/DataLostError above
        # never reaches here): admit the plan to the single-slot cache so a
        # repeat of the identical topology skips prep, TRANSFER and VERIFY.
        for u in units:
            u.staged = u.verified = True
        self._restore_plan_cache = (plan_key, {(u.gi, u.name): u for u in units})
        return shards

    # -- adaptive restore-chunk planner (DESIGN.md §14) ------------------ #
    # Fixed per-chunk overhead (pool dispatch, histogram/span bookkeeping,
    # checksum setup) and the fraction of chunk wall time it may consume:
    # together they set the chunk floor, step >= rate * OVERHEAD_S / FRAC.
    _CHUNK_OVERHEAD_S = 5e-5
    _CHUNK_OVERHEAD_FRAC = 0.05
    _CHUNK_MIN = 1 << 16
    _CHUNK_MAX = 1 << 24
    # The pipelined path's fixed setup cost; restores whose whole payload
    # decodes faster than this are cheaper on the serial sync path.
    _PIPELINE_SETUP_S = 1e-4

    def _effective_workers(self) -> int:
        """Worker-pool parallelism the restore drain can actually realize:
        threads beyond the machine's cores only contend (the GF decode is
        CPU-bound), so the planner sizes against min(workers, cores)."""
        return max(1, min(self.cfg.async_workers, os.cpu_count() or 1))

    def _decode_rate(self) -> float:
        """Sustained chunk-decode rate (range bytes/s) for the active codec:
        this process's peak-with-decay record first (seeded by earlier engine
        generations), else the GF backend probe — probed_gbps measures
        k-source payload per second at k=4, so /4 approximates the per-range
        rate the planner sizes against. The peak statistic (not a mean) is
        deliberate: one-off slow observations — jit compiles on a new chunk
        length, pool contention — would drag a mean down, shrink the step,
        change the chunk grid, and trigger MORE compiles."""
        with _DECODE_RATE_LOCK:
            prior = _DECODE_RATE.get(self.codec.name)
        if prior is not None:
            return prior
        return max(gf256.probed_gbps() * 1e9 / 4.0, 1e6)

    def _observe_decode_rate(self, nbytes: int, dt: float) -> None:
        if nbytes <= 0 or dt <= 0.0:
            return
        rate = nbytes / dt
        self._h_restore_rate.observe(rate, codec=self.codec.name)
        with _DECODE_RATE_LOCK:
            prev = _DECODE_RATE.get(self.codec.name)
            # Peak with slow decay: immune to compile/contention outliers,
            # yet tracks a genuinely slower environment within ~tens of
            # observations.
            _DECODE_RATE[self.codec.name] = (
                rate if prev is None else max(rate, 0.98 * prev)
            )

    def _plan_chunk_step(self) -> int:
        """Adaptive chunk size (cfg.restore_chunk_bytes == 0): large enough
        that fixed per-chunk overhead stays under _CHUNK_OVERHEAD_FRAC of
        decode time at the measured rate, rounded UP to a power of two so
        the jax backend's size-bucketed jit cache sees a handful of stable
        shapes instead of a new compile whenever the measured rate drifts.
        With no realizable parallelism (one core or one worker) chunking is
        pure overhead — the serial drain still decodes every byte — so the
        step jumps straight to the clamp ceiling."""
        if self._effective_workers() <= 1:
            return self._CHUNK_MAX
        step = int(self._decode_rate() * self._CHUNK_OVERHEAD_S
                   / self._CHUNK_OVERHEAD_FRAC)
        step = max(self._CHUNK_MIN, min(self._CHUNK_MAX, step))
        return 1 << (step - 1).bit_length()

    def _sync_crossover_bytes(self) -> int:
        """Payload below which pipelined setup cannot pay for itself."""
        est = int(self._decode_rate() * self._PIPELINE_SETUP_S)
        return max(1 << 14, min(1 << 18, est))

    def _estimate_restore_bytes(self) -> int:
        """Cheap whole-restore payload estimate for the crossover decision:
        one valid survivor's per-rank flat bytes times the world size
        (survivor unpacks and failed-origin rebuilds both scale with it)."""
        donor = next(
            (
                st for st in self.stores.values()
                if st.alive and st.buffer.valid
            ),
            None,
        )
        if donor is None:
            # Nothing valid in memory: let the pipelined path make the
            # DataLostError/escalation decision exactly as before.
            return 1 << 62
        per_rank = sum(
            flat.nbytes for flat, _ in donor.buffer.read_only.own.values()
        )
        return per_rank * max(1, self.n_ranks)

    def _prep_restore_unit(
        self, gi: int, groups: list, name: str, alive: set[int], ref_table: dict
    ) -> _RestoreUnit:
        """Capture everything one unit's chunks need — references to the
        surviving shards/stripes (so a rank dying mid-restore cannot pull
        bytes out from under the drain), arena-leased blob + output buffers
        on the recovering host, and the codec's precomputed chunk decoder."""
        codec = self._restore_codec(name)
        grp = groups[gi]

        def _has_data(m: int) -> bool:
            st = self.stores.get(m)
            return st is not None and st.alive and st.buffer.valid

        missing_idx = [i for i, m in enumerate(grp.members) if not _has_data(m)]
        if len(missing_idx) > codec.tolerance():
            raise dist.DataLostError(
                f"group {gi} lost {len(missing_idx)} members; "
                f"codec {codec.name!r} tolerates {codec.tolerance()}"
            )
        first_missing = grp.members[missing_idx[0]]

        stripe_srcs: dict[int, list[np.ndarray]] = {}
        for b, holders in enumerate(codec.placement(groups, gi, self.n_ranks)):
            stripes: list[np.ndarray] | None = []
            for j, member in enumerate(holders):
                stripe = (
                    self.stores[member].buffer.read_only.parity.get(gi, {}).get((name, b, j))
                    if _has_data(member)
                    else None
                )
                if stripe is None:
                    stripes = None  # any lost stripe kills the whole blob
                    break
                stripes.append(stripe)
            if stripes is not None:
                stripe_srcs[b] = stripes
        present: dict[int, np.ndarray] = {}
        for i, m in enumerate(grp.members):
            if i in missing_idx:
                continue
            ro = self.stores[m].buffer.read_only
            present[i] = ro.own_exch.get(name, ro.own[name])[0]

        # Repair locality (DESIGN.md §16): ask the codec which surviving
        # blobs its decode will actually solve through and drop the rest
        # BEFORE leasing/transferring them — an LRC single-failure repair
        # then moves one local parity, not the whole blob set. None = all.
        needed = codec.blobs_needed(
            sorted(present), sorted(stripe_srcs), missing_idx
        )
        if needed is not None:
            stripe_srcs = {b: s for b, s in stripe_srcs.items() if b in needed}

        # Blob + output buffers live in the recovering host's staging-bank
        # arenas (never the read-only bank — the same generation-parity
        # guarantee as the create path); single-stripe blobs adopt the
        # holder's bytes by reference, exactly like the sync path.
        host = codec.rebuilder(groups, gi, first_missing, alive)
        store = self.stores.get(host) if host is not None else None
        if store is None or not store.alive:
            cand = [r for r in alive if self.stores[r].alive]
            if not cand:
                raise dist.DataLostError(
                    f"no surviving rank can rebuild rank {first_missing}"
                )
            store = self.stores[min(cand)]
        blobs: dict[int, np.ndarray] = {}
        for b, stripes in stripe_srcs.items():
            if len(stripes) == 1:
                blobs[b] = stripes[0].reshape(-1)
            else:
                nb = sum(s.nbytes for s in stripes)
                blobs[b] = store.lease(("restore", gi, name, "blob", b), nb)
        multi = {b: s for b, s in stripe_srcs.items() if len(s) > 1}
        if multi and not codec.decode_chunked():
            # Codec without a chunked decode: it decodes EAGERLY inside
            # decode_into, so its blob bytes must be materialized up front
            # (the chunked TRANSFER stage then has nothing left to copy).
            for b, stripes in multi.items():
                np.copyto(blobs[b], parity_mod.join_stripes(
                    [s.reshape(-1) for s in stripes]
                ))
            multi = {}
        try:
            rebuilt, decode_chunk = codec.decode_into(
                present, blobs, missing_idx,
                lambda i, nb: store.lease(("restore", gi, name, "out", i), nb),
            )
        except codec_mod.CodecDecodeError as e:
            raise dist.DataLostError(
                f"rank {first_missing} (group {gi}) unrecoverable under codec "
                f"{codec.name!r}, entity {name!r}: {e}"
            ) from e

        n = max((bb.nbytes for bb in blobs.values()), default=0)
        cb = self.cfg.restore_chunk_bytes
        step = self._plan_chunk_step() if cb <= 0 else max(4, cb) & ~3
        bounds = [(lo, min(lo + step, n)) for lo in range(0, n, step)] or [(0, 0)]
        manifests = {i: self._redundancy_manifest(grp.members[i], name) for i in missing_idx}
        ref_sums: dict[int, Any] = {}
        decomp: dict[int, list] = {}
        for i in missing_idx:
            compressed = isinstance(manifests[i], tuple) and manifests[i][0] == "compressed"
            ref_sums[i] = None if compressed else ref_table.get((grp.members[i], name))
            if compressed and not codec.striped:
                # The full-copy codec adopts the whole compressed flat by
                # reference at prep — the tiny scale/meta leaves are
                # resolvable here and the expensive int8->f32 expansion
                # chunk-streams through the drain's DEQ stage instead of one
                # monolithic pass at finalize. Striped codecs resolve the
                # rebuilt bytes only as the decode chunks run, so their
                # scales are unreadable at prep: they decompress
                # monolithically in _finalize_restore_unit.
                plan = self._prep_decomp_plan(
                    manifests[i][1], np.asarray(rebuilt[i]).reshape(-1),
                    lambda key, nb, _i=i: store.lease(
                        ("restore", gi, name, "deq", _i, key), nb
                    ),
                )
                if plan:
                    decomp[i] = plan
        return _RestoreUnit(
            gi=gi, grp=grp, name=name, missing_idx=missing_idx,
            stripe_srcs=multi,
            blobs=blobs, rebuilt=rebuilt, decode_chunk=decode_chunk, bounds=bounds,
            manifests=manifests, ref_sums=ref_sums,
            sums={i: [None] * len(bounds) for i in missing_idx},
            decomp=decomp or None,
        )

    def _prep_decomp_plan(self, cman: Manifest, flat: np.ndarray, lease) -> list:
        """Chunked-dequantization plan for one compressed origin: one
        ``_DeqLeaf`` per quantized leaf (``_q``/``_scale``/``_meta`` triples
        in the packed manifest), with its f32 destination leased from the
        recovering host's staging-bank arenas."""
        plan: list[_DeqLeaf] = []
        by_name = {n: k for k, n in enumerate(cman.names)}
        for k, n in enumerate(cman.names):
            if not n.endswith("_q") or cman.dtypes[k] != "int8":
                continue
            sk = by_name.get(n[: -len("_q")] + "_scale")
            if sk is None or cman.dtypes[sk] != "float32":
                # Unresolvable packed node: the finalize walk pairs plan
                # entries with packed nodes 1:1, so a partial plan would
                # misalign — fall back to the monolithic _decompress.
                return []
            q_off = cman.offsets[k]
            q_n = int(np.prod(cman.shapes[k], dtype=np.int64))
            s_off = cman.offsets[sk]
            s_n = int(np.prod(cman.shapes[sk], dtype=np.int64))
            # scales are tiny: copy them out now, so the DEQ stage never
            # re-reads bytes a concurrent chunk could still be rebuilding
            scales = np.array(flat[s_off : s_off + 4 * s_n].view(np.float32))
            out = lease(k, q_n * 4).view(np.float32)
            plan.append(_DeqLeaf(
                q_off=q_off, q_n=q_n, block=q_n // max(s_n, 1),
                scales=scales, out=out,
            ))
        return plan

    def _restore_ref_sums(self) -> dict:
        """Replicated capture-time exchange checksums (empty for pre-§10
        checkpoints, e.g. migrated disk pickles — VERIFY then skips)."""
        for st in self.stores.values():
            if st.alive and st.buffer.valid:
                table = st.buffer.read_only.meta.get("exch_checksums")
                if table:
                    return table
        return {}

    def _restore_chunk_task(self, u: _RestoreUnit, ci: int) -> None:
        """Parallel-drain form of one chunk: its own TRANSFER→DECODE→VERIFY
        (chunks are range-disjoint, so any interleaving across workers is
        race-free and byte-identical to the serial pipeline)."""
        lo, hi = u.bounds[ci]
        if _TR.enabled:
            eng = self._obs_id
            with _TR.span("r_transfer", eng=eng, group=u.gi, entity=u.name, chunk=ci):
                t = time.perf_counter()
                self._restore_transfer_chunk(u, lo, hi)
                self._hr_transfer.observe(time.perf_counter() - t)
            with _TR.span("decode", eng=eng, group=u.gi, entity=u.name, chunk=ci):
                t = time.perf_counter()
                u.decode_chunk(lo, hi)
                dt = time.perf_counter() - t
                self._hr_decode.observe(dt)
                self._observe_decode_rate(hi - lo, dt)
            with _TR.span("r_verify", eng=eng, group=u.gi, entity=u.name, chunk=ci):
                t = time.perf_counter()
                self._restore_verify_chunk(u, ci)
                self._hr_verify.observe(time.perf_counter() - t)
            with _TR.span("deq", eng=eng, group=u.gi, entity=u.name, chunk=ci):
                t = time.perf_counter()
                self._restore_decompress_chunk(u, ci)
                self._hr_deq.observe(time.perf_counter() - t)
        else:
            # Disabled-tracer fast path: no span objects, no kwargs dicts —
            # only the pre-bound histogram children (DESIGN.md §14).
            t0 = time.perf_counter()
            self._restore_transfer_chunk(u, lo, hi)
            t1 = time.perf_counter()
            self._hr_transfer.observe(t1 - t0)
            u.decode_chunk(lo, hi)
            t2 = time.perf_counter()
            self._hr_decode.observe(t2 - t1)
            self._observe_decode_rate(hi - lo, t2 - t1)
            self._restore_verify_chunk(u, ci)
            t3 = time.perf_counter()
            self._hr_verify.observe(t3 - t2)
            self._restore_decompress_chunk(u, ci)
            self._hr_deq.observe(time.perf_counter() - t3)
        self._fault_hook("restore_chunk")

    def _restore_transfer_chunk(self, u: _RestoreUnit, lo: int, hi: int) -> None:
        """TRANSFER: copy the stripe segments covering [lo, hi) into the blob
        arenas (the simulated network hop that fetches remote stripes). A
        plan-cache hit means the arenas already hold exactly these immutable
        committed bytes — nothing to move."""
        if u.staged:
            return
        for b, stripes in u.stripe_srcs.items():
            dst = u.blobs[b]
            off = 0
            for s in stripes:
                s = s.reshape(-1)
                a, z = max(lo, off), min(hi, off + s.nbytes)
                if a < z:
                    np.copyto(dst[a:z], s[a - off : z - off])
                off += s.nbytes

    def _restore_decompress_chunk(self, u: _RestoreUnit, ci: int) -> None:
        """DEQ stage: blockwise int8 -> f32 dequantization of this chunk's
        slice of every compressed origin's quantized leaves — the restore
        mirror of the create path's compress, spread over the same chunk
        grid instead of one monolithic pass at finalize. Chunks write
        disjoint output ranges, so the parallel drain stays race-free; the
        math (codes · per-block scale, in f32) is the exact elementwise op
        of ``ops.dequantize_blockwise``, so the assembled payload is
        bit-identical to the monolithic ``_decompress`` baseline."""
        if not u.decomp:
            return
        lo, hi = u.bounds[ci]
        for i, plan in u.decomp.items():
            flat = np.asarray(u.rebuilt[i]).reshape(-1)
            for leaf in plan:
                a, z = max(lo, leaf.q_off), min(hi, leaf.q_off + leaf.q_n)
                if a >= z:
                    continue
                e0 = a - leaf.q_off
                codes = flat[a:z].view(np.int8).astype(np.float32)
                idx = np.arange(e0, e0 + (z - a), dtype=np.int64) // leaf.block
                np.multiply(codes, leaf.scales[idx], out=leaf.out[e0 : e0 + (z - a)])

    def _restore_verify_chunk(self, u: _RestoreUnit, ci: int) -> None:
        """VERIFY: Fletcher partials of the rebuilt chunk. Both sums are
        linear, so chunk partials at word offset *o* recombine exactly:
        s1 = Σ c1,  s2 = Σ (c2 + o·c1) — the final sums equal a monolithic
        ``np_checksum`` of the rebuilt payload. A plan-cache hit carries the
        previous restore's clean partials for these same immutable inputs,
        so recomputing them would derive the identical verdict."""
        if u.verified:
            return
        lo, hi = u.bounds[ci]
        for i in u.missing_idx:
            if u.ref_sums[i] is None:
                continue
            man = u.manifests[i]
            end = min(hi, man.total)
            if lo < end:
                c1, c2 = np_checksum(u.rebuilt[i][lo:end])
                u.sums[i][ci] = (lo // 4, c1, c2)

    def _finalize_restore_unit(
        self, u: _RestoreUnit, shards: dict, partials: dict
    ) -> None:
        """Checksum verdict + unpack of every rebuilt origin in the unit."""
        has_subset = hasattr(self._entities[u.name], "partner_payload")
        for i in u.missing_idx:
            origin = u.grp.members[i]
            ref = u.ref_sums[i]
            if ref is not None:
                s1 = s2 = 0
                for part in u.sums[i]:
                    if part is None:
                        continue
                    o, c1, c2 = part
                    s1 = (s1 + c1) & 0xFFFFFFFF
                    s2 = (s2 + c2 + o * c1) & 0xFFFFFFFF
                if (s1, s2) != tuple(ref):
                    raise IntegrityError(
                        f"reconstructed shard failed checksum validation: "
                        f"rank {origin} entity {u.name!r} (group {u.gi})"
                    )
            if self._restore_codec(u.name).striped:
                self.stats.reconstructed_restores += 1
            else:
                self.stats.adopted_restores += 1
            man = u.manifests[i]
            rebuilt = np.asarray(u.rebuilt[i]).reshape(-1)
            if isinstance(man, tuple) and man[0] == "compressed":
                if u.decomp and i in u.decomp:
                    payload = self._finalize_decompressed(rebuilt, man[1], u.decomp[i])
                else:
                    payload = self._decompress(rebuilt, man)
            else:
                payload = unpack_bytes(rebuilt[: man.total], man)
            (partials if has_subset else shards)[u.name][origin] = payload

    def _finalize_decompressed(self, flat: np.ndarray, cman: Manifest, plan: list):
        """Assemble a compressed origin's payload from the drain's chunk-
        dequantized buffers: the same tree walk as
        ``optim.grad_compress.decompress_tree``, minus the monolithic
        dequantization pass the DEQ stage already spread over the chunks
        (each packed node consumes its pre-expanded f32 arena; only shape /
        dtype metadata is read here)."""
        import jax

        from repro.optim.grad_compress import _DTYPES

        views = []
        for shape, dtype, off in zip(cman.shapes, cman.dtypes, cman.offsets):
            dt = dtype_from_name(dtype)
            n = int(np.prod(shape, dtype=np.int64)) * dt.itemsize if shape else dt.itemsize
            views.append(flat[off : off + n].view(dt).reshape(shape))
        packed = jax.tree.unflatten(cman.treedef, views)
        it = iter(plan)

        def is_packed(x):
            return isinstance(x, dict) and "_q" in x

        def decomp(x):
            if is_packed(x):
                leaf = next(it)
                meta = np.asarray(x["_meta"]).reshape(-1)
                shape = tuple(int(v) for v in meta[:-2])
                dtype = _DTYPES[int(meta[-2])]
                size = int(meta[-1])
                return leaf.out[:size].reshape(shape).astype(dtype)
            return np.array(x)  # passthrough views die with the arena: copy

        return jax.tree.map(decomp, packed, is_leaf=is_packed)

    # ------------------------------------------------------------------ #
    # Elastic N-to-M restore (beyond-paper: Ham et al.'s N-to-M algorithm)
    # ------------------------------------------------------------------ #
    def restore_elastic(self, new_n_ranks: int) -> dict[str, Any]:
        """Recover the last valid checkpoint (created on this engine's N
        ranks, possibly with failures) and restore it onto ``new_n_ranks``
        ranks — shrink after a failure without spares, or grow on scale-up.

        Entities exposing a global-coordinate manifest (``shard_coords``) are
        repartitioned with minimal data movement via elastic/plan.py; others
        restore through their old-world shard map unchanged. The engine's
        stores are rebuilt for the new world (empty until the next
        checkpoint re-protects it). Returns the checkpoint meta; movement
        accounting lands in ``self.last_elastic_report``.
        """
        import jax

        from repro.elastic.plan import ElasticReport, plan_repartition
        from repro.elastic.reshard import reshard_leaves

        assert new_n_ranks >= 1
        self.discard_pending()
        t0 = time.perf_counter()
        if not self.has_valid_checkpoint and self.has_tier_data():
            # Cold N-to-M restart: nothing in memory — rehydrate the stored
            # world first (the engine resizes to the generation's N), then
            # repartition onto the caller's M below.
            self.escalate_from_tiers()
            alive = self._store_alive()
        else:
            alive = self._alive_fn()
        failed = set(range(self.n_ranks)) - alive
        meta = self.checkpoint_step()  # read before the stores are rebuilt

        # Physical residency of every origin's recovered payload in the NEW
        # world: survivors keep their own shard on-host under the dense
        # renumbering; adopted/reconstructed shards materialize on the
        # recovering host. Hosts renumbered past M leave the job (their data
        # counts as movement if the plan still needs it).
        reassign = dist.shrink_reassignment(self.n_ranks, failed)
        residency: dict[int, int | None] = {}
        for origin in range(self.n_ranks):
            holder = self._recovery_host(origin, alive)
            dense = reassign.get(holder) if holder is not None else None
            residency[origin] = dense if dense is not None and dense < new_n_ranks else None

        report = ElasticReport(n_old=self.n_ranks, n_new=new_n_ranks)
        with _TR.span(
            "restore", eng=self._obs_id, failed=len(failed),
            mode=self.cfg.restore_mode, elastic=new_n_ranks,
        ), _TR.span("restore_rebuild", eng=self._obs_id):
            recovered = self._recover_all(alive, failed)  # pipelined or sync
        for name, ent in self._entities.items():
            shards = recovered[name]
            coords = self._stored_coords(name)
            if coords is None and hasattr(ent, "shard_coords"):
                coords = ent.shard_coords(self.n_ranks)
            if name in self._replicated or coords is None:
                # No global coordinates: the entity merges its old-world
                # shard map globally; it re-shards at the next checkpoint.
                ent.restore_shards(shards)
                continue
            leaves_by_origin = {o: jax.tree.leaves(p) for o, p in shards.items()}
            axes = [ls.axis for ls in coords[0]]
            row_nb = _row_nbytes(leaves_by_origin[min(leaves_by_origin)], coords[0])
            plan = plan_repartition(coords, new_n_ranks, residency, row_nb)
            new_leaves = reshard_leaves(plan, leaves_by_origin, axes)
            treedef = jax.tree.structure(shards[min(shards)])
            ent.restore_shards(
                {j: jax.tree.unflatten(treedef, new_leaves[j]) for j in range(new_n_ranks)}
            )
            report.add(name, plan)

        # Rebuild the engine topology for the new world. The consumed
        # checkpoint dies with the old rank space; callers re-protect by
        # checkpointing immediately (trainer/server do).
        self.n_ranks = new_n_ranks
        self.stores = {r: HostStore(r) for r in range(new_n_ranks)}
        self._delta_enc.clear()  # scratch parity belongs to the old world
        if self.topology is not None:
            # The failure-domain map resizes with the world (regular shapes
            # re-derive; _groups re-packs for the new rank space on next use).
            self.topology = self.topology.resized(new_n_ranks)
            self._groups_cache = None
        self.last_elastic_report = report
        self.stats.restored += 1
        self.stats.last_restore_s = time.perf_counter() - t0
        self.journal.record(
            "resize", n_old=report.n_old, n_new=report.n_new,
            failed=len(failed), bytes_moved=report.bytes_moved,
            bytes_total=report.bytes_total,
            duration_s=self.stats.last_restore_s,
        )
        log.info(
            "elastic restore %d->%d ranks: %.1f MiB held, %.1f MiB moved (lower bound %.1f)",
            report.n_old, report.n_new,
            report.bytes_total / 2**20, report.bytes_moved / 2**20,
            report.bytes_lower_bound / 2**20,
        )
        return meta

    def _recovery_host(self, origin: int, alive: set[int]) -> int | None:
        """Old-world rank whose host ends up holding ``origin``'s recovered
        payload (the survivor itself, the adopting copy holder, or the
        erasure rebuilder — the codec decides). An alive-but-empty origin
        (revived spare) holds nothing: its shard is rebuilt elsewhere, and
        residency must say so or elastic movement accounting undercounts."""
        if origin in alive and self.stores[origin].buffer.valid:
            return origin
        groups = self._groups()
        gi = self._group_of(origin)
        return self.codec.rebuilder(groups, gi, origin, alive)

    def _stored_coords(self, name: str):
        """Global-coordinate table recorded with the last valid checkpoint."""
        for st in self.stores.values():
            if st.alive and st.buffer.valid:
                table = st.buffer.read_only.meta.get("coords", {}).get(name)
                if table is not None:
                    return table
        return None

    def _recover_shard(
        self,
        origin: int,
        name: str,
        alive: set[int],
        failed: set[int],
        decode_cache: dict[int, dict[int, Any]] | None = None,
    ):
        """Returns ("full"|"partial", payload). Partial = partner-exchange
        subset needing a merge with a survivor's replicated leaves."""
        has_subset = hasattr(self._entities[name], "partner_payload")
        # 1. Survivor: restore from its own read-only buffer — local, no comm.
        if origin in alive and self.stores[origin].buffer.valid:
            flat, man = self.stores[origin].buffer.read_only.own[name]
            self.stats.zero_comm_restores += 1
            return "full", unpack_bytes(flat, man)

        # 1b. Replicated entity: any survivor's own copy is the payload.
        if name in self._replicated:
            for r in sorted(alive):
                if self.stores[r].buffer.valid:
                    flat, man = self.stores[r].buffer.read_only.own[name]
                    self.stats.zero_comm_restores += 1
                    return "full", unpack_bytes(flat, man)
            raise dist.DataLostError(f"replicated entity {name!r} lost everywhere")

        # 2. Codec rebuild: gather the group's surviving shards + intact
        # redundancy blobs and ask the codec to decode the missing ones.
        # Full-copy codecs take the same path — singleton group, present={},
        # decode adopts any surviving whole-copy blob (communication!).
        codec = self._restore_codec(name)
        groups = self._groups()
        gi = self._group_of(origin)
        grp = groups[gi]

        def _has_data(m: int) -> bool:
            st = self.stores.get(m)
            return st is not None and st.alive and st.buffer.valid

        rebuilt_map = decode_cache.get(gi) if decode_cache is not None else None
        if rebuilt_map is None:
            # Missing = dead ranks AND alive-but-empty ones (revived spares):
            # both lost their in-memory shard and count against tolerance().
            missing_idx = [i for i, m in enumerate(grp.members) if not _has_data(m)]
            if len(missing_idx) > codec.tolerance():
                raise dist.DataLostError(
                    f"group {gi} lost {len(missing_idx)} members; "
                    f"codec {codec.name!r} tolerates {codec.tolerance()}"
                )
            stripe_sets: dict[int, list[np.ndarray]] = {}
            for b, holders in enumerate(codec.placement(groups, gi, self.n_ranks)):
                stripes: list[np.ndarray] | None = []
                for j, member in enumerate(holders):
                    stripe = (
                        self.stores[member].buffer.read_only.parity.get(gi, {}).get((name, b, j))
                        if _has_data(member)
                        else None
                    )
                    if stripe is None:
                        stripes = None  # any lost stripe kills the whole blob
                        break
                    stripes.append(stripe)
                if stripes is not None:
                    stripe_sets[b] = stripes
            # Repair locality (DESIGN.md §16): join only the blobs the
            # codec's row selection will read (None = all survive the cut).
            needed = codec.blobs_needed(
                [i for i in range(len(grp.members)) if i not in missing_idx],
                sorted(stripe_sets),
                missing_idx,
            )
            if needed is not None:
                stripe_sets = {
                    b: s for b, s in stripe_sets.items() if b in needed
                }
            # Single-stripe blobs (whole copies) adopt by reference —
            # no memcpy, mirroring the distribute path.
            blobs: dict[int, np.ndarray] = {
                b: (s[0] if len(s) == 1 else parity_mod.join_stripes(s))
                for b, s in stripe_sets.items()
            }
            present: dict[int, np.ndarray] = {}
            for i, m in enumerate(grp.members):
                if i in missing_idx:
                    continue
                ro = self.stores[m].buffer.read_only
                present[i] = ro.own_exch.get(name, ro.own[name])[0]
            try:
                rebuilt_map = codec.decode(present, blobs, missing_idx)
            except codec_mod.CodecDecodeError as e:
                raise dist.DataLostError(
                    f"rank {origin} (group {gi}) unrecoverable under codec "
                    f"{codec.name!r}, entity {name!r}: {e}"
                ) from e
            if decode_cache is not None:
                decode_cache[gi] = rebuilt_map
        rebuilt = np.asarray(rebuilt_map[grp.members.index(origin)]).reshape(-1)
        if codec.striped:
            self.stats.reconstructed_restores += 1
        else:
            self.stats.adopted_restores += 1
        man = self._redundancy_manifest(origin, name)
        if isinstance(man, tuple) and man[0] == "compressed":
            return ("partial" if has_subset else "full"), self._decompress(rebuilt, man)
        return ("partial" if has_subset else "full"), unpack_bytes(rebuilt[: man.total], man)

    def _redundancy_manifest(self, origin: int, name: str) -> Manifest:
        # Manifests are tiny; replicate them with the stripes at distribute time.
        for st in self.stores.values():
            if st.alive and st.buffer.valid:
                mans = st.buffer.read_only.meta.get("manifests", {})
                if (origin, name) in mans:
                    return mans[(origin, name)]
        raise dist.DataLostError(f"manifest for rank {origin} entity {name!r} lost")

    # ------------------------------------------------------------------ #
    # memory accounting (paper eq. 2)
    # ------------------------------------------------------------------ #
    def memory_report(self) -> dict[str, Any]:
        """Eq.-2-style accounting, itemized per redundancy kind so the
        DESIGN.md §8 memory/tolerance trade-off table is checkable from code:
        ``by_kind[r]`` splits each rank's bytes into own snapshots, exchange
        subsets, and redundancy (copies / XOR stripes / RS blobs), and
        ``redundancy_bytes`` totals the latter under the active codec."""
        per_rank = {r: s.nbytes for r, s in self.stores.items() if s.alive}
        by_kind = {r: s.nbytes_by_kind() for r, s in self.stores.items() if s.alive}
        group = self.codec.group_size(self.n_ranks)
        return {
            "bytes_per_rank": per_rank,
            "by_kind": by_kind,
            "total_bytes": sum(per_rank.values()),
            "n_ranks": self.n_ranks,
            "codec": self.codec.name,
            "tolerance": self.codec.tolerance(),
            "redundancy_bytes": {
                self.codec.name: sum(k["redundancy"] for k in by_kind.values())
            },
            "exchange_bytes": sum(k["exchange"] for k in by_kind.values()),
            # Redundancy bytes per data byte the codec promises (copies: R;
            # xor: 1/g; rs: m/g; lrc: (l+g)/g) — compare against the
            # measured split above.
            "redundancy_overhead": self.codec.memory_overhead(group, self.n_ranks),
            "topology": repr(self.topology) if self.topology is not None else None,
            "entity_codecs": {
                n: self._codec_spec(c) for n, c in sorted(self.entity_codecs.items())
            },
        }


def _row_nbytes(leaves: list[Any], coords: list[Any]) -> list[int]:
    """Bytes per planner row for each leaf: a slice along the leaf's data
    axis, or the full leaf for replicated ones (one logical row)."""
    out = []
    for leaf, ls in zip(leaves, coords):
        a = np.asarray(leaf)
        if ls.axis is None:
            out.append(int(a.nbytes))
        else:
            out.append(int(a.nbytes // max(a.shape[ls.axis], 1)))
    return out


class _FnEntity:
    def __init__(self, create, restore) -> None:
        self._create, self._restore = create, restore

    def snapshot(self):
        return self._create()

    def restore(self, snap):
        self._restore(snap)
