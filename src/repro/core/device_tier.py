"""Device-tier snapshot/restore programs — the collective hot path on TPU.

The paper's pair-wise snapshot exchange (Algorithm 1 / Figure 1) maps to a
single ``collective-permute`` along the redundancy mesh axis: a fixed
permutation is exactly what TPU ICI executes at full per-link bandwidth with
no contention. ``build_snapshot_program`` returns a jit-able function whose
lowering the dry-run compiles per architecture; its collective bytes are the
paper's Fig-4/5 quantity (checkpoint-creation cost), reported as a roofline
row in EXPERIMENTS.md.

**Fused one-program creation (DESIGN.md §9).** All exchanged leaves are
concatenated into per-``(failure-axis, dtype)`` flat uint32 buffers *inside a
single ``shard_map``* — one program dispatch regardless of how many leaves
the state has (the previous per-leaf loop emitted one ``shard_map``/
``ppermute`` program per leaf, multiplying dispatch overhead), and the
handshake checksum folds into the same program. On top of the fused buffers
the active redundancy codec's parity is computed **on device, before the
host DMA**:

  * ``codec="copy"``  — the fused buffer ppermutes to the scheme partner
                        (Algorithm 1); the whole partner copy crosses PCIe.
  * ``codec="xor"/"rs"/"lrc"`` — a ring of ``g-1`` ppermutes collects the
                        parity group's buffers, the Pallas XOR / GF(2^8)
                        kernel (kernels/xor_parity.py, kernels/rs_encode.py)
                        encodes the parity blobs on device (for ``lrc`` the
                        generator is the shared ``codec.lrc_generator`` —
                        local XOR rows + global Cauchy rows, bit-identical
                        to the host codec), blob *b* routes to neighbor
                        group ``gi+1+b`` (mirroring the host codec's
                        placement), and each holder keeps only its stripe —
                        so only **own shard + parity stripes** cross PCIe
                        instead of whole partner copies. On ragged worlds
                        (``g ∤ axis``) the short group's members each hold
                        ``ceil(g/k')`` round-robin stripes instead of one —
                        the true ragged stripe layout (DESIGN.md §16) that
                        replaced the old whole-blob fallback.

Only *uniquely-owned* leaves are exchanged: a leaf whose PartitionSpec uses
the redundancy axis has exactly one owner per shard (ZeRO-1 optimizer state,
FSDP params); replicated leaves are already redundant and only enter the own
copy + checksum. This is the waLBerla property ("data is not stored
redundantly in any way") driving what needs protection.

Modes (hillclimb levers, see EXPERIMENTS §Perf):
  * ``compress``   — int8-quantize the fused buffers before the permute (4x
                     less ICI traffic for f32 state; lossy; full-copy codec
                     only, matching the host engine's restriction).
  * ``validate``   — fold a Fletcher checksum of the fused exchanged buffers
                     into the program (the handshake's integrity input).
"""

from __future__ import annotations

import functools
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distribution as dist
from repro.obs.trace import tracer
from repro.utils.logging import get_logger

log = get_logger("core.device_tier")
_TR = tracer()


def _traced(phase: str):
    """Span-wrap a program builder (trace-time cost shows up in Perfetto as
    one block per build, DESIGN.md §13) without touching its body."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _TR.span(phase):
                return fn(*args, **kwargs)
        return wrapper
    return deco

def _full_rank(pspec: P, ndim: int) -> tuple:
    entries = list(pspec) + [None] * (ndim - len(pspec))
    return tuple(entries[:ndim])


def _axes_of(entry) -> tuple[str, ...]:
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _uses_axis(pspec: P, ndim: int, axes: tuple[str, ...]) -> bool:
    for e in _full_rank(pspec, ndim):
        if any(a in axes for a in _axes_of(e)):
            return True
    return False


def _pad_shape(shape: tuple[int, ...], pspec: P, mesh: Mesh) -> tuple[int, ...]:
    out = []
    for size, entry in zip(shape, _full_rank(pspec, len(shape))):
        k = 1
        for a in _axes_of(entry):
            k *= mesh.shape[a]
        out.append(-(-size // k) * k)
    return tuple(out)


def _local_shape(padded: tuple[int, ...], pspec: P, mesh: Mesh) -> tuple[int, ...]:
    """Per-device shard shape of a padded leaf under its PartitionSpec."""
    out = []
    for size, entry in zip(padded, _full_rank(pspec, len(padded))):
        k = 1
        for a in _axes_of(entry):
            k *= mesh.shape[a]
        out.append(size // k)
    return tuple(out)


def _leaf_words(local: tuple[int, ...], itemsize: int) -> int:
    """uint32 words the local shard occupies in the fused buffer (ceil —
    as_u32 zero-pads sub-word tails)."""
    nbytes = int(np.prod(local, dtype=np.int64)) * itemsize
    return -(-nbytes // 4)


@dataclass(frozen=True)
class FusedBucket:
    """Layout of one per-(axis, dtype) fused exchange buffer.

    All exchanged leaves sharing a failure axis and dtype concatenate (as
    uint32 words, per shard) into one flat buffer; ``word_offsets[i]`` is
    leaf ``leaf_idx[i]``'s start inside the *local* buffer of ``words``
    words. ``axes`` is the union of mesh axes the member leaves vary on (in
    mesh order) — the buffer's output sharding and checksum-psum axes.
    """

    tag: str
    axis: str
    dtype: str
    axes: tuple[str, ...]
    leaf_idx: tuple[int, ...] = field(default=())
    word_offsets: tuple[int, ...] = field(default=())
    words: int = 0


@dataclass(frozen=True)
class SnapshotProgram:
    """Jit-able snapshot/restore closures + sharding metadata."""

    snapshot_fn: Any          # state -> snapshot payload (dict)
    restore_fn: Any           # payload -> exchanged leaves re-aligned to origin
    in_shardings: Any
    out_shardings: Any
    exchanged_names: tuple[str, ...]
    exchanged_bytes: int      # global bytes traversing the collectives
    own_bytes: int            # global snapshot bytes (own copies)
    buckets: tuple[FusedBucket, ...] = ()
    pcie_bytes: int = 0       # global device->host bytes per checkpoint
    codec: str = "copy"
    parity_group: int = 0
    # One program per staging chunk (own copy, then one per bucket) — the
    # double-buffered D2H path driven by ``staged_snapshot_fetch``.
    snapshot_chunk_fns: tuple = ()


def _take_rows(stacked: jax.Array, order: jax.Array) -> jax.Array:
    """``stacked[order]`` for a short traced ``order``, as one dynamic slice
    per row. The TPU compiler takes time linear in the operand's size to
    compile a gather, which for multi-GiB exchange buffers means minutes;
    a dynamic slice compiles in constant time."""
    return jnp.stack([
        jax.lax.dynamic_index_in_dim(stacked, order[i], 0, keepdims=False)
        for i in range(order.shape[0])
    ])


def _to_u32_local(x: jax.Array) -> jax.Array:
    """Flatten a local shard to packed uint32 words (pad tail with zeros) —
    the same packing the Pallas wrappers use, so fused-buffer parity stays
    byte-compatible with the host/kernel oracles."""
    from repro.kernels import ops as kops

    return kops.as_u32(x)


def _from_u32_local(
    words: jax.Array, dtype: np.dtype, local: tuple[int, ...]
) -> jax.Array:
    """Inverse of ``_to_u32_local`` (= kernels.ops.as_u32): unpack the words'
    bytes back into a local shard."""
    n = int(np.prod(local, dtype=np.int64))
    dtype = np.dtype(dtype)
    if dtype.itemsize == 4:
        flat = jax.lax.bitcast_convert_type(words, dtype)
        return flat[:n].reshape(local)
    u8 = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(-1)
    if dtype.itemsize == 1:
        flat = u8[:n] if dtype == np.uint8 else jax.lax.bitcast_convert_type(u8[:n], dtype)
    else:
        flat = jax.lax.bitcast_convert_type(
            u8[: n * dtype.itemsize].reshape(n, dtype.itemsize), dtype
        )
    return flat.reshape(local)


@_traced("build_snapshot_program")
def build_snapshot_program(
    mesh: Mesh,
    state_sds: Any,            # ShapeDtypeStruct pytree
    state_pspecs: Any,         # PartitionSpec pytree (same structure)
    *,
    redundancy_axis: str = "data",
    scheme: str = "pairwise",
    include_own_copy: bool = True,
    compress: bool = False,
    validate: bool = True,
    codec: str = "copy",       # "copy" | "xor" | "rs" | "lrc": on-device redundancy
    parity_group: int = 0,     # group size g (k) for the striped codecs
    rs_parity: int = 2,        # m parity blobs (global parities for "lrc")
    lrc_locals: int = 2,       # local XOR rows for codec="lrc"
    # Whole blobs on every group member instead of routed stripes (an
    # explicit opt-in: more PCIe, no routing hop). None/False take the
    # stripe path, which handles ragged worlds (g ∤ axis) natively via the
    # round-robin ragged stripe layout.
    emit_full_blobs: bool | None = None,
) -> SnapshotProgram:
    fail_axes = (redundancy_axis,) if redundancy_axis != "data" else ("data", "pod")
    striped = codec in ("xor", "rs", "lrc")
    if striped:
        assert parity_group >= 1, "striped codecs need parity_group (the group size)"
        assert not compress, "compress applies to the full-copy codec only"
    n_parity = {
        "copy": 0, "xor": 1, "rs": rs_parity,
        "lrc": min(lrc_locals, max(parity_group, 1)) + rs_parity,
    }[codec]

    leaves_sds, treedef = jax.tree.flatten(state_sds)
    leaves_ps = treedef.flatten_up_to(state_pspecs)
    exchanged_idx = [
        i
        for i, (sd, ps) in enumerate(zip(leaves_sds, leaves_ps))
        if _uses_axis(ps, len(sd.shape), fail_axes)
    ]

    def _leaf_axis(ps: P, ndim: int) -> str:
        """The failure axis this leaf is actually sharded on (ppermute over an
        axis the value doesn't vary on is vacuous and fails the rep check):
        prefer the requested redundancy axis, else any other failure axis."""
        cands = [redundancy_axis] + [a for a in fail_axes if a != redundancy_axis]
        for a in cands:
            if _uses_axis(ps, ndim, (a,)):
                return a
        return redundancy_axis

    mesh_axes = tuple(mesh.shape.keys())

    # -- bucket the exchanged leaves by (failure axis, dtype) ----------------
    padded_shapes = {i: _pad_shape(leaves_sds[i].shape, leaves_ps[i], mesh)
                     for i in exchanged_idx}
    local_shapes = {i: _local_shape(padded_shapes[i], leaves_ps[i], mesh)
                    for i in exchanged_idx}
    by_key: dict[tuple[str, str], list[int]] = {}
    for i in exchanged_idx:
        axis = _leaf_axis(leaves_ps[i], len(leaves_sds[i].shape))
        key = (axis, leaves_sds[i].dtype.name)
        by_key.setdefault(key, []).append(i)

    buckets: list[FusedBucket] = []
    for (axis, dtype), idxs in sorted(by_key.items()):
        offsets, off = [], 0
        axes_set: set[str] = set()
        for i in idxs:
            offsets.append(off)
            off += _leaf_words(local_shapes[i], leaves_sds[i].dtype.itemsize)
            for e in _full_rank(leaves_ps[i], len(leaves_sds[i].shape)):
                axes_set.update(_axes_of(e))
        g = parity_group if striped else 1
        off += (-off) % max(g, 1)  # stripe-divisible fused length
        buckets.append(
            FusedBucket(
                tag=f"{axis}:{dtype}",
                axis=axis,
                dtype=dtype,
                axes=tuple(a for a in mesh_axes if a in axes_set),
                leaf_idx=tuple(idxs),
                word_offsets=tuple(offsets),
                words=off,
            )
        )

    emit_full_blobs = bool(emit_full_blobs)

    # -- ragged stripe layout (DESIGN.md §16) ---------------------------------
    # Stripes have uniform width sw = words/g (bucket words are padded to a
    # multiple of g). A holder group of k_h members hosts the g stripes of
    # each blob it holds round-robin: member p keeps stripes {s : s ≡ p
    # (mod k_h)}, i.e. up to S = ceil(g/k_min) slots each. Divisible worlds
    # have k_h = g everywhere, S = 1, and collapse to the legacy one-stripe
    # layout bit-for-bit.
    def _stripe_slots(axis: str) -> int:
        if not striped:
            return 1
        groups = dist.parity_groups(mesh.shape[axis], parity_group)
        return max(-(-parity_group // len(grp.members)) for grp in groups)

    def _bucket_global_bytes(b: FusedBucket) -> int:
        k = 1
        for a in b.axes:
            k *= mesh.shape[a]
        return b.words * 4 * k

    # -- byte accounting ------------------------------------------------------
    own_bytes = sum(
        int(np.prod(sd.shape, dtype=np.int64)) * sd.dtype.itemsize for sd in leaves_sds
    )
    fused_bytes = sum(_bucket_global_bytes(b) for b in buckets)
    if striped:
        # ring collection (g-1 hops) + blob routing (m hops × S multicast
        # rounds, stripe path only — full blobs stay where they were
        # encoded), all fused-width
        exchanged_bytes = sum(
            (
                parity_group - 1
                + (0 if emit_full_blobs else n_parity * _stripe_slots(b.axis))
            )
            * _bucket_global_bytes(b)
            for b in buckets
        )
        if emit_full_blobs:
            pcie_payload = n_parity * fused_bytes
        else:  # holders keep S stripe slots of width words/g each
            pcie_payload = sum(
                n_parity * _bucket_global_bytes(b) * _stripe_slots(b.axis)
                // max(parity_group, 1)
                for b in buckets
            )
    else:
        exchanged_bytes = fused_bytes
        pcie_payload = fused_bytes if not compress else fused_bytes // 4
    pcie_bytes = (own_bytes if include_own_copy else 0) + pcie_payload

    # -- static collective schedules -----------------------------------------
    def _copy_pairs(axis: str) -> list[tuple[int, int]]:
        return dist.perm_pairs(mesh.shape[axis], scheme)

    def _ring_pairs(axis: str, g: int) -> list[tuple[int, int]]:
        """One within-group ring hop: position p receives p+1's buffer, so
        after t hops position p holds member (p+t) mod k of its group."""
        size = mesh.shape[axis]
        groups = dist.parity_groups(size, g)
        pairs = []
        for grp in groups:
            k = len(grp.members)
            for q, m in enumerate(grp.members):
                pairs.append((grp.members[(q + 1) % k], m))
        return pairs

    def _route_pairs(axis: str, g: int, b: int, rnd: int) -> list[tuple[int, int]]:
        """Round ``rnd`` of sending group gi's blob b to its holder group
        (the shared distribution.blob_holder_group rule — the device mirror
        of GroupCodecBase.placement). Every holder member must receive the
        full blob, but ppermute sources must be unique, so a short origin
        group reaches a larger holder group in ceil(k_h/k_o) rounds: round
        rnd covers holder positions p = rnd·k_o + i (so receiver p selects
        round p // k_o). Divisible worlds need exactly one round — the
        legacy single hop."""
        size = mesh.shape[axis]
        groups = dist.parity_groups(size, g)
        ng = len(groups)
        pairs = []
        for gi, grp in enumerate(groups):
            holder = groups[dist.blob_holder_group(ng, gi, b)]
            k_o = len(grp.members)
            for i in range(k_o):
                p = rnd * k_o + i
                if p < len(holder.members):
                    pairs.append((grp.members[i], holder.members[p]))
        return pairs

    # -- the ONE fused program ------------------------------------------------
    def _make_fused_local(sub_buckets, with_checksum):
        """Per-device body over a bucket subset: build each fused buffer,
        exchange / encode parity, and fold the handshake checksum — one
        program for the whole state (``sub_buckets=buckets``), or one per
        bucket for the double-buffered staging chunks."""
        def _fused_local(*local_leaves):
            from repro.kernels import ops as kops
            from repro.kernels import ref as kref

            by_leaf = dict(
                zip([i for b in sub_buckets for i in b.leaf_idx], local_leaves)
            )
            out: dict[str, Any] = {}
            checksum_acc = jnp.zeros((2,), jnp.uint32) if with_checksum else None
            for bi, bucket in enumerate(sub_buckets):
                parts = [_to_u32_local(by_leaf[i]) for i in bucket.leaf_idx]
                buf = jnp.concatenate(parts) if parts else jnp.zeros(0, jnp.uint32)
                if buf.shape[0] < bucket.words:
                    buf = jnp.pad(buf, (0, bucket.words - buf.shape[0]))
                axis = bucket.axis

                if with_checksum:
                    c = kref.checksum(buf)
                    c = jax.lax.psum(c, bucket.axes) if bucket.axes else c
                    checksum_acc = checksum_acc * jnp.uint32(1000003) + c * jnp.uint32(bi + 1)

                if compress:
                    flatf = jnp.concatenate(
                        [by_leaf[i].reshape(-1).astype(jnp.float32) for i in bucket.leaf_idx]
                    )
                    pad = (-flatf.shape[0]) % 256
                    if pad:
                        flatf = jnp.pad(flatf, (0, pad))
                    q, s = kref.quantize_blockwise(flatf, 256)
                    q = jax.lax.ppermute(q, axis, _copy_pairs(axis))
                    s = jax.lax.ppermute(s, axis, _copy_pairs(axis))
                    out.setdefault("partner", {})[bucket.tag] = {"q": q, "scale": s}
                    continue

                if not striped:
                    out.setdefault("partner", {})[bucket.tag] = jax.lax.ppermute(
                        buf, axis, _copy_pairs(axis)
                    )
                    continue

                # -- on-device codec encode (before any host DMA) ------------
                g = parity_group
                size = mesh.shape[axis]
                idx = jax.lax.axis_index(axis)
                gi = idx // g
                pos = idx % g
                n_full_groups = size // g
                k_local = jnp.where(gi < n_full_groups, g, size - n_full_groups * g)
                # ring-collect the group's buffers: slot t = member (pos+t) mod k
                slots = [buf]
                cur = buf
                ring = _ring_pairs(axis, g)
                for _t in range(1, g):
                    cur = jax.lax.ppermute(cur, axis, ring)
                    slots.append(cur)
                stacked = jnp.stack(slots)                      # (g, words)
                # canonical member order + zero rows past a ragged group's size
                order = (jnp.arange(g) - pos) % jnp.maximum(k_local, 1)
                canonical = _take_rows(stacked, order)
                canonical = jnp.where(
                    (jnp.arange(g) < k_local)[:, None], canonical, jnp.uint32(0)
                )
                # Pallas encode: XOR chain or GF(2^8) matmul. The zero rows
                # past a ragged group's k_local make the full-width generator
                # bit-identical to the host's coef[:, :k'] slice (0·x = 0).
                if codec == "xor":
                    blobs = kops.xor_reduce(canonical)[None, :]  # (1, words)
                else:
                    if codec == "lrc":
                        from repro.core.codec import lrc_generator

                        gen = lrc_generator(g, lrc_locals, rs_parity)
                    else:
                        from repro.core import gf256

                        gen = gf256.cauchy_matrix(rs_parity, g)
                    coefs = tuple(tuple(int(c) for c in row) for row in gen)
                    blobs = kops.gf256_matmul(canonical, coefs)  # (m, words)
                if emit_full_blobs:
                    out.setdefault("parity_full", {})[bucket.tag] = blobs
                    continue
                # Route each blob to its holder group; every holder member
                # receives the whole blob (in ceil(k_h/k_o) unique-source
                # permute rounds — see _route_pairs) and keeps its
                # round-robin stripe slots s = pos + j·k_mine (j < S),
                # masked past g. Divisible worlds: one round, S = 1,
                # s = pos — the legacy single stripe.
                sw = bucket.words // g
                n_slots = _stripe_slots(axis)
                ng = -(-size // g)
                stripes = []
                for b in range(n_parity):
                    rounds = []
                    for rnd in range(n_slots):
                        pr = _route_pairs(axis, g, b, rnd)
                        rounds.append(
                            jax.lax.ppermute(blobs[b], axis, pr)
                            if pr else jnp.zeros_like(blobs[b])
                        )
                    # my ORIGIN group (whose blob I hold) sets my round —
                    # the inverse of blob_holder_group's skip-self shift
                    # c = b mod (ng-1): holder h = o + 1 + c (mod ng)
                    o = (gi - 1 - b % max(ng - 1, 1)) % ng
                    k_o = jnp.maximum(
                        jnp.where(o < n_full_groups, g, size - n_full_groups * g), 1
                    )
                    routed = jax.lax.dynamic_slice(
                        jnp.stack(rounds),
                        (jnp.minimum(pos // k_o, n_slots - 1), 0),
                        (1, bucket.words),
                    )[0]
                    slots_out = []
                    for j in range(n_slots):
                        s = pos + j * k_local
                        piece = jax.lax.dynamic_slice(
                            routed, (jnp.minimum(s, g - 1) * sw,), (sw,)
                        )
                        slots_out.append(jnp.where(s < g, piece, jnp.uint32(0)))
                    stripes.append(jnp.concatenate(slots_out))
                out.setdefault("parity", {})[bucket.tag] = jnp.stack(stripes)
            if with_checksum:
                out["checksum"] = checksum_acc
            return out

        return _fused_local

    def _fused_specs(sub_buckets, with_checksum) -> tuple[Any, Any]:
        in_specs = tuple(
            P(*_full_rank(leaves_ps[i], len(leaves_sds[i].shape)))
            for b in sub_buckets
            for i in b.leaf_idx
        )
        out_specs: dict[str, Any] = {}
        for bucket in sub_buckets:
            sharded = P(bucket.axes) if bucket.axes else P(None)
            if compress:
                out_specs.setdefault("partner", {})[bucket.tag] = {
                    "q": sharded, "scale": sharded,
                }
            elif not striped:
                out_specs.setdefault("partner", {})[bucket.tag] = sharded
            elif emit_full_blobs:
                out_specs.setdefault("parity_full", {})[bucket.tag] = (
                    P(None, bucket.axes) if bucket.axes else P(None, None)
                )
            else:
                out_specs.setdefault("parity", {})[bucket.tag] = (
                    P(None, bucket.axes) if bucket.axes else P(None, None)
                )
        if with_checksum:
            out_specs["checksum"] = P()
        return in_specs, out_specs

    def _fused_args(leaves, sub_buckets):
        args = []
        for b in sub_buckets:
            for i in b.leaf_idx:
                x = leaves[i]
                target = padded_shapes[i]
                if target != tuple(x.shape):
                    x = jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, target)])
                args.append(x)
        return args

    def snapshot_fn(state):
        leaves = treedef.flatten_up_to(state)
        payload: dict[str, Any] = {}
        if include_own_copy:
            # Explicit copies: the snapshot must survive mutation of the live
            # state (XLA cannot alias these outputs to the inputs).
            payload["own"] = treedef.unflatten([jnp.copy(x) for x in leaves])
        if buckets:
            in_specs, out_specs = _fused_specs(buckets, validate)
            # Pallas calls carry no varying-axes rule, so the striped
            # (on-device-encode) program opts out of the check; its outputs
            # are fully varying anyway.
            fn = jax.shard_map(
                _make_fused_local(buckets, validate),
                mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=not striped,
            )
            payload.update(fn(*_fused_args(leaves, buckets)))
        elif validate:
            payload["checksum"] = jnp.zeros((2,), jnp.uint32)
        return payload

    # -- per-chunk programs for double-buffered D2H staging -------------------
    # Chunk 0 is the own-copy snapshot (pure DMA payload, no collective);
    # chunk i+1 runs bucket i's fused exchange/encode. staged_snapshot_fetch
    # dispatches chunk g+1 while chunk g's outputs D2H-copy in the
    # background, so the encode of stripe g+1 hides the DMA of stripe g.
    # The handshake checksum is not folded into the chunked programs — the
    # staged path recomputes it host-side over the fetched bytes.
    def _make_chunk_fn(bucket):
        in_specs, out_specs = _fused_specs([bucket], False)
        fused = jax.jit(  # built + jitted once: chunk calls hit the jit cache
            jax.shard_map(
                _make_fused_local([bucket], False),
                mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=not striped,
            )
        )

        def chunk_fn(state):
            leaves = treedef.flatten_up_to(state)
            return fused(*_fused_args(leaves, [bucket]))
        return chunk_fn

    snapshot_chunk_fns: list[Any] = []
    if include_own_copy:
        _own_copy = jax.jit(lambda state: {"own": jax.tree.map(jnp.copy, state)})
        snapshot_chunk_fns.append(_own_copy)
    snapshot_chunk_fns.extend(_make_chunk_fn(b) for b in buckets)

    # -- restore: one inverse program (full-copy codec only) ------------------
    def _restore_local(*partner_bufs):
        outs = []
        for bucket, buf in zip(buckets, partner_bufs):
            buf = jax.lax.ppermute(
                buf, bucket.axis,
                dist.inverse_perm(_copy_pairs(bucket.axis)),
            )
            for i, off in zip(bucket.leaf_idx, bucket.word_offsets):
                words = _leaf_words(local_shapes[i], leaves_sds[i].dtype.itemsize)
                leaf = _from_u32_local(
                    buf[off : off + words],
                    np.dtype(leaves_sds[i].dtype),
                    local_shapes[i],
                )
                # Re-replicate over axes the leaf doesn't vary on (the fused
                # buffer varies on the bucket union): numerically the copies
                # are identical; all_gather[0] makes it explicit. The rep
                # checker cannot prove this — hence check_vma=False below.
                leaf_axes: set[str] = set()
                for e in _full_rank(leaves_ps[i], len(leaves_sds[i].shape)):
                    leaf_axes.update(_axes_of(e))
                for a in bucket.axes:
                    if a not in leaf_axes:
                        leaf = jax.lax.all_gather(leaf, a)[0]
                outs.append(leaf)
        return tuple(outs)

    def restore_fn(payload):
        """Re-align partner copies to their origin coordinates (used by spare
        substitution; survivor restore is local and needs no program). Striped
        and compressed payloads reconstruct host-side through the codec."""
        partner = payload.get("partner")
        assert partner is not None and not compress and not striped, (
            "only full-copy uncompressed payloads restore on device; parity "
            "reconstruction is host-side (codec.decode)"
        )
        in_specs = tuple(
            P(b.axes) if b.axes else P(None) for b in buckets
        )
        out_specs = tuple(
            P(*_full_rank(leaves_ps[i], len(leaves_sds[i].shape)))
            for b in buckets
            for i in b.leaf_idx
        )
        fn = jax.shard_map(
            _restore_local, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        outs = fn(*[partner[b.tag] for b in buckets])
        result = {}
        pos = 0
        for b in buckets:
            for i in b.leaf_idx:
                y = outs[pos]
                pos += 1
                orig = leaves_sds[i].shape
                if tuple(y.shape) != tuple(orig):
                    y = y[tuple(slice(0, s) for s in orig)]
                result[str(i)] = y
        return result

    in_shardings = treedef.unflatten(
        [NamedSharding(mesh, ps) for ps in leaves_ps]
    )

    return SnapshotProgram(
        snapshot_fn=snapshot_fn,
        restore_fn=restore_fn,
        in_shardings=in_shardings,
        out_shardings=None,
        exchanged_names=tuple(str(i) for i in exchanged_idx),
        exchanged_bytes=exchanged_bytes,
        own_bytes=own_bytes,
        buckets=tuple(buckets),
        pcie_bytes=pcie_bytes,
        codec=codec,
        parity_group=parity_group,
        snapshot_chunk_fns=tuple(snapshot_chunk_fns),
    )


# ---------------------------------------------------------------------------
# Double-buffered device staging (create path)
# ---------------------------------------------------------------------------

#: Payload floor (modeled D2H bytes) below which the double-buffered staging
#: path loses to the sequential fetch: per-chunk async-copy dispatch and the
#: deferred merge pass are fixed costs, and under this payload they exceed
#: the DMA time the overlap could hide (same crossover shape as the restore
#: planner's sync collapse, DESIGN.md §14). Overridable for odd hosts via
#: REPRO_D2H_DBUF_MIN_BYTES.
_DBUF_MIN_BYTES = int(os.environ.get("REPRO_D2H_DBUF_MIN_BYTES", 32 << 20))


def staged_snapshot_fetch(
    prog: SnapshotProgram,
    state: Any,
    *,
    double_buffer: bool | None = None,
    skip_chunks: Any = None,
    prev_chunks: list | None = None,
    return_chunks: bool = False,
) -> Any:
    """Drive the snapshot's D2H staging through the per-chunk programs:
    dispatch chunk *g+1*'s fused encode, then start chunk *g*'s asynchronous
    device→host copy (``copy_to_host_async``) — the DMA of stripe *g*
    overlaps the on-device encode of stripe *g+1*, so staging wall time
    approaches max(encode, DMA) instead of their sum. ``double_buffer=False``
    fetches each chunk synchronously before dispatching the next — the A/B
    baseline the staging benchmark reports the overlap win against.
    ``double_buffer=None`` (the default) picks per payload: overlap only when
    the program's modeled D2H bytes clear ``_DBUF_MIN_BYTES``, else the
    fixed per-chunk overlap costs outweigh the DMA they could hide.

    Returns the host (numpy) payload, merged across chunks — byte-identical
    to fetching ``prog.snapshot_fn``'s payload minus the folded checksum
    (the staged path recomputes the handshake checksum host-side).

    Dirty-aware staging (DESIGN.md §17): ``skip_chunks`` names chunk indices
    whose state the caller's dirty map proved unchanged since the previous
    capture; those programs are neither dispatched nor fetched — the
    corresponding entry of ``prev_chunks`` (the prior call's host-resident
    chunk payloads, obtained via ``return_chunks=True``) is reused verbatim,
    so D2H bytes scale with *change* instead of state size. A skip entry
    without a usable previous chunk falls back to a normal fetch. With
    ``return_chunks=True`` the call returns ``(payload, host_chunks)``;
    feed ``host_chunks`` back as the next call's ``prev_chunks``.
    """
    if double_buffer is None:
        double_buffer = prog.pcie_bytes >= _DBUF_MIN_BYTES
    skip = set(skip_chunks) if skip_chunks is not None else set()
    fetched: list[Any] = []
    reused: set[int] = set()
    for i, fn in enumerate(prog.snapshot_chunk_fns):
        if (
            i in skip
            and prev_chunks is not None
            and i < len(prev_chunks)
            and prev_chunks[i] is not None
        ):
            # Host bytes of the unchanged chunk, from the previous capture:
            # no device dispatch, no D2H.
            fetched.append(prev_chunks[i])
            reused.add(i)
            continue
        with _TR.span("d2h_dispatch", chunk=i, double_buffer=double_buffer):
            out = fn(state)  # async dispatch: the device starts this chunk's encode
            if double_buffer:
                for x in jax.tree.leaves(out):
                    x.copy_to_host_async()  # D2H queued behind the chunk's compute
                fetched.append(out)
            else:
                fetched.append(jax.tree.map(np.asarray, out))  # blocking fetch
    payload: dict[str, Any] = {}
    host_chunks: list[Any] = []
    for i, out in enumerate(fetched):
        if double_buffer and i not in reused:
            with _TR.span("d2h_merge", chunk=i):
                out = jax.tree.map(np.asarray, out)  # already host-resident
        host_chunks.append(out)
        for key, val in out.items():
            if isinstance(val, dict) and isinstance(payload.get(key), dict):
                payload[key].update(val)
            elif isinstance(val, dict):
                # Copy on first merge: the payload must never alias a chunk
                # dict — reused prev_chunks entries are cached across calls,
                # and a later chunk's update() would scribble into the cache.
                payload[key] = dict(val)
            else:
                payload[key] = val
    if return_chunks:
        return payload, host_chunks
    return payload


# ---------------------------------------------------------------------------
# Hot-replica mirror program (DESIGN.md §15)
# ---------------------------------------------------------------------------

@_traced("build_mirror_program")
def build_mirror_program(
    mesh: Mesh,
    state_sds: Any,
    state_pspecs: Any,
    *,
    replica_axis: str = "data",
    validate: bool = True,
) -> SnapshotProgram:
    """Mirror variant of the fused snapshot program: the same per-(failure
    axis, dtype) uint32 buckets, but routed to the hot-replica *shadow mesh*
    instead of a parity group. ``replica_axis`` is modeled as primary half +
    shadow half (teams of T = axis/2 coordinates); one collective permute
    per bucket lands every primary coordinate's fused live state on its
    shadow twin at ``i + T`` — the transport a deployed ``ReplicaTeam`` uses
    for its lazy sync instead of the host-side payload copy the
    single-process simulation performs (runtime/replica.py).

    No parity, no own copy, no compression: the shadow receives the primary's
    shards verbatim (the replication rung is a full copy by definition — the
    codec ladder below it provides the erasure coding). ``snapshot_fn`` emits
    ``{"mirror": {tag: fused buffer}}`` (+ the folded handshake checksum when
    ``validate``), where each shadow device's slice of ``mirror[tag]`` holds
    its primary twin's fused bucket, unpackable with the bucket's
    ``word_offsets`` exactly like a partner payload.
    """
    prog = build_snapshot_program(
        mesh, state_sds, state_pspecs,
        redundancy_axis=replica_axis, scheme="mirror",
        include_own_copy=False, compress=False, validate=validate,
        codec="copy",
    )
    inner = prog.snapshot_fn

    def mirror_fn(state):
        payload = inner(state)
        if "partner" in payload:
            payload["mirror"] = payload.pop("partner")
        return payload

    return replace(prog, snapshot_fn=mirror_fn)


# ---------------------------------------------------------------------------
# Fused striped RESTORE program — the mirror image of the on-device encode
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StripedRestoreProgram:
    """Jit-able fused reconstruction for striped codecs + metadata.

    ``restore_fn(state, parity, decode_rows, survivor_mask)`` rebuilds every
    failed coordinate's shards ON DEVICE and returns origin-aligned leaves
    (same convention as ``SnapshotProgram.restore_fn``). ``decode_rows`` /
    ``survivor_mask`` are runtime arrays per failure axis (host-precomputed
    by :func:`striped_decode_rows`), so ONE compiled program serves every
    failure combination — the erasure solve happens on the tiny coefficient
    matrix host-side, the byte passes run through the runtime-coefficient
    GF(2^8) Pallas kernel (kernels/rs_decode.py).
    """

    restore_fn: Any
    buckets: tuple[FusedBucket, ...]
    pcie_bytes: int            # uploads: survivor shards + held stripes
    host_decode_pcie_bytes: int  # the host-decode alternative's PCIe bill
    codec: str
    parity_group: int
    rs_parity: int
    axes: tuple[str, ...]      # failure axes needing decode_rows/mask entries
    n_parity: int              # stripe rows per device (codec blobs)
    stripe_words: tuple[tuple[str, int], ...]  # tag -> per-device stripe words


def striped_decode_rows(
    axis_size: int,
    parity_group: int,
    codec: str,
    rs_parity: int,
    failed: set[int] | tuple[int, ...],
    lrc_locals: int = 2,
) -> tuple[np.ndarray, np.ndarray]:
    """Host precompute for the device restore program: per failure-axis
    coordinate, ONE decode row over the ``g + m`` canonical input slots
    ``[group data 0..g-1, blobs 0..m-1]``.

    Survivors get their one-hot identity row (the program then passes their
    own fused buffer through); each failed coordinate gets its row of
    ``gf256.erasure_decode_matrix`` — the e×e submatrix inversion folded
    with the generator, computed here by Gaussian elimination once per
    failure group. For ``codec="lrc"`` the generator is the shared
    Azure-LRC construction and row selection runs the codec's own
    cheapest-invertible-combination search, so a single failure solves
    through ONE local parity row (the zero data coefficients then cost
    nothing on device — 0·x byte passes). Ragged worlds are first-class:
    the short last group simply contributes fewer present columns, exactly
    like the host codec's ``coef[:, :k']`` slice.

    Returns ``(rows (size, g+m) uint32, mask (ng·g,) uint32)`` — the mask is
    padded to whole groups (zeros past ``axis_size``) so the device program
    can slice per-group windows; raises ``ValueError`` when the failure set
    exceeds the codec's tolerance or destroys the blobs needed to cover it
    (mirroring ``codec_recovery_plan``).
    """
    from repro.core import gf256

    assert codec in ("xor", "rs", "lrc"), codec
    g = parity_group
    helper = None
    if codec == "xor":
        coef = np.ones((1, g), np.uint8)
    elif codec == "rs":
        coef = gf256.cauchy_matrix(rs_parity, g)
    else:
        from repro.core import codec as codec_mod

        helper = codec_mod.LRCCodec(g, lrc_locals, rs_parity)
        coef = helper.coef
    m = coef.shape[0]
    failed = set(failed)
    groups = dist.parity_groups(axis_size, g)
    ng = len(groups)
    rows = np.zeros((axis_size, g + m), np.uint8)
    mask = np.zeros(ng * g, np.uint32)
    mask[:axis_size] = 1
    for r in failed:
        mask[r] = 0
    for gi, grp in enumerate(groups):
        missing = [q for q, r in enumerate(grp.members) if r in failed]
        present = [q for q in range(len(grp.members)) if q not in missing]
        for q in present:
            rows[grp.members[q], q] = 1
        if not missing:
            continue
        tolerance = rs_parity if codec == "lrc" else m
        if codec != "lrc" and len(missing) > tolerance:
            raise ValueError(
                f"group {gi} lost {len(missing)} members; "
                f"codec {codec!r} tolerates {tolerance}"
            )
        # A blob is usable iff every holder of its stripes survives.
        usable = [
            b for b in range(m)
            if all(
                h not in failed
                for h in groups[dist.blob_holder_group(ng, gi, b)].members
            )
        ]
        if codec == "lrc":
            from repro.core import codec as codec_mod

            try:
                sel = helper._decode_rows(sorted(usable), missing, present)
            except codec_mod.CodecDecodeError as exc:
                raise ValueError(str(exc)) from exc
        else:
            if len(usable) < len(missing):
                raise ValueError(
                    f"group {gi}: {len(missing)} losses but only "
                    f"{len(usable)} intact redundancy blobs (codec {codec!r})"
                )
            sel = usable[: len(missing)]
        D = gf256.erasure_decode_matrix(g, coef, present, sel, missing)
        for t, q in enumerate(missing):
            rows[grp.members[q]] = D[t]
    return rows.astype(np.uint32), mask


@_traced("build_striped_restore_program")
def build_striped_restore_program(
    mesh: Mesh,
    state_sds: Any,
    state_pspecs: Any,
    *,
    redundancy_axis: str = "data",
    codec: str = "xor",
    parity_group: int = 1,
    rs_parity: int = 2,
    lrc_locals: int = 2,
) -> StripedRestoreProgram:
    """The fused inverse of the striped snapshot program (DESIGN.md §10/§16).

    Survivors H2D-upload their own shards and the parity stripes they hold;
    everything else happens on device inside ONE ``shard_map``: a ring pass
    inside each holder group reassembles every blob from its round-robin
    stripes, one permute routes the blob home to its origin group, a second
    ring collects the group's (mask-zeroed) data buffers, and every
    coordinate applies its runtime decode row with the GF(2^8) Pallas
    kernels — so PCIe carries stripes instead of fully decoded partner
    copies and the reconstruction FLOPs move off the host. Bit-identical to
    host ``codec.decode`` (the erasure solution is unique). Ragged worlds
    (g ∤ axis) and the LRC codec are first-class: the short group holds
    extra stripe slots, and an LRC single-failure decode row has zero
    coefficients outside its local subgroup.
    """
    assert codec in ("xor", "rs", "lrc"), codec
    assert parity_group >= 1
    n_parity = {
        "xor": 1, "rs": rs_parity,
        "lrc": min(lrc_locals, parity_group) + rs_parity,
    }[codec]
    g = parity_group

    # Same bucketing as the snapshot program (must agree exactly: the parity
    # payload this program consumes is the one the snapshot emitted).
    snap = build_snapshot_program(
        mesh, state_sds, state_pspecs,
        redundancy_axis=redundancy_axis, include_own_copy=False,
        validate=False, codec=codec, parity_group=parity_group,
        rs_parity=rs_parity, lrc_locals=lrc_locals, emit_full_blobs=False,
    )
    buckets = snap.buckets
    leaves_sds, treedef = jax.tree.flatten(state_sds)
    leaves_ps = treedef.flatten_up_to(state_pspecs)
    padded_shapes = {
        i: _pad_shape(leaves_sds[i].shape, leaves_ps[i], mesh)
        for b in buckets for i in b.leaf_idx
    }
    local_shapes = {
        i: _local_shape(padded_shapes[i], leaves_ps[i], mesh)
        for b in buckets for i in b.leaf_idx
    }
    axes = tuple(sorted({b.axis for b in buckets}))

    def _ring_pairs(axis: str) -> list[tuple[int, int]]:
        size = mesh.shape[axis]
        groups = dist.parity_groups(size, g)
        pairs = []
        for grp in groups:
            k = len(grp.members)
            for q, member in enumerate(grp.members):
                pairs.append((grp.members[(q + 1) % k], member))
        return pairs

    def _home_pairs(axis: str, b: int, rnd: int) -> list[tuple[int, int]]:
        """Round ``rnd`` of routing each origin group's reassembled blob b
        home. Every origin member needs the blob, ppermute sources must be
        unique, so a short holder group reaches a larger origin group in
        ceil(k_o/k_h) rounds: round rnd covers origin positions
        q = rnd·k_h + i (receiver q selects round q // k_h). Divisible
        worlds: one round."""
        size = mesh.shape[axis]
        groups = dist.parity_groups(size, g)
        pairs = []
        for gi, grp in enumerate(groups):
            holder = groups[dist.blob_holder_group(len(groups), gi, b)]
            k_h = len(holder.members)
            for i in range(k_h):
                q = rnd * k_h + i
                if q < len(grp.members):
                    pairs.append((holder.members[i], grp.members[q]))
        return pairs

    def _stripe_slots(axis: str) -> int:
        groups = dist.parity_groups(mesh.shape[axis], g)
        return max(-(-g // len(grp.members)) for grp in groups)

    def _restore_local(*flat_args):
        from repro.kernels import ops as kops

        n_leaf_args = sum(len(b.leaf_idx) for b in buckets)
        leaf_args = flat_args[:n_leaf_args]
        parity_args = flat_args[n_leaf_args : n_leaf_args + len(buckets)]
        tail = flat_args[n_leaf_args + len(buckets):]
        rows_by_axis = dict(zip(axes, tail[: len(axes)]))
        mask_by_axis = dict(zip(axes, tail[len(axes):]))
        by_leaf = dict(
            zip([i for b in buckets for i in b.leaf_idx], leaf_args)
        )

        outs = []
        for bucket, parity_local in zip(buckets, parity_args):
            axis = bucket.axis
            rows_arr = rows_by_axis[axis]
            mask_arr = mask_by_axis[axis]
            size = mesh.shape[axis]
            n_full = size // g
            idx = jax.lax.axis_index(axis)
            gi = idx // g
            pos = idx % g
            sw = bucket.words // g
            # This coordinate's own group size (the last group may be short).
            k_mine = jnp.maximum(
                jnp.where(gi < n_full, g, size - n_full * g), 1
            )
            ring = _ring_pairs(axis)

            # -- reassemble the m blobs this group HOLDS, then route home -----
            blob_rows = []
            for b in range(n_parity):
                # 1. ring-collect my (holder-)group's stripe buffers: slot t
                #    holds member (pos+t) mod k_mine's round-robin stripes.
                mine = parity_local[b]                      # (S·sw,)
                slots = [mine]
                cur = mine
                for _t in range(1, g):
                    cur = jax.lax.ppermute(cur, axis, ring)
                    slots.append(cur)
                stacked = jnp.stack(slots)                  # (g, S·sw)
                order = (jnp.arange(g) - pos) % k_mine
                canon = _take_rows(stacked, order)          # row c = member c
                # 2. splice the full blob: stripe s lives at member s mod
                #    k_mine, slot s // k_mine (divisible worlds: member s,
                #    slot 0 — the legacy layout).
                pieces = []
                for s in range(g):
                    row = jax.lax.dynamic_slice(
                        canon, (s % k_mine, (s // k_mine) * sw), (1, sw)
                    )
                    pieces.append(row[0])
                full = jnp.concatenate(pieces)              # (words,)
                # 3. route home (ceil(k_o/k_h) unique-source rounds): after
                #    _home_pairs every coordinate holds blob b of its OWN
                #    group; my blob-b HOLDER group's size sets my round.
                n_slots = _stripe_slots(axis)
                ng = -(-size // g)
                rounds = []
                for rnd in range(n_slots):
                    pr = _home_pairs(axis, b, rnd)
                    rounds.append(
                        jax.lax.ppermute(full, axis, pr)
                        if pr else jnp.zeros_like(full)
                    )
                # blob_holder_group's skip-self shift: h = gi + 1 + c (mod ng)
                h = (gi + 1 + b % max(ng - 1, 1)) % ng
                k_h = jnp.maximum(
                    jnp.where(h < n_full, g, size - n_full * g), 1
                )
                blob_rows.append(
                    jax.lax.dynamic_slice(
                        jnp.stack(rounds),
                        (jnp.minimum(pos // k_h, n_slots - 1), 0),
                        (1, bucket.words),
                    )[0]
                )

            # -- ring-collect the group's (mask-zeroed) data buffers ----------
            parts = [_to_u32_local(by_leaf[i]) for i in bucket.leaf_idx]
            buf = jnp.concatenate(parts) if parts else jnp.zeros(0, jnp.uint32)
            if buf.shape[0] < bucket.words:
                buf = jnp.pad(buf, (0, bucket.words - buf.shape[0]))
            buf = buf * jax.lax.dynamic_slice(mask_arr, (idx,), (1,))[0]
            slots = [buf]
            cur = buf
            for _t in range(1, g):
                cur = jax.lax.ppermute(cur, axis, ring)
                slots.append(cur)
            stacked = jnp.stack(slots)
            order = (jnp.arange(g) - pos) % k_mine
            canonical = _take_rows(stacked, order)         # (g, words)
            canonical = jnp.where(
                (jnp.arange(g) < k_mine)[:, None], canonical, jnp.uint32(0)
            )
            group_mask = jax.lax.dynamic_slice(mask_arr, (gi * g,), (g,))
            canonical = canonical * group_mask[:, None]

            # -- apply this coordinate's decode row (runtime coefficients) ----
            inputs = jnp.concatenate([canonical, jnp.stack(blob_rows)])  # (g+m, words)
            my_row = jax.lax.dynamic_slice(rows_arr, (idx, 0), (1, g + n_parity))
            rebuilt = kops.gf256_matmul_dyn(inputs, my_row)[0]           # (words,)

            # -- unpack the fused buffer back into origin-aligned leaves ------
            for i, off in zip(bucket.leaf_idx, bucket.word_offsets):
                words = _leaf_words(local_shapes[i], leaves_sds[i].dtype.itemsize)
                leaf = _from_u32_local(
                    rebuilt[off : off + words],
                    np.dtype(leaves_sds[i].dtype),
                    local_shapes[i],
                )
                leaf_axes: set[str] = set()
                for e in _full_rank(leaves_ps[i], len(leaves_sds[i].shape)):
                    leaf_axes.update(_axes_of(e))
                for a in bucket.axes:
                    if a not in leaf_axes:
                        leaf = jax.lax.all_gather(leaf, a)[0]
                outs.append(leaf)
        return tuple(outs)

    # One program, compiled once: decode_rows / survivor_mask are runtime
    # inputs, so the same executable serves EVERY failure combination — the
    # jit wrapper must therefore live at build time (a per-call shard_map
    # would re-trace the whole program for each restore).
    _in_specs = (
        tuple(
            P(*_full_rank(leaves_ps[i], len(leaves_sds[i].shape)))
            for b in buckets for i in b.leaf_idx
        )
        + tuple(
            P(None, b.axes) if b.axes else P(None, None) for b in buckets
        )
        + tuple(P(None) for _ in axes) * 2
    )
    _out_specs = tuple(
        P(*_full_rank(leaves_ps[i], len(leaves_sds[i].shape)))
        for b in buckets for i in b.leaf_idx
    )
    _restore_prog = jax.jit(jax.shard_map(
        _restore_local, mesh=mesh, in_specs=_in_specs, out_specs=_out_specs,
        check_vma=False,
    ))

    def restore_fn(state, parity, decode_rows, survivor_mask):
        """state: the (survivor) state pytree — failed coordinates' shards
        may hold garbage, the mask zeroes them before reconstruction.
        parity: the snapshot payload's ``parity`` dict (uploaded stripes).
        decode_rows / survivor_mask: per-axis arrays from
        ``striped_decode_rows`` (runtime inputs: no recompile per failure).
        Returns {leaf index -> reconstructed full leaf} like
        ``SnapshotProgram.restore_fn``."""
        leaves = treedef.flatten_up_to(state)
        fn = _restore_prog
        args = []
        for b in buckets:
            for i in b.leaf_idx:
                x = leaves[i]
                target = padded_shapes[i]
                if target != tuple(x.shape):
                    x = jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, target)])
                args.append(x)
        args += [parity[b.tag] for b in buckets]
        args += [jnp.asarray(decode_rows[a], jnp.uint32) for a in axes]
        args += [jnp.asarray(survivor_mask[a], jnp.uint32) for a in axes]
        outs = fn(*args)
        result = {}
        pos = 0
        for b in buckets:
            for i in b.leaf_idx:
                y = outs[pos]
                pos += 1
                orig = leaves_sds[i].shape
                if tuple(y.shape) != tuple(orig):
                    y = y[tuple(slice(0, s) for s in orig)]
                result[str(i)] = y
        return result

    # PCIe bill: survivors upload own shards + every held stripe; the
    # host-decode alternative instead downloads stripes + survivor exchange
    # buffers, solves on host, and uploads fully decoded buffers back.
    fused = sum(
        b.words * 4 * int(np.prod([mesh.shape[a] for a in b.axes] or [1]))
        for b in buckets
    )
    stripes_bytes = sum(
        n_parity
        * b.words * 4
        * int(np.prod([mesh.shape[a] for a in b.axes] or [1]))
        * _stripe_slots(b.axis)
        // max(g, 1)
        for b in buckets
    )
    stripe_words = tuple(
        (b.tag, _stripe_slots(b.axis) * (b.words // max(g, 1)))
        for b in buckets
    )
    return StripedRestoreProgram(
        restore_fn=restore_fn,
        buckets=buckets,
        pcie_bytes=fused + stripes_bytes,
        host_decode_pcie_bytes=2 * fused + stripes_bytes,
        codec=codec,
        parity_group=parity_group,
        rs_parity=rs_parity,
        axes=axes,
        n_parity=n_parity,
        stripe_words=stripe_words,
    )


# ---------------------------------------------------------------------------
# Compiled-program cache (DESIGN.md §14) — building a snapshot / striped
# restore program walks the whole state pytree and traces jit programs, so
# repeated engine generations (and the dryrun/benchmark drivers) key the
# result on (topology, state structure, codec, dtype) instead of re-tracing.
# Thread-safe (async-worker pools build programs too) and LRU-bounded.
# ---------------------------------------------------------------------------

_PROGRAM_CACHE: OrderedDict = OrderedDict()
_PROGRAM_CACHE_LOCK = threading.Lock()
_PROGRAM_CACHE_MAX = 16
_PROGRAM_CACHE_STATS = {"hits": 0, "misses": 0}


def _program_cache_key(
    kind: str, mesh: Mesh, state_sds: Any, state_pspecs: Any, kw: dict
) -> tuple:
    leaves_sds, treedef = jax.tree.flatten(state_sds)
    leaves_ps = treedef.flatten_up_to(state_pspecs)
    return (
        kind,
        tuple(sorted(mesh.shape.items())),
        tuple(int(d.id) for d in mesh.devices.flat),
        treedef,
        tuple((tuple(sd.shape), sd.dtype.name) for sd in leaves_sds),
        tuple(str(ps) for ps in leaves_ps),
        tuple(sorted(kw.items())),
    )


def _cached_program(kind, builder, mesh, state_sds, state_pspecs, kw):
    key = _program_cache_key(kind, mesh, state_sds, state_pspecs, kw)
    with _PROGRAM_CACHE_LOCK:
        prog = _PROGRAM_CACHE.get(key)
        if prog is not None:
            _PROGRAM_CACHE.move_to_end(key)
            _PROGRAM_CACHE_STATS["hits"] += 1
            return prog
    # Trace outside the lock: builds are slow and independent; a rare
    # duplicate build under contention just overwrites with an equal value.
    prog = builder(mesh, state_sds, state_pspecs, **kw)
    with _PROGRAM_CACHE_LOCK:
        _PROGRAM_CACHE_STATS["misses"] += 1
        _PROGRAM_CACHE[key] = prog
        _PROGRAM_CACHE.move_to_end(key)
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.popitem(last=False)
    return prog


def cached_snapshot_program(
    mesh: Mesh, state_sds: Any, state_pspecs: Any, **kw: Any
) -> SnapshotProgram:
    """``build_snapshot_program`` through the bounded program cache."""
    return _cached_program(
        "snapshot", build_snapshot_program, mesh, state_sds, state_pspecs, kw
    )


def cached_striped_restore_program(
    mesh: Mesh, state_sds: Any, state_pspecs: Any, **kw: Any
) -> StripedRestoreProgram:
    """``build_striped_restore_program`` through the bounded program cache."""
    return _cached_program(
        "striped_restore", build_striped_restore_program,
        mesh, state_sds, state_pspecs, kw,
    )


def program_cache_stats() -> dict[str, int]:
    with _PROGRAM_CACHE_LOCK:
        return dict(_PROGRAM_CACHE_STATS, size=len(_PROGRAM_CACHE))


def program_cache_clear() -> None:
    with _PROGRAM_CACHE_LOCK:
        _PROGRAM_CACHE.clear()
        _PROGRAM_CACHE_STATS.update(hits=0, misses=0)
