"""Train/serve state as distributed checkpoint entities.

``ShardedStateEntity`` adapts a live jax state pytree to the engine's
DistributedEntity protocol: snapshot shards are numpy slices along each
leaf's failure-domain (data-axis) dimension — the per-host addressable shards
a real multi-host job would serialize. Leaves with no data-sharded dim are
replicated to every rank (every host owns a copy, like waLBerla's globally
known metadata).

The slicing plan derives from the *production* PartitionSpecs computed on an
AbstractMesh, so single-process CPU tests exercise exactly the distribution
semantics of the 512-chip job.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.serialization import LeafSlice
from repro.obs.trace import tracer

_TR = tracer()

DATA_AXES = ("pod", "data")


def _data_dim(pspec: P, ndim: int) -> int | None:
    """First dim sharded over a failure-domain axis, or None."""
    entries = list(pspec) + [None] * (ndim - len(pspec))
    for i, e in enumerate(entries[:ndim]):
        axes = (e,) if isinstance(e, str) else tuple(e or ())
        if any(a in DATA_AXES for a in axes):
            return i
    return None


@dataclass
class ShardPlan:
    """Per-leaf split dimension (None = replicated) + global shapes."""

    dims: list[int | None]
    shapes: list[tuple[int, ...]]
    treedef: Any

    @classmethod
    def from_pspecs(cls, sds_tree: Any, pspec_tree: Any) -> "ShardPlan":
        leaves, treedef = jax.tree.flatten(sds_tree)
        pspecs = treedef.flatten_up_to(pspec_tree)
        dims = [_data_dim(ps, len(sd.shape)) for sd, ps in zip(leaves, pspecs)]
        shapes = [tuple(sd.shape) for sd in leaves]
        return cls(dims, shapes, treedef)

    def split_dim(self, i: int, n_ranks: int) -> int | None:
        """Effective split dim for leaf i (None = replicated to every rank)."""
        d = self.dims[i]
        if d is None or self.shapes[i][d] % n_ranks != 0:
            return None
        return d

    def shard_coords(self, n_ranks: int) -> list[list[LeafSlice]]:
        """Global-coordinate manifest: per rank, each leaf's slice of the
        logical entity. ``axis`` records the leaf's failure-domain dim even
        when ``n_ranks`` does not divide it (the shard then holds the full
        range) — the elastic planner uses that to re-split on a world size
        that does divide."""
        out: list[list[LeafSlice]] = []
        for r in range(n_ranks):
            coords: list[LeafSlice] = []
            for i, shape in enumerate(self.shapes):
                d = self.dims[i]
                if d is None:
                    coords.append(LeafSlice(shape, None, 0, 1))
                    continue
                g = shape[d]
                eff = self.split_dim(i, n_ranks)
                if eff is None:
                    coords.append(LeafSlice(shape, d, 0, g))
                else:
                    rows = g // n_ranks
                    coords.append(LeafSlice(shape, d, r * rows, (r + 1) * rows))
            out.append(coords)
        return out


def _word_lanes(dtype: Any) -> int | None:
    """Elements of ``dtype`` in a 32-bit word (4, 2 or 1), or None for a
    dtype the device sum does not read: wider than a word, or packed below a
    byte on the device."""
    bits = jax.dtypes.itemsize_bits(dtype)
    return 32 // bits if bits in (8, 16, 32) else None


def _device_summable(x: Any, dim: int | None, n: int) -> bool:
    """Whether leaf ``x`` is summed on the device: a device array of a
    dtype :func:`_word_lanes` reads, whose piece boundaries fall on whole
    words of the piece (``_piece_sums`` shifts each piece by whole words)."""
    if not isinstance(x, jax.Array):
        return False
    m = _word_lanes(x.dtype)
    if m is None or x.size >= 1 << 32:
        return False
    if dim is None:
        return True
    block = int(np.prod(x.shape[dim:], dtype=np.int64)) // n
    return block % m == 0


def _piece_sums(x: jax.Array, dim: int | None, n: int) -> jax.Array:
    """(2, pieces) uint32: ``np_checksum``'s (s1, s2) of each piece the pack
    writes of leaf ``x``, as if the piece began at word 0. The pieces are
    the ``n`` blocks on ``dim`` in C order, or the whole leaf (``dim`` None).

    An element of e bytes at index i of its piece fills lane ``i mod (4/e)``
    of word ``i // (4/e)`` (little-endian, as the host reads the bytes), so
    the sums are one fused reduction over the leaf as it lies: no copy, no
    padding, no relayout."""
    shape = x.shape
    e = x.dtype.itemsize
    m = 4 // e
    if x.dtype == jnp.bool_:
        u = x.astype(jnp.uint32)
    else:
        u = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * e}")).astype(jnp.uint32)
    # Element index in the piece's C order, with the split dim counted from
    # the leaf's start: piece r reads ``r * block`` higher, corrected below.
    idx = jnp.zeros(shape, jnp.uint32)
    stride, block = 1, 0
    for k in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, k) * jnp.uint32(stride)
        stride *= shape[k] // n if k == dim else shape[k]
        if k == dim:
            block = stride
    if m > 1:
        u = u << ((idx & (m - 1)) * (8 * e)).astype(jnp.uint32)
    wu = ((idx >> (m.bit_length() - 1)) + 1) * u
    if dim is None:
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32), jnp.sum(wu, dtype=jnp.uint32)])[:, None]
    axes = tuple(k for k in range(len(shape)) if k != dim)
    s1 = jnp.sum(u, axis=axes, dtype=jnp.uint32).reshape(n, -1).sum(1, dtype=jnp.uint32)
    s2 = jnp.sum(wu, axis=axes, dtype=jnp.uint32).reshape(n, -1).sum(1, dtype=jnp.uint32)
    s2 = s2 - jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(block // m) * s1
    return jnp.stack([s1, s2])


@partial(jax.jit, static_argnames=("dims", "n"))
def _tree_piece_sums(leaves: list[jax.Array], dims: tuple, n: int) -> jax.Array:
    return jnp.concatenate([_piece_sums(x, d, n) for x, d in zip(leaves, dims)], axis=1)


@dataclass
class PieceSums:
    """The capture's device-computed sums of the pieces a pack writes: one
    (2, columns) uint32 array, still on the device, fetched in one transfer
    by :meth:`per_rank`. ``cols[i]`` is leaf i's first column (None: the
    leaf is summed on the host), ``split[i]`` whether it has a column per
    rank or one for every rank."""

    pairs: jax.Array
    cols: list[int | None]
    split: list[bool]

    def per_rank(self, n_ranks: int) -> list[list[tuple[int, int] | None]]:
        """Per rank, each leaf's (s1, s2) as ``integrity.payload_checksum``
        takes them."""
        s1, s2 = np.asarray(jax.device_get(self.pairs)).tolist()
        return [
            [None if c is None else (s1[c + r * sp], s2[c + r * sp])
             for c, sp in zip(self.cols, self.split)]
            for r in range(n_ranks)
        ]


class ShardedStateEntity:
    """DistributedEntity over a live state accessed via get/set callbacks.

    Exposes ``shard_coords`` (the plan's global-coordinate manifest), which
    the engine attaches to each shard's serialization Manifest — the layer
    the elastic N-to-M restore path repartitions on.

    ``release``, when given, drops the live device state; a restore calls it
    before its first host-to-device transfer, so the device never holds the
    old state and the restored one at once. ``set_state`` receives a tree of
    device arrays.

    ``capture_labels``, when given, maps the live state to further labels of
    the ``capture_d2h`` span (which of its bytes are which); it is called
    only while tracing is on.

    :meth:`capture_shards` also returns the device's checksums of every
    piece the pack will write (:class:`PieceSums`), so the engine's
    capture-time reference is taken over the bytes the device holds.
    """

    def __init__(
        self,
        get_state: Callable[[], Any],
        set_state: Callable[[Any], None],
        plan: ShardPlan,
        release: Callable[[], None] | None = None,
        capture_labels: Callable[[Any], dict[str, int]] | None = None,
    ) -> None:
        self._get = get_state
        self._set = set_state
        self._release = release
        self._labels = capture_labels
        self.plan = plan

    def shard_coords(self, n_ranks: int) -> list[list[LeafSlice]]:
        return self.plan.shard_coords(n_ranks)

    # -- snapshot ------------------------------------------------------------
    def snapshot_shards(self, n_ranks: int) -> list[Any]:
        return self.capture_shards(n_ranks, sums=False)[0]

    def capture_shards(
        self, n_ranks: int, sums: bool = True
    ) -> tuple[list[Any], PieceSums | None]:
        """Per-rank shards, and (``sums``) the device's sums of their pieces:
        one program dispatched over the live device leaves before the D2H,
        its result left on the device. None when no leaf lives there."""
        live = self._get()
        labels = self._labels(live) if self._labels is not None and _TR.enabled else {}
        with _TR.child("capture_d2h", bytes=sum(x.nbytes for x in jax.tree.leaves(live)), **labels):
            piece_sums = self._dispatch_sums(live, n_ranks) if sums else None
            state = jax.device_get(live)
        leaves = self.plan.treedef.flatten_up_to(state)
        shard_leaves: list[list[np.ndarray]] = [[] for _ in range(n_ranks)]
        for i, leaf in enumerate(leaves):
            a = np.asarray(leaf)
            dim = self.plan.split_dim(i, n_ranks)
            if dim is None:
                for r in range(n_ranks):
                    shard_leaves[r].append(a)
            else:
                pieces = np.split(a, n_ranks, axis=dim)
                for r in range(n_ranks):
                    shard_leaves[r].append(pieces[r])
        return [self.plan.treedef.unflatten(ls) for ls in shard_leaves], piece_sums

    def _dispatch_sums(self, live: Any, n_ranks: int) -> PieceSums | None:
        leaves = self.plan.treedef.flatten_up_to(live)
        dims = [self.plan.split_dim(i, n_ranks) for i in range(len(leaves))]
        take = [i for i, x in enumerate(leaves) if _device_summable(x, dims[i], n_ranks)]
        if not take:
            return None
        cols: list[int | None] = [None] * len(leaves)
        split = [d is not None for d in dims]
        c = 0
        for i in take:
            cols[i] = c
            c += n_ranks if split[i] else 1
        pairs = _tree_piece_sums([leaves[i] for i in take], tuple(dims[i] for i in take), n_ranks)
        return PieceSums(pairs, cols, split)

    # -- partner exchange subset (paper §5.2.1: replicated data needs no
    #    exchange — only uniquely-owned leaves travel to the partner) --------
    def partner_payload(self, shard: Any, n_ranks: int) -> Any:
        leaves = self.plan.treedef.flatten_up_to(shard)
        return {
            str(i): leaves[i]
            for i in range(len(leaves))
            if self.plan.split_dim(i, n_ranks) is not None
        }

    def replicated_nbytes(self, shard: Any, n_ranks: int) -> int:
        """Bytes of ``shard``'s leaves that every rank holds whole (the
        complement of :meth:`partner_payload`)."""
        leaves = self.plan.treedef.flatten_up_to(shard)
        return sum(
            leaves[i].nbytes
            for i in range(len(leaves))
            if self.plan.split_dim(i, n_ranks) is None
        )

    def merge_payload(self, partner_subset: Any, survivor_full: Any, n_ranks: int) -> Any:
        """Rebuild a dead rank's payload: uniquely-owned leaves from the
        partner copy + replicated leaves from any survivor's own snapshot."""
        leaves = list(self.plan.treedef.flatten_up_to(survivor_full))
        for key, piece in partner_subset.items():
            leaves[int(key)] = piece
        return self.plan.treedef.unflatten(leaves)

    # -- restore ---------------------------------------------------------
    def restore_shards(self, shards: dict[int, Any]) -> None:
        """Upload every origin's pieces in one batched transfer and join the
        split leaves on the device: the host copies nothing, and the device
        holds at most the state plus its largest leaf. Returns once the
        whole restored state is on the device."""
        n = max(shards) + 1
        assert set(shards) == set(range(n)), f"missing origins: {sorted(shards)}"
        per_origin = [self.plan.treedef.flatten_up_to(shards[r]) for r in range(n)]
        # A split leaf travels as its n pieces, a replicated one (or any leaf
        # of a one-rank world) once.
        dims = [self.plan.split_dim(i, n) if n > 1 else None
                for i in range(len(self.plan.dims))]
        host = [
            [per_origin[r][i] for r in range(n)] if dim is not None else [per_origin[0][i]]
            for i, dim in enumerate(dims)
        ]
        if self._release is not None:
            self._release()
        with _TR.child("restore_upload"):
            pieces = jax.block_until_ready(jax.device_put(host))
        out = []
        with _TR.child("restore_merge") as span:
            merged = 0
            for i, dim in enumerate(dims):
                if dim is None:
                    out.append(pieces[i][0])
                    continue
                # Wait for each join before freeing its pieces: joins enqueued
                # ahead of the device each hold their output while every
                # piece is still held, up to twice the state on the device.
                leaf = jnp.concatenate(pieces[i], axis=dim).block_until_ready()
                for p in pieces[i]:
                    p.delete()
                merged += leaf.nbytes
                out.append(leaf)
            span.label(bytes=merged)
        self._set(self.plan.treedef.unflatten(out))


class RngEntity:
    """Host-side RNG seed/counter entity (replicated)."""

    def __init__(self) -> None:
        self.seed = 0
        self.counter = 0

    def snapshot(self):
        return {"seed": np.int64(self.seed), "counter": np.int64(self.counter)}

    def restore(self, snap):
        self.seed = int(snap["seed"])
        self.counter = int(snap["counter"])
