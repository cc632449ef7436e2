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
        live = self._get()
        labels = self._labels(live) if self._labels is not None and _TR.enabled else {}
        with _TR.child("capture_d2h", bytes=sum(x.nbytes for x in jax.tree.leaves(live)), **labels):
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
        return [self.plan.treedef.unflatten(ls) for ls in shard_leaves]

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
