"""``ShardedStateEntity.restore_shards`` uploads every origin's pieces in one
batched transfer, after releasing the live state, and joins the split leaves
on the device: the installed leaves are device arrays holding the same bits
as a host concatenation of the pieces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.obs.trace import tracer
from repro.runtime.state import ShardedStateEntity, ShardPlan

SDS = {
    "a": jax.ShapeDtypeStruct((12, 6), jnp.float32),   # data on dim 0
    "b": jax.ShapeDtypeStruct((5,), jnp.float32),      # no data dim
    "c": jax.ShapeDtypeStruct((3, 24), jnp.bfloat16),  # data on dim 1
    "d": jax.ShapeDtypeStruct((7, 4), jnp.float32),    # data on dim 0, 7 rows
}
PSPECS = {"a": P("data", "model"), "b": P(), "c": P(None, "data"), "d": P("data")}


def _bits(x):
    a = np.asarray(x)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


@pytest.mark.parametrize("n_ranks", [2, 3, 4])
def test_restore_assembles_split_leaves_on_the_device(monkeypatch, n_ranks):
    plan = ShardPlan.from_pspecs(SDS, PSPECS)
    assert [plan.split_dim(i, n_ranks) for i in range(4)] == [0, None, 1, None]
    rng = np.random.default_rng(n_ranks)
    # Random bits, NaN patterns included: the restore must copy, not compute.
    live = {k: rng.integers(0, 256, (*s.shape, s.dtype.itemsize), np.uint8).view(s.dtype)[..., 0]
            for k, s in SDS.items()}
    calls = []
    holder = {"s": live}
    ent = ShardedStateEntity(
        lambda: holder["s"], lambda s: (calls.append("set"), holder.update(s=s)), plan,
        release=lambda: (calls.append("release"), holder.update(s=None)),
    )
    shards = ent.snapshot_shards(n_ranks)
    put = jax.device_put

    def counting_put(*a, **k):
        calls.append("device_put")
        return put(*a, **k)

    monkeypatch.setattr(jax, "device_put", counting_put)
    tr = tracer()
    tr.reset()
    tr.enable()
    try:
        ent.restore_shards(dict(enumerate(shards)))
        evs = tr.events()
    finally:
        tr.disable()
        tr.reset()
    assert calls == ["release", "device_put", "set"]
    for i, k in enumerate(SDS):
        got = holder["s"][k]
        dim = plan.split_dim(i, n_ranks)
        want = (live[k] if dim is None
                else np.concatenate([shards[r][k] for r in range(n_ranks)], axis=dim))
        assert isinstance(got, jax.Array), k
        assert got.dtype == want.dtype and got.shape == want.shape, k
        assert np.array_equal(_bits(got), _bits(want)), k
    (upload,) = [e for e in evs if e["name"] == "restore_upload"]
    (merge,) = [e for e in evs if e["name"] == "restore_merge"]
    assert upload["t0"] + upload["dur"] <= merge["t0"]
    assert merge["args"]["bytes"] == live["a"].nbytes + live["c"].nbytes
