"""The capture's reference checksums come from the device: a sharded state of
device arrays sums every piece the pack will write in one jitted program
before its D2H, and the engine places those sums at the pieces' offsets. The
result must equal ``np_checksum`` of each packed own payload bit for bit, for
any dtype, split dim and offset; host arrays are summed on the host as
before; and the drain's VERIFY, which still sums the host bytes, aborts a
save whose bytes changed after the device summed them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import CONFIGS
from repro.core.checkpoint import CheckpointEngine, EngineConfig
from repro.core.integrity import np_checksum, payload_checksum
from repro.core.serialization import pack_bytes
from repro.models import build_model
from repro.obs.trace import tracer
from repro.optim.adamw import init_opt_state
from repro.runtime.state import ShardedStateEntity, ShardPlan
from repro.runtime.trainer import state_pspecs, state_shape_dtypes
from repro.sharding.axes import rules_for_shape, tree_pspecs
from repro.sharding.mesh import abstract_mesh
from repro.sharding.spec import specs_to_shape_dtype

PROD_MESH = abstract_mesh(("data", 16), ("model", 16))

# Every dtype the device sum reads, split on a leading, a middle and the last
# dim, replicated, and 0-d. All lengths are whole words, so every offset is.
MIXED = {
    "f32_rows": ((8, 6), jnp.float32, P("data")),
    "bf16_cols": ((3, 8), jnp.bfloat16, P(None, "data")),
    "i32_last": ((2, 4, 12), jnp.int32, P(None, None, "data")),
    "bf16_mid": ((2, 12, 5, 2), jnp.bfloat16, P(None, "data")),
    "u16_rep": ((6, 2), jnp.uint16, P()),
    "f16_rows": ((12, 2), jnp.float16, P("data")),
    "i8_rows": ((24, 4), jnp.int8, P("data")),
    "bool_rep": ((4, 3), jnp.bool_, P()),
    "f32_scalar": ((), jnp.float32, P()),
    "i32_scalar": ((), jnp.int32, P()),
}
# Leaves of odd byte length: every leaf after the first of them starts off a
# word boundary, and a bf16 piece of one element per rank ends mid-word.
ODD = {
    "a_bf16_odd": ((5,), jnp.bfloat16, P()),
    "b_i8_odd": ((7,), jnp.int8, P()),
    "c_bf16_scalar": ((), jnp.bfloat16, P()),
    "d_f32": ((12, 3), jnp.float32, P("data")),
    "e_bf16_pieces": ((3, 12), jnp.bfloat16, P(None, "data")),
    "f_bool": ((3,), jnp.bool_, P()),
    "g_i32": ((4,), jnp.int32, P()),
}


@pytest.fixture
def tr():
    t = tracer()
    t.reset()
    t.enable()
    yield t
    t.disable()
    t.reset()


def _random_bits(sds_tree, seed):
    """Device arrays of random bits (NaN patterns included) in each leaf's
    dtype: the sums read bits, not values."""
    rng = np.random.default_rng(seed)

    def one(s):
        dt = np.dtype(s.dtype)
        if dt == np.bool_:
            return jnp.asarray(rng.integers(0, 2, s.shape).astype(bool))
        raw = rng.integers(0, 256, (*s.shape, dt.itemsize), np.uint8)
        return jnp.asarray(raw.view(dt).reshape(s.shape))

    return jax.tree.map(one, sds_tree)


def _table(spec):
    sds = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d, _) in spec.items()}
    return sds, {k: p for k, (_, _, p) in spec.items()}


def _train_tree():
    model = build_model(CONFIGS["mamba2-780m"].reduced())
    params = model.init(jax.random.PRNGKey(0))
    state = {"params": params, "opt": init_opt_state(params),
             "step": jnp.asarray(7, jnp.int32)}
    return state, state_shape_dtypes(model), state_pspecs(model, PROD_MESH)


def _hybrid_tree():
    model = build_model(CONFIGS["granite-4.0-h-small"].reduced())
    B, S = 4, 24
    cache = model.abstract_cache(B, S)
    sds = {"cache": specs_to_shape_dtype(cache),
           "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
           "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    pspecs = {"cache": tree_pspecs(cache, rules_for_shape(model.rules, "decode", B), PROD_MESH),
              "tokens": P(), "pos": P()}
    kinds = set(model.cfg.layer_pattern)
    assert {"mamba", "attn"} <= kinds, kinds
    return _random_bits(sds, 5), sds, pspecs


def _case(name):
    if name == "train":
        return _train_tree()
    if name == "hybrid":
        return _hybrid_tree()
    sds, pspecs = _table({"mixed": MIXED, "odd": ODD}[name])
    return _random_bits(sds, len(name)), sds, pspecs


def _saved(live, sds, pspecs, n_ranks):
    """One save of ``live`` through the engine, left pending (not drained)."""
    plan = ShardPlan.from_pspecs(sds, pspecs)
    eng = CheckpointEngine(n_ranks, EngineConfig())
    eng.register("state", ShardedStateEntity(lambda: live, lambda s: None, plan))
    assert eng.checkpoint_async({"step": 1}, background=False)
    return eng, plan


def _own_bytes(eng):
    return sum(flat.nbytes for st in eng.stores.values()
               for flat, _ in st.buffer.writable.own.values())


@pytest.mark.parametrize("n_ranks", [1, 2, 3, 4])
def test_each_piece_sum_is_np_checksum_of_the_piece(n_ranks):
    sds, pspecs = _table(MIXED)
    live = _random_bits(sds, n_ranks)
    plan = ShardPlan.from_pspecs(sds, pspecs)
    ent = ShardedStateEntity(lambda: live, lambda s: None, plan)
    shards, sums = ent.capture_shards(n_ranks)
    per_rank = sums.per_rank(n_ranks)
    for r, shard in enumerate(shards):
        leaves = plan.treedef.flatten_up_to(shard)
        assert len(per_rank[r]) == len(leaves)
        for leaf, pair in zip(leaves, per_rank[r]):
            assert pair == np_checksum(np.asarray(leaf))


@pytest.mark.parametrize("case", ["train", "mixed", "odd", "hybrid"])
@pytest.mark.parametrize("n_ranks", [2, 4])
def test_payload_sums_equal_np_checksum_of_the_packed_payload(tr, case, n_ranks):
    live, sds, pspecs = _case(case)
    eng, plan = _saved(live, sds, pspecs, n_ranks)
    for st in eng.stores.values():
        payload = st.buffer.writable
        flat, _ = payload.own["state"]
        assert payload.meta["checksums"]["state"] == np_checksum(flat)
    (span,) = [e for e in tr.events() if e["name"] == "capture_checksum"]
    device, host = span["args"]["device_bytes"], span["args"]["host_bytes"]
    assert device + host == _own_bytes(eng)
    if case == "odd":
        # Only the leaves at word offsets came from the device.
        assert 0 < device < _own_bytes(eng)
    else:
        assert host == 0
    assert eng.finalize_async() is True
    eng.close()


@pytest.mark.parametrize("n_ranks", [1, 3])
def test_sums_at_any_offset_are_exact(n_ranks):
    """``payload_checksum`` with only some leaves' sums known, at offsets
    and lengths that are not whole words, against ``np_checksum``."""
    sds, pspecs = _table(ODD)
    live = _random_bits(sds, 11)
    plan = ShardPlan.from_pspecs(sds, pspecs)
    shards, sums = ShardedStateEntity(lambda: live, lambda s: None, plan).capture_shards(n_ranks)
    per_rank = sums.per_rank(n_ranks)
    for r, shard in enumerate(shards):
        flat, man = pack_bytes(shard)
        assert any(off % 4 for off in man.offsets)
        want = np_checksum(flat)
        for keep in range(len(man.offsets) + 1):
            pieces = [p if i < keep else None for i, p in enumerate(per_rank[r])]
            got, known = payload_checksum(flat, man.offsets, pieces)
            assert got == want, (r, keep)
            assert known <= flat.nbytes
        got, known = payload_checksum(flat, man.offsets, None)
        assert (got, known) == (want, 0)


class _Counter:
    """A plain host entity: the engine wraps it as replicated."""

    def __init__(self):
        self.n = np.arange(5, dtype=np.int64)

    def snapshot(self):
        return {"n": self.n.copy()}

    def restore(self, snap):
        self.n = snap["n"]


def test_host_entities_take_the_host_path(tr):
    sds, pspecs = _table(MIXED)
    host_live = jax.tree.map(np.asarray, _random_bits(sds, 3))
    plan = ShardPlan.from_pspecs(sds, pspecs)
    ent = ShardedStateEntity(lambda: host_live, lambda s: None, plan)
    assert ent.capture_shards(4)[1] is None
    eng = CheckpointEngine(4, EngineConfig())
    eng.register("state", ent)
    eng.register("counter", _Counter())
    assert eng.checkpoint_async({"step": 1}, background=False)
    for st in eng.stores.values():
        payload = st.buffer.writable
        for name, (flat, _) in payload.own.items():
            assert payload.meta["checksums"][name] == np_checksum(flat)
    (span,) = [e for e in tr.events() if e["name"] == "capture_checksum"]
    assert span["args"] == {"eng": span["args"]["eng"], "gen": 1,
                            "device_bytes": 0, "host_bytes": _own_bytes(eng)}
    assert eng.finalize_async() is True
    eng.close()


def test_labels_split_a_mixed_save(tr):
    """A device entity and a host entity in one save: the labels sum to the
    payload bytes, the device part being the device entity's payloads."""
    sds, pspecs = _table(MIXED)
    live = _random_bits(sds, 9)
    plan = ShardPlan.from_pspecs(sds, pspecs)
    eng = CheckpointEngine(4, EngineConfig())
    eng.register("state", ShardedStateEntity(lambda: live, lambda s: None, plan))
    eng.register("counter", _Counter())
    assert eng.checkpoint_async({"step": 1}, background=False)
    (span,) = [e for e in tr.events() if e["name"] == "capture_checksum"]
    state_bytes = sum(st.buffer.writable.own["state"][0].nbytes for st in eng.stores.values())
    assert span["args"]["device_bytes"] == state_bytes
    assert span["args"]["host_bytes"] == _own_bytes(eng) - state_bytes > 0
    assert eng.finalize_async() is True
    eng.close()


@pytest.mark.parametrize("rank", [0, 3])
def test_a_byte_flipped_after_the_capture_aborts_the_save(rank):
    sds, pspecs = _table(MIXED)
    live = _random_bits(sds, 4)
    eng, _ = _saved(live, sds, pspecs, 4)
    flat, _ = eng.stores[rank].buffer.writable.own["state"]
    flat[flat.nbytes // 2] ^= 0x10
    assert eng.finalize_async() is False
    assert eng.stats.aborted == 1 and eng.stats.created == 0
    eng.close()


def test_a_byte_changed_in_the_d2h_aborts_the_save(monkeypatch):
    """The reference is summed over the device's bytes, so a fault in the
    device-to-host copy is caught too: the host bytes no longer match."""
    sds, pspecs = _table(MIXED)
    live = _random_bits(sds, 6)
    get = jax.device_get

    def faulty_get(tree):
        out = get(tree)
        if isinstance(out, dict) and "f32_rows" in out:
            out["f32_rows"] = out["f32_rows"].copy()
            out["f32_rows"].view(np.uint32)[1, 2] ^= 1
        return out

    monkeypatch.setattr(jax, "device_get", faulty_get)
    eng, _ = _saved(live, sds, pspecs, 4)
    assert eng.finalize_async() is False
    assert eng.stats.aborted == 1
    eng.close()


def test_one_sums_fetch_per_entity_per_save(monkeypatch):
    """Each device entity costs two fetches a save, however many leaves it
    has: its state and its one array of piece sums."""
    sds, pspecs = _table(MIXED)
    plan = ShardPlan.from_pspecs(sds, pspecs)
    eng = CheckpointEngine(4, EngineConfig())
    for name, seed in (("a", 1), ("b", 2)):
        live = _random_bits(sds, seed)
        eng.register(name, ShardedStateEntity(lambda live=live: live, lambda s: None, plan))
    eng.register("counter", _Counter())
    fetched = []
    get = jax.device_get

    def counting_get(tree):
        fetched.append(len(jax.tree.leaves(tree)))
        return get(tree)

    monkeypatch.setattr(jax, "device_get", counting_get)
    for step in (1, 2):
        assert eng.checkpoint({"step": step})
    assert sorted(fetched) == [1, 1, 1, 1] + [len(MIXED)] * 4
    eng.close()
