"""The program spans that split capture, restore and the serving tick
(DESIGN.md §13): each is recorded once per save, restore or tick, nested in
its parent, with the labels its readers need; a disabled tracer records
nothing; an enabled one mirrors every span into the JAX profiler's trace."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import CONFIGS
from repro.core.checkpoint import CheckpointEngine, EngineConfig
from repro.models import build_model
from repro.obs.trace import _NOOP, tracer
from repro.runtime.failures import FailureInjector
from repro.runtime.server import Server, ServerConfig
from repro.runtime.state import ShardedStateEntity, ShardPlan
from repro.runtime.trainer import Trainer, TrainerConfig

CAPTURE_CHILDREN = ("capture_d2h", "capture_pack", "capture_checksum")
RESTORE_CHILDREN = ("restore_rebuild", "restore_upload", "restore_merge")
TICK_CHILDREN = ("tick_control", "decode_step", "tick_update")


@pytest.fixture
def tr():
    t = tracer()
    t.reset()
    t.enable()
    yield t
    t.disable()
    t.reset()


def _named(evs, name):
    return [e for e in evs if e["name"] == name]


def _inside(child, parent):
    return (child["tid"] == parent["tid"] and child["t0"] >= parent["t0"]
            and child["t0"] + child["dur"] <= parent["t0"] + parent["dur"])


def _children(evs, parent, name):
    return [e for e in _named(evs, name) if _inside(e, parent)]


@pytest.fixture(scope="module")
def killed_run():
    """A tiny train run that saves every 2 steps and loses a host at step 5,
    traced from start to end."""
    t = tracer()
    t.reset()
    t.enable()
    try:
        model = build_model(CONFIGS["llama3.2-1b"].reduced())
        trainer = Trainer(
            model,
            TrainerConfig(batch=4, seq=32, total_steps=8, checkpoint_period=2,
                          n_virtual_hosts=4, n_spares=2),
            injector=FailureInjector(4, schedule={5: [1]}),
        )
        trainer.run(8)
        evs = t.events()
        assert t.open_spans() == 0
    finally:
        t.disable()
        t.reset()
    return trainer, evs


@pytest.mark.parametrize("child", CAPTURE_CHILDREN)
def test_capture_children_once_per_save(killed_run, child):
    trainer, evs = killed_run
    captures = _named(evs, "capture")
    assert len(captures) == trainer.engine.stats.created >= 3
    assert len(_named(evs, child)) == len(captures)
    for cap in captures:
        (c,) = _children(evs, cap, child)
        assert c["args"]["eng"] == cap["args"]["eng"]
        assert c["args"]["gen"] == cap["args"]["gen"]


def test_capture_children_in_order_and_labeled(killed_run):
    trainer, evs = killed_run
    for cap in _named(evs, "capture"):
        d2h, pack, check = (_children(evs, cap, n)[0] for n in CAPTURE_CHILDREN)
        assert d2h["t0"] + d2h["dur"] <= pack["t0"]
        assert pack["t0"] + pack["dur"] <= check["t0"]
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(trainer.state))
        assert d2h["args"]["bytes"] == state_bytes
        assert 0 < pack["args"]["replicated_bytes"] < pack["args"]["bytes"]
        # The train state's own payloads are summed on the device; the data
        # pipeline and the timers, host entities, on the host.
        n = trainer.engine.n_ranks
        own = sum(x.nbytes * (1 if trainer.plan.split_dim(i, n) is not None else n)
                  for i, x in enumerate(trainer.plan.treedef.flatten_up_to(trainer.state)))
        assert check["args"]["device_bytes"] == own
        assert check["args"]["host_bytes"] > 0


@pytest.mark.parametrize("child", RESTORE_CHILDREN)
def test_restore_children_once_per_restore(killed_run, child):
    trainer, evs = killed_run
    restores = _named(evs, "restore")
    assert len(restores) == trainer.n_recoveries == 1
    assert len(_named(evs, child)) == 1
    (c,) = _children(evs, restores[0], child)
    assert c["dur"] > 0


def test_restore_children_in_order_inside_recover(killed_run):
    _, evs = killed_run
    (rec,) = _named(evs, "recover")
    (res,) = _named(evs, "restore")
    assert _inside(res, rec)
    rebuild, upload, merge = (_named(evs, n)[0] for n in RESTORE_CHILDREN)
    assert rebuild["t0"] + rebuild["dur"] <= upload["t0"]
    assert upload["t0"] + upload["dur"] <= merge["t0"]


@pytest.mark.parametrize("n_ranks", [2, 4, 3])
def test_replicated_bytes_count_each_rank_copy(tr, n_ranks):
    """``replicated_bytes`` is the plan's replicated leaves' bytes times the
    ranks: leaves with no data dim, and leaves whose data dim the world
    size does not divide."""
    sds = {
        "a": jax.ShapeDtypeStruct((12, 6), jnp.float32),  # data on dim 0
        "b": jax.ShapeDtypeStruct((5,), jnp.float32),     # no data dim
        "c": jax.ShapeDtypeStruct((2, 8), jnp.bfloat16),  # data on dim 1
    }
    plan = ShardPlan.from_pspecs(sds, {"a": P("data", "model"), "b": P(), "c": P(None, "data")})
    rng = np.random.default_rng(0)
    live = {k: rng.standard_normal(s.shape).astype(s.dtype) for k, s in sds.items()}
    ent = ShardedStateEntity(lambda: live, lambda s: None, plan)
    eng = CheckpointEngine(n_ranks, EngineConfig())
    eng.register("state", ent)
    assert eng.checkpoint({"step": 1})
    leaves = plan.treedef.flatten_up_to(live)
    want = n_ranks * sum(
        leaves[i].nbytes for i in range(len(leaves)) if plan.split_dim(i, n_ranks) is None
    )
    (pack,) = _named(tr.events(), "capture_pack")
    assert pack["args"]["replicated_bytes"] == want
    assert pack["args"]["bytes"] == eng.stats.last_bytes_staged
    assert want == n_ranks * (live["b"].nbytes + (live["c"].nbytes if n_ranks == 3 else 0))
    eng.close()


@pytest.mark.parametrize("on_device", [True, False])
def test_checksum_labels_sum_to_the_own_payloads(tr, on_device):
    """``capture_checksum``'s ``device_bytes`` and ``host_bytes`` split the
    bytes of every own payload by where their reference sums came from."""
    sds = {"a": jax.ShapeDtypeStruct((12, 6), jnp.float32),
           "b": jax.ShapeDtypeStruct((2, 8), jnp.bfloat16)}
    plan = ShardPlan.from_pspecs(sds, {"a": P("data"), "b": P(None, "data")})
    rng = np.random.default_rng(1)
    live = {k: rng.standard_normal(s.shape).astype(s.dtype) for k, s in sds.items()}
    if on_device:
        live = jax.device_put(live)
    eng = CheckpointEngine(4, EngineConfig())
    eng.register("state", ShardedStateEntity(lambda: live, lambda s: None, plan))
    assert eng.checkpoint({"step": 1})
    (check,) = _named(tr.events(), "capture_checksum")
    own = sum(st.buffer.read_only.own["state"][0].nbytes for st in eng.stores.values())
    assert own == 12 * 6 * 4 + 2 * 8 * 2
    want = {"device_bytes": own, "host_bytes": 0} if on_device else {
        "device_bytes": 0, "host_bytes": own}
    assert {k: check["args"][k] for k in want} == want
    eng.close()


@pytest.fixture(scope="module")
def tiny_server_model():
    model = build_model(CONFIGS["gemma2-2b"].reduced())
    params = model.init(jax.random.PRNGKey(1))
    prompts = np.random.default_rng(0).integers(0, model.cfg.vocab_size, (4, 8), dtype=np.int32)
    return model, params, prompts


@pytest.mark.parametrize("n_tokens", [5, 12])
def test_decode_records_one_tick_per_token(tr, tiny_server_model, n_tokens):
    model, params, prompts = tiny_server_model
    s = Server(model, ServerConfig(batch=4, max_seq=40, checkpoint_every_tokens=6,
                                   checkpoint_mode="async"), params=params)
    s.prefill_and_decode(prompts, n_tokens)
    evs = tr.events()
    ticks = _named(evs, "decode_tick")
    assert len(ticks) == n_tokens
    for tick in ticks:
        for child in TICK_CHILDREN:
            assert len(_children(evs, tick, child)) == 1, child
    # The first save follows the prefill; each later one is captured inside
    # the tick that produced its last token.
    captures = _named(evs, "capture")
    assert len(captures) == 1 + n_tokens // 6
    assert sum(any(_inside(c, t) for t in ticks) for c in captures) == n_tokens // 6
    assert tr.open_spans() == 0
    s.engine.close()


def test_decode_spans_balance_across_a_kill(tr, tiny_server_model):
    """A kill raises ProcessFaultException out of a tick: its spans close,
    the recovery is named, and the replayed ticks are recorded too."""
    model, params, prompts = tiny_server_model
    s = Server(model, ServerConfig(batch=4, max_seq=40, checkpoint_every_tokens=6),
               params=params, injector=FailureInjector(4, schedule={8: [2]}))
    s.prefill_and_decode(prompts, 12)
    evs = tr.events()
    assert s.n_recoveries == 1 and tr.open_spans() == 0
    (rec,) = _named(evs, "recover")
    assert len(_named(evs, "restore_rebuild")) == 1
    assert all(_inside(e, rec) for e in _named(evs, "restore"))
    ticks = _named(evs, "decode_tick")
    assert len(_named(evs, "decode_step")) == 12 + 2  # two ticks since the save replay
    assert len(ticks) == len(_named(evs, "tick_control")) == 12 + 3
    s.engine.close()


def test_disabled_tracer_returns_noop_and_records_nothing(tiny_server_model):
    t = tracer()
    assert not t.enabled
    assert t.span("x") is _NOOP and t.child("y", gen=1) is _NOOP
    _NOOP.label(bytes=1)
    model, params, prompts = tiny_server_model
    s = Server(model, ServerConfig(batch=4, max_seq=40, checkpoint_every_tokens=3),
               params=params)
    s.prefill_and_decode(prompts, 6)
    assert t.events() == [] and t.open_spans() == 0
    s.engine.close()


def test_child_carries_the_enclosing_span_labels(tr):
    with tr.span("outer", eng=3, gen=7) as sp:
        with tr.child("inner", bytes=10):
            with tr.child("innermost"):
                pass
        sp.label(bytes=99)
    with tr.child("alone", k=1):
        pass
    by = {e["name"]: e["args"] for e in tr.events()}
    assert by["inner"] == {"eng": 3, "gen": 7, "bytes": 10}
    assert by["innermost"] == {"eng": 3, "gen": 7, "bytes": 10}
    assert by["outer"] == {"eng": 3, "gen": 7, "bytes": 99}
    assert by["alone"] == {"k": 1}


def test_spans_reach_the_profiler_host_plane(tr, tmp_path):
    """Every span, the drain worker's included, is an annotation of the same
    name on a host line of the profiler's trace."""
    from jax.profiler import ProfileData

    eng = CheckpointEngine(4, EngineConfig(async_workers=1))
    data = {r: np.arange(1024, dtype=np.float32) + r for r in range(4)}

    class Vec:
        def snapshot_shards(self, n):
            return [{"v": data[r]} for r in range(n)]

        def restore_shards(self, shards):
            pass

    eng.register("vec", Vec())
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert eng.checkpoint_async({"step": 1})
        assert eng.finalize_async() is True
    finally:
        jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    lines.setdefault(ev.name, set()).add((plane.name, i))
    recorded = {e["name"] for e in tr.events()}
    assert {"capture", "capture_pack", "capture_checksum", "encode", "commit"} <= recorded
    assert recorded <= set(lines)
    # The drain ran on a worker thread: its spans sit on another host line.
    assert lines["encode"] != lines["capture"]
