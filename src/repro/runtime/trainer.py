"""The fault-tolerant training loop — paper Algorithm 3, end to end.

    while current step < number of steps:
        try:
            barrier (faults surface here, deterministically)
            single step
            checkpoint if due (Algorithm 2, at the Daly interval)
        catch ProcessFaultException:
            stabilize parallel environment (revoke -> shrink / spares)
            recover last checkpoint (Algorithm 4; zero-comm for survivors)

Because the data pipeline's state is part of the checkpoint, the replayed
trajectory after a rollback is bitwise identical to a fault-free run — the
recovery tests assert exactly that.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ModelConfig
from repro.core.checkpoint import CheckpointEngine, EngineConfig
from repro.core.interval import CheckpointScheduler, MultiLevelScheduler, system_mtbf
from repro.data.synthetic import SyntheticDataPipeline
from repro.models.common import ShardCtx
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state, abstract_opt_state
from repro.optim.schedule import warmup_cosine
from repro.runtime.cluster import VirtualCluster
from repro.runtime.failures import FailureInjector, ProcessFaultException
from repro.runtime.state import ShardPlan, ShardedStateEntity
from repro.runtime.straggler import StragglerDetector
from repro.sharding.axes import tree_pspecs, tree_zero1_pspecs
from repro.sharding.mesh import abstract_mesh
from repro.sharding.spec import specs_to_shape_dtype
from repro.obs.trace import tracer
from repro.utils.logging import bind, get_logger
from repro.utils.timing import TimerRegistry

log = get_logger("runtime.trainer")
_TR = tracer()


def state_pspecs(model: Model, mesh, moment_dtype: Any = jnp.float32) -> dict[str, Any]:
    """PartitionSpecs of the train state on ``mesh``: params by the model's
    rules, optimizer state ZeRO-1 over the data axis."""
    rules = model.rules
    p_specs = model.abstract_params
    opt_specs = abstract_opt_state(p_specs, moment_dtype)
    return {
        "params": tree_pspecs(p_specs, rules, mesh),
        "opt": {
            "master": tree_zero1_pspecs(opt_specs["master"], rules, mesh),
            "m": tree_zero1_pspecs(opt_specs["m"], rules, mesh),
            "v": tree_zero1_pspecs(opt_specs["v"], rules, mesh),
        },
        "step": jax.sharding.PartitionSpec(),
    }


def state_shape_dtypes(model: Model, moment_dtype: Any = jnp.float32) -> dict[str, Any]:
    """ShapeDtypeStructs of the train state (params, AdamW state, step)."""
    o = abstract_opt_state(model.abstract_params, moment_dtype)
    return {
        "params": specs_to_shape_dtype(model.abstract_params),
        "opt": specs_to_shape_dtype(o),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


@dataclass
class TrainerConfig:
    batch: int = 8
    seq: int = 64
    lr: float = 1e-3
    warmup_steps: int = 10
    total_steps: int = 1000
    seed: int = 0
    # fault tolerance
    n_virtual_hosts: int = 4          # failure-domain ranks in the simulation
    n_spares: int = 0
    recovery_policy: str = "spare"    # spare | shrink | elastic (N-to-M repartition)
    mtbf_individual_s: float = 3600.0
    checkpoint_period: int | None = None  # None -> Daly-optimal (adaptive)
    engine: EngineConfig = field(default_factory=EngineConfig)
    moment_dtype: Any = jnp.float32
    # Storage-tier ladder (paper §5.2.1 "checkpointing to disk at a lower
    # frequency"; DESIGN.md §12): `tier_dir` adds a persistent disk rung to
    # EngineConfig.tiers. Flushes run in the background on the engine's
    # drain pool every `disk_flush_every` committed checkpoints; 0 derives
    # the cadence adaptively from the per-level Daly schedule
    # (interval.MultiLevelScheduler at `tier_mtbf_s`, the MTBF of the
    # failures the diskless tier cannot survive).
    tier_dir: str | None = None
    disk_flush_every: int = 0
    tier_mtbf_s: float = 30 * 24 * 3600.0
    # Content-addressed delta flushes on the trainer-managed disk rung
    # (DESIGN.md §17): generations share unchanged chunks through the tier's
    # chunk store instead of re-writing full rank files.
    tier_dedup: bool = False
    # Deprecated aliases for (tier_dir, disk_flush_every) — pre-ladder
    # configs keep their exact cadence.
    disk_path: str | None = None
    disk_every: int = 8
    # Overlapped checkpointing: "sync" blocks the step loop for the full
    # create+distribute+handshake; "async" captures the snapshot at the step
    # boundary (consistency preserved) and runs the encode/transfer/verify
    # pipeline behind the next step (compute/comm overlap, background worker
    # per EngineConfig.async_workers), committing at the following boundary.
    checkpoint_mode: str = "sync"     # sync | async
    # Deprecated alias for checkpoint_mode="async" (kept for old configs).
    async_checkpoint: bool = False


class Trainer:
    def __init__(
        self,
        model: Model,
        tcfg: TrainerConfig,
        mesh: Mesh | None = None,
        injector: FailureInjector | None = None,
    ) -> None:
        assert tcfg.checkpoint_mode in ("sync", "async"), tcfg.checkpoint_mode
        self.model = model
        self.cfg = model.cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.timers = TimerRegistry()

        # -- data pipeline (its (seed, step) state is a checkpoint entity) ---
        self.data = SyntheticDataPipeline(self.cfg, tcfg.batch, tcfg.seq, tcfg.seed)

        # -- live state -------------------------------------------------------
        key = jax.random.PRNGKey(tcfg.seed)
        params = model.init(key)
        self.state: dict[str, Any] = {
            "params": params,
            "opt": init_opt_state(params, tcfg.moment_dtype),
            "step": jnp.zeros((), jnp.int32),
        }

        # -- sharding plan against the PRODUCTION mesh (abstract) -------------
        prod_mesh = abstract_mesh(("data", 16), ("model", 16))
        pspecs = state_pspecs(model, prod_mesh, tcfg.moment_dtype)
        sds = state_shape_dtypes(model, tcfg.moment_dtype)
        self.plan = ShardPlan.from_pspecs(sds, pspecs)

        # -- cluster + engine + scheduler -------------------------------------
        self._engine_cfg = self._resolve_engine_cfg(tcfg)
        self.cluster = VirtualCluster(tcfg.n_virtual_hosts, tcfg.n_spares)
        self.engine = CheckpointEngine(tcfg.n_virtual_hosts, self._engine_cfg)
        self.cluster.attach_engine(self.engine)
        self.timers.attach_metrics(self.engine.registry)
        self.engine.register(
            "train_state",
            ShardedStateEntity(lambda: self.state, self._set_state, self.plan,
                               release=self._release_state),
        )
        self.engine.register("data_pipeline", self.data)
        self.engine.register("timers", self.timers)

        mtbf = system_mtbf(tcfg.mtbf_individual_s, tcfg.n_virtual_hosts)
        self.scheduler = CheckpointScheduler(mtbf_s=mtbf, step_time_s=0.1)
        # Per-level Daly schedule for the tier ladder: active when a disk
        # rung exists and no fixed flush cadence was pinned (DESIGN.md §12).
        self.mlsched: MultiLevelScheduler | None = None
        if self.engine.persistent_tiers and self._auto_flush_every:
            self.mlsched = MultiLevelScheduler(
                base=self.scheduler, level_mtbf_s=[tcfg.tier_mtbf_s]
            )
        self.injector = injector or FailureInjector(tcfg.n_virtual_hosts)
        self.straggler = StragglerDetector(tcfg.n_virtual_hosts)

        # -- jitted step -------------------------------------------------------
        self._train_step = self._build_train_step()
        self.history: list[dict[str, float]] = []
        self.n_recoveries = 0
        self._last_ckpt_step = -(10**9)
        self._pending_ckpt_step = -(10**9)
        self._seen_flushes = 0

    # ------------------------------------------------------------------ #
    def _resolve_engine_cfg(self, tcfg: TrainerConfig) -> EngineConfig:
        """Fold the trainer's tier knobs into the engine config: `tier_dir`
        (or the deprecated `disk_path`) appends a disk rung to
        `EngineConfig.tiers` unless the caller configured a ladder
        explicitly. A pinned cadence (`disk_flush_every` > 0, or the legacy
        `disk_every` alias) fixes `every`; otherwise the MultiLevelScheduler
        retunes it after every checkpoint."""
        from dataclasses import replace

        from repro.core import storage as storage_mod

        tier_dir = tcfg.tier_dir or tcfg.disk_path
        self._auto_flush_every = False
        if tcfg.engine.tiers or tier_dir is None:
            return tcfg.engine
        every = tcfg.disk_flush_every
        if every <= 0 and tcfg.disk_path:
            every = tcfg.disk_every          # legacy alias keeps its cadence
        if every <= 0:
            self._auto_flush_every = True
            every = 4                        # placeholder until first retune
        return replace(
            tcfg.engine,
            tiers=(storage_mod.disk(tier_dir, every=every, dedup=tcfg.tier_dedup),),
        )

    def _retune_tier_schedule(self) -> None:
        """Post-commit tier upkeep, called from the step loop right after a
        checkpoint commits: kick the staged background flush (the executor
        wake-up happens here, behind the next train step, never on the
        blocked capture+finalize path) and fold the last measured flush into
        the per-level Daly cadence."""
        self.engine.kick_tier_flush()
        if self.mlsched is None:
            return
        stats = self.engine.stats
        if stats.tier_flushes > self._seen_flushes and stats.last_flush_s > 0:
            self._seen_flushes = stats.tier_flushes
            self.mlsched.record_flush_duration(1, stats.last_flush_s)
        for tier in self.engine.persistent_tiers:
            tier.every = self.mlsched.flush_every(1)

    def _release_state(self) -> None:
        # A restore calls this before its first upload: holding the live
        # device state and the restored one would need twice the state in
        # device memory, which a chip filled by its optimizer state does
        # not have.
        self.state = None

    def _set_state(self, state: dict[str, Any]) -> None:
        # The restore released the live state (``_release_state``) before
        # uploading, and hands over the restored tree already on the device.
        self.state = state

    def _build_train_step(self):
        model, tcfg = self.model, self.tcfg
        hp = AdamWConfig(lr=tcfg.lr)
        sched = warmup_cosine(tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)
        ctx = None
        if self.mesh is not None:
            ctx = ShardCtx(self.mesh, model.rules)

        def step_fn(state, batch):
            def loss_of(p):
                return model.loss(p, batch, ctx=ctx)

            (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(state["params"])
            new_params, new_opt, stats = adamw_update(
                grads, state["opt"], state["step"], hp,
                lr_schedule=sched, param_dtype=model.cfg.param_dtype,
            )
            new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
            return new_state, {"loss": loss, **metrics, **stats}

        return jax.jit(step_fn, donate_argnums=(0,))

    # ------------------------------------------------------------------ #
    # Algorithm 3
    # ------------------------------------------------------------------ #
    def run(self, num_steps: int) -> list[dict[str, float]]:
        ckpt_count = 0
        while int(self.state["step"]) < num_steps:
            try:
                self.cluster.barrier("step")

                # Finalize an overlapped checkpoint from the previous step
                # (its exchange ran "behind" that step's compute).
                pending = self.engine.finalize_async()
                if pending is not None:
                    self.engine._fault_hook = lambda phase: None
                if pending is True:
                    self._last_ckpt_step = self._pending_ckpt_step
                    self.scheduler.record_checkpoint_duration(
                        self.timers("checkpoint").mean
                    )
                    self._retune_tier_schedule()
                elif pending is False:
                    raise ProcessFaultException(
                        sorted(self.cluster.failed), "checkpoint"
                    )

                # Fault injection models hosts dying *during* the step; the
                # fault surfaces at the next barrier (step granularity).
                step = int(self.state["step"])
                for r in self.injector.kills_at_step(step):
                    self.cluster.kill(r)
                self.cluster.barrier("step")

                with self.timers("train_step"), _TR.span("train_step", step=step):
                    batch = self.data.next()
                    self.state, metrics = self._train_step(self.state, batch)
                    jax.block_until_ready(self.state["step"])
                self.scheduler.record_step_time(self.timers("train_step").mean)
                self.history.append(
                    {"step": step, "loss": float(metrics["loss"])}
                )
                # Per-step structured record (DESIGN.md §13): DEBUG level so
                # run logs stay quiet by default; under REPRO_LOG_JSON=1 the
                # fields become machine-parseable JSON keys.
                log.debug(
                    "step", extra={"fields": {
                        "component": "trainer", "step": step,
                        "loss": float(metrics["loss"]),
                        "generation": self.engine.stats.created,
                        "alive": len(self.cluster.alive()),
                        "step_s": self.timers("train_step").last,
                    }},
                )

                if self._checkpoint_due(int(self.state["step"])):
                    kills = self.injector.kills_at_checkpoint(ckpt_count)
                    hook_fired = {"done": False}

                    def hook(phase: str) -> None:
                        if phase == "after_create" and kills and not hook_fired["done"]:
                            hook_fired["done"] = True
                            for r in kills:
                                self.cluster.kill(r)

                    self.engine._fault_hook = hook
                    ckpt_count += 1
                    if self.tcfg.checkpoint_mode == "async" or self.tcfg.async_checkpoint:
                        # Capture now; exchange overlaps the next step.
                        with self.timers("checkpoint"):
                            created = self.engine.checkpoint_async(
                                {"step": int(self.state["step"])}
                            )
                        self._pending_ckpt_step = int(self.state["step"])
                        if not created:
                            raise ProcessFaultException(
                                sorted(self.cluster.failed), "checkpoint"
                            )
                        continue
                    with self.timers("checkpoint"):
                        ok = self.engine.checkpoint({"step": int(self.state["step"])})
                    self.engine._fault_hook = lambda phase: None
                    if ok:
                        self._last_ckpt_step = int(self.state["step"])
                        self.scheduler.record_checkpoint_duration(
                            self.timers("checkpoint").mean
                        )
                        # A due disk rung was flushed by the engine in the
                        # background (after the pointer swap, off the blocked
                        # window); only the cadence retune happens here.
                        self._retune_tier_schedule()
                    else:
                        raise ProcessFaultException(
                            sorted(self.cluster.failed), "checkpoint"
                        )

            except ProcessFaultException as e:
                log.warning("fault caught in main loop: %s", e)
                self.recover()
        return self.history

    # ------------------------------------------------------------------ #
    def _checkpoint_due(self, step: int) -> bool:
        if self.tcfg.checkpoint_period is not None:
            return step > 0 and step % self.tcfg.checkpoint_period == 0
        return self.scheduler.due(step, max(self._last_ckpt_step, 0))

    def recover(self) -> None:
        """Stabilize the parallel environment, then roll back (Algorithm 3).

        Recovery escalates down the storage-tier ladder (DESIGN.md §12):
        the engine first reconstructs from surviving hosts via the codec;
        a whole-system loss (below) or a burst beyond codec tolerance
        (inside ``engine.restore``) rehydrates the newest valid disk
        generation and recovery re-runs against it. Failures within
        tolerance never touch disk."""
        with _TR.span("recover"):
            if not self.engine.has_valid_checkpoint:
                if self.engine.has_tier_data():
                    # Full-restart policy: every in-memory snapshot died with its
                    # host; all ranks rejoin and the engine escalates internally.
                    log.warning("no in-memory checkpoint; escalating to the tier ladder")
                    self.cluster.restart_all()
                    meta = self.engine.restore()
                    self.n_recoveries += 1
                    log.info("recovered from the tier ladder to step %s", meta.get("step"))
                    return
                raise RuntimeError(
                    "fault before the first checkpoint and no persistent tier configured"
                )
            report = self.cluster.stabilize(self.tcfg.recovery_policy)  # revoke+shrink
            if report.policy == "elastic":
                meta = self._elastic_recover(report.n_ranks_after)
            elif report.policy == "shrink":
                meta = self._shrink_engine(report)
            else:
                meta = self.engine.restore()  # Algorithm 4 under the hood
            # Restored entities include the data pipeline + timers + train state;
            # the loop continues from the checkpointed step.
            self.n_recoveries += 1
            s = self.engine.stats
            log.info(
                "recovered to step %s (policy=%s, codec=%s/t%d, load_factor=%.2f, "
                "restore=%s %.3fs: %d chunks, %.1f MiB rebuilt)",
                meta.get("step"), report.policy,
                self.engine.codec.name, self.engine.codec.tolerance(),
                report.load_factor,
                self.tcfg.engine.restore_mode, s.last_restore_s,
                s.last_restore_chunks, s.last_restore_bytes_rebuilt / 2**20,
            )

    def _shrink_engine(self, report) -> dict[str, Any]:
        """Elastic shrink: restore from the OLD world's surviving stores, then
        rebuild the engine over the dense-renumbered survivor set. The live
        state pytree is global in this simulation, so 'survivors inherit the
        failed ranks' blocks' happens inside restore_shards (the re-sharding
        to new_n ranks occurs at the next checkpoint — the paper's post-
        recovery load-balancing step)."""
        old = self.engine
        failed = set(report.failed)
        old._alive_fn = lambda: {
            r for r in range(old.n_ranks) if r not in failed
        }
        meta = old.restore()  # Algorithm 4 against the old rank space

        new_n = report.n_ranks_after
        self._swap_engine(new_n)
        return meta

    def _elastic_recover(self, n_new: int) -> dict[str, Any]:
        """N-to-M recovery: repartition the checkpoint onto the ``n_new``-rank
        world (engine.restore_elastic), realign the cluster, and immediately
        re-checkpoint so the new world is protected before the next step.

        restore_elastic consumes the old checkpoint, so a failed re-protect
        (a rank dying during the exchange) must not be ignored: the restored
        state is still live in memory, so we shrink onto whoever survived and
        re-protect again until a checkpoint commits."""
        meta = self.engine.restore_elastic(n_new)
        self.cluster.resize(n_new)
        while not self.engine.checkpoint({"step": int(self.state["step"])}):
            survivors = len(self.cluster.alive())
            if survivors < 1:
                raise RuntimeError("all ranks died while re-protecting the elastic world")
            log.warning(
                "re-protect checkpoint failed; shrinking to %d survivors", survivors
            )
            self._swap_engine(survivors)
            self.cluster.resize(survivors)  # clears the revoked flag too
        self._last_ckpt_step = int(self.state["step"])
        return meta

    def restore_elastic(self, n_new: int) -> dict[str, Any]:
        """Elastic transition to ``n_new`` ranks from the last checkpoint —
        shrink (fewer hosts, no spares needed) or grow (scale-up). The merged
        global state is bit-identical; only the shard topology changes."""
        return self._elastic_recover(n_new)

    def cold_restart(self) -> dict[str, Any]:
        """Restart a **fresh job** from the persistent tier ladder: nothing
        in memory (the previous process died), the newest valid disk
        generation rehydrates the stores, and training resumes from the
        flushed step — bit-identically, including the data-pipeline state.
        When the stored world size N differs from this job's
        ``n_virtual_hosts`` M, the checkpoint is repartitioned N→M through
        ``restore_elastic`` (the elastic layer's cold-start pairing)."""
        eng = self.engine
        if not eng.has_tier_data():
            raise RuntimeError("cold restart requested but no tier holds data")
        eng.escalate_from_tiers()         # engine resizes to the stored N
        n_stored = eng.n_ranks
        self.cluster.resize(n_stored)     # realign liveness to the loaded world
        if n_stored != self.tcfg.n_virtual_hosts:
            log.info(
                "cold restart: stored world %d -> job world %d (elastic N-to-M)",
                n_stored, self.tcfg.n_virtual_hosts,
            )
            meta = self._elastic_recover(self.tcfg.n_virtual_hosts)
        else:
            meta = eng.restore()
            self._last_ckpt_step = int(meta.get("step", 0))
        self.n_recoveries += 1
        log.info("cold restart complete: resuming from step %s", meta.get("step"))
        return meta

    def _swap_engine(self, n_new: int) -> None:
        """Rebuild the engine for a new world size; entities carry over and
        re-shard themselves at the next checkpoint."""
        old = self.engine
        old.close()  # join + release the old engine's pipeline worker
        new_engine = CheckpointEngine(n_new, self._engine_cfg)
        for name, ent in old._entities.items():
            new_engine._entities[name] = ent
        new_engine._replicated = set(old._replicated)
        # Carry the tier ladder's adaptive state across the resize: the
        # retuned flush cadence, and the flush counter the Daly retune
        # compares against (the new engine's stats restart at zero).
        for old_tier, new_tier in zip(old.persistent_tiers, new_engine.persistent_tiers):
            new_tier.every = old_tier.every
        self._seen_flushes = 0
        self.cluster.n_ranks = n_new
        self.cluster._alive = set(range(n_new))
        self.cluster.attach_engine(new_engine)
        self.engine = new_engine
        # Re-point the timer mirror at the new engine-local registry so
        # `timer_seconds` keeps accumulating after an elastic resize.
        self.timers.attach_metrics(new_engine.registry)

    def regrow(self, n_new: int) -> None:
        """Elastic scale-up (paper §5.2.4: reintegrate resources during
        runtime, 'also apart from a failure scenario'): expand the failure-
        domain world to ``n_new`` ranks and immediately checkpoint so the new
        ranks hold their re-balanced shards + backups."""
        assert n_new >= self.engine.n_ranks
        self._swap_engine(n_new)
        ok = self.engine.checkpoint({"step": int(self.state["step"])})
        if ok:
            self._last_ckpt_step = int(self.state["step"])
        log.info("regrown to %d ranks (checkpoint %s)", n_new, "ok" if ok else "failed")
