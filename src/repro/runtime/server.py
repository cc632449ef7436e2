"""Fault-tolerant batched serving loop.

The paper's scheme applies to any "sequence of well-defined states" — for
inference that state is the decode session set: KV/SSM caches, generated
tokens, and the position counter. The server checkpoints sessions every
``checkpoint_every_tokens`` decode steps under the same engine (params are
registered too but change never, so their snapshot cost is paid once per
checkpoint — or excluded via ``snapshot_params=False`` since they can be
re-read from the job's initial weights).

Recovery rolls sessions back to the last snapshot and re-decodes; greedy
decoding makes the regenerated continuation bitwise identical.
"""

from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from repro.core.checkpoint import CheckpointEngine, EngineConfig
from repro.models.model import Model
from repro.obs.trace import tracer
from repro.runtime.cluster import HeartbeatMonitor, VirtualCluster
from repro.runtime.failures import FailureInjector, ProcessFaultException
from repro.runtime.replica import ReplicaTeam
from repro.runtime.state import ShardPlan, ShardedStateEntity
from repro.runtime.straggler import StragglerDetector
from repro.sharding.axes import rules_for_shape, tree_pspecs
from repro.sharding.mesh import abstract_mesh
from repro.sharding.spec import specs_to_shape_dtype
from repro.utils.logging import get_logger

log = get_logger("runtime.server")
_TR = tracer()


class MetricsServer:
    """Tiny stdlib scrape endpoint for a :class:`repro.obs.MetricsRegistry`.

    ``GET /metrics`` renders Prometheus text exposition; ``GET /metrics.json``
    renders the same registry as a JSON snapshot. The registry is resolved
    through ``registry_fn`` at every request — the trainer/server swaps its
    CheckpointEngine (and with it the engine-local registry) on elastic
    shrink, and the endpoint must follow the live engine, not a stale one.
    """

    def __init__(self, registry_fn: Callable[[], Any], port: int = 0) -> None:
        self._registry_fn = registry_fn

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(handler) -> None:  # noqa: N805 — http.server idiom
                try:
                    reg = registry_fn()
                    if handler.path.rstrip("/") in ("", "/metrics"):
                        body = reg.render_prometheus().encode()
                        ctype = "text/plain; version=0.0.4; charset=utf-8"
                    elif handler.path == "/metrics.json":
                        body = json.dumps(reg.snapshot()).encode()
                        ctype = "application/json"
                    else:
                        handler.send_error(404)
                        return
                except Exception as e:  # pragma: no cover — scrape must not kill serving
                    handler.send_error(500, str(e))
                    return
                handler.send_response(200)
                handler.send_header("Content-Type", ctype)
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.wfile.write(body)

            def log_message(handler, fmt, *args) -> None:
                log.debug("metrics scrape: " + fmt, *args)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
        self._httpd.daemon_threads = True
        self.port: int = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="metrics-server", daemon=True
        )
        self._thread.start()
        log.info("metrics endpoint listening on 127.0.0.1:%d", self.port)

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}/metrics"

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


def start_metrics_server(registry_fn: Callable[[], Any], port: int = 0) -> MetricsServer:
    """Serve ``registry_fn()`` on ``/metrics`` + ``/metrics.json``; ``port=0``
    picks a free port (read it back from ``.port``)."""
    return MetricsServer(registry_fn, port)


@dataclass
class ServerConfig:
    batch: int = 4
    max_seq: int = 64
    checkpoint_every_tokens: int = 8
    n_virtual_hosts: int = 4
    n_spares: int = 4
    snapshot_params: bool = False
    # "spare": paper §5.2.4 substitution (falls back to elastic when the spare
    # pool runs dry). "elastic": N-to-M shrink onto the survivors — serving
    # capacity degrades instead of the job dying.
    recovery_policy: str = "spare"
    # "async" captures session snapshots at the decode boundary and overlaps
    # the encode/transfer/verify pipeline with the next decode steps,
    # committing at the following boundary (DESIGN.md §9).
    checkpoint_mode: str = "sync"     # sync | async
    # Hot-replica team (DESIGN.md §15): a shadow cluster + engine lazy-synced
    # one committed generation behind the primary; on primary failure it is
    # *promoted* (zero-comm unpack) instead of blocking on a codec rebuild.
    replica_team: bool = False
    # Heartbeat liveness (DESIGN.md §15): timeout detection per serving tick,
    # the only path that notices silent deaths (no fault at the barrier).
    # A rank is declared dead after miss_threshold x straggler-grace ticks.
    heartbeat: bool = True
    heartbeat_miss_threshold: int = 3
    engine: EngineConfig = field(default_factory=EngineConfig)


class Server:
    def __init__(self, model: Model, scfg: ServerConfig, params: Any | None = None,
                 injector: FailureInjector | None = None) -> None:
        assert not model.cfg.is_encoder, "serving loop decodes; encoder archs export prefill only"
        assert scfg.checkpoint_mode in ("sync", "async"), scfg.checkpoint_mode
        self.model = model
        self.scfg = scfg
        self.params = params if params is not None else model.init(jax.random.PRNGKey(0))

        self.sessions: dict[str, Any] = {}  # cache/tokens/pos once prefilled
        self._prefill = jax.jit(
            lambda p, toks, **kw: model.prefill(p, tokens=toks, **kw)
        )
        self._decode = jax.jit(
            lambda p, cache, tok, pos: model.decode_step(p, cache, tok, pos)
        )

        # Failure-domain plan from production decode rules.
        prod_mesh = abstract_mesh(("data", 16), ("model", 16))
        rules = rules_for_shape(model.rules, "decode", scfg.batch)
        cache_specs = model.abstract_cache(scfg.batch, scfg.max_seq)
        sess_sds = {
            "cache": specs_to_shape_dtype(cache_specs),
            "tokens": jax.ShapeDtypeStruct((scfg.batch, scfg.max_seq), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
        sess_pspecs = {
            "cache": tree_pspecs(cache_specs, rules, prod_mesh),
            "tokens": jax.sharding.PartitionSpec(),
            "pos": jax.sharding.PartitionSpec(),
        }
        self.plan = ShardPlan.from_pspecs(sess_sds, sess_pspecs)

        self.cluster = VirtualCluster(scfg.n_virtual_hosts, scfg.n_spares)
        self._build_engine(scfg.n_virtual_hosts)
        self.injector = injector or FailureInjector(scfg.n_virtual_hosts)
        self.n_recoveries = 0
        self.promotions = 0
        self._metrics_server: MetricsServer | None = None
        self.straggler = StragglerDetector(scfg.n_virtual_hosts)
        self._hb_tick = 0  # monotonic serving tick feeding the heartbeat
        self.heartbeat = self._new_heartbeat() if scfg.heartbeat else None
        # Shadow team: its engine comes from the same factory, so promotion
        # restores through the identical entity hooks.
        self.replica = (
            ReplicaTeam(scfg.n_virtual_hosts, self._new_engine,
                        n_spares=scfg.n_spares)
            if scfg.replica_team else None
        )

    def start_metrics_server(self, port: int = 0) -> MetricsServer:
        """Expose the live engine's registry (survives engine swaps) on
        ``/metrics`` + ``/metrics.json``; returns the running endpoint."""
        if self._metrics_server is None:
            self._metrics_server = start_metrics_server(
                lambda: self.engine.registry, port
            )
        return self._metrics_server

    def stop_metrics_server(self) -> None:
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None

    def _new_engine(self, n_ranks: int) -> CheckpointEngine:
        """Engine factory shared by the primary and the shadow team: both
        register the same live-session entity, so whichever engine restores
        resolves the in-flight sessions against itself."""
        eng = CheckpointEngine(n_ranks, self.scfg.engine)
        eng.register(
            "sessions",
            ShardedStateEntity(lambda: self.sessions, self._set_sessions, self.plan),
        )
        return eng

    def _build_engine(self, n_ranks: int) -> None:
        if getattr(self, "engine", None) is not None:
            self.engine.close()  # join + release the old pipeline worker
        self.engine = self._new_engine(n_ranks)
        self.cluster.attach_engine(self.engine)

    def _new_heartbeat(self) -> HeartbeatMonitor:
        return HeartbeatMonitor(
            self.cluster.n_ranks,
            miss_threshold=self.scfg.heartbeat_miss_threshold,
            straggler=self.straggler,
            registry=self.engine.registry,
            journal=self.engine.journal,
        )

    def _set_sessions(self, sessions: dict[str, Any]) -> None:
        self.sessions = sessions

    # ------------------------------------------------------------------ #
    def prefill(self, prompts: np.ndarray, **extra_inputs: Any) -> None:
        """prompts: (batch, prompt_len) int32."""
        B, P = prompts.shape
        assert B == self.scfg.batch
        logits, cache = self._prefill(self.params, jnp.asarray(prompts), **extra_inputs)
        # Grow prefill cache (length P) into the max_seq serving cache.
        full = self.model.init_cache(B, self.scfg.max_seq)
        def merge(fc, pc):
            if fc.shape == pc.shape:
                return pc
            return fc.at[tuple(slice(0, s) for s in pc.shape)].set(pc)
        cache = jax.tree.map(merge, full, cache)
        tokens = jnp.zeros((B, self.scfg.max_seq), jnp.int32)
        tokens = tokens.at[:, :P].set(jnp.asarray(prompts))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tokens = tokens.at[:, P].set(nxt)
        self.sessions = {"cache": cache, "tokens": tokens, "pos": jnp.asarray(P, jnp.int32)}

    def decode(self, n_tokens: int) -> np.ndarray:
        """Greedy-decode n_tokens for every session, fault-tolerantly."""
        produced = 0
        ticks = 0
        while produced < n_tokens:
            try:
                with _TR.span("decode_tick"):
                    with _TR.span("tick_control"):
                        self.cluster.barrier("decode")
                        # Commit an overlapped checkpoint from the previous decode
                        # boundary (its pipeline ran behind the last steps).
                        pending = self.engine.finalize_async()
                        if pending is False:
                            raise ProcessFaultException(
                                sorted(self.cluster.failed), "checkpoint"
                            )
                        if pending and self.replica is not None:
                            self._replica_tick()
                        # staged tier flush starts here, behind the next decode steps
                        self.engine.kick_tier_flush()
                        for r in self.injector.kills_at_step(ticks):
                            self.cluster.kill(r)
                        for r in self.injector.silent_kills_at_step(ticks):
                            self.cluster.kill(r, cause="silent_death", silent=True)
                        if self.replica is not None:
                            for r in self.injector.replica_kills_at_step(ticks):
                                self.replica.cluster.kill(r, cause="replica_host_failure")
                        ticks += 1
                        self._hb_tick += 1
                        if self.heartbeat is not None:
                            lost = self.heartbeat.observe(
                                self.cluster.alive(), self._hb_tick
                            )
                            if lost:
                                for r in lost:
                                    self.injector.note_detection(r)
                                raise ProcessFaultException(lost, "heartbeat")
                        self.cluster.barrier("decode")
                        pos = int(self.sessions["pos"])
                        tok = self.sessions["tokens"][:, pos]

                    with _TR.span("decode_step"):
                        logits, cache = self._decode(
                            self.params, self.sessions["cache"], tok, jnp.asarray(pos, jnp.int32)
                        )
                    with _TR.span("tick_update"):
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        tokens = self.sessions["tokens"].at[:, pos + 1].set(nxt)
                        self.sessions = {
                            "cache": cache, "tokens": tokens,
                            "pos": jnp.asarray(pos + 1, jnp.int32),
                        }
                        produced = self._produced()

                    if produced % self.scfg.checkpoint_every_tokens == 0:
                        if self.scfg.checkpoint_mode == "async":
                            # Capture now; the pipeline overlaps the next decodes.
                            ok = self.engine.checkpoint_async({"pos": pos + 1})
                        else:
                            ok = self.engine.checkpoint({"pos": pos + 1})
                            if ok and self.replica is not None:
                                self._replica_tick()
                        if not ok:
                            raise ProcessFaultException(
                                sorted(self.cluster.failed), "checkpoint"
                            )
            except ProcessFaultException as e:
                log.warning("serving fault: %s", e)
                self.recover()
                produced = self._produced()
        # Commit a still-in-flight overlapped checkpoint before handing the
        # tokens back, so the final session state is protected.
        final = self.engine.finalize_async()
        if final is False:
            log.warning(
                "final session checkpoint aborted (rank died during the "
                "trailing pipeline); sessions re-protect on the next decode"
            )
        elif final and self.replica is not None:
            self._replica_tick()
        return np.asarray(self.sessions["tokens"])

    def _produced(self) -> int:
        return int(self.sessions["pos"]) - self._prompt_len

    def prefill_and_decode(self, prompts: np.ndarray, n_tokens: int, **extra) -> np.ndarray:
        self._prompt_len = prompts.shape[1]
        self.prefill(prompts, **extra)
        # First checkpoint right after prefill (the serving baseline state).
        if self.engine.checkpoint({"pos": int(self.sessions["pos"])}):
            if self.replica is not None:
                self._replica_tick()
        return self.decode(n_tokens)

    def _replica_tick(self) -> None:
        """Lazy-sync step at every commit point: install the generation
        staged at the PREVIOUS commit into the shadow stores, then stage the
        generation that just committed. The shadow thus trails the primary
        by exactly one committed generation (DESIGN.md §15)."""
        self.replica.catch_up()
        self.replica.stage(self.engine)

    def recover(self) -> None:
        """Recovery entry: the replication rung sits ABOVE the codec ladder —
        a synced shadow team is promoted (no blocking rebuild) and only teams
        without a promotable shadow fall into the restore machinery."""
        with _TR.span("recover"):
            if self.replica is not None and self.replica.can_promote:
                self._promote_replica()
            else:
                self._recover_current()
            if self.heartbeat is not None:
                # Rebuild against the (possibly promoted/resized) engine so the
                # liveness gauge lands in the live registry, and re-arm beats.
                self.heartbeat = self._new_heartbeat()
                self.heartbeat.reset(self.cluster.alive(), self._hb_tick)

    def _promote_replica(self) -> None:
        """Zero-downtime failover: swap the shadow team in as the serving
        cluster + engine, roll sessions back to its synced generation (an
        all-survivor zero-comm unpack when the shadow is intact; a codec
        rebuild for members that died during catch-up; tier escalation
        beyond tolerance), then rebuild the old team off the critical path
        and re-enroll it as the new shadow."""
        t0 = time.perf_counter()
        failed_primary = sorted(self.cluster.failed)
        old_engine = self.engine
        old_engine.discard_pending()  # stop in-flight pipeline workers
        self.cluster, self.engine = self.replica.release()
        failed_shadow = sorted(self.cluster.failed)
        gen = self.replica.synced_gen
        _TR.instant(
            "replica_promote", gen=gen,
            failed_primary=len(failed_primary), failed_shadow=len(failed_shadow),
        )
        with _TR.span("replica_promote_restore", gen=gen):
            self._recover_current()
        stall = time.perf_counter() - t0
        self.promotions += 1
        self.engine.journal.record(
            "replica_promote", gen=gen, duration_s=stall,
            failed_primary=len(failed_primary),
            failed_shadow=len(failed_shadow),
            zero_comm=not failed_shadow,
        )
        log.info(
            "replica promoted at gen %d in %.3fs (primary lost %d rank(s); "
            "shadow lost %d)", gen, stall, len(failed_primary),
            len(failed_shadow),
        )
        # Old team: rebuilt in the background and re-enrolled as the shadow;
        # it lazy-syncs back to ready at the next commit point.
        self.replica.re_enroll(old_engine)

    def _recover_current(self) -> None:
        if not self.engine.has_valid_checkpoint:
            if not self.engine.has_tier_data():
                raise RuntimeError("no valid session checkpoint")
            # Whole-serving-job loss: every in-memory session snapshot died
            # with its host — all ranks rejoin and the engine escalates to
            # the persistent tier ladder inside restore (DESIGN.md §12).
            log.warning("no in-memory session checkpoint; escalating to the tier ladder")
            self.cluster.restart_all()
        # With no failed ranks (a clean replica promotion) there is nothing
        # to shrink around: stabilize is a no-op and restore is zero-comm.
        elastic = bool(self.cluster.failed) and (
            self.scfg.recovery_policy == "elastic"
            or self.cluster.spares_left < len(self.cluster.failed)
        )
        if elastic:
            # Shrink onto the survivors: repartition the session checkpoint
            # onto M = |alive| ranks and re-protect the new world right away.
            # restore_elastic consumed the old checkpoint, so a failed
            # re-protect (rank death mid-exchange) shrinks again and retries
            # — the restored sessions are still live in memory.
            m = len(self.cluster.alive())
            meta = self.engine.restore_elastic(m)
            self.cluster.resize(m)
            while not self.engine.checkpoint({"pos": int(meta.get("pos", 0))}):
                m = len(self.cluster.alive())
                if m < 1:
                    raise RuntimeError("all ranks died while re-protecting sessions")
                log.warning("re-protect checkpoint failed; shrinking to %d", m)
                self._build_engine(m)
                self.cluster.resize(m)
            log.info(
                "elastic shrink to %d ranks; sessions rolled back to pos %s",
                m, meta.get("pos"),
            )
        else:
            self.cluster.stabilize("spare")
            meta = self.engine.restore()
            s = self.engine.stats
            log.info(
                "sessions rolled back to pos %s (codec=%s/t%d, restore=%s "
                "%.3fs: %d chunks, %.1f MiB rebuilt)",
                meta.get("pos"), self.engine.codec.name, self.engine.codec.tolerance(),
                self.scfg.engine.restore_mode, s.last_restore_s,
                s.last_restore_chunks, s.last_restore_bytes_rebuilt / 2**20,
            )
        self.n_recoveries += 1
