"""Fault-tolerant batched serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
        --batch 4 --prompt-len 8 --gen 32 --kill-at 10:2

``--reduced`` swaps in a tiny same-family config for CPU runs. Without it
the architecture serves at its published size on the default JAX device: on
one TPU v5e, for example,

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m \
        --batch 4 --prompt-len 512 --gen 16 --ckpt-every 8 --kill-at 10:2

``chip_smoke.py`` at the repository root drives this entry point on the chip
and checks the regenerated tokens against a run without the kill.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.core.checkpoint import EngineConfig
from repro.models import build_model
from repro.models.model import Model
from repro.obs.trace import tracer
from repro.runtime.failures import FailureInjector
from repro.runtime.server import Server, ServerConfig
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import get_logger

log = get_logger("launch.serve")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--hosts", type=int, default=4)
    ap.add_argument("--ckpt-every", type=int, default=8, help="tokens between session checkpoints")
    ap.add_argument("--kill-at", default=None,
                    help="comma list of tick:rank kill events, e.g. 10:2,17:0")
    ap.add_argument("--silent-kill-at", default=None,
                    help="comma list of tick:rank SILENT kills (the rank "
                         "stops heartbeating without a fault at the barrier; "
                         "only the heartbeat timeout detects it)")
    ap.add_argument("--replica-team", action="store_true",
                    help="run a hot-replica shadow team lazy-synced one "
                         "generation behind; failures promote it instead of "
                         "blocking on a codec rebuild (DESIGN.md §15)")
    ap.add_argument("--heartbeat-miss", type=int, default=3,
                    help="beats a rank may miss (x straggler grace) before "
                         "the heartbeat monitor declares it dead")
    ap.add_argument("--codec", default="",
                    help="redundancy codec: copy | xor | rs (default: inferred)")
    ap.add_argument("--parity-group", type=int, default=0,
                    help="erasure group size k for xor/rs codecs")
    ap.add_argument("--rs-parity", type=int, default=2,
                    help="m parity blobs per group for --codec rs")
    ap.add_argument("--checkpoint-mode", choices=["sync", "async"], default="sync",
                    help="async overlaps the session-checkpoint pipeline with "
                         "the next decode steps (DESIGN.md §9)")
    ap.add_argument("--trace-out", default=None,
                    help="record checkpoint/restore spans and write a "
                         "Chrome-trace JSON here (Perfetto-loadable)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the engine's Prometheus registry on "
                         "http://127.0.0.1:PORT/metrics (0 = free port)")
    return ap


def build_server(args: argparse.Namespace, model: Model) -> Server:
    """The server this entry point runs for ``args``, with the ``--kill-at`` /
    ``--silent-kill-at`` schedules wired into its failure injector."""

    def _parse_kills(spec: str | None) -> dict[int, list[int]]:
        schedule: dict[int, list[int]] = {}
        for ev in (spec or "").split(","):
            if not ev:
                continue
            t, r = ev.split(":")
            schedule.setdefault(int(t), []).append(int(r))
        return schedule

    injector = None
    if args.kill_at or args.silent_kill_at:
        injector = FailureInjector(
            args.hosts,
            schedule=_parse_kills(args.kill_at),
            silent_schedule=_parse_kills(args.silent_kill_at),
        )

    scfg = ServerConfig(
        batch=args.batch,
        max_seq=args.prompt_len + args.gen + 2,
        checkpoint_every_tokens=args.ckpt_every,
        n_virtual_hosts=args.hosts,
        checkpoint_mode=args.checkpoint_mode,
        replica_team=args.replica_team,
        heartbeat_miss_threshold=args.heartbeat_miss,
        engine=EngineConfig(
            codec=args.codec, parity_group=args.parity_group, rs_parity=args.rs_parity
        ),
    )
    return Server(model, scfg, injector=injector)


def make_prompts(args: argparse.Namespace, vocab_size: int) -> np.ndarray:
    """The seeded random prompts this entry point serves."""
    return np.random.default_rng(0).integers(
        0, vocab_size, (args.batch, args.prompt_len), dtype=np.int32
    )


def main() -> None:
    args = build_parser().parse_args()

    use_compile_cache()
    if args.trace_out:
        tracer().enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only (no decode step)")
    model = build_model(cfg)
    server = build_server(args, model)
    if args.metrics_port is not None:
        server.start_metrics_server(args.metrics_port)
    prompts = make_prompts(args, cfg.vocab_size)
    extra = {}
    if cfg.vision_tokens:
        extra["vision"] = jax.random.normal(
            jax.random.PRNGKey(0), (args.batch, cfg.vision_tokens, cfg.frontend_stub_dim)
        )
    out = server.prefill_and_decode(prompts, args.gen, **extra)
    log.info("generated %d tokens x %d sessions; %d recoveries (%d via "
             "replica promotion)",
             args.gen, args.batch, server.n_recoveries, server.promotions)
    for b in range(min(args.batch, 2)):
        log.info("session %d: %s", b, out[b, : args.prompt_len + args.gen].tolist())
    if args.trace_out:
        tracer().write(args.trace_out)
        log.info("trace written to %s (%d events)", args.trace_out,
                 len(tracer().events()))
    server.stop_metrics_server()


if __name__ == "__main__":
    main()
