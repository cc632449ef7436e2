"""End-to-end fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
        --steps 200 --batch 8 --seq 128 --mtbf 3600 --spares 2

``--reduced`` swaps in a tiny same-family config, which is what a CPU run
can afford. Without it the architecture runs at its published size on the
default JAX device: on one TPU v5e, for example,

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
        --batch 4 --seq 2048 --steps 6 --period 2 --checkpoint-mode async \
        --codec xor --parity-group 4

The train step runs unsharded on that one device; the ``--hosts`` virtual
failure-domain ranks split its checkpoint in host memory. ``chip_smoke.py``
at the repository root drives this entry point on the chip end to end.
"""

from __future__ import annotations

import argparse
import json

from repro.configs import get_config, list_archs
from repro.core.checkpoint import EngineConfig
from repro.models import build_model
from repro.models.model import Model
from repro.obs.trace import tracer
from repro.runtime.failures import FailureInjector
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import get_logger

log = get_logger("launch.train")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--hosts", type=int, default=4, help="virtual failure-domain ranks")
    ap.add_argument("--spares", type=int, default=2)
    ap.add_argument("--policy", choices=["spare", "shrink", "elastic"], default="spare")
    ap.add_argument("--mtbf", type=float, default=3600.0, help="per-host MTBF (s)")
    ap.add_argument("--inject-mtbf", type=float, default=None,
                    help="simulate failures with this per-host MTBF (s)")
    ap.add_argument("--period", type=int, default=None,
                    help="checkpoint period in steps (default: Daly-optimal)")
    ap.add_argument("--scheme", default="pairwise")
    ap.add_argument("--parity-group", type=int, default=0,
                    help="erasure group size k (selects the xor codec unless --codec)")
    ap.add_argument("--codec", default="",
                    help="redundancy codec: copy | xor | rs (default: inferred)")
    ap.add_argument("--rs-parity", type=int, default=2,
                    help="m parity blobs per group for --codec rs")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--delta", action="store_true",
                    help="differential checkpointing (DESIGN.md §17): diff each "
                         "snapshot against the committed generation on a chunk "
                         "grid, skip clean-chunk copies on the transfer path, "
                         "and patch striped parity incrementally instead of "
                         "re-encoding in full")
    ap.add_argument("--delta-chunk-bytes", type=int, default=1 << 20,
                    help="dirty-map chunk granularity for --delta")
    ap.add_argument("--tier-dedup", action="store_true",
                    help="content-addressed delta flushes on --tier-dir: "
                         "generations reference unchanged chunks in the tier's "
                         "chunk store instead of re-writing full rank files "
                         "(refcounted GC replaces blind keep-2 pruning)")
    ap.add_argument("--checkpoint-mode", choices=["sync", "async"], default="sync",
                    help="async overlaps the encode/transfer/verify pipeline "
                         "with the next train steps (DESIGN.md §9)")
    ap.add_argument("--async-workers", type=int, default=1,
                    help="background pipeline workers for --checkpoint-mode async "
                         "(0 drains at the next step boundary instead); >1 also "
                         "parallelizes recovery across failure groups")
    ap.add_argument("--restore-mode", choices=["pipelined", "sync"], default="pipelined",
                    help="pipelined drains the chunked TRANSFER/DECODE/VERIFY "
                         "recovery pipeline (DESIGN.md §10); sync keeps the "
                         "serial per-origin decode baseline")
    ap.add_argument("--tier-dir", default=None,
                    help="persistent disk rung of the storage-tier ladder "
                         "(DESIGN.md §12): committed checkpoints flush here in "
                         "the background; recovery escalates to it when "
                         "failures exceed codec tolerance or on cold restart")
    ap.add_argument("--disk-flush-every", type=int, default=0,
                    help="flush the disk tier every k-th committed checkpoint "
                         "(0 = adaptive per-level Daly schedule)")
    ap.add_argument("--tier-mtbf", type=float, default=30 * 24 * 3600.0,
                    help="MTBF (s) of the failures the diskless tier cannot "
                         "survive (whole-job loss / beyond-tolerance bursts) — "
                         "drives the adaptive disk-flush cadence")
    ap.add_argument("--cold-restart", action="store_true",
                    help="resume from the newest --tier-dir generation instead "
                         "of initializing fresh (elastic N-to-M when the stored "
                         "world size differs from --hosts)")
    ap.add_argument("--out", default=None, help="write history JSON here")
    ap.add_argument("--trace-out", default=None,
                    help="record checkpoint/restore spans and write a "
                         "Chrome-trace JSON here (load in Perfetto, or render "
                         "with `python -m repro.launch.report <file>`)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the engine's Prometheus registry on "
                         "http://127.0.0.1:PORT/metrics (0 = pick a free port)")
    return ap


def build_trainer(
    args: argparse.Namespace, model: Model, injector: FailureInjector | None = None
) -> Trainer:
    """The trainer this entry point runs for ``args``; ``injector`` replaces the
    ``--inject-mtbf`` one (a caller's scripted kill schedule)."""
    if injector is None and args.inject_mtbf:
        injector = FailureInjector(
            args.hosts, mtbf_rank_s=args.inject_mtbf, step_time_s=1.0, seed=17
        )

    tcfg = TrainerConfig(
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        total_steps=args.steps,
        n_virtual_hosts=args.hosts,
        n_spares=args.spares,
        recovery_policy=args.policy,
        mtbf_individual_s=args.mtbf,
        checkpoint_period=args.period,
        checkpoint_mode=args.checkpoint_mode,
        tier_dir=args.tier_dir,
        disk_flush_every=args.disk_flush_every,
        tier_mtbf_s=args.tier_mtbf,
        tier_dedup=args.tier_dedup,
        engine=EngineConfig(
            scheme=args.scheme,
            parity_group=args.parity_group,
            codec=args.codec,
            rs_parity=args.rs_parity,
            compress=args.compress,
            delta=args.delta,
            delta_chunk_bytes=args.delta_chunk_bytes,
            async_workers=args.async_workers,
            restore_mode=args.restore_mode,
        ),
    )
    return Trainer(model, tcfg, injector=injector)


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    if args.cold_restart and not args.tier_dir:
        ap.error("--cold-restart requires --tier-dir")

    use_compile_cache()
    if args.trace_out:
        tracer().enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    log.info("arch %s: %s params (%s active)", cfg.name, f"{model.n_params:,}",
             f"{model.n_active_params:,}")

    trainer = build_trainer(args, model)
    metrics_server = None
    if args.metrics_port is not None:
        from repro.runtime.server import start_metrics_server

        metrics_server = start_metrics_server(
            lambda: trainer.engine.registry, args.metrics_port
        )
    if args.cold_restart:
        meta = trainer.cold_restart()
        log.info("cold restart: resuming from step %s", meta.get("step"))
    history = trainer.run(args.steps)

    log.info(
        "done: %d steps, %d recoveries, %d checkpoints (%.3fs each), "
        "Daly period %d steps, predicted overhead %.2f%%",
        int(trainer.state["step"]),
        trainer.n_recoveries,
        trainer.engine.stats.created,
        trainer.engine.stats.last_create_s,
        trainer.scheduler.period_steps,
        100 * trainer.scheduler.expected_overhead,
    )
    log.info("loss: first=%.4f last=%.4f", history[0]["loss"], history[-1]["loss"])
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": history, "timers": trainer.timers.report()}, f, indent=2)
    if args.trace_out:
        tracer().write(args.trace_out)
        log.info("trace written to %s (%d events)", args.trace_out,
                 len(tracer().events()))
    if metrics_server is not None:
        metrics_server.stop()


if __name__ == "__main__":
    main()
