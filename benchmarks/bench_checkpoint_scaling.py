"""Paper Fig. 4/5: weak scaling of checkpoint-creation duration, plus the
sync-vs-async pipeline comparison (DESIGN.md §9).

Fixed per-rank payload, growing rank count — the paper's claim is that the
duration stays (nearly) constant because the exchange volume per rank depends
on the redundancy, not on the rank count. Measured here on the host-tier
engine (virtual ranks, one process); the TPU-tier bound comes from the
dry-run roofline (see §Roofline checkpoint rows).

The async rows measure the **blocked time** of the pipelined path: phase A
capture + whatever of phase B the overlap window didn't hide (the window is
the simulated train step; the benchmark waits for the background drain the
way a real step would run concurrently). The tier-flush rows (DESIGN.md §12)
compare that blocked time against the same engine with a disk rung flushing
every commit — the background flush must stay off the critical path (<10%
overhead is the acceptance target; ``run.py --smoke`` gates at 20%).
``RESULTS`` carries the machine-readable numbers run.py folds into
BENCH_results.json: GB/s creation throughput, modeled PCIe bytes, speedup,
overlap efficiency, tier-flush overhead + write throughput.
"""

from __future__ import annotations

import os
import time

import numpy as np

from repro.core.checkpoint import CheckpointEngine, EngineConfig

#: populated by main(); run.py serializes it into BENCH_results.json
RESULTS: dict = {}


class _Payload:
    """Fixed bytes-per-rank sharded entity (the paper's blocks-per-process)."""

    def __init__(self, n_ranks: int, bytes_per_rank: int) -> None:
        self.n = n_ranks
        self.per = bytes_per_rank // 4
        self.data = [np.random.default_rng(r).standard_normal(self.per).astype(np.float32)
                     for r in range(n_ranks)]

    def snapshot_shards(self, n):
        return [{"blocks": self.data[r]} for r in range(n)]

    def restore_shards(self, shards):
        for origin, payload in shards.items():
            self.data[origin] = np.asarray(payload["blocks"])


def _blocked_checkpoint(eng: CheckpointEngine, meta, async_mode: bool) -> float:
    """Wall time the caller is blocked for one checkpoint. Async: capture +
    finalize join, with the overlap window (the next train step) simulated by
    waiting for the background drain before finalizing — the best the overlap
    can do, which is exactly what the pipeline buys on a real step."""
    if not async_mode:
        t0 = time.perf_counter()
        ok = eng.checkpoint(meta)
        assert ok
        return time.perf_counter() - t0
    t0 = time.perf_counter()
    ok = eng.checkpoint_async(meta)
    blocked = time.perf_counter() - t0
    assert ok
    while not eng.drain_done():            # the overlapped "train step"
        time.sleep(1e-4)
    t1 = time.perf_counter()
    done = eng.finalize_async()
    blocked += time.perf_counter() - t1
    assert done
    return blocked


def run(bytes_per_rank: int = 1 << 20, ranks=(2, 4, 8, 16, 32, 64), scheme: str = "pairwise",
        parity_group: int = 0, repeats: int = 3, async_mode: bool = False):
    rows = []
    for n in ranks:
        eng = CheckpointEngine(
            n, EngineConfig(scheme=scheme, parity_group=parity_group, validate=True)
        )
        eng.register("domain", _Payload(n, bytes_per_rank))
        eng.checkpoint({"step": 0})  # warm
        times = []
        for _ in range(repeats):
            times.append(_blocked_checkpoint(eng, {"step": 1}, async_mode))
        # normalize: host-tier sim does all ranks' work serially in one
        # process; per-rank time is the scalable quantity (paper's y-axis).
        per_rank_us = min(times) / n * 1e6
        rows.append((n, per_rank_us, eng.stats.last_bytes_per_rank, min(times), eng))
    return rows


def _pcie_model(eng: CheckpointEngine) -> int:
    """Modeled device->host bytes for one checkpoint across all ranks: every
    own/exchange byte staged once, plus (striped codecs) the m/g parity
    stripes — mirrors SnapshotProgram.pcie_bytes for the host tier."""
    staged = eng.stats.last_bytes_staged
    return staged + eng.stats.last_bytes_exchanged


def run_staging(
    mbytes: int = 8, repeats: int = 3
) -> tuple[float, float, float, bool, int]:
    """Double-buffered device staging (DESIGN.md §9 follow-up): drive the
    snapshot's per-chunk programs through ``staged_snapshot_fetch`` and
    compare overlapped D2H (dispatch encode of chunk g+1, then start chunk
    g's async host copy) against the sequential fetch-then-dispatch
    baseline. On a real accelerator the win approaches hiding the full DMA
    behind the encode; on this CPU container it mainly validates the
    mechanism and its bit-identical payloads. The third timing drives the
    default auto mode — the payload crossover (DESIGN.md §14) that falls
    back to the sequential fetch when the modeled D2H bytes are too small
    for the overlap to pay. Returns (t_seq, t_dbuf, t_auto, auto_dbuf,
    payload_bytes)."""
    import jax
    import jax.numpy as jnp

    from repro.core.device_tier import (
        _DBUF_MIN_BYTES, build_snapshot_program, staged_snapshot_fetch,
    )
    from repro.sharding.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    n = mbytes << 20
    sds = {
        "f32": jax.ShapeDtypeStruct((n // 8,), jnp.float32),
        "bf16": jax.ShapeDtypeStruct((n // 4,), jnp.bfloat16),
        "i8": jax.ShapeDtypeStruct((n // 4,), jnp.int8),
    }
    ps = {k: jax.sharding.PartitionSpec("data") for k in sds}
    prog = build_snapshot_program(
        mesh, sds, ps, validate=False, codec="xor", parity_group=1,
    )
    rng = np.random.default_rng(0)
    state = {
        "f32": jnp.asarray(rng.standard_normal(n // 8), jnp.float32),
        "bf16": jnp.asarray(rng.standard_normal(n // 4), jnp.bfloat16),
        "i8": jnp.asarray(rng.integers(-100, 100, n // 4), jnp.int8),
    }
    times = {True: float("inf"), False: float("inf"), None: float("inf")}
    payloads = {}
    for db in (True, False, None):
        payloads[db] = staged_snapshot_fetch(prog, state, double_buffer=db)  # warm
        for _ in range(repeats):
            t0 = time.perf_counter()
            staged_snapshot_fetch(prog, state, double_buffer=db)
            times[db] = min(times[db], time.perf_counter() - t0)
    # overlap / crossover must never change bytes
    for tag in payloads[True]["parity"]:
        assert np.array_equal(payloads[True]["parity"][tag], payloads[False]["parity"][tag])
        assert np.array_equal(payloads[None]["parity"][tag], payloads[False]["parity"][tag])
    total = sum(np.asarray(v).nbytes for v in jax.tree.leaves(payloads[True]))
    auto_dbuf = prog.pcie_bytes >= _DBUF_MIN_BYTES
    return times[False], times[True], times[None], auto_dbuf, total


def run_tier_flush(
    n: int = 8, bytes_per_rank: int = 1 << 20, repeats: int = 12
) -> dict:
    """Background disk-tier flush (DESIGN.md §12): compare the async blocked
    time (capture + finalize join) WITH a disk rung flushing every commit
    against a baseline that writes the SAME generation to disk out-of-band
    between steps — the A/B isolates the cost of the *engine-integrated*
    background flush (snapshot staging at the commit point, deferred kick,
    bank-conflict discipline) from the cache/page-cache side-effects any
    disk write pays regardless of who issues it. The flush runs on the
    drain pool after the pointer swap; the acceptance criterion is that it
    adds <10% to the blocked capture window. Also reports the flush's own
    wall time and throughput (the background cost the per-level Daly
    schedule consumes)."""
    import shutil
    import tempfile

    from repro.core import storage

    tmp = tempfile.mkdtemp(prefix="bench-tier-")
    out: dict = {}
    try:
        engines = {}
        oob_tier = storage.DiskTier(storage.disk(os.path.join(tmp, "oob"), every=1))
        for tag, tiers in [
            ("base", ()),
            ("flush", (storage.disk(os.path.join(tmp, "eng"), every=1),)),
        ]:
            eng = CheckpointEngine(
                n, EngineConfig(parity_group=4, validate=True, tiers=tiers)
            )
            pay = _Payload(n, bytes_per_rank)
            eng.register("domain", pay)
            eng.checkpoint({"step": 0})  # warm
            eng._join_flush()
            best = float("inf")
            for i in range(repeats):
                best = min(best, _blocked_checkpoint(eng, {"step": i + 1}, True))
                eng._join_flush()
                if tag == "base":
                    # equalize disk/cache side-effects: same bytes written,
                    # just not through the engine's background machinery
                    oob_tier.flush(storage.capture_snapshot(eng))
                for d in pay.data:  # the inter-checkpoint "train step": the
                    d *= np.float32(1.0)  # live state is touched either way
            engines[tag] = eng
            out[f"blocked_s_{tag}"] = best
        eng = engines["flush"]
        eng._join_flush()
        out["tier_flush_overhead"] = max(
            0.0, out["blocked_s_flush"] / max(out["blocked_s_base"], 1e-9) - 1.0
        )
        out["flush_s"] = eng.stats.last_flush_s
        out["flush_bytes"] = eng.stats.last_flush_bytes
        out["flush_gbps"] = eng.stats.last_flush_bytes / max(eng.stats.last_flush_s, 1e-9) / 1e9
        out["tier_flushes"] = eng.stats.tier_flushes
        out["tier_flush_skipped"] = eng.stats.tier_flush_skipped
        for e in engines.values():
            e.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def run_delta_ab(
    n: int = 8, bytes_per_rank: int = 1 << 20, commits: int = 6,
    churn: float = 0.10,
) -> dict:
    """Differential-checkpointing A/B at low churn (DESIGN.md §17): the same
    contiguous ~10%-of-state mutation sequence drives a full-encode engine
    with a plain disk rung against a delta engine with a dedup (content-
    addressed) rung. Reports the per-commit flushed bytes both ways — the
    headline ``delta_flush_ratio`` run.py gates at 0.35 — plus the delta
    engine's dirty fraction, transfer bytes skipped, chunk-store dedup ratio,
    and the async blocked time (the delta bookkeeping must not push the
    create path >20% over the full-encode baseline)."""
    import shutil
    import tempfile

    from repro.core import storage

    tmp = tempfile.mkdtemp(prefix="bench-delta-")
    out: dict = {}
    try:
        for tag, delta in (("full", False), ("delta", True)):
            eng = CheckpointEngine(
                n,
                EngineConfig(
                    parity_group=4, validate=True, delta=delta,
                    delta_chunk_bytes=1 << 14,
                    tiers=(storage.disk(os.path.join(tmp, tag), every=1,
                                        dedup=delta, chunk_bytes=1 << 14),),
                ),
            )
            pay = _Payload(n, bytes_per_rank)
            eng.register("domain", pay)
            eng.checkpoint({"step": 0})   # cold commit: full bytes either way
            eng._join_flush()
            best = float("inf")
            flushed = []
            for i in range(commits):
                rng = np.random.default_rng(1000 + i)
                for d in pay.data:
                    m = max(1, int(d.size * churn))
                    start = int(rng.integers(0, d.size - m + 1))
                    d[start : start + m] += rng.standard_normal(m).astype(np.float32)
                best = min(best, _blocked_checkpoint(eng, {"step": i + 1}, True))
                eng._join_flush()
                flushed.append(eng.stats.last_flush_bytes)
            out[f"blocked_s_{tag}"] = best
            out[f"flush_bytes_{tag}"] = sum(flushed) / len(flushed)
            if delta:
                out["dirty_fraction"] = eng.stats.last_dirty_fraction
                out["dedup_ratio"] = eng.stats.last_dedup_ratio
                out["transfer_bytes_skipped"] = eng.stats.last_transfer_bytes_skipped
                out["delta_encodes"] = eng.stats.delta_encodes
                out["chunks_written"] = eng.stats.last_flush_chunks_written
                out["chunks_reused"] = eng.stats.last_flush_chunks_reused
            eng.close()
        out["delta_flush_ratio"] = (
            out["flush_bytes_delta"] / max(out["flush_bytes_full"], 1e-9)
        )
        out["delta_blocked_ratio"] = (
            out["blocked_s_delta"] / max(out["blocked_s_full"], 1e-9)
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def run_trace_overhead(
    n: int = 8, bytes_per_rank: int = 1 << 19, repeats: int = 10, batch: int = 4
) -> dict:
    """Tracing-overhead A/B (DESIGN.md §13 budget): wall time of ``batch``
    async checkpoints (capture + drain + finalize) with the span tracer
    disabled vs enabled. The two legs are *interleaved* (off, on, off, on,
    ...) on the same pair of warm engines, and the reported overhead is the
    **min over per-pair ratios** ``t_on/t_off`` of adjacent repeats: a real
    per-span cost inflates every pair's ratio, while container noise
    (scheduler, page cache, a noisy neighbour) would have to corrupt all
    ``repeats`` adjacent pairs the same way to trip the run.py smoke gate
    (enabled overhead <2%) — one quiet pair is enough for an honest
    measurement. The caller's tracer state is saved and restored
    (run.py --trace-out keeps recording around this A/B)."""
    from repro.obs.trace import tracer

    tr = tracer()
    was_enabled = tr.enabled
    engines = {}
    pairs: list[tuple[float, float]] = []
    try:
        for tag in ("off", "on"):
            eng = CheckpointEngine(n, EngineConfig(parity_group=4, validate=True))
            eng.register("domain", _Payload(n, bytes_per_rank))
            tr.enabled = tag == "on"
            eng.checkpoint({"step": 0})  # warm
            engines[tag] = eng
        step = 1
        for _ in range(repeats):
            leg = {}
            for tag in ("off", "on"):
                tr.enabled = tag == "on"
                eng = engines[tag]
                t0 = time.perf_counter()
                for _ in range(batch):
                    _blocked_checkpoint(eng, {"step": step}, True)
                    step += 1
                leg[tag] = time.perf_counter() - t0
            pairs.append((leg["off"], leg["on"]))
    finally:
        tr.enabled = was_enabled
        for eng in engines.values():
            eng.close()
    off, on = min(pairs, key=lambda p: p[1] / p[0])
    return {
        "t_off": off,
        "t_on": on,
        "trace_overhead_enabled": max(0.0, on / off - 1.0),
    }


def main(smoke: bool = False) -> list[str]:
    lines = []
    weak_ranks = (2, 4, 8) if smoke else (2, 4, 8, 16, 32, 64)
    par_ranks = (4, 8) if smoke else (4, 8, 16, 32, 64)
    per_rank = 1 << 19 if smoke else 1 << 20
    for tag, kw in [
        ("ckpt_weakscale_pairwise", {"ranks": weak_ranks}),
        ("ckpt_weakscale_parity4", {"parity_group": 4, "ranks": par_ranks}),
    ]:
        rows = run(bytes_per_rank=per_rank, **kw)
        base = rows[0][1]
        for n, us, nbytes, _, _ in rows:
            lines.append(f"{tag}_n{n},{us:.1f},scale_vs_min={us / base:.2f};bytes_per_rank={nbytes}")

    # -- sync vs async pipeline at the largest parity config -----------------
    n = par_ranks[-1]
    big = per_rank if smoke else 4 << 20
    sync_rows = run(bytes_per_rank=big, ranks=(n,), parity_group=4, async_mode=False)
    async_rows = run(bytes_per_rank=big, ranks=(n,), parity_group=4, async_mode=True)
    t_sync, eng_s = sync_rows[0][3], sync_rows[0][4]
    t_async, eng_a = async_rows[0][3], async_rows[0][4]
    total_bytes = eng_s.stats.last_bytes_staged
    gbps_sync = total_bytes / t_sync / 1e9
    gbps_async = total_bytes / t_async / 1e9
    speedup = t_sync / t_async
    # overlap efficiency: fraction of the sync critical path the pipeline hid
    overlap_eff = max(0.0, 1.0 - t_async / t_sync)
    for _, _, _, _, eng in (*sync_rows, *async_rows):
        eng.close()  # release the pipeline worker thread (stats stay readable)
    lines.append(f"ckpt_create_sync_n{n},{t_sync * 1e6:.0f},GBps={gbps_sync:.2f}")
    lines.append(
        f"ckpt_create_async_n{n},{t_async * 1e6:.0f},"
        f"GBps={gbps_async:.2f};speedup={speedup:.2f};overlap_eff={overlap_eff:.2f}"
    )

    # -- background disk-tier flush vs tier-less async baseline ---------------
    tier = run_tier_flush(n=8, bytes_per_rank=1 << 18 if smoke else 1 << 20)
    lines.append(
        f"ckpt_tier_flush_blocked,{tier['blocked_s_flush'] * 1e6:.0f},"
        f"overhead_vs_base={tier['tier_flush_overhead']:.3f};"
        f"base_us={tier['blocked_s_base'] * 1e6:.0f}"
    )
    lines.append(
        f"ckpt_tier_flush_write,{tier['flush_s'] * 1e6:.0f},"
        f"GBps={tier['flush_gbps']:.2f};bytes={tier['flush_bytes']}"
    )

    # -- differential checkpointing A/B at ~10% churn (DESIGN.md §17) ---------
    delta = run_delta_ab(
        n=8, bytes_per_rank=1 << 18 if smoke else 1 << 20,
        commits=4 if smoke else 6,
    )
    lines.append(
        f"ckpt_delta_flush,{delta['flush_bytes_delta']:.0f},"
        f"ratio_vs_full={delta['delta_flush_ratio']:.3f};"
        f"full_bytes={delta['flush_bytes_full']:.0f};"
        f"dedup_ratio={delta['dedup_ratio']:.3f}"
    )
    lines.append(
        f"ckpt_delta_blocked,{delta['blocked_s_delta'] * 1e6:.0f},"
        f"full_us={delta['blocked_s_full'] * 1e6:.0f};"
        f"dirty_fraction={delta['dirty_fraction']:.3f};"
        f"skipped_bytes={delta['transfer_bytes_skipped']}"
    )

    # -- span-tracing overhead A/B (DESIGN.md §13 budget) ---------------------
    # min-of-k over longer interleaved legs: the per-pair ratio at batch=4 /
    # repeats=5 was noisy enough to read container jitter as 19% span cost —
    # 12 pairs of 8-checkpoint legs keep one quiet pair under the 2% gate.
    trace = run_trace_overhead(
        n=8, bytes_per_rank=1 << 18 if smoke else 1 << 19,
        repeats=12 if smoke else 16, batch=8,
    )
    lines.append(
        f"ckpt_trace_overhead,{trace['t_on'] * 1e6:.0f},"
        f"enabled_vs_off={trace['trace_overhead_enabled']:.4f};"
        f"off_us={trace['t_off'] * 1e6:.0f}"
    )

    # -- double-buffered device staging (D2H overlap) -------------------------
    t_seq, t_dbuf, t_auto, auto_dbuf, staged_bytes = run_staging(
        mbytes=2 if smoke else 8
    )
    stage_win = t_seq / max(t_dbuf, 1e-9)
    auto_win = t_seq / max(t_auto, 1e-9)
    lines.append(
        f"ckpt_stage_d2h_seq,{t_seq * 1e6:.0f},GBps={staged_bytes / t_seq / 1e9:.2f}"
    )
    lines.append(
        f"ckpt_stage_d2h_dbuf,{t_dbuf * 1e6:.0f},"
        f"GBps={staged_bytes / t_dbuf / 1e9:.2f};overlap_win={stage_win:.2f}"
    )
    lines.append(
        f"ckpt_stage_d2h_auto,{t_auto * 1e6:.0f},"
        f"GBps={staged_bytes / t_auto / 1e9:.2f};"
        f"mode={'dbuf' if auto_dbuf else 'seq'};auto_win={auto_win:.2f}"
    )
    RESULTS.clear()
    RESULTS.update(
        {
            "n_ranks": n,
            "bytes_per_rank": big,
            "create_gbps_sync": round(gbps_sync, 3),
            "create_gbps_async": round(gbps_async, 3),
            "async_speedup": round(speedup, 3),
            "overlap_efficiency": round(overlap_eff, 3),
            "bytes_staged": eng_a.stats.last_bytes_staged,
            "bytes_exchanged": eng_a.stats.last_bytes_exchanged,
            "bytes_over_pcie_modeled": _pcie_model(eng_a),
            "blocked_s_sync": round(t_sync, 6),
            "blocked_s_async": round(t_async, 6),
            "pipeline_chunks": eng_a.stats.last_pipeline_chunks,
            "staging_overlap_win": round(stage_win, 3),
            "staging_auto_win": round(auto_win, 3),
            "staging_auto_mode": "dbuf" if auto_dbuf else "seq",
            "staging_bytes_fetched": staged_bytes,
            # storage-tier ladder rows (DESIGN.md §12): blocked-time overhead
            # of the background disk flush + its own write throughput
            "tier_flush_overhead": round(tier["tier_flush_overhead"], 3),
            "blocked_s_async_tierless": round(tier["blocked_s_base"], 6),
            "blocked_s_async_flush": round(tier["blocked_s_flush"], 6),
            "tier_flush_s": round(tier["flush_s"], 6),
            "tier_flush_bytes": tier["flush_bytes"],
            "tier_flush_gbps": round(tier["flush_gbps"], 3),
            # differential checkpointing rows (DESIGN.md §17): flushed bytes
            # full vs delta at ~10% churn (run.py gates the ratio at 0.35),
            # the dirty fraction the chunk grid measured, transfer bytes the
            # create path skipped, and the chunk store's dedup accounting
            "delta_flush_bytes": round(delta["flush_bytes_delta"]),
            "full_flush_bytes": round(delta["flush_bytes_full"]),
            "delta_flush_ratio": round(delta["delta_flush_ratio"], 3),
            "delta_blocked_ratio": round(delta["delta_blocked_ratio"], 3),
            "delta_dirty_fraction": round(delta["dirty_fraction"], 3),
            "delta_dedup_ratio": round(delta["dedup_ratio"], 3),
            "delta_transfer_bytes_skipped": delta["transfer_bytes_skipped"],
            "delta_chunks_written": delta["chunks_written"],
            "delta_chunks_reused": delta["chunks_reused"],
            "blocked_s_async_delta": round(delta["blocked_s_delta"], 6),
            "blocked_s_async_full": round(delta["blocked_s_full"], 6),
            # span-tracing observability rows (DESIGN.md §13): the enabled-
            # tracing overhead the smoke gate enforces, and the async
            # engine's `eng` span label so run.py can reconstruct overlap
            # efficiency from the recorded trace (--trace-out) and compare
            # it against the A/B-derived number above
            "trace_overhead_enabled": round(trace["trace_overhead_enabled"], 4),
            "trace_t_on_s": round(trace["t_on"], 6),
            "trace_t_off_s": round(trace["t_off"], 6),
            "trace_eng_async": eng_a._obs_id,
            "trace_eng_sync": eng_s._obs_id,
        }
    )
    return lines


if __name__ == "__main__":
    import sys

    print("\n".join(main(smoke="--smoke" in sys.argv)))
