"""Smoke run of the checkpointed train and serve paths on a TPU.

    python chip_smoke.py             # one chip: the train and serve phases
    python chip_smoke.py --chips 4   # four chips: the device-tier phase only

Everything runs in this one process, which holds the chip(s) from the device
check to the end. The model is mamba2-780m at its published widths, driven
through the same builders as ``python -m repro.launch.train`` and
``python -m repro.launch.serve``:

* **train** — all AdamW state, ``seq 2048``, an asynchronous checkpoint every
  2 steps, one virtual host killed after step 3, a restore, and on to step 6.
  The replayed steps' losses must equal the first pass's bit for bit, and the
  restore must have verified the rebuilt shards' checksums. The redundancy
  codec is the trainer's default unless host memory cannot hold it; depth is
  cut only when no codec fits at all, and every such choice is printed.
* **serve** — a batch of 4 prompts of 512 tokens prefilled, 16 tokens decoded
  with a session checkpoint every 8, one host killed at tick 10; the tokens
  must equal those of a run without the kill.
* **--chips 4** — the train state sharded over a ``data=4`` mesh, the fused
  device-tier snapshot program (xor and rs) checked against the host codec,
  and the striped restore program with one device's shards dropped.

Without a TPU the script exits non-zero before any phase runs; there is no
CPU fallback. Times printed here are one cold run's smoke timings, not
benchmark numbers. The last line of standard output, printed only when every
phase passed, is the JSON result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "mamba2-780m"
HOSTS = 4                # virtual failure-domain ranks, as launch/train.py defaults
# Largest batch whose full-depth step the TPU compiler fits in one v5e's HBM
# at seq 2048 (batch 8 needs under 15.75 GiB, batch 9 16.28 GiB).
TRAIN_BATCH = 8
TRAIN_SEQ = 2048
TRAIN_STEPS = 6
CKPT_PERIOD = 2
KILL_AFTER_STEP = 3
KILL_RANK = 1
# Host memory kept free beyond the checkpoint engine's estimated peak, on
# top of what the process already holds when the train phase is planned (on
# a v5e host the TPU runtime alone holds about 13 GiB by then).
HOST_HEADROOM = 3 << 30
SERVE_ARGV = [
    "--arch", ARCH, "--batch", "4", "--prompt-len", "512", "--gen", "16",
    "--ckpt-every", "8",
]
SERVE_KILL = "10:2"
# Depth of the four-chip phase. The fused device-tier programs hold several
# copies of each device's exchange buffer at once (ring slots, the stacked
# group, the kernel's padded input): at all 48 layers the xor group-4
# snapshot needs 26.17 GiB of a v5e's 15.75 GiB, at 36 layers 20.27 GiB.
# At 20 layers every program of the phase compiles for v5e.
DEVICE_TIER_LAYERS = 20


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def host_peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def host_rss() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def host_available() -> int:
    """Host bytes this process can still take: MemAvailable, capped by the
    cgroup's limit less its usage where one is set (v2, then v1)."""
    with open("/proc/meminfo") as f:
        avail = next(int(line.split()[1]) * 1024 for line in f
                     if line.startswith("MemAvailable:"))
    for limit, usage in (
        ("/sys/fs/cgroup/memory.max", "/sys/fs/cgroup/memory.current"),
        ("/sys/fs/cgroup/memory/memory.limit_in_bytes",
         "/sys/fs/cgroup/memory/memory.usage_in_bytes"),
    ):
        try:
            cap = int(Path(limit).read_text()) - int(Path(usage).read_text())
        except (OSError, ValueError):  # absent, or "max" (no limit)
            continue
        return min(avail, cap)
    return avail


class RssWatch:
    """Prints the host RSS each time it has grown by another GiB, from a
    daemon thread, so that a run ended for memory still shows how far its
    footprint got and when."""

    def __init__(self, every_s: float = 0.5) -> None:
        import threading

        self._t0 = time.perf_counter()
        self._next = 1 << 30
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(every_s,), name="rss-watch", daemon=True
        )
        self._thread.start()

    def _run(self, every_s: float) -> None:
        while not self._stop.wait(every_s):
            rss = host_rss()
            if rss >= self._next:
                say(f"host rss {gib(rss)} at {time.perf_counter() - self._t0:.0f} s")
                self._next = (rss >> 30) + 1 << 30

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


class CompileClock:
    """Sums JAX's own compile-duration events (trace, lowering, backend)."""

    def __init__(self) -> None:
        import jax

        self.secs: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_kw) -> None:
        if name.startswith("/jax/core/compile/"):
            self.secs[name.rsplit("/", 1)[-1]] += secs

    def total(self) -> float:
        return sum(self.secs.values())


# --------------------------------------------------------------------------
# device check
# --------------------------------------------------------------------------

def device_check(chips: int):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's default device is {d0.platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    check(len(devices) == chips,
          f"--chips {chips} asked for, JAX reports {len(devices)} device(s)")
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} jax={jax.__version__} libtpu={libtpu}")
    return devices


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def state_bytes(model, hosts: int) -> tuple[int, int]:
    """(bytes the trainer's shard plan splits over ``hosts`` ranks, bytes it
    replicates to every rank), from the same plan the Trainer builds."""
    import numpy as np
    import jax

    from repro.runtime.state import ShardPlan
    from repro.runtime.trainer import state_pspecs, state_shape_dtypes
    from repro.sharding.mesh import abstract_mesh

    sds = state_shape_dtypes(model)
    plan = ShardPlan.from_pspecs(
        sds, state_pspecs(model, abstract_mesh(("data", 16), ("model", 16)))
    )
    split = rep = 0
    for i, leaf in enumerate(jax.tree.leaves(sds)):
        n = int(np.prod(leaf.shape, dtype=np.int64)) * leaf.dtype.itemsize
        if plan.split_dim(i, hosts) is None:
            rep += n
        else:
            split += n
    return split, rep


def host_bytes_needed(split: int, rep: int, hosts: int, codec: str, group: int) -> int:
    """Peak host bytes of the checkpoint engine over the train phase.

    Per rank and generation bank the engine stages its own shard (split part
    plus every replicated leaf) and its exchange subset (the split part), and
    hosts redundancy: a whole partner copy under the default copy codec, or
    1/group of it under xor. Both banks fill once a second checkpoint
    commits. Kept beside them: xor's blob accumulators (one blob per group),
    and after a restore the rebuilt shard and the blob it was solved from
    (the copy codec adopts both by reference). On top: the full state
    fetched from the device during a capture (or rebuilt by a restore), and
    the checksum's cached weight vector, sized to the next power of two of
    the largest own shard in words.
    """
    own = split + hosts * rep
    if codec == "copy":
        redundancy, scratch, restore = split, 0, 0
    else:
        redundancy, scratch, restore = split // group, split // group, 2 * split // hosts
    weights = 4 << max(((split // hosts + rep) // 4 - 1).bit_length(), 0)
    banks = 2 * (own + split + redundancy)
    return banks + scratch + restore + split + rep + weights


def plan_train(cfg, hosts: int, available: int):
    """Codec and depth for the train phase: the deepest depth at which the
    host holds the default codec, or failing that xor; full depth first."""
    from repro.models import build_model

    # One kill must stay recoverable: with every rank in one parity group
    # the group's parity would sit on its own members, so the group is half
    # the world (groups {0,1} and {2,3} hold each other's stripes).
    group = hosts // 2
    options = [("copy", []), ("xor", ["--codec", "xor", "--parity-group", str(group)])]
    notes = []
    for layers in range(cfg.num_layers, 0, -1):
        cut = cfg.with_(num_layers=layers)
        split, rep = state_bytes(build_model(cut), hosts)
        for codec, argv in options:
            need = host_bytes_needed(split, rep, hosts, codec, group)
            if layers == cfg.num_layers:
                notes.append(f"{codec}: {gib(need)}")
            if need + HOST_HEADROOM <= available:
                if layers < cfg.num_layers:
                    notes.append(f"{codec} at {layers} layers: {gib(need)}")
                return cut, codec, argv, split + rep, notes
    raise SmokeFailure(f"no depth of {cfg.name} fits {gib(available)} of host memory")


def train_phase(cfg, *, batch: int, seq: int, clock: CompileClock) -> None:
    import jax

    from repro.launch import train as train_launch
    from repro.models import build_model
    from repro.runtime.failures import FailureInjector

    available, held = host_available(), host_rss()
    run_cfg, codec, codec_argv, total_bytes, notes = plan_train(
        cfg, HOSTS, available - held
    )
    say(f"train plan: host MemAvailable {gib(available)}, this process holds "
        f"{gib(held)}, headroom {gib(HOST_HEADROOM)}; estimated checkpoint "
        "host bytes " + "; ".join(notes))
    if codec != "copy":
        say(f"train plan: the default copy codec does not fit this host at "
            f"{run_cfg.num_layers} layers; running {' '.join(codec_argv)}")
    if run_cfg.num_layers != cfg.num_layers:
        say(f"train plan: DEPTH CUT {cfg.num_layers} -> {run_cfg.num_layers} "
            f"layers (widths unchanged) so the checkpoint fits host memory")
    model = build_model(run_cfg)
    say(f"train: {run_cfg.name} layers={run_cfg.num_layers} d_model={run_cfg.d_model} "
        f"params={model.n_params:,} state={gib(total_bytes)} batch={batch} "
        f"seq={seq} hosts={HOSTS} codec={codec}")

    args = train_launch.build_parser().parse_args([
        "--arch", ARCH, "--batch", str(batch), "--seq", str(seq),
        "--steps", str(TRAIN_STEPS), "--period", str(CKPT_PERIOD),
        "--checkpoint-mode", "async", "--hosts", str(HOSTS), *codec_argv,
    ])
    injector = FailureInjector(HOSTS, schedule={KILL_AFTER_STEP: [KILL_RANK]})
    trainer = train_launch.build_trainer(args, model, injector)
    try:
        c0, t0 = clock.total(), time.perf_counter()
        trainer.run(1)
        first = time.perf_counter() - t0
        compile_s = clock.total() - c0
        t1 = time.perf_counter()
        history = trainer.run(TRAIN_STEPS)
        rest = time.perf_counter() - t1
        committed = trainer.engine.finalize_async()
        eng = trainer.engine

        check(committed is not False, "the step-6 checkpoint did not commit")
        check(int(trainer.state["step"]) == TRAIN_STEPS,
              f"stopped at step {int(trainer.state['step'])}")
        check(trainer.n_recoveries == 1, f"{trainer.n_recoveries} recoveries, expected 1")
        first_pass: dict[int, float] = {}
        replayed = []
        for h in history:
            if h["step"] in first_pass:
                replayed.append((h["step"], first_pass[h["step"]], h["loss"]))
            else:
                first_pass[h["step"]] = h["loss"]
        check(bool(replayed), "no step was replayed after the restore")
        for step, a, b in replayed:
            check(a == b, f"step {step} loss {a!r} first, {b!r} replayed")
        check(all(abs(v) < float("inf") for v in first_pass.values()),
              f"non-finite loss in {first_pass}")
        verified = eng.registry.get("restore_stage_seconds").stats(phase="r_verify")
        rebuilt = eng.stats.reconstructed_restores + eng.stats.adopted_restores
        check(rebuilt >= 1, "the restore rebuilt no shard from redundancy")
        check(verified["count"] > 0, "the restore verified no rebuilt checksum")
        bad_flush = [e for e in eng.journal.events("flush") if not e.get("ok")]
        check(not bad_flush, f"tier flush failed: {bad_flush}")

        say("train losses: " + json.dumps(
            [[h["step"], h["loss"]] for h in history]))
        say(f"train replayed steps bit-identical: "
            + ", ".join(f"step {s} loss {a!r}" for s, a, _ in replayed))
        say(f"train restore: {rebuilt} shard(s) rebuilt, {verified['count']} "
            f"checksum-verify chunk(s), {eng.stats.last_restore_s:.3f} s, "
            f"{eng.stats.last_restore_bytes_rebuilt} bytes rebuilt")
        say(f"train smoke timing (one cold run, not a benchmark): compile "
            f"{compile_s:.1f} s; first step incl. compile {first:.1f} s; "
            f"steps 1-{TRAIN_STEPS} with {eng.stats.created} checkpoint(s), "
            f"1 kill and 1 restore {rest:.1f} s; last train step "
            f"{trainer.timers('train_step').last:.3f} s; last capture "
            f"{eng.stats.last_capture_s:.3f} s")
        from repro.core import gf256

        rates = {n: round(gf256.probed_gbps(n, default=float("nan")), 3)
                 for n in gf256.available_backends()}
        say(f"GF(2^8) backend: {gf256.active_backend_name()}, probe GB/s {rates}")
        say("engine memory_report: " + json.dumps(eng.memory_report(), default=str))
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        say(f"train device peak_bytes_in_use {peak} ({gib(peak or 0)}); "
            f"host peak RSS {host_peak_rss()} ({gib(host_peak_rss())})")
    finally:
        trainer.engine.close()


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def serve_phase(cfg, clock: CompileClock, argv: list[str] | None = None) -> None:
    import jax
    import numpy as np

    from repro.launch import serve as serve_launch
    from repro.models import build_model

    argv = SERVE_ARGV if argv is None else argv
    model = build_model(cfg)
    clean_args = serve_launch.build_parser().parse_args(argv)
    kill_args = serve_launch.build_parser().parse_args(argv + ["--kill-at", SERVE_KILL])
    prompts = serve_launch.make_prompts(clean_args, cfg.vocab_size)
    say(f"serve: {cfg.name} layers={cfg.num_layers} params={model.n_params:,} "
        f"batch={clean_args.batch} prompt={clean_args.prompt_len} "
        f"gen={clean_args.gen} ckpt_every={clean_args.ckpt_every} kill={SERVE_KILL}")

    c0, t0 = clock.total(), time.perf_counter()
    clean = serve_launch.build_server(clean_args, model)
    ref = clean.prefill_and_decode(prompts, clean_args.gen)
    t_clean = time.perf_counter() - t0
    compile_s = clock.total() - c0
    clean.engine.close()
    del clean
    gc.collect()

    t1 = time.perf_counter()
    faulty = serve_launch.build_server(kill_args, model)
    try:
        out = faulty.prefill_and_decode(prompts, kill_args.gen)
        t_faulty = time.perf_counter() - t1
        check(faulty.n_recoveries == 1, f"{faulty.n_recoveries} recoveries, expected 1")
        p, g = clean_args.prompt_len, clean_args.gen
        check(np.array_equal(ref, out),
              "tokens after the kill differ from the run without it")
        check(bool(np.all(ref[:, p : p + g + 1] < cfg.vocab_size)), "token out of vocab")
        say(f"serve tokens identical across the kill; session 0 generated "
            f"{out[0, p : p + g + 1].tolist()}")
        say(f"serve smoke timing (one cold run, not a benchmark): clean run "
            f"{t_clean:.1f} s incl. {compile_s:.1f} s compile; run with kill "
            f"and restore {t_faulty:.1f} s")
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        say(f"serve device peak_bytes_in_use {peak} ({gib(peak or 0)}); "
            f"host peak RSS {host_peak_rss()} ({gib(host_peak_rss())})")
    finally:
        faulty.engine.close()


# --------------------------------------------------------------------------
# four chips: the device tier
# --------------------------------------------------------------------------

DEVICE_TIER_CODECS = (
    # (codec, group, parity blobs, restore with one device dropped)
    ("xor", 4, 1, False),  # one group over all four: encode only (its parity
                           # sits on its own members, so no single loss heals)
    ("xor", 2, 1, True),
    ("rs", 2, 2, True),
)


def sharded_train_state(model, mesh, seed: int = 0):
    """mamba2 train state laid out as the trainer plans it on ``mesh``
    (params by the model's rules, AdamW state ZeRO-1 over ``data``), made on
    the devices in its final sharding. m and v are filled from the seed so
    parity is computed over nonzero moments."""
    import jax
    from jax.sharding import NamedSharding

    from repro.optim.adamw import init_opt_state
    from repro.runtime.trainer import state_pspecs

    pspecs = state_pspecs(model, mesh)
    shardings = jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), pspecs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )

    def init(key):
        kp, km, kv = jax.random.split(key, 3)
        params = model.init(kp)
        opt = init_opt_state(params)
        leaves, tdef = jax.tree.flatten(opt["m"])
        ks = jax.random.split(km, len(leaves))
        opt["m"] = tdef.unflatten([
            jax.random.normal(k, x.shape, x.dtype) for k, x in zip(ks, leaves)])
        ks = jax.random.split(kv, len(leaves))
        opt["v"] = tdef.unflatten([
            jax.random.uniform(k, x.shape, x.dtype) for k, x in zip(ks, leaves)])
        return {"params": params, "opt": opt, "step": jax.numpy.zeros((), "int32")}

    state = jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(seed))
    return state, pspecs


def member_buffers(prog, state, n: int) -> dict[str, list]:
    """Host bytes of each data coordinate's fused exchange buffer, built from
    the devices' own shards — what the host codec would encode."""
    import numpy as np
    import jax

    leaves = jax.tree.leaves(state)
    out = {}
    for b in prog.buckets:
        bufs = [np.zeros(b.words * 4, np.uint8) for _ in range(n)]
        for i, off in zip(b.leaf_idx, b.word_offsets):
            for shard in leaves[i].addressable_shards:
                d = _data_coord(shard, leaves[i], n)
                raw = np.asarray(shard.data).reshape(-1).view(np.uint8)
                bufs[d][off * 4 : off * 4 + raw.nbytes] = raw
        out[b.tag] = bufs
    return out


def _data_coord(shard, leaf, n: int) -> int:
    """The data-axis coordinate of a shard of a leaf split over it."""
    for dim, sl in enumerate(shard.index):
        size = leaf.shape[dim]
        width = (sl.stop or size) - (sl.start or 0)
        if width != size:
            return (sl.start or 0) // width
    raise SmokeFailure(f"shard {shard.index} of {leaf.shape} is not split")


def four_chip_phase(cfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distribution as dist
    from repro.core.codec import RSCodec, XorCodec
    from repro.core.device_tier import (
        build_snapshot_program, build_striped_restore_program, striped_decode_rows,
    )
    from repro.models import build_model
    from repro.runtime.trainer import state_shape_dtypes
    from repro.sharding.mesh import make_mesh

    n = 4
    mesh = make_mesh((n, 1), ("data", "model"))
    if cfg.num_layers > DEVICE_TIER_LAYERS:
        say(f"device tier: DEPTH CUT {cfg.num_layers} -> {DEVICE_TIER_LAYERS} "
            f"layers (widths unchanged): the fused programs do not fit HBM at "
            f"full depth")
        cfg = cfg.with_(num_layers=DEVICE_TIER_LAYERS)
    model = build_model(cfg)
    sds = state_shape_dtypes(model)
    t0 = time.perf_counter()
    state, pspecs = sharded_train_state(model, mesh)
    jax.block_until_ready(state)
    say(f"device tier: {cfg.name} layers={cfg.num_layers} params={model.n_params:,} "
        f"state sharded over mesh {dict(mesh.shape)} in {time.perf_counter() - t0:.1f} s")

    split_leaves = 0
    for leaf in jax.tree.leaves(state):
        devs = {s.device for s in leaf.addressable_shards}
        if not leaf.sharding.is_fully_replicated:
            check(len(devs) == n, f"leaf {leaf.shape} sits on {len(devs)} device(s)")
            shapes = {tuple(s.data.shape) for s in leaf.addressable_shards}
            check(len(shapes) == 1 and np.prod(next(iter(shapes))) * n == leaf.size,
                  f"leaf {leaf.shape} shards {shapes} do not split it {n} ways")
            split_leaves += 1
    check(split_leaves > 0, "no leaf of the train state is split over the devices")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in mesh.devices.flat]
    say(f"device tier: {split_leaves} leaves split 4 ways; bytes_in_use per device {in_use}")

    lost = 1
    for codec, g, m, restore in DEVICE_TIER_CODECS:
        c0, t1 = clock.total(), time.perf_counter()
        prog = build_snapshot_program(
            mesh, sds, pspecs, validate=False, include_own_copy=False,
            codec=codec, parity_group=g, rs_parity=m,
        )
        payload = jax.jit(prog.snapshot_fn)(state)
        jax.block_until_ready(payload)
        t_snap = time.perf_counter() - t1
        host = XorCodec(g) if codec == "xor" else RSCodec(g, m)
        groups = dist.parity_groups(n, g)
        members = member_buffers(prog, state, n)
        host_blobs = {}
        for b in prog.buckets:
            sw = b.words // g
            stripes = np.asarray(payload["parity"][b.tag]).reshape(m, n, sw)
            for src, grp in enumerate(groups):
                blobs = host.encode([members[b.tag][d] for d in grp.members], m)
                host_blobs[(b.tag, src)] = blobs
                for j in range(m):
                    holder = groups[dist.blob_holder_group(len(groups), src, j)]
                    for pos, d in enumerate(holder.members):
                        want = blobs[j][pos * sw * 4 : (pos + 1) * sw * 4]
                        check(np.array_equal(stripes[j, d].view(np.uint8), want),
                              f"{codec} g={g} bucket {b.tag} blob {j} of group "
                              f"{src} differs from the host codec on device {d}")
        line = (f"device tier {codec} g={g} m={m}: parity bit-identical to host "
                f"{type(host).__name__}.encode; pcie_bytes={prog.pcie_bytes} "
                f"exchanged_bytes={prog.exchanged_bytes}; snapshot {t_snap:.1f} s")
        if restore:
            rest = build_striped_restore_program(
                mesh, sds, pspecs, codec=codec, parity_group=g, rs_parity=m)
            rows, mask = striped_decode_rows(n, g, codec, m, {lost})
            bad = _drop_device(state, lost, n)
            t2 = time.perf_counter()
            out = rest.restore_fn(bad, payload["parity"], {"data": rows}, {"data": mask})
            jax.block_until_ready(out)
            t_rest = time.perf_counter() - t2
            leaves = jax.tree.leaves(state)
            same = jax.jit(_bit_equal)
            for idx, leaf in out.items():
                check(bool(same(leaf, leaves[int(idx)])),
                      f"{codec} g={g}: restored leaf {idx} differs from the state")
            gi = lost // g
            grp = groups[gi]
            lost_pos = grp.members.index(lost)
            for b in prog.buckets:
                present = {i: members[b.tag][d] for i, d in enumerate(grp.members)
                           if d != lost}
                blobs = dict(enumerate(host_blobs[(b.tag, gi)]))
                rebuilt = host.decode(present, blobs, [lost_pos])[lost_pos]
                check(np.array_equal(np.asarray(rebuilt).reshape(-1)[: b.words * 4],
                                     members[b.tag][lost]),
                      f"host {codec} decode of bucket {b.tag} differs")
            line += (f"; striped restore with device {lost} dropped bit-identical "
                     f"({len(out)} leaves, host decode agrees) in {t_rest:.1f} s; "
                     f"restore pcie_bytes={rest.pcie_bytes}")
        say(line + f"; compile {clock.total() - c0:.1f} s")
        del payload
        gc.collect()
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in mesh.devices.flat]
    say(f"device tier peak_bytes_in_use per device {peak}; host peak RSS "
        f"{host_peak_rss()} ({gib(host_peak_rss())})")


def _bit_equal(a, b):
    """Bitwise equality of two same-dtype arrays, reduced on the device."""
    import jax
    import jax.numpy as jnp

    bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    return jnp.array_equal(
        jax.lax.bitcast_convert_type(a, bits), jax.lax.bitcast_convert_type(b, bits)
    )


def _drop_device(state, lost: int, n: int):
    """The state as the survivors would upload it: every shard the lost
    data coordinate held is replaced by garbage."""
    import jax
    import jax.numpy as jnp

    def drop(leaf):
        if leaf.sharding.is_fully_replicated:
            return leaf
        shards = []
        for s in leaf.addressable_shards:
            data = s.data
            if _data_coord(s, leaf, n) == lost:
                data = jax.device_put(jnp.full(data.shape, 7, data.dtype), s.device)
            shards.append(data)
        return jax.make_array_from_single_device_arrays(leaf.shape, leaf.sharding, shards)

    return jax.tree.map(drop, state)


# --------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the device-tier phase, on four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    try:
        devices = device_check(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke.py: {e}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.utils.compile_cache import use_compile_cache

    say(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    watch = RssWatch()
    cfg = get_config(ARCH)
    try:
        if args.chips == 4:
            four_chip_phase(cfg, clock)
        else:
            train_phase(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, clock=clock)
            gc.collect()
            serve_phase(cfg, clock)
    finally:
        watch.stop()
    say(f"compile seconds by stage: {dict(clock.secs)}")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
