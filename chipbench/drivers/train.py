"""The training loop kind: ``Trainer.run`` built by ``launch/train.build_trainer``.

Set-up builds the trainer, replaces its state with weights made from the seed
in one jitted call and its data with token rows drawn from the seed, and
drives it through the window's own call for the first ``CHECK_STEPS`` steps
(the readings the reference is compared with) and on through ``warm_cycles``
whole cycles, so that every program is compiled and both generation banks of
the engine are leased. A cycle is ``save_every_steps`` steps ending in a save,
or, where the traffic kills, ``kill_every_steps`` steps of which the first is
a kill, its restore and the replay of the step it lost. The window runs whole
cycles until ``--seconds`` have passed.

After the window: the last save is committed, a virtual host is killed and the
state restored, and the restored bits must equal the captured ones; the step
taken from the restored state must equal, bit for bit, the step taken from the
live state before the kill; replayed steps must repeat their losses bit for
bit; and the plain reference follows the first steps from the same seed and
rows.
"""

from __future__ import annotations

import gc

import harness
from harness import BenchError, now, setup_note

KILL_HORIZON = 10**6  # kills are scheduled this many steps ahead: beyond any window
CHECK_STEPS = 3       # steps the reference follows
REFERENCE_ROWS = 2    # token rows per block of the float32 reference's gradient


class _Probe:
    """Host-clock readings around the program's own calls, taken by
    wrapping the trainer's public objects on this instance only."""

    def __init__(self, trainer, injector) -> None:
        self.step_s: list[float] = []
        self.resume_s: list[float] = []
        self.restore_s: list[float] = []
        self.finalize_wait_s: list[float] = []
        self.rss_before_first_save: int | None = None
        self._awaiting: float | None = None
        self._restored = False
        self.trainer = trainer

        timer = trainer.timers("train_step")
        inner = timer._observer

        def observe(name, dt):
            t = now()
            self.step_s.append(dt)
            if self._awaiting is not None and self.restore_s and self._restored:
                self.resume_s.append(t - self._awaiting)
                self._awaiting = None
            if inner is not None:
                inner(name, dt)

        timer._observer = observe

        kills = injector.kills_at_step

        def kills_at_step(step):
            out = kills(step)
            if out:
                self._awaiting = now()
                self._restored = False
            return out

        injector.kills_at_step = kills_at_step
        eng = trainer.engine
        restore, finalize, capture = eng.restore, eng.finalize_async, eng.checkpoint_async

        def restore_wrapped(*a, **k):
            meta = restore(*a, **k)
            self.restore_s.append(eng.stats.last_restore_s)
            self._restored = True
            return meta

        def finalize_wrapped(*a, **k):
            res = finalize(*a, **k)
            if res is not None:
                self.finalize_wait_s.append(eng.stats.last_finalize_wait_s)
            return res

        def capture_wrapped(*a, **k):
            if self.rss_before_first_save is None:
                self.rss_before_first_save = harness.host_rss()
            return capture(*a, **k)

        eng.restore, eng.finalize_async, eng.checkpoint_async = (
            restore_wrapped, finalize_wrapped, capture_wrapped)

    def capture_stats(self):
        return self.trainer.engine.registry.get("ckpt_stage_seconds").stats(phase="capture")


def _seeded_state(cfgmod, config, key, model):
    """Params from the configuration's own init, in one jitted call; AdamW
    state from the program's own ``init_opt_state`` (eager copies and zeros).
    Made inside the weights' jitted call instead, the f32 master and moments
    left the program's change after three steps far from the reference's on
    the chip, on every seed, with distinct buffers; the cause is not
    isolated. The train step donates its state, so no two leaves may share a
    buffer."""
    import jax
    import jax.numpy as jnp

    from repro.optim.adamw import init_opt_state

    params = harness.seeded_params(cfgmod, config, key, model)
    state = {"params": params, "opt": init_opt_state(params),
             "step": jnp.zeros((), jnp.int32)}
    leaves = jax.tree.leaves(state)
    if len({x.unsafe_buffer_pointer() for x in leaves}) != len(leaves):
        raise BenchError("two leaves of the seeded train state share a device buffer")
    return state


def _rows(key, batch: int, seq: int, vocab: int):
    """The token rows of every step: step -> {"tokens", "labels"} (next tokens)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rows(step):
        t = jax.random.randint(jax.random.fold_in(key, step), (batch, seq + 1), 0, vocab, jnp.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    return rows


def run(ctx: harness.Context) -> harness.Outcome:
    import jax
    import jax.numpy as jnp

    from reference import leaf_norms
    from repro.launch import train as train_launch
    from repro.models import build_model
    from repro.runtime.failures import FailureInjector

    cell, t = ctx.cell, ctx.cell.traffic
    eng_cfg, opt = t["engine"], t["optimizer"]
    B, S = t["batch"], t["seq"]
    save_every, kill_every = t["save_every_steps"], t["kill_every_steps"]
    hosts = eng_cfg["hosts"]
    cycle = kill_every or save_every
    # Kills land one step after a save (state step 4k + 3 with saves every 2):
    # each costs a restore and the replay of the step since that save.
    offset = kill_every - 1 if kill_every else 0
    marks = [("start", now())]
    cfg = ctx.program_config()
    model = build_model(cfg)
    args = train_launch.build_parser().parse_args([
        "--arch", cfg.name, "--batch", str(B), "--seq", str(S),
        "--steps", str(opt["total_steps"]), "--lr", str(opt["lr"]),
        "--period", str(save_every), "--checkpoint-mode", eng_cfg["checkpoint_mode"],
        "--hosts", str(hosts), "--codec", eng_cfg["codec"], "--spares", str(eng_cfg["spares"]),
    ])
    schedule = {}
    if kill_every:
        schedule = {k * kill_every + offset: [k % hosts] for k in range(KILL_HORIZON // kill_every)}
    injector = FailureInjector(hosts, schedule=schedule)
    trainer = train_launch.build_trainer(args, model, injector)
    if trainer.tcfg.warmup_steps != opt["warmup_steps"]:
        raise BenchError(f"the trainer warms up over {trainer.tcfg.warmup_steps} steps, "
                         f"the traffic states {opt['warmup_steps']}")
    probe = _Probe(trainer, injector)
    marks.append(("build_trainer", now()))

    key = jax.random.PRNGKey(ctx.key_seed)
    k_params, k_rows = jax.random.split(key)
    trainer.state = None
    trainer.state = _seeded_state(cell.config_mod, cell.config, k_params, model)
    p0 = jax.tree.map(jnp.copy, trainer.state["params"])
    rows = _rows(k_rows, B, S, cfg.vocab_size)
    pipe = trainer.data

    def next_rows():
        b = rows(pipe.step)
        pipe.step += 1
        return b

    pipe.next = next_rows
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(trainer.state))
    jax.block_until_ready(trainer.state)
    marks.append(("seeded_weights", now()))

    # -- set-up: the readings of the first steps, then warm cycles ----------
    n_check = CHECK_STEPS
    b1 = opt["b1"]
    trainer.run(1)
    grad_norms = [n / (1.0 - b1) for n in leaf_norms(trainer.state["opt"]["m"])]
    trainer.run(n_check)
    change = jax.jit(lambda w, p: jax.tree.map(lambda a, b: a - b.astype(jnp.float32), w, p))
    change_norms = leaf_norms(change(trainer.state["opt"]["master"], p0))
    del p0
    first_losses = [h["loss"] for h in trainer.history[:n_check]]
    marks.append(("first_steps", now()))
    trainer.run(t["warm_cycles"] * cycle + offset)
    marks.append(("warm_cycles", now()))

    # -- the window ---------------------------------------------------------
    compiles0 = ctx.clock.events
    cap0 = probe.capture_stats()
    n_fin0, n_rest0, n_step0 = len(probe.finalize_wait_s), len(probe.restore_s), len(probe.step_s)
    n_resume0 = len(probe.resume_s)
    target = int(trainer.state["step"])
    trace = None
    if ctx.trace:
        with harness.TraceWindow(ctx.trace_dir) as tw:
            for _ in range(t["trace_cycles"]):
                target += cycle
                trainer.run(target)
        t0, t1 = tw.t0, tw.t1
    else:
        t0 = now()
        while True:
            target += cycle
            trainer.run(target)
            if now() - t0 >= ctx.seconds:
                break
        t1 = now()
    setup_s = t0 - ctx.t_start
    peak_rss = harness.host_peak_rss()
    cap1 = probe.capture_stats()
    steps = len(probe.step_s) - n_step0
    compiles = ctx.clock.events - compiles0
    if compiles:
        ctx.notes.append(f"note: {compiles} compilation(s) inside the window")
    mem = trainer.engine.memory_report()
    device_peak = harness.device_info(ctx.devices)["memory_peak_bytes"]
    if ctx.trace:
        trace = tw.reduce()

    record = {
        "train_step_s": probe.step_s[n_step0:],
        "capture_s": (cap1["sum"] - cap0["sum"]) / (cap1["count"] - cap0["count"])
        if cap1["count"] > cap0["count"] else None,
        "finalize_wait_s": probe.finalize_wait_s[n_fin0:],
        "restore_s": probe.restore_s[n_rest0:],
        "host_store_bytes": mem["total_bytes"],
        "state_bytes": state_bytes,
        "flops_per_step": cell.config_mod.train_flops(cell.config, B, S),
        "peak_flops": harness.peaks(ctx.devices[0].device_kind)["bf16_flops_per_s"],
        "trace": trace,
        "kind": "train",
    }
    e2e = {"setup_s": setup_s,
           "host_bytes_per_state_byte": (peak_rss - probe.rss_before_first_save) / state_bytes}
    if not kill_every:
        e2e["train_tokens_per_s"] = steps * B * S / (t1 - t0)
    resumes = probe.resume_s[n_resume0:]
    if resumes:
        e2e["resume_s"] = sum(resumes) / len(resumes)
    ctx.notes.append(setup_note(ctx, marks))
    ctx.notes.append(
        f"window {t1 - t0:.3f} s, {steps} steps, {len(probe.finalize_wait_s) - n_fin0} saves, "
        f"{len(resumes)} kills; setup {setup_s:.3f} s; host rss before first save "
        f"{probe.rss_before_first_save}, peak {peak_rss}; state {state_bytes} bytes")

    # -- correctness ---------------------------------------------------------
    checks = _round_trip(trainer, injector, rows)
    checks.update(_replays(trainer.history))
    trainer.engine.close()
    trainer.state = None
    del trainer, probe
    gc.collect()
    ref = _reference(cell, k_params, k_rows, B, S, cfg.vocab_size, opt, n_check, "f32")
    checks.update(compare(
        {"loss": first_losses, "grad": grad_norms, "change": change_norms}, ref, cell.limits))
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        cell.config_mod.param_layout(cell.config), is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[0], tuple))[0]]
    for what, prog in (("grad", grad_norms), ("change", change_norms)):
        ctx.notes.append(f"{what} norm per leaf (program, reference): " + "; ".join(
            f"{n} {a:.6g} {b:.6g}" for n, a, b in zip(names, prog, ref[what])))
    if ctx.control:
        record["control"] = {
            "fp8": compare(_reference(cell, k_params, k_rows, B, S, cfg.vocab_size, opt,
                                      n_check, "fp8"), ref, cell.limits),
            "half_batch": compare(_reference(cell, k_params, k_rows, B, S, cfg.vocab_size, opt,
                                             n_check, "f32", fault="half_batch"), ref, cell.limits),
        }
    return harness.Outcome(e2e, record, checks, attempted=steps, failed=0,
                           device_peak_bytes=device_peak, trace=trace)


def _round_trip(trainer, injector, rows) -> dict[str, tuple[float, float]]:
    """Commit the last save, kill a host, restore: the state must come back
    bit for bit, through shards rebuilt from redundancy whose checksums the
    restore verified. The program's step from the restored state, uploaded
    by the restore, must then give bit for bit what its step from the live
    state gave before the kill: the update after a restore is the update the
    reference checks."""
    import jax
    import jax.numpy as jnp

    eng = trainer.engine
    committed = eng.finalize_async()
    if eng.checkpoint_step().get("step") != int(trainer.state["step"]):
        committed = committed is not False and eng.checkpoint({"step": int(trainer.state["step"])})
    before = harness.tree_digest(trainer.state)
    step = trainer.data.step
    live, _ = trainer._train_step(jax.tree.map(jnp.copy, trainer.state), rows(step))
    live_next = harness.tree_digest(live)
    del live
    verify = eng.registry.get("restore_stage_seconds")
    v0 = verify.stats(phase="r_verify")["count"] if verify is not None else 0
    rebuilt0 = eng.stats.reconstructed_restores + eng.stats.adopted_restores
    injector.schedule.clear()
    trainer.cluster.kill(1)
    trainer.recover()
    after = harness.tree_digest(trainer.state)
    verify = eng.registry.get("restore_stage_seconds")
    v1 = verify.stats(phase="r_verify")["count"]
    rebuilt = eng.stats.reconstructed_restores + eng.stats.adopted_restores - rebuilt0
    differ = sum(a != b for a, b in zip(before, after)) + (trainer.data.step != step)
    trainer.state, _ = trainer._train_step(trainer.state, rows(trainer.data.step))
    restored_next = harness.tree_digest(trainer.state)
    return {
        "save_not_committed": (float(committed is False), 0.0),
        "restored_leaves_differ": (float(differ), 0.0),
        "restore_rebuilt_no_shard": (float(rebuilt < 1), 0.0),
        "restore_verified_nothing": (float(v1 - v0 < 1), 0.0),
        "step_after_restore_differs": (
            float(sum(a != b for a, b in zip(live_next, restored_next))), 0.0),
    }


def _replays(history) -> dict[str, tuple[float, float]]:
    first: dict[int, float] = {}
    differ = 0
    for h in history:
        if h["step"] in first:
            differ += first[h["step"]] != h["loss"]
        else:
            first[h["step"]] = h["loss"]
    return {"replayed_losses_differ": (float(differ), 0.0)}


def _reference(cell, k_params, k_rows, B, S, vocab, opt, n_steps, precision,
               fault: str | None = None):
    """The configuration's plain reference over the first ``n_steps`` steps:
    losses, the clipped gradient of the first step per leaf, and each leaf's
    change after the last. ``fault`` plants one of the faults the check must
    catch (``"half_batch"``) in the reference put in the program's place."""
    import jax
    import jax.numpy as jnp

    from reference import adamw_step, leaf_norms, make_mm, nll_sum

    cfgmod, config = cell.config_mod, cell.config
    mm = make_mm(precision)
    rows = _rows(k_rows, B, S, vocab)
    block = REFERENCE_ROWS
    n_rows = B // 2 if fault == "half_batch" else B
    p = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jnp.float32),
                                       cfgmod.init_params(k, config)))(k_params)
    p0 = p
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)

    @jax.jit
    def block_grad(params, tokens, labels):
        def f(q):
            return nll_sum(cfgmod.logits(q, tokens, config, mm), labels, config["vocab_size"])
        return jax.value_and_grad(f)(params)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a))
    step_fn = jax.jit(lambda p, g, m, v, t: adamw_step(p, g, m, v, t, opt), static_argnums=4)
    losses, grad_norms = [], []
    for t in range(n_steps):
        r = rows(t)
        total, grads = 0.0, None
        for lo in range(0, n_rows, block):
            l, g = block_grad(p, r["tokens"][lo:lo + block], r["labels"][lo:lo + block])
            total += float(l)
            grads = g if grads is None else add(grads, g)
        n_tok = n_rows * S
        grads = scale(grads, 1.0 / n_tok)
        losses.append(total / n_tok)
        p, m, v, clip = step_fn(p, grads, m, v, t)
        if t == 0:
            grad_norms = [n * float(clip) for n in leaf_norms(grads)]
    change = leaf_norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(p, p0))
    return {"loss": losses, "grad": grad_norms, "change": change}


def compare(prog: dict, ref: dict, limits: dict) -> dict[str, tuple[float, float]]:
    """The three numbers ``correct`` holds a train cell to. Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    import numpy as np

    from reference import worst_leaf_gap

    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    med = float(np.median(ref["grad"]))
    keep = [g >= 1e-3 * med for g in ref["grad"]]
    return {
        "loss_gap": (loss_gap, limits["loss_gap"]),
        "grad_gap": (worst_leaf_gap(prog["grad"], ref["grad"]), limits["grad_gap"]),
        "change_gap": (worst_leaf_gap(prog["change"], ref["change"], keep), limits["change_gap"]),
    }
