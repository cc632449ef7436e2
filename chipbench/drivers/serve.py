"""The serving loop kind: ``Server.decode`` built by ``launch/serve.build_server``.

A static batch of sessions (a closed loop: every session's next token waits
for its last), prompts drawn from the seed, weights made from the seed in one
jitted call. Set-up prefills, takes the first session save and decodes
``warm_cycles`` cycles. A cycle is ``save_every_tokens`` decode ticks ending
in an asynchronous session save. The window runs whole cycles until
``--seconds`` have passed, or until the sessions reach their decode budget.

Each tick's new tokens are fetched to the host as a streaming server delivers
them (``Server.decode`` does not wait for them itself): the harness wraps the
server's jitted decode on this instance with one wait per tick, and the gaps
between those arrivals are the token gaps.

After the window: a virtual host is killed and the sessions restored, and the
restored bits must equal the committed ones; and the plain reference, run
over the prompt and served tokens of sessions drawn from the seed, must rank
every served token within the limit of its best.
"""

from __future__ import annotations

import gc

import numpy as np

import harness
from harness import now, setup_note

CHECK_SESSIONS = 4  # sessions drawn from the seed that the reference reruns


class _Probe:
    def __init__(self, server) -> None:
        import jax
        import jax.numpy as jnp

        self.ticks: list[float] = []
        self.finalize_wait_s: list[float] = []
        self.rss_before_first_save: int | None = None
        self.server = server
        decode = server._decode
        pick = jax.jit(lambda logits: jnp.argmax(logits, axis=-1))

        def decode_and_deliver(*a):
            logits, cache = decode(*a)
            jax.device_get(pick(logits))
            self.ticks.append(now())
            return logits, cache

        server._decode = decode_and_deliver
        eng = server.engine
        finalize, capture = eng.finalize_async, eng.checkpoint_async

        def finalize_wrapped(*a, **k):
            res = finalize(*a, **k)
            if res is not None:
                self.finalize_wait_s.append(eng.stats.last_finalize_wait_s)
            return res

        def capture_wrapped(*a, **k):
            if self.rss_before_first_save is None:
                self.rss_before_first_save = harness.host_rss()
            return capture(*a, **k)

        eng.finalize_async, eng.checkpoint_async = finalize_wrapped, capture_wrapped

    def capture_stats(self):
        return self.server.engine.registry.get("ckpt_stage_seconds").stats(phase="capture")


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    from repro.launch import serve as serve_launch
    from repro.models import build_model

    cell, t = ctx.cell, ctx.cell.traffic
    eng_cfg = t["engine"]
    B, P, budget, every = t["batch"], t["prompt_len"], t["decode_budget"], t["save_every_tokens"]
    marks = [("start", now())]
    cfg = ctx.program_config()
    model = build_model(cfg)
    args = serve_launch.build_parser().parse_args([
        "--arch", cfg.name, "--batch", str(B), "--prompt-len", str(P), "--gen", str(budget),
        "--ckpt-every", str(every), "--checkpoint-mode", eng_cfg["checkpoint_mode"],
        "--hosts", str(eng_cfg["hosts"]), "--codec", eng_cfg["codec"],
    ])
    server = serve_launch.build_server(args, model)
    key = jax.random.PRNGKey(ctx.key_seed)
    k_params, k_prompts, k_sample = jax.random.split(key, 3)
    server.params = None
    gc.collect()
    server.params = harness.seeded_params(cell.config_mod, cell.config, k_params, model)
    prompts = np.asarray(jax.random.randint(k_prompts, (B, P), 0, cfg.vocab_size, "int32"))
    probe = _Probe(server)
    jax.block_until_ready(server.params)
    marks.append(("build_server_and_weights", now()))

    # -- set-up --------------------------------------------------------------
    produced = every * t["warm_cycles"]
    server.prefill_and_decode(prompts, produced)
    marks.append(("prefill_first_save_warm_cycles", now()))
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(server.sessions))

    # -- the window ----------------------------------------------------------
    compiles0 = ctx.clock.events
    cap0 = probe.capture_stats()
    n_tick0, n_fin0 = len(probe.ticks), len(probe.finalize_wait_s)
    t0 = probe.ticks[-1]
    trace = None
    budget_hit = False

    def cycle() -> bool:
        nonlocal produced, budget_hit
        if produced + every > budget:
            budget_hit = True
            return False
        produced += every
        server.decode(produced)
        return True

    if ctx.trace:
        with harness.TraceWindow(ctx.trace_dir) as tw:
            for _ in range(t["trace_cycles"]):
                cycle()
    else:
        while cycle() and probe.ticks[-1] - t0 < ctx.seconds:
            pass
    ticks = probe.ticks[n_tick0:]
    t1 = ticks[-1]
    setup_s = t0 - ctx.t_start
    peak_rss = harness.host_peak_rss()
    cap1 = probe.capture_stats()
    mem = server.engine.memory_report()
    device_peak = harness.device_info(ctx.devices)["memory_peak_bytes"]
    if budget_hit:
        ctx.notes.append(f"note: the sessions reached their decode budget of {budget} "
                         "tokens; the window ends there")
    compiles = ctx.clock.events - compiles0
    if compiles:
        ctx.notes.append(f"note: {compiles} compilation(s) inside the window")
    if ctx.trace:
        trace = tw.reduce()
    gaps = np.diff(np.asarray([t0] + ticks)) * 1e3
    saves = len(probe.finalize_wait_s) - n_fin0
    record = {
        "capture_s": (cap1["sum"] - cap0["sum"]) / (cap1["count"] - cap0["count"])
        if cap1["count"] > cap0["count"] else None,
        "finalize_wait_s": probe.finalize_wait_s[n_fin0:],
        "host_store_bytes": mem["total_bytes"],
        "state_bytes": state_bytes,
        "trace": trace,
        "kind": "serve",
    }
    e2e = {
        "setup_s": setup_s,
        "token_gap_p95_ms": float(np.percentile(gaps, 95)),
        "host_bytes_per_state_byte": (peak_rss - probe.rss_before_first_save) / state_bytes,
    }
    ctx.notes.append(setup_note(ctx, marks))
    ctx.notes.append(
        f"window {t1 - t0:.3f} s, {len(ticks)} ticks, {saves} saves, gap p50 "
        f"{np.percentile(gaps, 50):.3f} ms, max {gaps.max():.3f} ms; setup {setup_s:.3f} s; "
        f"host rss before first save {probe.rss_before_first_save}, peak {peak_rss}; "
        f"session state {state_bytes} bytes")

    # -- correctness -----------------------------------------------------------
    checks = round_trip(server)
    tokens = np.asarray(server.sessions["tokens"])
    end = int(server.sessions["pos"])
    sample = np.asarray(jax.random.choice(k_sample, B, (CHECK_SESSIONS,), replace=False))
    server.engine.close()
    server.params = server.sessions = None
    del server, probe
    gc.collect()
    seqs = tokens[sample, : end + 1]
    ref = reference_logits(cell, k_params, seqs, P, budget, "f32")
    checks["logit_gap"] = (max(g for g, _, _ in ref), cell.limits["logit_gap"])
    if ctx.control:
        record["control"] = control_gaps(cell, k_params, k_sample, seqs, P, budget, ref)
    return harness.Outcome(e2e, record, checks, attempted=len(ticks) * B, failed=0,
                           device_peak_bytes=device_peak, trace=trace)


def round_trip(server) -> dict[str, tuple[float, float]]:
    """Kill a host after the last committed save and restore the sessions:
    they must come back bit for bit, through shards rebuilt from redundancy
    whose checksums the restore verified."""
    eng = server.engine
    committed = eng.finalize_async()
    before = harness.tree_digest(server.sessions)
    verify = eng.registry.get("restore_stage_seconds")
    v0 = verify.stats(phase="r_verify")["count"] if verify is not None else 0
    rebuilt0 = eng.stats.reconstructed_restores + eng.stats.adopted_restores
    server.cluster.kill(1)
    server.recover()
    eng = server.engine
    after = harness.tree_digest(server.sessions)
    v1 = eng.registry.get("restore_stage_seconds").stats(phase="r_verify")["count"]
    rebuilt = eng.stats.reconstructed_restores + eng.stats.adopted_restores - rebuilt0
    return {
        "save_not_committed": (float(committed is False), 0.0),
        "restored_leaves_differ": (float(sum(a != b for a, b in zip(before, after))), 0.0),
        "restore_rebuilt_no_shard": (float(rebuilt < 1), 0.0),
        "restore_verified_nothing": (float(v1 - v0 < 1), 0.0),
    }


def control_gaps(cell, k_params, key, seqs, prompt_len, budget, ref) -> dict[str, float]:
    """What the check reads for the control (the reference in fp8: at each
    served position, the gap of the token fp8 puts first) and for one served
    token altered where it is produced (a random other token at a random
    position of the first sampled session)."""
    import jax

    low = reference_logits(cell, k_params, seqs, prompt_len, budget, "fp8")
    fp8 = 0.0
    for (_, logits, _), (_, low_logits, _) in zip(ref, low):
        picked = low_logits.argmax(axis=-1)
        fp8 = max(fp8, float(np.max(logits.max(axis=-1) - logits[np.arange(len(picked)), picked])))
    logits, served = ref[0][1], ref[0][2]
    kp, kt = jax.random.split(key)
    j = int(jax.random.randint(kp, (), 0, len(served)))
    other = (int(served[j]) + 1 + int(jax.random.randint(kt, (), 0, logits.shape[1] - 1))) % logits.shape[1]
    altered = max(max(g for g, _, _ in ref), float(logits[j].max() - logits[j, other]))
    return {"fp8": fp8, "altered_token": altered}


def reference_logits(cell, k_params, seqs: np.ndarray, prompt_len: int, budget: int,
                     precision: str):
    """Run the configuration's plain reference over each session's prompt and
    served tokens (padded at the end to one shape, which leaves the causal
    logits before it as they are). Per session: the widest gap by which a
    served token's logit lies below the reference's best, the reference's
    logits at the served positions (T, V), and the served tokens (T,)."""
    import jax
    import jax.numpy as jnp

    from reference import make_mm, worst_logit_gap

    cfgmod, config = cell.config_mod, cell.config
    mm = make_mm(precision)
    params = jax.jit(lambda k: cfgmod.init_params(k, config))(k_params)
    fwd = jax.jit(lambda p, toks: cfgmod.logits(p, toks, config, mm))
    width = prompt_len + budget
    out = []
    for seq in seqs:
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(seq) - 1] = seq[:-1]
        logits = np.asarray(fwd(params, jnp.asarray(padded))[0, prompt_len - 1: len(seq) - 1])
        served = seq[prompt_len:]
        out.append((worst_logit_gap(logits, served), logits, served))
    return out
