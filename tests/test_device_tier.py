"""Device-tier fused snapshot program: one-program exchange semantics,
on-device codec parity vs the host oracle, and PCIe accounting on a virtual
8-device mesh (subprocess, so the 1-device test env is untouched)."""

import os
import subprocess
import sys
import textwrap

def _run(code: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": "src", "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_exchange_roll_semantics_and_restore():
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((8, 6), jnp.float32),
               "rep": jax.ShapeDtypeStruct((5,), jnp.float32)}
        ps = {"w": P("data", "model"), "rep": P()}
        prog = build_snapshot_program(mesh, sds, ps)
        assert len(prog.exchanged_names) == 1
        assert len(prog.buckets) == 1 and prog.buckets[0].tag == "data:float32"
        w = jnp.arange(48, dtype=jnp.float32).reshape(8, 6)
        state = {"w": jax.device_put(w, NamedSharding(mesh, P("data", "model"))),
                 "rep": jnp.ones((5,), jnp.float32)}
        payload = jax.jit(prog.snapshot_fn)(state)
        # partner fused buffer carries each device's shard rolled to its
        # pairwise partner (N/2 shift along the data axis)
        pw = np.asarray(payload["partner"]["data:float32"]).view(np.float32).reshape(4, 2, 6)
        own = np.ascontiguousarray(np.asarray(w).reshape(4, 2, 2, 3).swapaxes(1, 2)).reshape(4, 2, 6)
        assert np.array_equal(pw, np.roll(own, 2, axis=0))
        # own copy present and intact
        assert np.array_equal(np.asarray(payload["own"]["w"]), np.asarray(w))
        rest = jax.jit(prog.restore_fn)(payload)
        assert np.array_equal(np.asarray(rest[prog.exchanged_names[0]]), np.asarray(w))
        # checksum present
        assert payload["checksum"].shape == (2,)
        # compiled HLO carries collective-permutes
        txt = jax.jit(prog.snapshot_fn).lower(state).compile().as_text()
        assert "collective-permute" in txt
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_fused_single_program_many_leaves():
    """The fused path emits ONE collective-permute for any number of
    exchanged leaves, and validate=True folds the checksum into the same
    program — dispatch no longer scales with the leaf count (the pre-fused
    path lowered one permute per leaf and one psum-program per leaf)."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        from repro.utils.hlo import analyze_hlo_collectives
        mesh = make_mesh((4, 2), ("data", "model"))
        L = 6
        sds = {f"w{i}": jax.ShapeDtypeStruct((8, 4 + 2 * i), jnp.float32) for i in range(L)}
        ps = {f"w{i}": (P("data", "model") if i % 2 else P("data", None)) for i in range(L)}
        prog = build_snapshot_program(mesh, sds, ps, include_own_copy=False)
        assert len(prog.exchanged_names) == L
        assert len(prog.buckets) == 1   # one (axis, dtype) bucket -> one program
        state = {f"w{i}": jax.device_put(
                    jnp.arange(8 * (4 + 2 * i), dtype=jnp.float32).reshape(8, 4 + 2 * i),
                    NamedSharding(mesh, ps[f"w{i}"]))
                 for i in range(L)}
        txt = jax.jit(prog.snapshot_fn).lower(state).compile().as_text()
        coll = analyze_hlo_collectives(txt)
        assert coll.count_by_kind.get("collective-permute", 0) == 1, coll.count_by_kind
        # restore returns every leaf bit-identically
        payload = jax.jit(prog.snapshot_fn)(state)
        rest = jax.jit(prog.restore_fn)(payload)
        names = sorted(sds)  # dict flatten order
        for name in prog.exchanged_names:
            orig = np.asarray(state[names[int(name)]])
            assert np.array_equal(np.asarray(rest[name]), orig), name
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_uneven_leaf_padded_exchange():
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"u": jax.ShapeDtypeStruct((7, 2), jnp.float32)}
        ps = {"u": P("data", None)}
        prog = build_snapshot_program(mesh, sds, ps, validate=False)
        u = jnp.arange(14, dtype=jnp.float32).reshape(7, 2)
        st = {"u": jax.device_put(u, NamedSharding(mesh, P(None, None)))}
        payload = jax.jit(prog.snapshot_fn)(st)
        rest = jax.jit(prog.restore_fn)(payload)
        assert np.array_equal(np.asarray(rest[prog.exchanged_names[0]]), np.asarray(u))
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_compressed_exchange_shrinks_traffic():
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        from repro.utils.hlo import analyze_hlo_collectives
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((1024, 512), jnp.float32)}
        ps = {"w": P("data", "model")}
        full = build_snapshot_program(mesh, sds, ps, validate=False, include_own_copy=False)
        comp = build_snapshot_program(mesh, sds, ps, validate=False, include_own_copy=False, compress=True)
        s1 = analyze_hlo_collectives(jax.jit(full.snapshot_fn).lower(sds).compile().as_text())
        s2 = analyze_hlo_collectives(jax.jit(comp.snapshot_fn).lower(sds).compile().as_text())
        b1 = s1.bytes_by_kind.get("collective-permute", 0)
        b2 = s2.bytes_by_kind.get("collective-permute", 0)
        print("full", b1, "compressed", b2)
        assert b2 < b1 / 3   # int8 + scales vs f32
        print("OK")
        """
    )
    assert "OK" in _run(code)


_PARITY_ORACLE = textwrap.dedent(
    """
    import jax, jax.numpy as jnp, numpy as np
    from repro.sharding.mesh import make_mesh
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.device_tier import build_snapshot_program
    from repro.core.codec import XorCodec, RSCodec
    from repro.core import distribution as dist

    mesh = make_mesh((4, 2), ("data", "model"))
    sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
           "v": jax.ShapeDtypeStruct((8,), jnp.bfloat16),
           "b": jax.ShapeDtypeStruct((16,), jnp.int8)}
    ps = {"w": P("data", "model"), "v": P("data"), "b": P("data")}
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((8,)), jnp.bfloat16)
    b = jnp.asarray(rng.integers(-100, 100, (16,)), jnp.int8)
    state = {"w": jax.device_put(w, NamedSharding(mesh, P("data", "model"))),
             "v": jax.device_put(v, NamedSharding(mesh, P("data"))),
             "b": jax.device_put(b, NamedSharding(mesh, P("data")))}

    def member_buf(tag, d, m):
        if tag == "data:float32":
            raw = np.ascontiguousarray(np.asarray(w)[2*d:2*d+2, 2*m:2*m+2]).tobytes()
        elif tag == "data:bfloat16":
            raw = np.ascontiguousarray(np.asarray(v)[2*d:2*d+2]).tobytes()
        else:
            raw = np.ascontiguousarray(np.asarray(b)[4*d:4*d+4]).tobytes()
        a = np.frombuffer(raw, np.uint8)
        return np.pad(a, (0, (-a.nbytes) % 4))

    def check(codec_name, g, mpar):
        prog = build_snapshot_program(
            mesh, sds, ps, validate=False, include_own_copy=False,
            codec=codec_name, parity_group=g, rs_parity=mpar, emit_full_blobs=True)
        assert len(prog.buckets) == 3  # one per dtype, all in ONE program
        payload = jax.jit(prog.snapshot_fn)(state)
        host = XorCodec(g) if codec_name == "xor" else RSCodec(g, mpar)
        groups = dist.parity_groups(4, g)
        shapes = {"data:float32": (4, 2), "data:bfloat16": (4,), "data:int8": (4,)}
        for bucket in prog.buckets:
            pf = np.asarray(payload["parity_full"][bucket.tag])
            per = pf.reshape((mpar,) + shapes[bucket.tag] + (bucket.words,))
            for gi, grp in enumerate(groups):
                mcoords = [0, 1] if len(shapes[bucket.tag]) == 2 else [None]
                for m in mcoords:
                    bufs = [member_buf(bucket.tag, d, m or 0) for d in grp.members]
                    blobs = host.encode(bufs, mpar)
                    for d in grp.members:
                        for j in range(mpar):
                            dev = per[j, d, m] if m is not None else per[j, d]
                            got = dev.view(np.uint8)[: blobs[j].nbytes]
                            assert np.array_equal(got, blobs[j]), (bucket.tag, gi, d, j)
    """
)


def test_unaligned_local_shard_words():
    """A leaf whose per-device shard is not 4-byte aligned (int8 (4,2) over
    data=4 -> 2-byte shards) still lays out, exchanges, and restores
    correctly — regression for ceil word sizing in the fused layout."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"a": jax.ShapeDtypeStruct((4, 2), jnp.int8),
               "b": jax.ShapeDtypeStruct((8, 3), jnp.int8)}
        ps = {"a": P("data", None), "b": P("data", None)}
        prog = build_snapshot_program(mesh, sds, ps, validate=False, include_own_copy=False)
        bkt = prog.buckets[0]
        assert bkt.word_offsets == (0, 1) and bkt.words == 3, (bkt.word_offsets, bkt.words)
        a = jnp.arange(8, dtype=jnp.int8).reshape(4, 2)
        b = jnp.arange(24, dtype=jnp.int8).reshape(8, 3)
        state = {"a": jax.device_put(a, NamedSharding(mesh, P("data", None))),
                 "b": jax.device_put(b, NamedSharding(mesh, P("data", None)))}
        payload = jax.jit(prog.snapshot_fn)(state)
        rest = jax.jit(prog.restore_fn)(payload)
        names = sorted(sds)
        for name in prog.exchanged_names:
            assert np.array_equal(np.asarray(rest[name]), np.asarray(state[names[int(name)]])), name
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_device_xor_parity_matches_host_oracle():
    """On-device XOR encode (Pallas kernel inside the fused program) is
    bit-identical to host-side codec.encode across f32/bf16/int8 buckets."""
    assert "OK" in _run(_PARITY_ORACLE + 'check("xor", 2, 1)\nprint("OK")\n')


def test_device_rs_parity_matches_host_oracle_ragged():
    """On-device GF(2^8) RS encode matches the host oracle, including a
    ragged last group (axis 4, g=3 -> groups {0,1,2},{3})."""
    assert "OK" in _run(_PARITY_ORACLE + 'check("rs", 3, 2)\nprint("OK")\n')


def test_device_stripes_and_pcie_accounting():
    """The production stripe path: blob b routes to neighbor group gi+1+b and
    each holder keeps its 1/g stripe — only own + m/g parity bytes cross
    PCIe, and the program metadata accounts for it."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        from repro.core.codec import XorCodec
        from repro.core import distribution as dist
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
        ps = {"w": P("data", "model")}
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
        state = {"w": jax.device_put(w, NamedSharding(mesh, P("data", "model")))}
        g = 2
        prog = build_snapshot_program(mesh, sds, ps, validate=False,
                                      include_own_copy=False, codec="xor", parity_group=g)
        full = build_snapshot_program(mesh, sds, ps, validate=False, include_own_copy=False)
        # PCIe: m/g of the fused bytes vs the whole partner copy
        assert prog.pcie_bytes * g == full.pcie_bytes * 1
        payload = jax.jit(prog.snapshot_fn)(state)
        bucket = prog.buckets[0]
        per = np.asarray(payload["parity"][bucket.tag]).reshape(1, 4, 2, bucket.words // g)
        def member_buf(d, m):
            raw = np.ascontiguousarray(np.asarray(w)[2*d:2*d+2, 2*m:2*m+2]).tobytes()
            return np.frombuffer(raw, np.uint8)
        groups = dist.parity_groups(4, g)
        codec = XorCodec(g)
        sw = bucket.words // g * 4
        for gi, grp in enumerate(groups):
            src = groups[(gi - 1) % len(groups)]   # holder gi hosts gi-1's blob
            for m in range(2):
                blob = codec.encode([member_buf(d, m) for d in src.members], 1)[0]
                for pos, d in enumerate(grp.members):
                    got = per[0, d, m].view(np.uint8)
                    assert np.array_equal(got, blob[pos*sw:(pos+1)*sw]), (gi, d, m)
        print("OK")
        """
    )
    assert "OK" in _run(code)


_STRIPED_RESTORE = textwrap.dedent(
    """
    import itertools
    import jax, jax.numpy as jnp, numpy as np
    from repro.sharding.mesh import make_mesh
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.device_tier import (
        build_snapshot_program, build_striped_restore_program, striped_decode_rows,
    )

    mesh = make_mesh((4, 2), ("data", "model"))
    sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
           "v": jax.ShapeDtypeStruct((8,), jnp.bfloat16),
           "b": jax.ShapeDtypeStruct((16,), jnp.int8)}
    ps = {"w": P("data", "model"), "v": P("data"), "b": P("data")}
    rng = np.random.default_rng(0)
    state = {"w": jax.device_put(jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
                                 NamedSharding(mesh, ps["w"])),
             "v": jax.device_put(jnp.asarray(rng.standard_normal((8,)), jnp.bfloat16),
                                 NamedSharding(mesh, ps["v"])),
             "b": jax.device_put(jnp.asarray(rng.integers(-100, 100, (16,)), jnp.int8),
                                 NamedSharding(mesh, ps["b"]))}
    names = sorted(sds)

    def corrupt(failed):
        # failed data-coordinates upload garbage: the survivor mask must
        # zero it before reconstruction
        out = {}
        for k, val in state.items():
            a = np.asarray(val).copy()
            fl = a.reshape(-1); fl[:] = fl  # writable
            for r in failed:
                if k == "w":   a[2*r:2*r+2] = 99.0
                elif k == "v": a[2*r:2*r+2] = 99.0
                else:          a[4*r:4*r+4] = 99
            out[k] = jax.device_put(jnp.asarray(a, val.dtype), NamedSharding(mesh, ps[k]))
        return out

    def check(codec, g, mpar, ll=2):
        snap = build_snapshot_program(
            mesh, sds, ps, validate=False, include_own_copy=False,
            codec=codec, parity_group=g, rs_parity=mpar, lrc_locals=ll)
        payload = jax.jit(snap.snapshot_fn)(state)
        rest = build_striped_restore_program(
            mesh, sds, ps, codec=codec, parity_group=g, rs_parity=mpar,
            lrc_locals=ll)
        tol = 1 if codec == "xor" else mpar
        n_ok = 0
        for nfail in range(0, tol + 1):
            for failed in itertools.combinations(range(4), nfail):
                try:
                    rows, mask = striped_decode_rows(
                        4, g, codec, mpar, set(failed), lrc_locals=ll)
                except ValueError:
                    continue  # burst exceeds this group's tolerance/blobs
                bad = corrupt(failed)
                out = rest.restore_fn(bad, payload["parity"],
                                      {"data": rows}, {"data": mask})
                for idx, leaf in out.items():
                    orig = np.asarray(state[names[int(idx)]])
                    got = np.asarray(leaf)
                    assert got.dtype == orig.dtype, (codec, failed, idx)
                    assert np.array_equal(got.view(np.uint8), orig.view(np.uint8)), \
                        (codec, failed, idx)
                n_ok += 1
        assert n_ok > 1, (codec, g, mpar, n_ok)  # at least no-fail + singles
    """
)


def test_device_striped_restore_xor_all_failure_combos():
    """The fused inverse restore program reconstructs every failed
    coordinate ON DEVICE (inverse stripe routing + ring blob reassembly +
    runtime-coefficient GF kernel), bit-identical to the pre-failure state —
    i.e. to host codec.decode, which the host oracle tests pin to the same
    bytes — across f32/bf16/int8 buckets for every failure combo <= 1."""
    assert "OK" in _run(_STRIPED_RESTORE + 'check("xor", 2, 1)\nprint("OK")\n')


def test_device_striped_restore_rs_all_failure_combos():
    """Same for rs(m=2): every 1- and 2-failure combo the decode-rows
    precompute accepts restores bit-identically, including garbage uploads
    on the failed coordinates (the survivor mask zeroes them)."""
    assert "OK" in _run(_STRIPED_RESTORE + 'check("rs", 2, 2)\nprint("OK")\n')


def test_device_striped_restore_ragged_world():
    """g=3 on a 4-wide axis (groups {0,1,2},{3}): the ragged round-robin
    stripe layout — NOT a full-blob fallback — encodes, routes, and restores
    every accepted failure combo bit-identically (DESIGN.md §16)."""
    assert "OK" in _run(_STRIPED_RESTORE + 'check("rs", 3, 2)\nprint("OK")\n')


def test_device_striped_restore_lrc():
    """The LRC codec runs through the SAME fused stripe/restore machinery:
    local+global blobs (n_parity = l+g rows), decode rows selected by the
    codec's own cheapest-invertible search, bit-identical recovery —
    including the ragged g=3 world."""
    assert "OK" in _run(
        _STRIPED_RESTORE
        + 'check("lrc", 2, 1)\ncheck("lrc", 3, 2)\nprint("OK")\n'
    )


def test_staged_snapshot_fetch_double_buffered_bit_identical():
    """The per-chunk staging programs (own copy + one per bucket) fetch the
    same bytes as the monolithic program, with and without D2H overlap."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program, staged_snapshot_fetch
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
               "v": jax.ShapeDtypeStruct((8,), jnp.bfloat16)}
        ps = {"w": P("data", "model"), "v": P("data")}
        rng = np.random.default_rng(0)
        state = {"w": jax.device_put(jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
                                     NamedSharding(mesh, ps["w"])),
                 "v": jax.device_put(jnp.asarray(rng.standard_normal((8,)), jnp.bfloat16),
                                     NamedSharding(mesh, ps["v"]))}
        prog = build_snapshot_program(mesh, sds, ps, validate=False,
                                      codec="xor", parity_group=2)
        assert len(prog.snapshot_chunk_fns) == 1 + len(prog.buckets)
        mono = jax.jit(prog.snapshot_fn)(state)
        for db in (True, False):
            staged = staged_snapshot_fetch(prog, state, double_buffer=db)
            for tag in mono["parity"]:
                assert np.array_equal(np.asarray(mono["parity"][tag]),
                                      staged["parity"][tag]), (db, tag)
            for k in sds:
                assert np.array_equal(np.asarray(mono["own"][k]), staged["own"][k]), (db, k)
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_ragged_world_takes_stripe_path_not_fallback():
    """parity_group not dividing the axis (g=3 on 4): the default now takes
    the TRUE ragged stripe path — the payload carries round-robin stripe
    slots, not whole blobs — and full blobs remain an explicit opt-in
    (emit_full_blobs=True)."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
        ps = {"w": P("data", "model")}
        w = jnp.asarray(np.random.default_rng(1).standard_normal((8, 4)), jnp.float32)
        state = {"w": jax.device_put(w, NamedSharding(mesh, ps["w"]))}
        # g=3 does not divide 4: stripes anyway (groups {0,1,2},{3}; S=3)
        prog = build_snapshot_program(mesh, sds, ps, validate=False,
                                      include_own_copy=False, codec="xor", parity_group=3)
        payload = jax.jit(prog.snapshot_fn)(state)
        assert "parity" in payload and "parity_full" not in payload
        # per-device stripe buffer: n_parity rows of S*(words/g) words each,
        # S = 3 (the short group {3} has k=1 -> ceil(3/1) slots)
        bkt = prog.buckets[0]
        per = np.asarray(payload["parity"][bkt.tag])
        assert per.size == 4 * 2 * 1 * 3 * (bkt.words // 3), per.shape
        # full blobs stay available as the explicit opt-in
        full = build_snapshot_program(mesh, sds, ps, validate=False,
                                      include_own_copy=False, codec="xor",
                                      parity_group=3, emit_full_blobs=True)
        pf = jax.jit(full.snapshot_fn)(state)
        assert "parity_full" in pf and "parity" not in pf
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_stripe_pcie_accounting_exact_divisible_ragged_and_full_blob():
    """``pcie_bytes`` equals the measured payload exactly — own copies
    (unpadded leaves) + the stripe slots every device keeps — on a dividing
    world (S=1), a ragged world (S>1), AND the explicit full-blob opt-in
    (m whole parity blobs per group member)."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_snapshot_program

        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
               "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
        ps = {"w": P("data", "model"), "b": P("data")}
        rng = np.random.default_rng(0)
        state = {k: jax.device_put(
                     jnp.asarray(rng.standard_normal(sds[k].shape), jnp.float32),
                     NamedSharding(mesh, ps[k]))
                 for k in sds}
        for g, full_blobs in ((2, False), (3, False), (3, True)):
            prog = build_snapshot_program(
                mesh, sds, ps, validate=False, include_own_copy=True,
                codec="rs", parity_group=g, rs_parity=2,
                emit_full_blobs=full_blobs)
            payload = jax.jit(prog.snapshot_fn)(state)
            own = sum(np.asarray(x).nbytes for x in jax.tree.leaves(payload["own"]))
            key = "parity_full" if full_blobs else "parity"
            assert key in payload and len(payload) == 2, sorted(payload)
            parity = sum(np.asarray(payload[key][b.tag]).nbytes
                         for b in prog.buckets)
            assert prog.pcie_bytes == own + parity, (
                g, full_blobs, prog.pcie_bytes, own, parity)
        print("OK")
        """
    )
    assert "OK" in _run(code)


def test_mirror_program_routes_primary_buckets_to_shadow_twins():
    """Hot-replica transport (DESIGN.md §15): build_mirror_program emits the
    same fused uint32 buckets but routes them through the half-rotation to
    the shadow team — each shadow coordinate's slice of ``mirror[tag]`` is
    its primary twin's bucket, verbatim (no parity, no own copy), with the
    handshake checksum folded into the same single-permute program."""
    code = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.mesh import make_mesh
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.device_tier import build_mirror_program
        from repro.utils.hlo import analyze_hlo_collectives
        mesh = make_mesh((4, 2), ("data", "model"))
        sds = {"w": jax.ShapeDtypeStruct((8, 6), jnp.float32),
               "rep": jax.ShapeDtypeStruct((5,), jnp.float32)}
        ps = {"w": P("data", "model"), "rep": P()}
        prog = build_mirror_program(mesh, sds, ps)
        w = jnp.arange(48, dtype=jnp.float32).reshape(8, 6)
        state = {"w": jax.device_put(w, NamedSharding(mesh, P("data", "model"))),
                 "rep": jnp.ones((5,), jnp.float32)}
        payload = jax.jit(prog.snapshot_fn)(state)
        assert "mirror" in payload and "partner" not in payload
        assert "own" not in payload and "parity" not in payload
        # oracle: per-coordinate fused bucket, rotated by the team size T=2
        mw = np.asarray(payload["mirror"]["data:float32"]).view(np.float32).reshape(4, 2, 6)
        own = np.ascontiguousarray(np.asarray(w).reshape(4, 2, 2, 3).swapaxes(1, 2)).reshape(4, 2, 6)
        assert np.array_equal(mw, np.roll(own, 2, axis=0))
        assert payload["checksum"].shape == (2,)
        txt = jax.jit(prog.snapshot_fn).lower(state).compile().as_text()
        coll = analyze_hlo_collectives(txt)
        assert coll.count_by_kind.get("collective-permute", 0) == 1, coll.count_by_kind
        print("OK")
        """
    )
    assert "OK" in _run(code)
