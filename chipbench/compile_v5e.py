"""Compile each cell's programs for a described TPU v5e, with no chip, and
print what the compiler reports of their device memory, and each cell's
reckoning of the host memory its checkpoint engine needs.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/compile_v5e.py [cell ...]

The programs are the ones the cell's window drives: the program's jitted
train step (built by ``Trainer._build_train_step``), or the server's prefill
and decode step, at the cell's sizes. Nothing runs; the numbers are the
compiler's, per program, not a measurement.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def _mem(compiled) -> dict[str, int]:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes", "alias_size_in_bytes")
    out = {k: int(getattr(m, k)) for k in keys}
    out["peak_estimate_bytes"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                                  + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    return out


def host_bytes_needed(split: int, rep: int, hosts: int) -> int:
    """Peak host bytes of the engine under the copy codec (after
    chip_smoke.py's reckoning): per rank and generation bank the own shard
    (split part plus every replicated leaf), the exchange subset and the
    partner copy, two banks once a second save commits; on top the whole
    state fetched during a capture and the checksum's cached weight vector."""
    own = split + hosts * rep
    weights = 4 << max(((split // hosts + rep) // 4 - 1).bit_length(), 0)
    return 2 * (own + split + split) + split + rep + weights


def state_split(sds, pspecs, hosts: int) -> tuple[int, int]:
    """(bytes the program's shard plan splits over ``hosts`` ranks, bytes it
    replicates to every rank)."""
    import math

    import jax

    from repro.runtime.state import ShardPlan

    plan = ShardPlan.from_pspecs(sds, pspecs)
    split = rep = 0
    for i, leaf in enumerate(jax.tree.leaves(sds)):
        n = math.prod(leaf.shape) * leaf.dtype.itemsize
        if plan.split_dim(i, hosts) is None:
            rep += n
        else:
            split += n
    return split, rep


def host_reckoning(name: str) -> dict[str, int]:
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.runtime.trainer import state_pspecs, state_shape_dtypes
    from repro.sharding.axes import rules_for_shape, tree_pspecs
    from repro.sharding.mesh import abstract_mesh
    from repro.sharding.spec import specs_to_shape_dtype

    cell = harness.load_cell(name)
    ctx = harness.Context(cell, 0, 0.0, False, [], None, 0.0, Path("."))
    model = build_model(ctx.program_config())
    t = cell.traffic
    hosts = t["engine"]["hosts"]
    mesh = abstract_mesh(("data", 16), ("model", 16))
    if t["driver"] == "train":
        sds, pspecs = state_shape_dtypes(model), state_pspecs(model, mesh)
    else:
        B, max_seq = t["batch"], t["prompt_len"] + t["decode_budget"] + 2
        cache = model.abstract_cache(B, max_seq)
        P = jax.sharding.PartitionSpec
        sds = {"cache": specs_to_shape_dtype(cache),
               "tokens": jax.ShapeDtypeStruct((B, max_seq), jnp.int32),
               "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        pspecs = {"cache": tree_pspecs(cache, rules_for_shape(model.rules, "decode", B), mesh),
                  "tokens": P(), "pos": P()}
    split, rep = state_split(sds, pspecs, hosts)
    return {"state_bytes": split + rep, "split_bytes": split, "replicated_bytes": rep,
            "engine_host_bytes_estimate": host_bytes_needed(split, rep, hosts)}


def compile_cell(name: str, dev) -> dict[str, dict[str, int]]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.models import build_model
    from repro.runtime.trainer import Trainer, TrainerConfig, state_shape_dtypes

    cell = harness.load_cell(name)
    ctx = harness.Context(cell, 0, 0.0, False, [], None, 0.0, Path("."))
    cfg = ctx.program_config()
    model = build_model(cfg)
    t = cell.traffic
    one = SingleDeviceSharding(dev)
    put = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)
    out = {}
    if t["driver"] == "train":
        tcfg = TrainerConfig(batch=t["batch"], seq=t["seq"], lr=t["optimizer"]["lr"],
                             total_steps=t["optimizer"]["total_steps"])
        step = Trainer._build_train_step(SimpleNamespace(model=model, tcfg=tcfg, mesh=None))
        tok = jax.ShapeDtypeStruct((t["batch"], t["seq"]), jnp.int32, sharding=one)
        out["train_step"] = _mem(step.lower(put(state_shape_dtypes(model)),
                                            {"tokens": tok, "labels": tok}).compile())
    else:
        B, P = t["batch"], t["prompt_len"]
        max_seq = P + t["decode_budget"] + 2
        params = put(model.param_shape_dtypes())
        cache = put(jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                                 model.abstract_cache(B, max_seq),
                                 is_leaf=lambda x: hasattr(x, "dims")))
        prefill = jax.jit(lambda p, toks: model.prefill(p, tokens=toks))
        decode = jax.jit(lambda p, c, tok, pos: model.decode_step(p, c, tok, pos))
        out[f"prefill_{B}x{P}"] = _mem(prefill.lower(
            params, jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one)).compile())
        out[f"decode_{B}x{max_seq}"] = _mem(decode.lower(
            params, cache, jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile())
    return out


def main(argv: list[str]) -> int:
    from jax.experimental import topologies

    bench = harness.read_json(harness.REPO_ROOT / "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    sys.path.insert(0, str(harness.REPO_ROOT / "src"))
    for name in names:
        print(json.dumps({name: {"host_reckoning": host_reckoning(name)}}), flush=True)
        print(json.dumps({name: compile_cell(name, topo.devices[0])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
