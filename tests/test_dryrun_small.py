"""Dry-run machinery integration test on a small fake-device mesh.

Runs in a subprocess so XLA_FLAGS device-count never pollutes the main test
process (smoke tests must see 1 device, per the launcher contract)."""

import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json, dataclasses
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import smoke_config
    from repro.models import model_schema, cache_schema
    from repro.models import schema as schema_mod
    from repro.sharding import rules, ctx as shard_ctx
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import make_train_step, make_serve_step
    from repro.launch.dryrun import abstract_opt_state, collective_bytes

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = smoke_config("granite_moe_3b_a800m")
    sch = model_schema(cfg)
    pa = schema_mod.abstract(sch)
    ps = rules.param_shardings(sch, mesh, fsdp=True)
    b = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    bs = {k: NamedSharding(mesh, P("data", None)) for k in b}
    repl = NamedSharding(mesh, P())
    pl = jax.ShapeDtypeStruct((cfg.n_layers, cfg.moe_experts), jnp.int32)
    step = make_train_step(cfg, OptConfig(), microbatches=2)
    oa = abstract_opt_state(pa)
    os_ = {"m": ps, "v": ps, "master": ps, "step": repl}
    with shard_ctx.use_mesh(mesh):
        jt = jax.jit(step, in_shardings=(ps, os_, bs, repl),
                     donate_argnums=(0, 1))
        lowered = jt.lower(pa, oa, b, pl)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({
        "flops": float(cost.get("flops", 0)),
        "coll_ops": sorted(coll),
        "coll_total": sum(coll.values()),
        "arg_bytes": getattr(mem, "argument_size_in_bytes", 0),
    }))

    # decode path on the same mesh
    csch = cache_schema(cfg, 8, 128)
    ca = schema_mod.abstract(csch)
    cs = rules.cache_shardings(csch, mesh, 8)
    serve = make_serve_step(cfg)
    with shard_ctx.use_mesh(mesh):
        js = jax.jit(lambda p, c, bb, plc: serve(p, c, bb, 127, plc),
                     in_shardings=(ps, cs, {"tokens": NamedSharding(mesh, P("data", None))}, repl),
                     donate_argnums=(1,))
        low2 = js.lower(pa, ca, {"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32)}, pl)
    comp2 = low2.compile()
    print(json.dumps(
        {"decode_flops": float(comp2.cost_analysis().get("flops", 0))}))
""")


@pytest.mark.slow
def test_small_mesh_dryrun_compiles():
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd="/root/repo",
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    train = json.loads(lines[0])
    decode = json.loads(lines[1])
    assert train["flops"] > 0
    assert train["coll_total"] > 0            # DP sync + EP dispatch exist
    assert "all-reduce" in train["coll_ops"]
    assert decode["decode_flops"] > 0
