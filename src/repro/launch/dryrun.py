import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax (device count is locked above) ---------
import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, get_config              # noqa: E402
from repro.launch import specs as specs_mod              # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402
from repro.models import model_schema, cache_schema      # noqa: E402
from repro.models import schema as schema_mod            # noqa: E402
from repro.models.config import SHAPES                   # noqa: E402
from repro.sharding import rules                         # noqa: E402
from repro.sharding import ctx as shard_ctx                # noqa: E402
from repro.train.optimizer import OptConfig              # noqa: E402
from repro.train.train_step import (make_serve_step,     # noqa: E402
                                    make_train_step)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# default microbatch counts per train cell (keeps MoE dispatch transients sane)
TRAIN_MICROBATCHES = {"train_4k": 8}

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u64": 8, "s64": 8,
                "u32": 4, "s32": 4, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}


def collective_bytes(hlo_text: str) -> dict:
    """Sum collective result bytes per op family from optimized HLO.

    Link-traffic multipliers applied downstream (roofline.py): all-reduce
    moves ~2x its payload over the ring; others ~1x.
    """
    out = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        op, dt, dims = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0) + n * _DTYPE_BYTES.get(dt, 4)
    return out


def _probe_costs(cfg, shape, mesh, fsdp: bool, remat: bool):
    """XLA's cost_analysis counts a while-loop body ONCE, so scan-over-layers
    (and microbatch) totals are undercounted. Probe with 1-group and 2-group
    variants of the same config at microbatches=1; per-group deltas give the
    exact linear-in-depth totals:  total(n) = base + n * delta.
    """
    import dataclasses as dc
    period = cfg.pattern_period
    n_groups = cfg.n_layers // period
    # probe at 2 and 3 groups: the 1-group edge case occasionally flips SPMD
    # partitioner decisions (observed: logits path replicated at g=1 for
    # internvl2), corrupting the delta. 2->3 sits in the steady regime.
    reports = []
    for g in (2, 3):
        # encoder scales 1:1 with decoder groups (whisper: 32 enc / 32 dec)
        c = dc.replace(cfg, n_layers=g * period,
                       encoder_layers=g if cfg.encoder_layers else 0)
        reports.append(_lower_raw(c, shape, mesh, fsdp, remat,
                                  microbatches=1))
    c2, c3 = reports
    out = {}
    for key in ("flops", "bytes_accessed"):
        delta = c3[key] - c2[key]
        out[key] = c2[key] + (n_groups - 2) * delta
        out[key + "_per_group"] = delta
    coll = {}
    ops = set(c2["collective_bytes"]) | set(c3["collective_bytes"])
    for op in ops:
        v2 = c2["collective_bytes"].get(op, 0)
        v3 = c3["collective_bytes"].get(op, 0)
        coll[op] = v2 + (n_groups - 2) * (v3 - v2)
    out["collective_bytes"] = coll
    # microbatch scan scales tokens linearly and probes ran the full batch at
    # microbatches=1, so no further correction is needed for train cells.
    return out


def _lower_raw(cfg, shape, mesh, fsdp, remat, microbatches):
    """Lower+compile one step; return raw cost numbers (no caching)."""
    from repro.models import attention as attn_mod
    with shard_ctx.use_mesh(mesh), attn_mod.unrolled_chunks():
        return _lower_raw_inner(cfg, shape, mesh, fsdp, remat, microbatches)


def _lower_raw_inner(cfg, shape, mesh, fsdp, remat, microbatches):
    sch = model_schema(cfg)
    params_abs = schema_mod.abstract(sch)
    p_shard = rules.param_shardings(sch, mesh, fsdp=fsdp)
    b_specs = specs_mod.batch_specs(cfg, shape)
    b_shard = specs_mod.batch_shardings(cfg, shape, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())
    placements_abs = None
    place_shard = None
    if cfg.moe_experts:
        placements_abs = jax.ShapeDtypeStruct(
            (cfg.n_layers, cfg.moe_experts), jnp.int32)
        place_shard = repl
    if shape.kind == "train":
        # loss_chunks=1 + unrolled layer scan: no loops left for XLA's
        # loop-blind cost model, so totals are exact for architectures
        # without inner time scans (see roofline.py).
        step = make_train_step(cfg, OptConfig(), microbatches=microbatches,
                               remat=remat, loss_chunks=1, unroll=True)
        opt_abs = abstract_opt_state(params_abs)
        opt_shard = {"m": p_shard, "v": p_shard, "master": p_shard,
                     "step": repl}
        jitted = jax.jit(step,
                         in_shardings=(p_shard, opt_shard, b_shard,
                                       place_shard),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_abs, opt_abs, b_specs, placements_abs)
    else:
        step = make_serve_step(cfg, unroll=True)
        csch = cache_schema(cfg, shape.global_batch,
                            specs_mod.cache_max_seq(cfg, shape))
        cache_abs = schema_mod.abstract(csch)
        c_shard = rules.cache_shardings(csch, mesh, shape.global_batch)
        index = shape.seq_len - 1 if shape.kind == "decode" else 0
        if cfg.moe_experts:
            jitted = jax.jit(lambda p, c, b, pl: step(p, c, b, index, pl),
                             in_shardings=(p_shard, c_shard, b_shard,
                                           place_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, b_specs,
                                   placements_abs)
        else:
            jitted = jax.jit(lambda p, c, b: step(p, c, b, index, None),
                             in_shardings=(p_shard, c_shard, b_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, b_specs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes": collective_bytes(compiled.as_text())}


def abstract_opt_state(param_abstract):
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return {
        "m": jax.tree.map(f32, param_abstract),
        "v": jax.tree.map(f32, param_abstract),
        "master": jax.tree.map(f32, param_abstract),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int | None = None, fsdp: bool = True,
               remat: bool = True, extra_tag: str = ""):
    """Lower + compile one (arch x shape x mesh) cell; return the report."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = specs_mod.cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": True, "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    sch = model_schema(cfg)
    params_abs = schema_mod.abstract(sch)
    p_shard = rules.param_shardings(sch, mesh, fsdp=fsdp)
    b_specs = specs_mod.batch_specs(cfg, shape)
    b_shard = specs_mod.batch_shardings(cfg, shape, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    placements_abs = None
    place_shard = None
    if cfg.moe_experts:
        placements_abs = jax.ShapeDtypeStruct(
            (cfg.n_layers, cfg.moe_experts), jnp.int32)
        place_shard = repl

    shard_ctx_cm = shard_ctx.use_mesh(mesh)
    shard_ctx_cm.__enter__()
    if shape.kind == "train":
        mb = microbatches or TRAIN_MICROBATCHES.get(shape_name, 1)
        step = make_train_step(cfg, OptConfig(), microbatches=mb,
                               remat=remat)
        opt_abs = abstract_opt_state(params_abs)
        opt_shard = {"m": p_shard, "v": p_shard, "master": p_shard,
                     "step": repl}
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, opt_shard, b_shard, place_shard),
            donate_argnums=(0, 1))
        lowered = jitted.lower(params_abs, opt_abs, b_specs, placements_abs)
    else:
        step = make_serve_step(cfg)
        csch = cache_schema(cfg, shape.global_batch,
                            specs_mod.cache_max_seq(cfg, shape))
        cache_abs = schema_mod.abstract(csch)
        c_shard = rules.cache_shardings(csch, mesh, shape.global_batch)
        index = shape.seq_len - 1 if shape.kind == "decode" else 0
        if cfg.moe_experts:
            jitted = jax.jit(
                lambda p, c, b, pl: step(p, c, b, index, pl),
                in_shardings=(p_shard, c_shard, b_shard, place_shard),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, b_specs,
                                   placements_abs)
        else:
            jitted = jax.jit(
                lambda p, c, b: step(p, c, b, index, None),
                in_shardings=(p_shard, c_shard, b_shard),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, b_specs)
    shard_ctx_cm.__exit__(None, None, None)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    # depth-corrected totals (scan bodies are undercounted by cost_analysis).
    # Multi-pod cells skip probes: §Roofline is single-pod by design and the
    # multi-pod pass exists to prove the pod axis shards + report memory.
    if multi_pod:
        probe = {"skipped": "multi-pod: no probes"}
    else:
        try:
            probe = _probe_costs(cfg, shape, mesh, fsdp, remat)
        except Exception as e:  # noqa: BLE001
            probe = {"error": f"{type(e).__name__}: {e}"}

    report = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "devices": int(mesh.devices.size),
        "skipped": False,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "corrected": probe,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
        "params": schema_mod.count_params(sch),
        "replicated_fallbacks": rules.replication_report(sch, mesh, fsdp),
        "microbatches": microbatches or TRAIN_MICROBATCHES.get(shape_name, 1)
        if shape.kind == "train" else None,
        "tag": extra_tag,
    }
    return report


def cell_list():
    cells = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape_name in SHAPES:
            ok, _ = specs_mod.cell_applicable(cfg, SHAPES[shape_name])
            if ok:
                cells.append((arch, shape_name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    if args.all:
        cells = cell_list()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch.replace("-", "_"), args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch, shape_name in cells:
        for multi in meshes:
            tag = f"{args.tag}_" if args.tag else ""
            name = f"{tag}{arch}__{shape_name}__{'multi' if multi else 'single'}.json"
            out = RESULTS_DIR / name
            if out.exists() and not args.force:
                print(f"[skip-cached] {name}")
                continue
            print(f"[dryrun] {arch} x {shape_name} x "
                  f"{'multi' if multi else 'single'} ...", flush=True)
            try:
                rep = lower_cell(arch, shape_name, multi,
                                 microbatches=args.microbatches,
                                 fsdp=not args.no_fsdp,
                                 remat=not args.no_remat,
                                 extra_tag=args.tag)
            except Exception as e:  # noqa: BLE001 - report and continue
                rep = {"arch": arch, "shape": shape_name,
                       "mesh": "multi" if multi else "single",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            out.write_text(json.dumps(rep, indent=1))
            status = ("ERROR " + rep["error"][:120]) if "error" in rep else \
                ("skipped: " + rep["reason"] if rep.get("skipped") else
                 f"ok flops={rep['flops']:.3e} compile={rep['compile_s']}s")
            print(f"  -> {status}", flush=True)


if __name__ == "__main__":
    main()
