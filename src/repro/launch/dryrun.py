import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init) — do not move or reorder them.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, lower + compile the real
train_step / serve_step against ShapeDtypeStruct inputs (no allocation) on:

  * the single-pod production mesh  (16, 16)       = 256 chips
  * the multi-pod production mesh   (2, 16, 16)    = 512 chips

and record, per cell: memory_analysis (fits-on-chip proof), cost_analysis
(FLOPs / bytes for §Roofline), and the collective schedule (bytes moved per
collective class, parsed from the partitioned HLO).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all --multipod --out results/dryrun.json
"""
import argparse
import json
import re
import time
import traceback
from typing import Optional

import jax
import numpy as np

from repro import configs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import ShardingRules, needs_fsdp
from repro.launch import mesh as mesh_lib
from repro.launch.steps import make_decode_step, make_train_step
from repro.models import api
from repro.optim import AdamWConfig, adamw_init

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:[0-9]+)?|pred)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all dtype[dims] terms in an HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def stencil_plan_report(physics: str, nz: int, order: int,
                        block, plan_cache=None, interp=None,
                        **plan_kwargs) -> dict:
    """Joint two-level TB plan selection for one per-device stencil block
    (DESIGN.md §4) — the stencil analogue of an LM dry-run cell.

    Runs `core.temporal_blocking.plan_hierarchy` (outer exchange depth x
    inner (tile, T) x overlapped-vs-serialized exchange, under the
    mesh-aware cost model) BEHIND the survey plan cache
    (`survey/plan_cache.py`): repeated cells — and repeated CI runs when
    ``REPRO_PLAN_CACHE_DIR`` points at a persistent directory — answer
    from the cache instead of re-sweeping, and the report's ``cache``
    field records the resolved key and hit/miss.  Records what the
    executor will do plus the per-field exchange-byte saving against the
    uniform-depth baseline and the VMEM saving of the time-nested
    schedule against the flat plan at the same exchange depth.  Consumed
    by `launch/stencil_dist.py --dryrun`.

    `interp` (a `core.interp.InterpSpec`; default multilinear) annotates
    the report: the ``interp`` field records the kernel, radius, and
    (2r)**3 footprint that size the sparse-point tables the executor will
    bin.
    """
    from repro.core import interp as interp_mod
    from repro.core.temporal_blocking import PHYSICS_COSTS, TBPlan
    from repro.survey.plan_cache import cached_plan_hierarchy

    ispec = interp_mod.LINEAR if interp is None else interp
    hier, entry, info = cached_plan_hierarchy(physics, nz, order, block,
                                              cache=plan_cache,
                                              **plan_kwargs)
    uni = hier.exchange_bytes_uniform(nz)
    pf = hier.exchange_bytes(nz)
    fields = PHYSICS_COSTS[physics].fields
    flat_vmem = TBPlan(hier.inner.tile, hier.outer_T,
                       hier.inner.radius).vmem_bytes(nz, fields)
    return {
        "physics": physics, "order": order, "block": list(block), "nz": nz,
        "outer": {"T": hier.outer_T, "halo": hier.halo,
                  "overlap": hier.overlap,
                  "field_depths": list(hier.field_depths)},
        "inner": {"tile": list(hier.inner.tile), "T": hier.inner.T,
                  "passes": -(-hier.outer_T // hier.inner.T),
                  "grid": [block[0] // hier.inner.tile[0],
                           block[1] // hier.inner.tile[1]]},
        "exchange_bytes": int(pf),
        "exchange_bytes_uniform": int(uni),
        "exchange_saving": round(1.0 - pf / uni, 4) if uni else 0.0,
        "vmem_bytes": int(hier.vmem_bytes(nz, fields)),
        "vmem_bytes_flat": int(flat_vmem),
        "model": {k: entry[k] for k in
                  ("compute_s", "memory_s", "comm_s", "split_s", "cost_s")
                  if k in entry},
        "cache": {"key": info.key, "hit": info.hit},
        "interp": {**ispec.to_dict(), "footprint": ispec.footprint(3)},
    }


def collective_bytes(hlo_text: str) -> dict:
    """Bytes moved per collective class: sum of result-shape sizes of every
    collective op in the partitioned module (per-device view)."""
    out = {k: 0 for k in COLLECTIVE_OPS}
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        rhs = rhs.strip()
        for op in COLLECTIVE_OPS:
            # match '<type> op-name(' at the start of the rhs expression
            m = re.match(r"^(\(?[a-z0-9\[\],\s{}/#_:\.]+\)?)\s+" + op
                         + r"(-start|-done)?\(", rhs)
            if m:
                if m.group(2) == "-done":
                    break  # counted at -start
                out[op] += _shape_bytes(m.group(1))
                counts[op] += 1
                break
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def build_rules(cfg: ModelConfig, mesh, multi_pod: bool) -> ShardingRules:
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    tp = mesh.shape["model"]
    return ShardingRules(mesh=mesh, cfg=cfg, dp_axes=dp_axes, tp_axis="model",
                         fsdp=needs_fsdp(cfg, tp))


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               multi_pod: bool = False):
    """Lower + compile one cell; returns (compiled, lowered, meta)."""
    rules = build_rules(cfg, mesh, multi_pod)
    params = api.param_specs(cfg, shape)
    p_sh = rules.param_shardings(params)

    if shape.kind in ("train",):
        opt = jax.eval_shape(adamw_init, params)
        o_sh = rules.opt_shardings(opt)
        batch = api.input_specs(cfg, shape)
        b_sh = rules.batch_shardings(batch)
        step = make_train_step(cfg, AdamWConfig(), rules)
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
            ).lower(params, opt, batch)
            compiled = lowered.compile()
        return compiled, lowered, {"kind": "train_step"}

    if shape.kind == "prefill":
        batch = api.input_specs(cfg, shape)
        b_sh = rules.batch_shardings(batch)
        from repro.launch.steps import make_prefill_step
        step = make_prefill_step(cfg, max_len=shape.seq_len, rules=rules)
        cache = api.cache_specs(cfg, shape.global_batch, shape.seq_len,
                                enc_len=shape.seq_len)
        c_sh = rules.cache_shardings(cache)
        with mesh:
            lowered = jax.jit(
                step, in_shardings=(p_sh, b_sh),
                out_shardings=(None, c_sh),
            ).lower(params, batch)
            compiled = lowered.compile()
        return compiled, lowered, {"kind": "prefill_step"}

    # decode: one new token against a KV/SSM cache of capacity seq_len
    batch = api.input_specs(cfg, shape)
    cache = api.cache_specs(cfg, shape.global_batch, shape.seq_len,
                            enc_len=shape.seq_len)
    c_sh = rules.cache_shardings(cache)
    t_sh = rules.batch_shardings(batch)["tokens"]
    step = make_decode_step(cfg, rules)
    with mesh:
        lowered = jax.jit(
            step, in_shardings=(p_sh, t_sh, c_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),  # in-place cache update (halves HBM)
        ).lower(params, batch["tokens"], cache)
        compiled = lowered.compile()
    return compiled, lowered, {"kind": "serve_step"}


def analyze(compiled, lowered) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        out["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and (
                           "flops" in k or "bytes" in k or "utilization" in k)}
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        out["cost"] = {"error": str(e)}
    try:
        hlo = compiled.as_text()
        out["collectives"] = collective_bytes(hlo)
        out["hlo_bytes"] = len(hlo)
    except Exception as e:  # pragma: no cover
        out["collectives"] = {"error": str(e)}
    return out


def with_depth(cfg: ModelConfig, k: int) -> ModelConfig:
    """Reduced-depth variant with k 'depth units' (see depth_units)."""
    import dataclasses
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, num_layers=cfg.shared_attn_every * k)
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, num_layers=2 * k,
                                   num_decoder_layers=2 * k)
    return dataclasses.replace(cfg, num_layers=2 * k)


def depth_units(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.shared_attn_every
    return cfg.num_layers // 2


def roofline_measure(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     multi_pod: bool) -> dict:
    """Exact per-step FLOPs/bytes/collectives via two reduced-depth UNROLLED
    compiles + linear extrapolation (cost(k) = c0 + k*c_unit; exact because
    layers are homogeneous).  Needed because XLA cost_analysis counts a
    while-loop (lax.scan) body once (DESIGN.md §3)."""
    from repro.models import runtime

    meas = {}
    for k in (1, 2):
        cfg_k = with_depth(cfg, k)
        with runtime.unrolled_scans():
            compiled, lowered, _ = lower_cell(cfg_k, shape, mesh, multi_pod)
        a = analyze(compiled, lowered)
        meas[k] = {
            "flops": a.get("flops", 0.0),
            "bytes_accessed": a.get("bytes_accessed", 0.0),
            "collective_bytes": a.get("collectives", {}).get("total_bytes", 0),
            "collectives": a.get("collectives", {}).get("bytes", {}),
        }
    units = depth_units(cfg)

    def extrap(key):
        f1, f2 = meas[1][key], meas[2][key]
        return f1 + (units - 1) * (f2 - f1)

    coll = {}
    for op in COLLECTIVE_OPS:
        b1 = meas[1]["collectives"].get(op, 0)
        b2 = meas[2]["collectives"].get(op, 0)
        coll[op] = b1 + (units - 1) * (b2 - b1)
    return {
        "units": units,
        "per_unit_flops": meas[2]["flops"] - meas[1]["flops"],
        "flops": extrap("flops"),
        "bytes_accessed": extrap("bytes_accessed"),
        "collective_bytes": extrap("collective_bytes"),
        "collectives": coll,
        "raw": meas,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             mesh=None, roofline: bool = False,
             remat: str = None) -> dict:
    import dataclasses
    cfg = configs.get(arch)
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    shape = configs.SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "multi_pod": multi_pod, "kind": shape.kind}
    if shape_name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        rec["status"] = "skipped"
        rec["reason"] = ("long_500k needs sub-quadratic attention; "
                        f"{arch} is pure full attention (DESIGN.md §5)")
        return rec
    mesh = mesh or mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        from repro.models import runtime
        # memory-bounded attention schedule for long-context cells
        qc = 1024 if shape.seq_len >= 8192 else 0
        # shard-local MoE dispatch groups = DP degree (EXPERIMENTS.md §Perf)
        dp = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                          if a in mesh.shape]))
        with runtime.attn_q_chunk(qc), runtime.moe_dp_groups(dp):
            compiled, lowered, meta = lower_cell(cfg, shape, mesh, multi_pod)
            rec["attn_q_chunk"] = qc
            rec["moe_dp_groups"] = dp
            rec.update(meta)
            rec.update(analyze(compiled, lowered))
            rec["status"] = "ok"
            rec["compile_s"] = round(time.time() - t0, 2)
            rec["devices"] = int(np.prod(list(mesh.shape.values())))
            rec["model_params"] = cfg.param_count()
            rec["active_params"] = cfg.active_param_count()
            if roofline:
                rec["roofline"] = roofline_measure(cfg, shape, mesh,
                                                   multi_pod)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="add exact FLOP/collective accounting per cell")
    ap.add_argument("--remat", default=None,
                    choices=["full", "dots", "none"],
                    help="override the activation-checkpoint policy")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    archs = configs.ARCHS if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(configs.SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]

    results = []
    for mp in meshes:
        mesh = mesh_lib.make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mp, mesh=mesh,
                               roofline=args.roofline, remat=args.remat)
                results.append(rec)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    coll = rec.get("collectives", {}).get("total_bytes", 0)
                    extra = (f" flops={rec.get('flops', 0):.3e}"
                             f" coll={coll:.3e}B"
                             f" t={rec['compile_s']}s")
                elif status == "error":
                    extra = " " + rec["error"][:120]
                print(f"[{'multi' if mp else 'single'}] {arch} x {shape}: "
                      f"{status}{extra}", flush=True)
                if args.out:
                    outdir = os.path.dirname(os.path.abspath(args.out))
                    os.makedirs(outdir, exist_ok=True)
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
                if status == "ok":
                    ma = rec.get("memory", {})
                    print("   memory:", ma, flush=True)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"dry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
