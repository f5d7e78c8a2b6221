"""Distributed multi-physics stencil launcher + self-check.

Runs the sharded temporally-blocked execution layer (DESIGN.md §4), the
Pallas TB kernel per shard (interpret mode off-TPU), for any registered
physics over whatever devices exist (real TPUs or forced host
devices) and optionally checks agreement — wavefields AND per-step receiver
traces — with the single-device Listing-1 reference.

  # correctness check on 8 forced host devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.stencil_dist --check --n 32 --nt 8 --T 2

  # the same for the 9-field elastic system, remainder tile included:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.stencil_dist --check --physics elastic \
      --n 32 --nt 5 --T 2

  # receiver-trace invariance across time-tile depths:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.stencil_dist --sweep-T 1,2,4 --n 32 --nt 8

  # two-level plan: inner tile strictly smaller than the shard block,
  # overlapped (split-first-step) deep exchange:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.stencil_dist --check \
      --inner-tile 4,8 --overlap --n 32

  # time-nested: a depth-4 exchange consumed by depth-2 inner passes
  # (--T is the INNER depth once --outer-T decouples the levels):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.stencil_dist --check \
      --inner-tile 4,8 --T 2 --outer-T 4 --n 32

  # let the joint autotuner pick (T, inner tile, overlap) for the block:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.stencil_dist --check --auto-plan --n 32

  # production-mesh dry-run (lower+compile only) for the paper's 512^3 case,
  # reporting the joint plan selection alongside the collective schedule:
  python -m repro.launch.stencil_dist --dryrun --multipod
"""
import argparse
import json
import os
import sys


def _build_case(physics_name, shape, order, dt, grid, rng):
    """(physics, state tuple, params dict, ref_fn) for one physics.

    The model itself comes from the ONE shared builder
    (`launch.stencil_survey.build_model` — also the survey CLI's and
    test_survey's model); this adds the random initial state
    and the single-device reference closure.

    ref_fn(nt, g, gr) -> (state tuple in state_fields order,
                          rec (nt, nrec, rec_channels))."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels import tb_physics as phys
    from repro.launch.stencil_survey import build_model

    physics = phys.PHYSICS[physics_name]
    params = build_model(physics_name, shape, grid, rng)

    def rand_fields(k):
        return [jnp.asarray(0.01 * rng.randn(*shape), jnp.float32)
                for _ in range(k)]

    if physics_name == "acoustic":
        state = tuple(rand_fields(2))          # (u_prev, u)

        def ref_fn(nt, g, gr):
            (r0, r1), recs = ref.acoustic_reference(
                nt, state[0], state[1], params["m"], params["damp"], dt,
                grid.spacing, order, g=g, receivers=gr)
            return (r0, r1), recs[..., None]
    elif physics_name == "tti":
        from repro.core.propagators import tti as tt
        state = tuple(rand_fields(4))          # (p, p_prev, r, r_prev)

        def ref_fn(nt, g, gr):
            rst, recs = ref.tti_reference(
                nt, tt.TTIState(*state), tt.TTIParams(**params),
                dt, grid.spacing, order, g=g, receivers=gr)
            return (tuple(getattr(rst, f) for f in physics.state_fields),
                    recs[..., None])
    elif physics_name == "elastic":
        from repro.core.propagators import elastic as el
        state = tuple(rand_fields(9))

        def ref_fn(nt, g, gr):
            rst, recs = ref.elastic_reference(
                nt, el.ElasticState(*state), el.ElasticParams(**params),
                dt, grid.spacing, order, g=g, receivers=gr)
            return (tuple(getattr(rst, f) for f in physics.state_fields),
                    recs)
    else:
        raise ValueError(f"unknown physics {physics_name!r}")
    return physics, state, params, ref_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--physics", default="acoustic",
                    choices=("acoustic", "tti", "elastic"))
    ap.add_argument("--inner-tile", default=None,
                    help="tx,ty spatial tile of the inner trapezoid "
                         "(must divide the shard block); default: one tile "
                         "covering the block")
    ap.add_argument("--outer-T", type=int, default=None, dest="outer_T",
                    help="time-nest the two levels: exchange at this depth "
                         "while --T becomes the INNER (per-pass, VMEM) "
                         "depth — ceil(outer/inner) passes per deep "
                         "exchange over shrinking windows; default: flat "
                         "(outer depth = --T)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped deep exchange: split first step into "
                         "interior (runs under the ppermute) + rim strips")
    ap.add_argument("--auto-plan", action="store_true",
                    help="joint two-level autotune: pick T, inner tile and "
                         "overlap for this block via plan_hierarchy "
                         "(overrides --T; mutually exclusive with "
                         "--inner-tile/--overlap/--sweep-T)")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--sweep-T", default=None,
                    help="comma list of T depths; checks per-step receiver "
                         "traces agree across all of them")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--interp", default="linear",
                    choices=("linear", "sinc"),
                    help="source/receiver interpolation kernel: trilinear "
                         "or Kaiser-windowed sinc (Hicks 2002)")
    ap.add_argument("--interp-order", type=int, default=None,
                    dest="interp_order",
                    help="sinc support radius r ((2r)**3 grid points per "
                         "off-grid coordinate; default 1 linear / 4 sinc)")
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--nt", type=int, default=8)
    ap.add_argument("--T", type=int, default=2)
    ap.add_argument("--order", type=int, default=4)
    ap.add_argument("--telemetry", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="enable telemetry: span collection, exported as "
                         "a Chrome trace to PATH (default "
                         "results/telemetry_dist.json)")
    args = ap.parse_args()
    if args.auto_plan and (args.inner_tile or args.overlap or args.sweep_T
                           or args.outer_T):
        ap.error("--auto-plan picks T/inner tile/overlap itself; it cannot "
                 "be combined with --inner-tile, --overlap, --outer-T or "
                 "--sweep-T")
    if args.outer_T and args.sweep_T:
        ap.error("--sweep-T sweeps the exchange depth; it cannot be "
                 "combined with --outer-T")

    if args.dryrun and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import telemetry as tele
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core import interp as interp_mod
    from repro.core import sources as S
    from repro.core.grid import Grid
    from repro.core.temporal_blocking import TBPlan
    from repro.distributed.halo import (DistTBPlan, dist_plan_from_hier,
                                        sharded_lower, sharded_propagate)
    from repro.kernels import tb_physics as phys
    from repro.launch import mesh as mesh_lib
    from repro.survey.plan_cache import cached_plan_hierarchy

    print("compile cache:", enable_compile_cache())
    telemetry_path = None
    if args.telemetry is not None:
        tele.enable()
        telemetry_path = args.telemetry or os.path.join(
            "results", "telemetry_dist.json")

    # one candidate space for BOTH the --auto-plan build and the --dryrun
    # report, so the plan printed is the plan compiled
    AUTO_TILES = (4, 8, 16, 32, 64, 128)
    AUTO_DEPTHS = (1, 2, 4, 8)

    def build_plan(mesh, shape, grid, physics, order, dt, T):
        """DistTBPlan from the CLI's two-level flags (or the joint
        autotuner with --auto-plan)."""
        px, py = mesh.shape["data"], mesh.shape["model"]
        block = (shape[0] // px, shape[1] // py)
        if args.auto_plan:
            # through the survey plan cache: when --dryrun already swept
            # this configuration for its report (same candidate space),
            # the sweep is NOT rerun here — the second consult hits
            hier, _entry, info = cached_plan_hierarchy(
                args.physics, shape[2], order, block,
                tiles=AUTO_TILES, depths=AUTO_DEPTHS)
            print(f"plan cache {'HIT' if info.hit else 'MISS'} "
                  f"key={info.key}")
            print(f"auto-plan: outer T={hier.outer_T} "
                  f"inner T={hier.inner.T} inner tile={hier.inner.tile} "
                  f"overlap={hier.overlap} "
                  f"field depths={hier.field_depths}")
            return dist_plan_from_hier(mesh, shape, physics, order, hier,
                                       dt, grid.spacing)
        # --outer-T decouples the levels: --T is then the inner depth
        T_outer = args.outer_T or T
        inner_plan = None
        if args.inner_tile or T != T_outer:
            if args.inner_tile:
                tile = tuple(int(v) for v in args.inner_tile.split(","))
            else:
                tile = block
            inner_plan = TBPlan(tile, T, physics.step_radius(order))
        return DistTBPlan(mesh=mesh, grid_shape=shape, physics=physics,
                          order=order, T=T_outer, dt=dt, spacing=grid.spacing,
                          inner_plan=inner_plan, overlap=args.overlap)

    if args.dryrun:
        mesh = mesh_lib.make_production_mesh(multi_pod=args.multipod)
        n = 512
        shape = (n, n, n)
        grid = Grid(shape=shape, spacing=(10.0,) * 3)
        px, py = mesh.shape["data"], mesh.shape["model"]
        from repro.launch.dryrun import stencil_plan_report
        # same candidate space as build_plan's --auto-plan branch, so with
        # --auto-plan the recommendation below IS the compiled plan
        report = stencil_plan_report(
            args.physics, shape[2], args.order,
            (shape[0] // px, shape[1] // py),
            interp=interp_mod.spec_for(args.interp, args.interp_order),
            tiles=AUTO_TILES, depths=AUTO_DEPTHS)
        print("autotuner recommendation:", json.dumps(report))
        plan = build_plan(mesh, shape, grid, phys.PHYSICS[args.physics],
                          args.order, 1e-3, args.T)
        print(f"compiled plan: outer_T={plan.T} inner_T={plan.inner_T} "
              f"inner_tile={plan.inner_tile} overlap={plan.overlap} "
              f"field_depths={plan.field_depths(plan.T)}")
        ns = len(plan.physics.state_fields)
        npar = len(plan.physics.param_fields)
        u = jax.ShapeDtypeStruct(shape, jnp.float32)

        with mesh:
            lowered = sharded_lower(
                plan, args.T * 2, (u,) * ns,
                dict(zip(plan.physics.param_fields, (u,) * npar)))
            compiled = lowered.compile()
            print("memory:", compiled.memory_analysis())
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):  # per-device list on some jax
                ca = ca[0] if ca else {}
            print("flops: %.4g" % ca.get("flops", float("nan")))
            hlo = compiled.as_text()
            from repro.launch.dryrun import collective_bytes
            print("collectives:", collective_bytes(hlo))
        if telemetry_path:
            print("telemetry trace:",
                  tele.collector().export(telemetry_path))
        print(f"stencil distributed dry-run OK ({args.physics}, "
              f"{'multi' if args.multipod else 'single'}-pod)")
        return 0

    mesh = mesh_lib.make_xy_mesh()
    n, nt, order = args.n, args.nt, args.order
    shape = (n, n, n // 2)
    grid = Grid(shape=shape, spacing=(10.0,) * 3)
    dt = grid.cfl_dt(3000.0, order)

    rng = np.random.RandomState(0)
    physics, state, params, ref_fn = _build_case(args.physics, shape, order,
                                                 dt, grid, rng)
    ext = np.asarray(grid.extent)
    ispec = interp_mod.spec_for(args.interp, args.interp_order)
    src = S.SparseOperator(5.0 + rng.rand(3, 3) * (ext - 10.0))
    wav = S.ricker_wavelet(nt, dt, f0=12.0, num=3)
    g = S.precompute(src, grid, wav, interp=ispec)
    rec = S.SparseOperator(5.0 + rng.rand(4, 3) * (ext - 10.0))
    gr = S.precompute_receivers(rec, grid, interp=ispec)

    def run(T):
        plan = build_plan(mesh, shape, grid, physics, order, dt, T)
        with mesh:
            with tele.span("dist.propagate", T=T, nt=nt) as sp:
                # the entry donates its state: every call gets a copy
                out = sharded_propagate(plan, nt,
                                        [jnp.copy(f) for f in state],
                                        params, g, gr)
                sp.sync(out)
        return out

    def tol_ok(err, scale):
        return err <= 5e-4 * scale + 1e-6

    if args.sweep_T:
        depths = [int(t) for t in args.sweep_T.split(",")]
        traces = {T: np.asarray(run(T)[1]) for T in depths}
        base = traces[depths[0]]
        scale = float(np.max(np.abs(base))) + 1e-30
        ok = True
        for T in depths[1:]:
            err = float(np.max(np.abs(traces[T] - base)))
            print(f"trace T={T} vs T={depths[0]}: max|err| {err:.3e} "
                  f"(scale {scale:.3e})")
            ok = ok and tol_ok(err, scale)
        if telemetry_path:
            print("telemetry trace:",
                  tele.collector().export(telemetry_path))
        print("SWEEP", "PASS" if ok else "FAIL")
        return 0 if ok else 1

    dstate, drec = run(args.T)
    print(f"sharded {args.physics} propagate done on mesh "
          f"{dict(mesh.shape)} (inner_tile={args.inner_tile or 'block'}, "
          f"overlap={args.overlap}, nt={nt}, "
          f"outer_T={args.outer_T or args.T}"
          + (f", inner_T={args.T}" if args.outer_T else "") + ")")

    if telemetry_path:
        print("telemetry trace:", tele.collector().export(telemetry_path))

    if args.check:
        rstate, rrec = ref_fn(nt, g, gr)
        ok = True
        for f, dv, rv in zip(physics.state_fields, dstate, rstate):
            err = float(jnp.max(jnp.abs(dv - rv)))
            scale = float(jnp.max(jnp.abs(rv))) + 1e-30
            print(f"max|err| {f}={err:.3e} (field scale {scale:.3e})")
            ok = ok and tol_ok(err, scale)
        rec_err = float(np.max(np.abs(np.asarray(drec) - np.asarray(rrec))))
        rec_scale = float(np.max(np.abs(np.asarray(rrec)))) + 1e-30
        print(f"max|err| rec={rec_err:.3e} (trace scale {rec_scale:.3e})")
        ok = ok and tol_ok(rec_err, rec_scale)
        print("CHECK", "PASS" if ok else "FAIL")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
