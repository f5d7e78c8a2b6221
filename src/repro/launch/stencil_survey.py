"""Multi-shot survey launcher over the single-device TB stack.

Builds a synthetic survey (shot geometries drawn with varying source /
receiver counts so multiple buckets exercise the shape-bounding) over a
random-velocity model, runs it through `survey.SurveyEngine` (the Pallas
TB kernel per shot, interpret mode off-TPU), and reports throughput plus
the plan-cache / per-bucket-compile statistics.  With
``--check`` every batched trace is compared against a sequential
`kernels.ops.*_tb_propagate` call for the same shot.

  # 6-shot acoustic survey, 2-shot compiled batches:
  python -m repro.launch.stencil_survey --physics acoustic --shots 6 \
      --bucket-cap 2

  # with parity against sequential propagates:
  python -m repro.launch.stencil_survey --shots 2 --check

Exit codes: 0 ok / parity pass, 1 parity fail.
"""
import argparse
import json
import os
import sys


def build_survey(grid, dt, nt, num_shots, rng):
    """Shots with heterogeneous (nsrc, nrec) so bucketing has work to do."""
    import numpy as np

    from repro.core import sources as S
    from repro.survey import Shot

    ext = np.asarray(grid.extent)
    shots = []
    for i in range(num_shots):
        nsrc = 1 + (i % 3)
        nrec = 3 + 2 * (i % 2)
        shots.append(Shot(
            src_coords=5.0 + rng.rand(nsrc, 3) * (ext - 10.0),
            wavelet=S.ricker_wavelet(nt, dt, f0=12.0, num=nsrc),
            rec_coords=5.0 + rng.rand(nrec, 3) * (ext - 10.0),
            shot_id=i))
    return shots


def build_model(physics_name, shape, grid, rng):
    """params dict for `tb_physics.PHYSICS[physics_name]`."""
    import jax.numpy as jnp

    from repro.core import boundary

    vp = 1500.0 + 1000.0 * rng.rand(*shape)
    damp = boundary.damping_field(shape, nbl=3, spacing=grid.spacing)
    if physics_name == "acoustic":
        return {"m": jnp.asarray(1.0 / vp ** 2, jnp.float32), "damp": damp}
    if physics_name == "tti":
        return {"m": jnp.asarray(1.0 / vp ** 2, jnp.float32), "damp": damp,
                "epsilon": jnp.asarray(0.2 * rng.rand(*shape), jnp.float32),
                "delta": jnp.asarray(0.1 * rng.rand(*shape), jnp.float32),
                "theta": jnp.asarray(0.3 * rng.randn(*shape), jnp.float32),
                "phi": jnp.asarray(0.3 * rng.randn(*shape), jnp.float32)}
    if physics_name == "elastic":
        rho = 2000.0 + 100.0 * rng.rand(*shape)
        vs = vp / 1.9
        return {"lam": jnp.asarray(rho * (vp ** 2 - 2 * vs ** 2) * 1e-6,
                                   jnp.float32),
                "mu": jnp.asarray(rho * vs ** 2 * 1e-6, jnp.float32),
                "b": jnp.asarray(1.0 / rho, jnp.float32), "damp": damp}
    raise ValueError(f"unknown physics {physics_name!r}")


def sequential_traces(physics_name, shots, grid, params, plan, order, dt, nt,
                      interp=None):
    """K independent `*_tb_propagate` calls — the batching oracle.

    `interp` is the `core.interp.InterpSpec` the engine under test uses
    (None = default multilinear), so the oracle interpolates identically.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import interp as interp_mod
    from repro.core import sources as S
    from repro.core.propagators import elastic as el
    from repro.core.propagators import tti as tt
    from repro.kernels import ops as ops_mod
    from repro.kernels import tb_physics as phys

    interp = interp_mod.LINEAR if interp is None else interp
    shape = tuple(grid.shape)
    out = []
    for s in shots:
        g = S.precompute(S.SparseOperator(s.src_coords), grid, s.wavelet,
                         interp=interp)
        gr = S.precompute_receivers(S.SparseOperator(s.rec_coords), grid,
                                    interp=interp)
        if physics_name == "acoustic":
            zero = jnp.zeros(shape, jnp.float32)
            _, rec = ops_mod.acoustic_tb_propagate(
                nt, zero, zero, params["m"], params["damp"], g, gr, plan,
                order, dt, grid.spacing)
        elif physics_name == "tti":
            state = tt.TTIState(*(jnp.zeros(shape, jnp.float32)
                                  for _ in phys.TTI.state_fields))
            _, rec = ops_mod.tti_tb_propagate(
                nt, state, tt.TTIParams(**params), g, gr, plan, order, dt,
                grid.spacing)
        else:
            state = el.ElasticState(*(jnp.zeros(shape, jnp.float32)
                                      for _ in phys.ELASTIC.state_fields))
            _, rec = ops_mod.elastic_tb_propagate(
                nt, state, el.ElasticParams(**params), g, gr, plan, order,
                dt, grid.spacing)
        out.append(np.asarray(rec))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--physics", default="acoustic",
                    choices=("acoustic", "tti", "elastic"))
    ap.add_argument("--shots", type=int, default=4,
                    help="number of synthetic shots in the survey")
    ap.add_argument("--bucket-cap", type=int, default=2, dest="bucket_cap",
                    help="compiled batch size (shots per dispatch; partial "
                         "batches pad with silent null shots)")
    ap.add_argument("--interp", default="linear",
                    choices=("linear", "sinc"),
                    help="source/receiver interpolation kernel: trilinear "
                         "or Kaiser-windowed sinc (Hicks 2002)")
    ap.add_argument("--interp-order", type=int, default=None,
                    dest="interp_order",
                    help="sinc support radius r (table caps scale as "
                         "(2r)**3 * n; default 1 linear / 4 sinc)")
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--nt", type=int, default=8)
    ap.add_argument("--order", type=int, default=4)
    ap.add_argument("--check", action="store_true",
                    help="compare every batched trace against a sequential "
                         "*_tb_propagate call")
    ap.add_argument("--telemetry", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="enable telemetry spans (sweep/prep/dispatch/"
                         "readback) and export the Chrome trace to PATH "
                         "(default results/telemetry_survey.json)")
    args = ap.parse_args()

    import numpy as np

    from repro import telemetry as tele
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core import interp as interp_mod
    from repro.core.grid import Grid
    from repro.survey import PlanCache, SurveyEngine

    print("compile cache:", enable_compile_cache())
    telemetry_path = None
    if args.telemetry is not None:
        tele.enable()
        telemetry_path = args.telemetry or os.path.join(
            "results", "telemetry_survey.json")

    n, nt, order = args.n, args.nt, args.order
    shape = (n, n, n // 2)
    grid = Grid(shape=shape, spacing=(10.0,) * 3)
    dt = grid.cfl_dt(3000.0, order)
    rng = np.random.RandomState(0)
    params = build_model(args.physics, shape, grid, rng)
    shots = build_survey(grid, dt, nt, args.shots, rng)

    spec = interp_mod.spec_for(args.interp, args.interp_order)
    cache = PlanCache()
    engine = SurveyEngine(args.physics, grid, params, nt, dt, order=order,
                          plan_cache=cache,
                          bucket_cap=args.bucket_cap, interp=spec)
    result = engine.run(shots)
    print("survey stats:", json.dumps(result.stats))
    print(f"survey {args.physics} x{args.shots} shots "
          f"({result.stats['buckets']} buckets, "
          f"{result.stats['batches']} batches): "
          f"{result.stats['shots_per_s']:.3f} shots/s, "
          f"{result.stats['mpoints_per_s']:.3f} Mpt/s "
          f"(warm {result.stats['warm_seconds']:.3f}s / "
          f"cold {result.stats['cold_seconds']:.3f}s), "
          f"{cache.sweeps} autotune sweep(s)")

    if telemetry_path:
        print("telemetry trace:", tele.collector().export(telemetry_path))

    if args.check:
        seq = sequential_traces(args.physics, shots, grid, params,
                                engine.plan, order, dt, nt, interp=spec)
        ok = True
        for i, (batched, ref) in enumerate(zip(result.traces, seq)):
            err = float(np.max(np.abs(batched - ref))) if ref.size else 0.0
            scale = float(np.max(np.abs(ref))) + 1e-30
            good = err <= 5e-4 * scale + 1e-6
            print(f"shot {i}: max|err| {err:.3e} (scale {scale:.3e})")
            ok = ok and good
        print("CHECK", "PASS" if ok else "FAIL")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
