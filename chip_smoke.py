"""Smoke test of the temporally-blocked stencil stack on a TPU.

Drives the main path once through the entry points a user calls, at the
full width of the paper's §IV.B acoustic case (512^3, 10 m, space order 4):

  phase 1  one `acoustic_tb_propagate` under the autotuned plan, with an
           off-grid Ricker source and a 32-receiver line near the surface,
           checked against the plain reference propagator on the chip;
  phase 2  a 4-shot `SurveyEngine.run` (executor "pallas", 2-shot
           batches), checked against sequential `acoustic_tb_propagate`.

With --four-chips it runs only the sharded route instead: the same case
through the sharded entry point `sharded_propagate` on a 2x2 mesh (Pallas
inner kernel), checked against `acoustic_tb_propagate` on one device of the
same process.

Every timing and parity number printed was measured on the device named on
the lines above it.  The last line of stdout is one JSON object,
{"ok": true, "device": {...}}; any failed phase exits non-zero first.
There is no CPU fallback: without a TPU the script exits 1.

  python chip_smoke.py                 # one chip
  python chip_smoke.py --four-chips    # four chips, sharded route only
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TOL = 1e-4        # max|trace - reference| / max|reference|
NREC = 32
SURVEY_NT = 67    # survey shot length, cut from the case's 399 steps


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _rel_err(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want))) / scale if scale else 0.0, scale


def _check(name, err, scale):
    print(f"{name}: max|d|/max|ref| = {err!r} (max|ref| = {scale!r}, "
          f"tolerance {TOL})")
    if not scale > 0.0:
        raise AssertionError(f"{name}: reference traces are all zero — no "
                             "energy reached the receivers")
    if not err <= TOL:
        raise AssertionError(f"{name}: parity {err!r} exceeds {TOL}")


def build_case(seed):
    """The paper's acoustic SO-4 case on its 512^3 grid: a layered model
    (vp 1500 -> 3500 m/s in 8 layers, made on the device), one off-grid
    10 Hz Ricker source near the surface (z is the last axis) and an
    off-grid line of 32 receivers around it."""
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.paper_stencil import full_case
    from repro.core import boundary
    from repro.core import sources as S
    from repro.core.grid import Grid

    case = full_case("acoustic", 4)
    shape = case.shape
    n = shape[0]
    grid = Grid(shape=shape, spacing=case.spacing)
    dt = grid.cfl_dt(case.vmax, case.space_order)
    rng = np.random.default_rng(seed)

    layer = np.floor(np.arange(n) * 8 / n) / 7.0
    vp_z = case.vmin + (case.vmax - case.vmin) * layer
    m = jnp.broadcast_to(jnp.asarray(1.0 / vp_z ** 2, jnp.float32),
                         shape) + 0.0
    damp = boundary.damping_field(shape, nbl=case.nbl, spacing=grid.spacing)

    h = case.spacing[0]
    centre = 0.5 * h * (n - 1)
    src = np.array([[centre, centre, 15.0 * h]]) + rng.uniform(0, h, (1, 3))
    xs = centre + h * np.linspace(-0.3 * n, 0.3 * n, NREC) / 2
    rec = np.stack([xs, np.full(NREC, centre + 1.3 * h),
                    np.full(NREC, 10.0 * h)], axis=1)
    rec = rec + rng.uniform(0, h, rec.shape)
    return case, grid, dt, m, damp, src, rec


def phase_propagate(args):
    import jax.numpy as jnp

    from repro.core import sources as S
    from repro.core.temporal_blocking import plan_for_physics
    from repro.kernels import ops
    from repro.kernels import ref

    case, grid, dt, m, damp, src, rec = build_case(args.seed)
    order = case.space_order
    nt = case.nt(dt)
    plan, _ = plan_for_physics("acoustic", grid.shape[2], order)
    print(f"phase 1 case: {case.name} grid {grid.shape} spacing "
          f"{grid.spacing} dt {dt!r} nt {nt} (the full {case.time_ms} ms)")
    print(f"phase 1 plan: tile {plan.tile} T {plan.T} "
          f"(remainder tile T {nt % plan.T})")
    if nt < 64 or nt % plan.T == 0:
        raise ValueError(f"nt={nt} must be >= 64 and not a multiple of "
                         f"T={plan.T}, so the remainder tile runs")
    g = S.precompute(S.SparseOperator(src), grid,
                     S.ricker_wavelet(nt, dt, f0=case.f0))
    gr = S.precompute_receivers(S.SparseOperator(rec), grid)
    zero = jnp.zeros(grid.shape, jnp.float32)

    def tb():
        return ops.acoustic_tb_propagate(nt, zero, zero, m, damp, g, gr,
                                         plan, order, dt, grid.spacing)

    (state, traces), cold = _timed(tb)
    _, warm = _timed(tb)
    print(f"phase 1 acoustic_tb_propagate on device: cold {cold!r} s "
          f"(compile + run), warm {warm!r} s, "
          f"{grid.npoints * nt / warm!r} point-steps/s warm")
    def reference():
        return ref.acoustic_reference(nt, zero, zero, m, damp, dt,
                                      grid.spacing, order, g=g, receivers=gr)

    (rstate, rtraces), rcold = _timed(reference)
    _, rwarm = _timed(reference)
    print(f"phase 1 reference propagate on device: cold {rcold!r} s "
          f"(compile + run), warm {rwarm!r} s, "
          f"{grid.npoints * nt / rwarm!r} point-steps/s warm")
    _check("phase 1 receiver traces vs reference", *_rel_err(traces,
                                                             rtraces))
    werr, wscale = _rel_err(state[1], rstate[1])
    print(f"phase 1 final wavefield vs reference: max|d|/max|ref| = "
          f"{werr!r} (max|ref| = {wscale!r})")


def phase_survey(args):
    import numpy as np

    from repro.core import sources as S
    from repro.launch.stencil_survey import sequential_traces
    from repro.survey import PlanCache, Shot, SurveyEngine

    case, grid, dt, m, damp, src, rec = build_case(args.seed + 1)
    nt = SURVEY_NT
    h = case.spacing[0]
    shots = [Shot(src_coords=src + np.array([[(i - 1.5) * 8.0 * h, 0.0,
                                              0.0]]),
                  wavelet=S.ricker_wavelet(nt, dt, f0=case.f0),
                  rec_coords=rec, shot_id=i) for i in range(4)]
    params = {"m": m, "damp": damp}
    engine = SurveyEngine("acoustic", grid, params, nt, dt,
                          order=case.space_order, executor="pallas",
                          plan_cache=PlanCache(), bucket_cap=2)
    print(f"phase 2 survey: grid {grid.shape} nt {nt} 4 shots x "
          f"{NREC} receivers, bucket_cap 2, plan tile {engine.plan.tile} "
          f"T {engine.plan.T}")
    res = engine.run(shots)
    again = engine.run(shots)
    for tag, r in (("first run", res), ("second run", again)):
        st = r.stats
        print(f"phase 2 SurveyEngine.run on device, {tag}: seconds "
              f"{st['seconds']!r} cold {st['cold_seconds']!r} warm "
              f"{st['warm_seconds']!r} shots/s {st['shots_per_s']!r} "
              f"batches {st['batches']}")
    seq = sequential_traces("acoustic", shots, grid, params, engine.plan,
                            case.space_order, dt, nt)
    for i, (got, want) in enumerate(zip(again.traces, seq)):
        _check(f"phase 2 shot {i} survey vs sequential", *_rel_err(got,
                                                                  want))


def phase_four_chips(args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import sources as S
    from repro.core.temporal_blocking import plan_for_physics
    from repro.distributed.halo import sharded_plan, sharded_propagate
    from repro.kernels import ops
    from repro.kernels import tb_physics as phys
    from repro.launch.mesh import make_xy_mesh

    case, grid, dt, m, damp, src, rec = build_case(args.seed)
    order = case.space_order
    nt = case.nt(dt)
    mesh = make_xy_mesh()
    plan = sharded_plan(mesh, phys.ACOUSTIC, grid.shape, order, dt,
                        grid.spacing)
    block = plan.block
    print(f"four-chip case: {case.name} grid {grid.shape} nt {nt} mesh "
          f"{dict(mesh.shape)} block {block} outer T {plan.T} inner tile "
          f"{plan.inner_tile} inner T {plan.inner_T} overlap {plan.overlap}")
    g = S.precompute(S.SparseOperator(src), grid,
                     S.ricker_wavelet(nt, dt, f0=case.f0))
    gr = S.precompute_receivers(S.SparseOperator(rec), grid)

    shard = NamedSharding(mesh, P("data", "model", None))
    params = {"m": jax.device_put(m, shard),
              "damp": jax.device_put(damp, shard)}
    zeros = jax.jit(lambda: tuple(jnp.zeros(grid.shape, jnp.float32)
                                  for _ in range(2)), out_shardings=shard)

    def run():
        # the entry donates its state: every call gets fresh zeros
        return sharded_propagate(plan, nt, zeros(), params, g, gr)

    (state, traces), cold = _timed(run)
    _, warm = _timed(run)
    print(f"four-chip sharded_propagate on device: cold {cold!r} s, "
          f"warm {warm!r} s, {grid.npoints * nt / warm!r} point-steps/s warm")
    devs = [s.device for s in state[1].addressable_shards]
    shapes = {tuple(s.data.shape) for s in state[1].addressable_shards}
    print(f"four-chip shards: {len(set(devs))} distinct devices "
          f"{sorted(d.id for d in devs)}, shard shapes {sorted(shapes)}")
    if len(set(devs)) != mesh.size or shapes != {block + (grid.shape[2],)}:
        raise AssertionError("wavefield shards are not one block per device")

    plan1, _ = plan_for_physics("acoustic", grid.shape[2], order)
    one = jnp.zeros(grid.shape, jnp.float32)      # on device 0, as m, damp
    (_, traces1), sec1 = _timed(lambda: ops.acoustic_tb_propagate(
        nt, one, one, m, damp, g, gr, plan1, order, dt, grid.spacing))
    print(f"four-chip reference: acoustic_tb_propagate on device 0, tile "
          f"{plan1.tile} T {plan1.T}: {sec1!r} s (cold)")
    _check("four-chip traces vs one device", *_rel_err(traces[..., 0],
                                                       traces1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true", dest="four_chips",
                    help="run only the sharded route on a 2x2 mesh")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    if args.four_chips and count != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {count}",
              file=sys.stderr)
        return 1

    from repro.kernels.platform import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{count}")
    print(f"compile cache: {enable_compile_cache()}")
    print(f"Pallas interpret mode: {resolve_interpret()}")
    if args.four_chips:
        phase_four_chips(args)
    else:
        phase_propagate(args)
        phase_survey(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
