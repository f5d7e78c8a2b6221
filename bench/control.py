"""The control of the comparison that decides `correct`: the plain
reference put in the program's place, computed one precision below the
configuration's (bfloat16 for float32), compared against the float32
reference by the benchmark's own comparison.  Its readings set the upper
end of each limit; a limit that passes them is too loose.

    python bench/control.py --workload <cell> --seeds 1,2,3

Each driver's `control` says what it compares: for a propagate cell one
propagate (traces and final wavefields), for a survey cell the window's
first group of shots.  Prints one JSON line per seed.  Without a TPU it
exits 1 (the tests run it on the CPU at a small size through
`readings`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def readings(cfg, mix, physics, seed):
    """[(name, value, limit)] of the control for one seed."""
    import jax.numpy as jnp

    from harness import files

    checks, _ = files.driver(mix["driver"])(cfg, mix, physics,
                                            seed).control(jnp.bfloat16)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    from harness import files

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    bench = files.benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = files.config(bench, cell["config"])
    mix = files.mix(cell["traffic"])
    physics = files.physics(cfg["physics"])
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = readings(cfg, mix, physics, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": {n: {"value": v, "limit": lim}
                                      for n, v, lim in checks},
                          "fails": any(not v <= lim
                                       for _, v, lim in checks)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
