"""Run one cell of the benchmark on the accelerator this machine holds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of `workloads` in BENCHMARK.json) names a configuration
and a traffic mix; everything else is found by name under `bench/`.  The
run builds the earth model on the device from the seed, warms every shape
the window uses (set-up), drives the system under test for `--seconds`
seconds, checks what the window produced against the configuration's plain
reference, and prints one JSON line last on standard output.  With
`--trace 1` the window runs under the profiler and the line carries the
per-layer metrics, with `--trace 0` the end-to-end ones.

Compiled programs are kept in JAX's persistent cache by the program's own
`enable_compile_cache`: in `$JAX_COMPILATION_CACHE_DIR` where that is set
and in `.jax_cache` at the checkout's root otherwise, so only a cell's
first run in a checkout compiles.  There is no CPU fallback: without a TPU, or with fewer chips
than the cell asks for, the run exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import files, program, runner

    bench = files.benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        print(f"run: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run: {args.workload} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    program.on_path()
    from repro.launch.compile_cache import enable_compile_cache

    runner.say(f"compile cache: {enable_compile_cache()}")
    result = runner.run(bench, cell, args.seed, args.seconds,
                        bool(args.trace), devices[:cell["chips"]], T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
