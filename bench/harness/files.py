"""Finds each piece of a cell by its name: BENCHMARK.json at the checkout's
root, `configs/<config>.json` (as the configuration entry's `file` says),
`traffic/<mix>.json`, `drivers/<driver>.py` (the mix's `driver` key) with
its per-physics calls `drivers/<driver>.<physics>.py`,
`references/<physics>.py`, `metrics/<metric>.py` and `peaks.json`, all
under the benchmark's directory."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def read_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return read_json(ROOT / "BENCHMARK.json")


def module(path: Path, name: str):
    """Import one file as a module of its own (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def physics(name: str):
    return module(BENCH / "references" / f"{name}.py", f"reference_{name}")


def driver(name: str):
    """The driver class of `drivers/<name>.py`."""
    return module(BENCH / "drivers" / f"{name}.py", f"driver_{name}").Driver


def entry(driver_name: str, physics_name: str):
    """A driver's call into the system under test for one physics."""
    return module(BENCH / "drivers" / f"{driver_name}.{physics_name}.py",
                  f"entry_{driver_name}_{physics_name}")


def mix(name: str) -> dict:
    return read_json(BENCH / "traffic" / f"{name}.json")


def config(bench: dict, name: str) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return read_json(ROOT / entry["file"])


def metric(name: str):
    return module(BENCH / "metrics" / f"{name}.py",
                  "metric_" + name.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]
