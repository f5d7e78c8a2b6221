"""Shared fixtures: the benchmark's own modules on the path, and small
copies of the real configurations and mixes that the CPU can run (Pallas
in interpret mode)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import pytest  # noqa: E402

from harness import files  # noqa: E402


def small_cfg(name: str) -> dict:
    """The configuration at 32^3 with a 4-cell sponge and the planner held
    to 16-wide tiles: every key and formula as the real one, less work."""
    cfg = files.read_json(os.path.join(BENCH, "configs", f"{name}.json"))
    steps = {"acoustic": 40.0, "tti": 60.0}[cfg["physics"]]
    cfg.update(shape=[32, 32, 32], time_ms=steps, nbl=4,
               planner={"tiles": [16]})
    return cfg


def small_mix(name: str) -> dict:
    mix = dict(files.mix(name))
    if mix["driver"] == "survey":
        mix.update(shots=4, shot_spacing_cells=2, receivers=6)
    return mix


@pytest.fixture(scope="session")
def program():
    """The system under test's modules, imported from the checkout."""
    from harness import program as prog
    prog.on_path()
    from repro.kernels import ops
    from repro.survey import SurveyEngine
    return {"ops": ops, "SurveyEngine": SurveyEngine}
