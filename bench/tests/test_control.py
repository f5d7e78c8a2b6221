"""The control (the reference in bfloat16 in the program's place) must fail
the comparison, and the float32 reference in the program's place pass it,
at a small size on the CPU; on the chip `control.py` reads it at the
cells' own size."""
import jax.numpy as jnp
import pytest

import control
from conftest import small_cfg, small_mix
from harness import compare, files


@pytest.mark.parametrize("cfg_name,mix_name", [
    ("acoustic-so4-512", "propagate"), ("tti-so4-512", "propagate"),
    ("acoustic-so4-512", "survey")])
def test_control_fails_the_limits(cfg_name, mix_name):
    cfg, mix = small_cfg(cfg_name), small_mix(mix_name)
    checks = control.readings(cfg, mix, files.physics(cfg["physics"]), 5)
    assert any(not compare.passes(v, lim) for _, v, lim in checks)


def test_float32_reference_passes_against_itself():
    cfg, mix = small_cfg("acoustic-so4-512"), small_mix("propagate")
    drv = files.driver("propagate")(cfg, mix, files.physics("acoustic"), 5)
    drv.build_model()
    src, rec = drv.geometry[0]
    a = drv.reference_traces(src, rec, jnp.float32)
    b = drv.reference_traces(src, rec, jnp.float32)
    assert compare.rel_err(a, b) == 0.0
