"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a run (set-up, window, check, result line)
past the harness's look for a chip, at a small size on the CPU, with one
fault planted in what the window calls: a propagate that returns its
state unchanged, half of the batch (receivers, or shots) left out, and
one answer altered where it is produced.  A one-chip cell has no exchange
between chips to leave out.  The sound run beside them must be correct.
"""
import time

import jax
import numpy as np
import pytest

from conftest import small_cfg, small_mix
from harness import files, runner

CELLS = {"propagate": "acoustic-so4-512", "survey": "acoustic-so4-512",
         "tti": "tti-so4-512"}


def _run(cfg_name, mix_name):
    bench = files.benchmark()
    cell = {"name": f"{cfg_name}.{mix_name}", "config": cfg_name,
            "traffic": mix_name, "chips": 1}
    return runner.run(bench, cell, 2 ** 31 + 7, 0.01, False,
                      jax.devices()[:1], time.perf_counter(),
                      cfg=small_cfg(cfg_name), mix=small_mix(mix_name),
                      peaks={"f32_vpu_flops_per_s": 1.0,
                             "hbm_bytes_per_s": 1.0})


def _plant(monkeypatch, program, name, fault):
    """Route the program's `ops.<name>` through `fault`."""
    ops = program["ops"]
    real = getattr(ops, name)
    monkeypatch.setattr(ops, name,
                        lambda *a, **k: fault(a, real(*a, **k)))


def _unchanged(args, out):
    nt, u0, u1 = args[:3]
    return (u0, u1), out[1] * 0.0


def _half_receivers(args, out):
    state, tr = out
    return state, tr.at[:, ::2].set(0.0)


def _altered_sample(args, out):
    state, tr = out
    i = int(np.argmax(np.abs(np.asarray(tr))))
    flat = tr.reshape(-1)
    return state, flat.at[i].add(1e-2 * flat[i]).reshape(tr.shape)


def test_sound_propagate_is_correct(program):
    res = _run("acoustic-so4-512", "propagate")
    assert res["correct"] and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_unchanged, _half_receivers,
                                   _altered_sample])
def test_propagate_fault_is_caught(program, monkeypatch, fault):
    _plant(monkeypatch, program, "acoustic_tb_propagate", fault)
    res = _run("acoustic-so4-512", "propagate")
    assert not res["correct"] and res["failed"] >= 1


def test_tti_state_unchanged_is_caught(program, monkeypatch):
    def unchanged(args, out):
        nt, state = args[:2]
        return state, out[1] * 0.0
    _plant(monkeypatch, program, "tti_tb_propagate", unchanged)
    assert not _run("tti-so4-512", "propagate")["correct"]


def test_sound_survey_is_correct(program):
    res = _run("acoustic-so4-512", "survey")
    assert res["correct"] and res["failed"] == 0


@pytest.mark.parametrize("mutate", [
    lambda trs: [t * 0.0 for t in trs],                       # unchanged
    lambda trs: trs[:len(trs) // 2] * 2,                      # half left out
    lambda trs: [trs[0] + 1e-2 * np.abs(trs[0]).max()] + trs[1:],
], ids=["state-unchanged", "half-batch", "altered-answer"])
def test_survey_fault_is_caught(program, monkeypatch, mutate):
    engine = program["SurveyEngine"]
    real = engine.run

    def run(self, shots, **kw):
        res = real(self, shots, **kw)
        return res._replace(traces=mutate(list(res.traces)))
    monkeypatch.setattr(engine, "run", run)
    res = _run("acoustic-so4-512", "survey")
    assert not res["correct"] and res["failed"] >= 1
