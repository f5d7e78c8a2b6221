"""What every driver shares.

A traffic mix names its driver by its `driver` key, and the driver lives in
`drivers/<driver>.py` as a class `Driver` built on `Base`.  It owns one
cell's run and imports the program's entry points it calls itself:

  setup()          builds the earth model on the device, draws the geometry
                   from the seed and warms every shape the window uses;
  unit(k)          the window's k-th unit of work, as a `Unit`;
  window(seconds)  drives `unit` back to back, one caller, until the
                   window's time is spent, always finishing the unit it is
                   in;
  check(window)    frees the program's state and compares what the window
                   produced against the configuration's plain reference;
  control(dtype)   the same comparison with the plain reference computed
                   in `dtype` put in the program's place.
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from harness import numerics as nm, traffic


class Unit:
    """One completed unit of window work."""

    def __init__(self, t0, t1, point_steps, shots, outputs):
        self.t0, self.t1 = t0, t1
        self.point_steps = point_steps   # grid points x steps computed
        self.shots = shots               # shots whose traces reached host
        self.outputs = outputs


class Window:
    def __init__(self, units: List[Unit]):
        self.units = units

    @property
    def t0(self):
        return self.units[0].t0

    @property
    def t1(self):
        return self.units[-1].t1

    @property
    def elapsed_s(self):
        return self.t1 - self.t0


def annotate(name):
    return jax.profiler.TraceAnnotation(name)


class Base:
    def __init__(self, cfg: dict, mix: dict, physics, seed: int):
        self.cfg, self.mix, self.physics, self.seed = cfg, mix, physics, seed
        self.shape = tuple(cfg["shape"])
        self.h = float(cfg["spacing_m"])
        self.order = int(cfg["space_order"])
        self.dt = nm.cfl_dt(self.h, float(cfg["vp_max_m_s"]), self.order,
                            safety=float(cfg["assumed"]["cfl_safety"]))
        self.nt = nm.nt_for(float(cfg["time_ms"]), self.dt)
        self.npoints = int(np.prod(self.shape))
        self.limits = cfg["limits"]
        self.wavelet = nm.ricker(self.nt, self.dt, float(cfg["f0_hz"]))
        self.geometry = traffic.shots(cfg, mix, seed)
        self.model = None

    def build_model(self):
        key = jax.random.key(int(np.random.SeedSequence(
            self.seed).generate_state(1)[0]))
        self.model = jax.block_until_ready(
            self.physics.build_model(self.cfg, key))

    def reference_traces(self, src, rec, dtype=np.float32, want_state=False):
        si, sw = nm.trilinear(src, self.h, self.shape)
        ri, rw = nm.trilinear(rec, self.h, self.shape)
        state, traces = self.physics.reference(
            self.nt, self.model, si, sw, self.wavelet[:, None], ri, rw,
            self.dt, self.h, self.order, dtype=dtype)
        traces = np.asarray(traces, np.float64)
        return (state, traces) if want_state else traces

    def describe(self) -> str:
        return (f"grid {self.shape} h {self.h} dt {self.dt!r} nt {self.nt} "
                f"order {self.order}")

    def window(self, seconds: float) -> Window:
        units = []
        start = time.perf_counter()
        with annotate("bench.window"):
            while not units or units[-1].t1 - start < seconds:
                units.append(self.unit(len(units)))
        return Window(units)
