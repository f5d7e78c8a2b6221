"""`SurveyEngine.run` over consecutive groups of a shot line.  A unit is one
group; the window starts at the shot the seed draws (`traffic.first_shot`)
and walks on along the line, so the driver's seeds cover every shot.  The
set-up warms the executable with the batch of shots just before that
start, which the window reaches only after going round the whole line."""
from __future__ import annotations

import time

import numpy as np

from harness import compare, traffic
from harness.driver import Base, Unit, annotate


class Driver(Base):
    def __init__(self, cfg, mix, physics, seed):
        super().__init__(cfg, mix, physics, seed)
        self.group = int(self.mix["group_shots"])
        self.first = traffic.first_shot(mix, seed)

    def _index(self, k: int):
        n = len(self.geometry)
        return [(self.first + k * self.group + j) % n
                for j in range(self.group)]

    def setup(self):
        from repro.core.grid import Grid
        from repro.survey import PlanCache, Shot, SurveyEngine

        self.build_model()
        self.grid = Grid(shape=self.shape, spacing=(self.h,) * 3)
        self.shots = [Shot(src_coords=s, wavelet=self.wavelet[:, None],
                           rec_coords=r, shot_id=i)
                      for i, (s, r) in enumerate(self.geometry)]
        cap = int(self.mix["bucket_cap"])
        self.engine = SurveyEngine(
            self.cfg["physics"], self.grid,
            {k: self.model[k] for k in self.physics.PARAMS}, self.nt,
            self.dt, order=self.order, executor=self.mix["executor"],
            plan_cache=PlanCache(), bucket_cap=cap)
        self.plan = self.engine.plan
        # the executable is warmed only by running a batch through it
        n = len(self.shots)
        self.engine.run([self.shots[(self.first - cap + j) % n]
                         for j in range(cap)])

    def describe(self) -> str:
        return (super().describe() + f" plan tile {self.plan.tile} T "
                f"{self.plan.T} remainder T {self.nt % self.plan.T}, "
                f"{len(self.shots)} shots in groups of {self.group} from "
                f"shot {self.first}, bucket_cap {self.mix['bucket_cap']}")

    def unit(self, k: int) -> Unit:
        idx = self._index(k)
        t0 = time.perf_counter()
        with annotate("bench.unit"):
            with annotate("bench.survey_run"):
                res = self.engine.run([self.shots[i] for i in idx])
        t1 = time.perf_counter()
        outs = [(i, np.asarray(tr, np.float64))
                for i, tr in zip(idx, res.traces)]
        return Unit(t0, t1, self.npoints * self.nt * len(idx), len(idx),
                    outs)

    def check(self, window):
        self.engine = None
        got = {}
        for u in window.units:
            for i, tr in u.outputs:
                got.setdefault(i, []).append(tr)
        want = {i: self.reference_traces(*self.geometry[i]) for i in got}
        return compare.survey_checks(got, want, self.limits)

    def control(self, dtype):
        """The window's first group, each shot's traces from the reference
        in `dtype`."""
        self.build_model()
        idx = self._index(0)
        got = {i: [self.reference_traces(*self.geometry[i], dtype)]
               for i in idx}
        want = {i: self.reference_traces(*self.geometry[i]) for i in idx}
        return compare.survey_checks(got, want, self.limits)
