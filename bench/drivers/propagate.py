"""One shot re-propagated back to back, as an FWI iteration's forward pass
does.  A unit is the program's sparse-operator precompute, one propagate
through the physics' temporally-blocked entry point
(`drivers/propagate.<physics>.py`), and the traces to the host."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, files
from harness.driver import Base, Unit, annotate


class Driver(Base):
    def setup(self):
        from repro.core import sources
        from repro.core.grid import Grid
        from repro.core.temporal_blocking import plan_for_physics

        self.sources = sources
        self.entry = files.entry("propagate", self.cfg["physics"])
        self.build_model()
        self.grid = Grid(shape=self.shape, spacing=(self.h,) * 3)
        self.plan, _ = plan_for_physics(
            self.cfg["physics"], self.shape[2], self.order,
            **self.cfg.get("planner", {}))
        self.zero = jnp.zeros(self.shape, jnp.float32)
        self.src, self.rec = self.geometry[0]
        self.unit(0)          # warm: compiles or loads every program
        self.last_state = None

    def describe(self) -> str:
        return (super().describe() + f" plan tile {self.plan.tile} T "
                f"{self.plan.T} remainder T {self.nt % self.plan.T}")

    def unit(self, k: int) -> Unit:
        S = self.sources
        self.last_state = None          # free the previous unit's state
        t0 = time.perf_counter()
        with annotate("bench.unit"):
            with annotate("bench.precompute"):
                g = S.precompute(S.SparseOperator(self.src), self.grid,
                                 self.wavelet[:, None])
                gr = S.precompute_receivers(S.SparseOperator(self.rec),
                                            self.grid)
            with annotate("bench.propagate"):
                state, traces = self.entry.run(
                    self.nt, self.zero, self.model, g, gr, self.plan,
                    self.order, self.dt, self.grid.spacing)
            with annotate("bench.readback"):
                host = np.asarray(traces, np.float64)
                jax.block_until_ready(state)
        t1 = time.perf_counter()
        self.last_state = state
        return Unit(t0, t1, self.npoints * self.nt, 1, host)

    def check(self, window):
        # the program's state goes to host before anything of the
        # reference's is on the device
        got = [np.asarray(f) for f in self.last_state]
        self.last_state = self.zero = None
        return self._compare([u.outputs for u in window.units], got)

    def control(self, dtype):
        self.build_model()
        lo_state, lo = self.reference_traces(*self.geometry[0], dtype, True)
        got = [np.asarray(f.astype(jnp.float32)) for f in lo_state]
        del lo_state
        return self._compare([lo], got)

    def _compare(self, unit_traces, got_state):
        state, ref_traces = self.reference_traces(*self.geometry[0],
                                                  want_state=True)
        want = [np.asarray(f) for f in state]
        del state
        return compare.propagate_checks(unit_traces, ref_traces, got_state,
                                        want, self.limits)
