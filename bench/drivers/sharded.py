"""One shot re-propagated back to back from rest on a grid decomposed over
the cell's chips, as an FWI forward pass on a model too large for one chip.
The configuration's `mesh` [px, py] lays grid x over "data" and y over
"model"; the earth model is built sharded `P("data", "model", None)`, so
no chip ever holds the whole grid, and the physics' plain reference for a
decomposed grid (`references/<physics>_sharded.py`) runs on the sharded
model over the same chips.  A unit is the
program's sparse-operator precompute, one propagate from rest through the
physics' sharded entry point (`drivers/sharded.<physics>.py`), and the
traces to the host."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, files
from harness.driver import Base, Unit, annotate


class Driver(Base):
    def __init__(self, cfg, mix, physics, seed):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        super().__init__(cfg, mix, physics, seed)
        # the physics' reference for a decomposed grid
        self.physics = files.physics(f"{cfg['physics']}_sharded")
        px, py = cfg["mesh"]
        self.mesh = Mesh(np.asarray(jax.devices()[:px * py]).reshape(px, py),
                         ("data", "model"))
        self.shard = NamedSharding(self.mesh, P("data", "model", None))

    def setup(self):
        from repro.core import sources
        from repro.core.grid import Grid

        self.sources = sources
        self.entry = files.entry("sharded", self.cfg["physics"])
        self.grid = Grid(shape=self.shape, spacing=(self.h,) * 3)
        self.plan = self.entry.plan(self.mesh, self.shape, self.order,
                                    self.dt, self.grid.spacing,
                                    **self.cfg.get("planner", {}))
        self.build_model()
        nstate = len(self.physics.STATE)
        self.zeros = jax.jit(
            lambda: tuple(jnp.zeros(self.shape, jnp.float32)
                          for _ in range(nstate)),
            out_shardings=self.shard)
        self.src, self.rec = self.geometry[0]
        self.last_state = None
        self.unit(0)          # warm: compiles or loads every program
        self.last_state = None

    def build_model(self):
        """The earth model made sharded on the device, one block a chip."""
        key = jax.random.key(int(np.random.SeedSequence(
            self.seed).generate_state(1)[0]))
        make = jax.jit(lambda k: self.physics.build_model(self.cfg, k),
                       out_shardings=self.shard)
        self.model = jax.block_until_ready(make(key))

    def describe(self) -> str:
        p = self.plan
        return (super().describe() + f" mesh {dict(self.mesh.shape)} block "
                f"{p.block} outer T {p.T} inner tile {p.inner_tile} inner T "
                f"{p.inner_T} overlap {p.overlap} remainder T "
                f"{self.nt % p.T}")

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
                state, traces = self.entry.run(self.nt, self.zeros(),
                                               self.model, g, gr, self.plan)
            with annotate("bench.readback"):
                host = np.asarray(traces, np.float64)
                jax.block_until_ready(state)
        t1 = time.perf_counter()
        self.last_state = state
        return Unit(t0, t1, self.npoints * self.nt, 1, host)

    def check(self, window):
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.mesh.devices.flat]
        print(f"memory_peak_bytes per chip {peaks!r}", file=sys.stderr,
              flush=True)
        # the program's state goes to host before anything of the
        # reference's is on the device
        got = [np.asarray(f) for f in self.last_state]
        self.last_state = None
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
