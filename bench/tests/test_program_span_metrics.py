"""The readers of the program's own spans and counters, on synthetic
records: what each computes, and that each reads nothing (and raises
nothing) from a program that lacks the spans."""
from types import SimpleNamespace as NS

import pytest

from harness import files


def rec(name, dur=0.0, parent=None, **attrs):
    return NS(name=name, dur=dur, parent=parent, attrs=attrs)


# device-idle seconds by the innermost host span open in each gap
IDLE = {"ops.dispatch": 0.25, "ops.tile_pass": 1.5, "ops.tables": 0.5,
        "sources.precompute": 2.0}


def ctx(spans, propagates=2, shots=(4,)):
    return NS(spans=spans, propagates=propagates,
              window=NS(units=[NS(shots=n) for n in shots]),
              summary=NS(idle_by_host_span=lambda: dict(IDLE)))


SLOTS = dict(src_live=[8, 8], src_slots=[16, 16], rec_live=[8, 8],
             rec_slots=[8, 8], steps=[2, 1])
SPANS = [rec("bench.unit", 9.0),
         rec("sources.precompute", 0.5, npts=8),
         rec("sources.precompute_receivers", 0.25),
         rec("sources.precompute", 0.5, npts=8),
         rec("ops.tables", 0.1, **SLOTS),
         rec("ops.tables", 0.1, **SLOTS),
         rec("survey.tables", 0.2, **SLOTS),
         rec("ops.tile_pass", 0.1, parent="ops.dispatch"),
         rec("ops.dispatch", 0.3, parent="ops.propagate", compiles=2),
         rec("ops.dispatch", 0.5, parent="ops.propagate", compiles=1),
         rec("ops.propagate", 0.9)]


@pytest.mark.parametrize("name,want", [
    ("sources_precompute_s_per_call", 1.25 / 2),
    ("sources_precompute_s_per_call.survey", 1.25 / 4),
    # idle under ops.dispatch and the tile pass traced inside it
    ("ops_dispatch_idle_s_per_call", 1.75 / 2),
    ("ops_compiles_per_call", 3 / 2),
    # (8 + 8) x 2 + (8 + 8) x 1 live slot-steps of (16 + 8) x 2 + (16 + 8)
    ("sparse_slot_fill_pct", 100.0 * 48 / 72),
    ("sparse_slot_fill_pct.survey", 100.0 * 48 / 72),
])
def test_reader_on_synthetic_spans(name, want):
    assert files.metric(name).read(ctx(SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "sources_precompute_s_per_call", "sources_precompute_s_per_call.survey",
    "ops_dispatch_idle_s_per_call", "ops_compiles_per_call",
    "sparse_slot_fill_pct", "sparse_slot_fill_pct.survey"])
def test_reader_reads_nothing_without_the_spans(name):
    m = files.metric(name)
    assert m.read(ctx(None)) is None
    # the spans a program without these counters records
    older = [rec("bench.unit", 9.0), rec("ops.tables", 0.1),
             rec("ops.tile_pass", 4.0), rec("ops.propagate", 5.0),
             rec("survey.tables", 0.2), rec("survey.dispatch", 0.1)]
    assert m.read(ctx(older)) is None
