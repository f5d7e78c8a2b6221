"""The reader of the one-chip propagate's trace counter (`traced` on the
`ops.dispatch` spans), on synthetic records: what it computes, and that it
reads nothing (and raises nothing) from a program without the counter."""
from types import SimpleNamespace as NS

import pytest

from harness import files


def rec(name, **attrs):
    return NS(name=name, dur=0.1, parent=None, attrs=attrs)


def ctx(spans, propagates):
    return NS(spans=spans, propagates=propagates)


@pytest.mark.parametrize("flags,propagates,want", [
    ([False, False, False], 3, 0.0),     # every call served by the cache
    ([True, False, False, False], 4, 0.25),
    ([True, True], 2, 1.0),              # shapes that change every call
])
def test_traces_per_call(flags, propagates, want):
    spans = [rec("bench.unit"), rec("survey.dispatch", traced=True)]
    spans += [rec("ops.dispatch", compiles=int(f), traced=f) for f in flags]
    m = files.metric("ops_traces_per_call")
    assert m.read(ctx(spans, propagates)) == pytest.approx(want)


def test_reads_nothing_without_the_counter():
    m = files.metric("ops_traces_per_call")
    assert m.read(ctx(None, 1)) is None
    # the spans of a program whose dispatch counts compiles but not traces
    older = [rec("ops.tables"), rec("ops.dispatch", compiles=2),
             rec("ops.tile_pass"), rec("survey.dispatch", traced=True)]
    assert m.read(ctx(older, 1)) is None
