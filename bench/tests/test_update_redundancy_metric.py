"""The reader of the TB kernel's update-redundancy counter
(`update_points` / `useful_points` on the `ops.tables` and
`survey.tables` spans), on synthetic records: what it computes, and that
it reads nothing (and raises nothing) from a program without the
counter."""
from types import SimpleNamespace as NS

import pytest

from harness import files


def rec(name, **attrs):
    return NS(name=name, dur=0.1, parent=None, attrs=attrs)


# main tiles and a remainder tile, as `ops.update_counts` reports them
POINTS = dict(update_points=[600, 40], useful_points=[200, 20])


@pytest.mark.parametrize("name,span", [
    ("tb_update_redundancy", "ops.tables"),
    ("tb_update_redundancy.survey", "survey.tables"),
])
def test_redundancy_over_the_spans(name, span):
    spans = [rec("bench.unit"), rec(span, **POINTS), rec(span, **POINTS),
             rec("ops.tables" if span != "ops.tables" else "survey.tables",
                 update_points=[1], useful_points=[1])]
    got = files.metric(name).read(NS(spans=spans))
    assert got == pytest.approx(2 * 640 / (2 * 220))


@pytest.mark.parametrize("name", ["tb_update_redundancy",
                                  "tb_update_redundancy.survey"])
def test_reads_nothing_without_the_counter(name):
    m = files.metric(name)
    assert m.read(NS(spans=None)) is None
    # the spans of a program that reports slot fill but not this counter
    older = [rec("ops.tables", steps=[8], src_live=[1], src_slots=[8],
                 rec_live=[1], rec_slots=[8]),
             rec("survey.tables", steps=[8], src_live=[1], src_slots=[8],
                 rec_live=[1], rec_slots=[8])]
    assert m.read(NS(spans=older)) is None
