"""The one traffic generator: turns a mix's parameters (`traffic/<mix>.json`)
and a seed into acquisition geometry.

Every seed gets the same number of shots, sources and receivers, at the
same nominal cells; the seed moves them only within their cells (sub-cell
offsets), so the work is identical from seed to seed.  Depth is the last
axis.  A shot is (source coords (1, 3), receiver coords (nrec, 3)) in
metres, origin at grid index 0.
"""
from __future__ import annotations

import numpy as np


def _shot(rng, cfg: dict, mix: dict, x_cell: float):
    n = cfg["shape"]
    h = float(cfg["spacing_m"])
    yc = 0.5 * (n[1] - 1)
    src = np.array([[x_cell, yc, mix["src_depth_cells"]]]) * h
    src = src + rng.uniform(0.0, h, src.shape)
    nrec = int(mix["receivers"])
    half = 0.5 * mix["receiver_span_frac"] * n[0]
    xs = x_cell + np.linspace(-half, half, nrec)
    rec = np.stack([xs, np.full(nrec, yc + mix["receiver_y_offset_cells"]),
                    np.full(nrec, float(mix["rec_depth_cells"]))], axis=1) * h
    rec = rec + rng.uniform(0.0, h, rec.shape)
    return src, rec


def shots(cfg: dict, mix: dict, seed: int):
    """`mix["shots"]` shots on a line along x through the grid's centre,
    `mix["shot_spacing_cells"]` apart; receivers move with their source."""
    rng = np.random.default_rng(seed)
    n = cfg["shape"]
    count = int(mix["shots"])
    step = float(mix.get("shot_spacing_cells", 0.0))
    x0 = 0.5 * (n[0] - 1) - 0.5 * step * (count - 1)
    return [_shot(rng, cfg, mix, x0 + i * step) for i in range(count)]


def first_shot(mix: dict, seed: int) -> int:
    """The shot a survey window starts at: the first of a group of
    `mix["group_shots"]` drawn from the seed, apart from the geometry's
    draws."""
    group = int(mix["group_shots"])
    groups = int(mix["shots"]) // group
    return int(np.random.default_rng([seed, 1]).integers(groups)) * group
