"""The ``stencil4k`` deployment has SP-MZ class E's widths at the cut
step count: 4096 zones on a 64x64 torus, 25 steps of 24 calls and 4 sends
a rank, faces of 5 doubles over 54x92 (west, east) and 66x92 (south,
north) points."""

import json

import numpy as np
import pytest

from bench.gen import npbmz, tracegen
from bench.harness import BENCH

SEED = 2**31 + 29


@pytest.fixture(scope="module")
def cfg():
    return json.loads((BENCH / "configs" / "stencil4k.json").read_text())


def test_stencil4k_widths_are_sp_mz_class_e(cfg):
    r = npbmz.rank(cfg, SEED, 0)
    t = tracegen._rank_truth(0, r)
    assert t["start"].size == 25 * 24 + 1
    assert t["m_ts"].size == 25 * 4 and r.ts.size == 25 * 52 + 2
    assert len(npbmz.names(cfg)) == 18 and int(r.depth.max()) == 3
    assert sorted(np.unique(t["m_size"])) == [198_720, 242_880]
    w, e, s, n = npbmz.neighbours(cfg, 0)
    assert (w, e, s, n) == (63, 1, 4032, 64)
    assert sorted(np.unique(t["m_partner"])) == [1, 63, 64, 4032]


def test_stencil4k_volume_matches_stencil256(cfg):
    # the two message cells differ in the rank axis, not in the volume
    c256 = json.loads((BENCH / "configs" / "stencil256.json").read_text())
    rows = 25 * 52 + 2
    assert cfg["ranks"] * rows == 5_332_992
    assert cfg["ranks"] * 25 * 4 == c256["ranks"] * 400 * 4 == 409_600
    # a pair sums at most 25 faces: below 2^24, so exact in float32
    assert 25 * int(npbmz.face_bytes(cfg).max()) < 2**24
