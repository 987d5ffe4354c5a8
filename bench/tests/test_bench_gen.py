"""The benchmark's trace generator: the same seed gives the same trace,
every seed the same sizes, the deployment's widths are the source's, and
the pack shards it writes hold exactly the ground truth it reports."""

import json

import numpy as np
import pytest

from bench.gen import npbmz, tracegen
from bench.harness import BENCH
from bench.tests import tiny

CONFIGS = tiny.CONFIGS
SEED = 2**31 + 11


def _arrays(truth):
    return {k: v for k, v in vars(truth).items()
            if isinstance(v, np.ndarray)}


@pytest.mark.parametrize("name", CONFIGS)
def test_same_seed_same_trace(name):
    cfg = tiny.config_named(name)
    a = _arrays(tracegen.generate(cfg, SEED, None)[1])
    b = _arrays(tracegen.generate(cfg, SEED, None)[1])
    c = _arrays(tracegen.generate(cfg, SEED + 1, None)[1])
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["call_end"], c["call_end"])


@pytest.mark.parametrize("name", CONFIGS)
def test_sizes_do_not_depend_on_seed(name):
    cfg = tiny.config_named(name)
    a = tracegen.generate(cfg, 1, None)[1]
    b = tracegen.generate(cfg, 2**33 + 5, None)[1]
    assert (a.n_events, a.call_start.size, a.msg_ts.size) == \
        (b.n_events, b.call_start.size, b.msg_ts.size)
    assert np.array_equal(np.sort(a.msg_size), np.sort(b.msg_size))


def test_stencil256_widths_are_sp_mz_class_c():
    # 256 zones on a 16x16 torus, 400 steps of 24 calls and 4 sends a rank;
    # faces of 5 doubles over 20x28 (west, east) and 30x28 (south, north)
    cfg = json.loads((BENCH / "configs" / "stencil256.json").read_text())
    r = npbmz.rank(cfg, SEED, 17)
    t = tracegen._rank_truth(17, r)
    assert t["start"].size == 400 * 24 + 1
    assert t["m_ts"].size == 400 * 4 and r.ts.size == 400 * 52 + 2
    assert len(npbmz.names(cfg)) == 18 and int(r.depth.max()) == 3
    assert sorted(np.unique(t["m_size"])) == [22_400, 33_600]
    assert sorted(np.unique(t["m_partner"])) == [1, 16, 18, 33]
    w, e, s, n = npbmz.neighbours(cfg, 0)
    assert (w, e, s, n) == (15, 1, 240, 16)


@pytest.mark.parametrize("name", CONFIGS)
def test_calls_nest(name):
    t = tracegen.generate(tiny.config_named(name), SEED, None)[1]
    inc = t.call_end - t.call_start
    assert np.all((t.call_exc > 0) & (t.call_exc <= inc))


@pytest.mark.parametrize("name", CONFIGS)
def test_packs_hold_the_ground_truth(name, tmp_path):
    from repro.core.constants import ET, MSG_SIZE, NAME, PARTNER, PROC, TS
    from repro.core.trace import Trace
    paths, truth = tracegen.generate(tiny.config_named(name), SEED,
                                     str(tmp_path))
    ev = Trace.open(paths).events
    assert len(ev) == truth.n_events
    ts = np.asarray(ev[TS], np.int64)
    et = np.asarray(ev[ET]).astype(str)
    names = np.asarray(ev[NAME]).astype(str)
    enter = et == "Enter"
    key = np.lexsort((np.asarray(ev[PROC])[enter], ts[enter]))
    tkey = np.lexsort((truth.call_proc, truth.call_start))
    assert np.array_equal(ts[enter][key], truth.call_start[tkey])
    assert np.array_equal(names[enter][key],
                          truth.names[truth.call_name[tkey]])
    send = et == "Instant"
    assert send.sum() == truth.msg_ts.size
    assert np.array_equal(np.sort(np.asarray(ev[MSG_SIZE])[send]),
                          np.sort(truth.msg_size.astype(float)))
    assert np.array_equal(np.sort(np.asarray(ev[PARTNER])[send]),
                          np.sort(truth.msg_partner))
