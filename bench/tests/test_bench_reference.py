"""The plain reference against the program's own ``numpy`` backend at a
tiny size: counts and every exact part equal, sums within float64 rounding.
The reference reads only the generator's ground truth."""

import numpy as np
import pytest

from bench import ops, selection
from bench.compare import Tally
from bench.gen import tracegen
from bench.tests import tiny

SEED = 2**31 + 23
WHOLE = [("flat_profile", {"metrics": ("time.exc", "time.inc")}),
         ("time_profile", {"num_bins": 32}), ("load_imbalance", {}),
         ("stragglers", {}), ("comm_matrix", {"output": "size"}),
         ("message_histogram", {"bins": 16})]
# float64 sums in another order: a few ulps
TOL = 1e-12


@pytest.fixture(scope="module", params=tiny.CONFIGS)
def trace(request, tmp_path_factory):
    from repro.core.trace import Trace
    paths, truth = tracegen.generate(tiny.config_named(request.param), SEED,
                                     str(tmp_path_factory.mktemp("t")))
    return Trace.open(paths, streaming=True, cache=False), truth


def _numpy(handle, op, kw):
    from repro.serving import protocol
    return protocol.decode_value(protocol.encode_value(
        handle.query().run(op, backend="numpy", **kw)))


@pytest.mark.parametrize("op,kw", WHOLE, ids=[c[0] for c in WHOLE])
def test_whole_trace(trace, op, kw):
    handle, truth = trace
    want = ops.reference(op, selection.select(truth), kw)
    t = Tally()
    t.compare(op, _numpy(handle, op, kw), want)
    v = t.values
    assert v["exact_mismatch"] == 0, (op, v)
    assert v["sum_gap"] <= TOL and v["profile_gap"] <= TOL, (op, v)


def test_stragglers_reference_flags_a_slow_rank():
    """A rank whose computation is made 30% longer is flagged, and only
    it: the reference is not trivially empty on a balanced trace."""
    truth = tracegen.generate(tiny.config_named(tiny.CONFIGS[0]), SEED,
                              None)[1]
    sel = selection.select(truth)
    slow = sel.proc == 3
    sel.exc = np.where(slow, sel.exc * 13 // 10, sel.exc)
    got = ops.reference("stragglers", sel, {})
    assert list(got["process"]) == [3]
