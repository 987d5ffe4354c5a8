"""A pack shard's opened state (footer, name table, one map of the file,
column and sidecar views) is kept across streaming passes while the file's
stat token holds: an unchanged shard costs at most two ``os.stat`` a pass
and no ``open`` or ``mmap``; a rewritten, replaced, grown or damaged shard
is read afresh, and damage is re-diagnosed on every pass."""

import builtins
import mmap
import os
import shutil
from collections import Counter

import numpy as np
import pytest

from repro.core import PROC, TS
from repro.core.trace import Trace
from repro.readers import pack as packmod
from repro.readers.pack import PackWriter, read_footer, write_pack
from repro.runtime import tracer
from repro.tracegen.big import big_trace


def _counter_delta(s0, s1, name):
    return s1["counters"].get(name, 0) - s0["counters"].get(name, 0)


def _opens(s0, s1):
    return (_counter_delta(s0, s1, "read.opens_reused"),
            _counter_delta(s0, s1, "read.opens_fresh"))


def _ts(path, col=TS):
    """Every timestamp (or ``col`` value) of one streaming pass over
    ``path``."""
    st = Trace.open(path, streaming=True, cache=False)
    return np.concatenate([np.asarray(f[col]) for f in st._iter_frames()])


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    out = tmp_path_factory.mktemp("reuse")
    big_trace(str(out), nprocs=4, events_per_proc=600, calls_per_iter=40,
              seed=7, format="pack")
    return sorted(str(p) for p in out.glob("*.pack"))


@pytest.fixture
def counted(monkeypatch):
    """Counts of ``os.stat``, ``open`` and ``mmap.mmap`` calls while
    ``state["on"]`` is set."""
    calls = Counter()
    state = {"on": False}
    real_stat, real_open, real_mmap = os.stat, builtins.open, mmap.mmap

    def wrap(name, real):
        def counted_call(*a, **k):
            if state["on"]:
                calls[name] += 1
            return real(*a, **k)
        return counted_call

    monkeypatch.setattr(os, "stat", wrap("stat", real_stat))
    monkeypatch.setattr(builtins, "open", wrap("open", real_open))
    monkeypatch.setattr(mmap, "mmap", wrap("mmap", real_mmap))
    return calls, state


def test_second_pass_makes_no_open_or_mmap(shards, counted):
    calls, state = counted
    st = Trace.open(shards, streaming=True, cache=False)
    first = st.flat_profile()
    state["on"] = True
    second = st.flat_profile()
    state["on"] = False
    assert calls["open"] == 0 and calls["mmap"] == 0, calls
    # one stat a shard: the format check's, handed to the pack open
    assert calls["stat"] <= len(shards), calls
    assert list(first.columns) == list(second.columns)
    for col in first.columns:
        a, b = np.asarray(first[col]), np.asarray(second[col])
        if a.dtype.kind == "f":
            np.testing.assert_array_equal(a, b)
        else:
            assert list(a) == list(b), col


def test_opens_are_counted_fresh_then_reused(shards):
    for p in shards:
        packmod._forget(p)
    st = Trace.open(shards, streaming=True, cache=False)
    s0 = tracer.snapshot()
    st.flat_profile()
    s1 = tracer.snapshot()
    st.comm_matrix(output="size")
    s2 = tracer.snapshot()
    assert _opens(s0, s1) == (0, len(shards))
    assert _opens(s1, s2) == (len(shards), 0)
    # a whole-file read of a kept shard reuses the same record
    Trace.open(shards[0])
    assert _opens(s2, tracer.snapshot()) == (1, 0)


def test_shard_rewritten_with_other_content_is_read_afresh(shards, tmp_path):
    p = str(tmp_path / "s.pack")
    shutil.copyfile(shards[0], p)
    assert set(_ts(p, PROC)) == {0}
    other = str(tmp_path / "other.pack")
    write_pack(Trace.open(shards[1]), other, chunk_rows=200)
    ino, size = os.stat(p).st_ino, os.stat(p).st_size
    shutil.copyfile(other, p)         # same inode, other size
    assert os.stat(p).st_ino == ino and os.stat(p).st_size != size
    assert set(_ts(p, PROC)) == {1}
    np.testing.assert_array_equal(_ts(p), _ts(shards[1]))


def test_shard_replaced_by_rename_is_read_afresh(shards, tmp_path):
    """Only the inode tells the two files apart: same size, same mtime."""
    p = str(tmp_path / "s.pack")
    write_pack(Trace.open(shards[0]), p, chunk_rows=200)
    before = _ts(p)
    ch = read_footer(p)["chunks"][0]
    new = str(tmp_path / "new.pack")
    shutil.copyfile(p, new)
    with open(new, "r+b") as f:       # shift chunk 0's timestamps
        f.seek(ch["offset"])
        n = ch["hi"] - ch["lo"]
        ts = np.frombuffer(f.read(8 * n), "<i8") + 1000
        f.seek(ch["offset"])
        f.write(ts.astype("<i8").tobytes())
    old = os.stat(p)
    os.utime(new, ns=(old.st_atime_ns, old.st_mtime_ns))
    os.replace(new, p)
    st = os.stat(p)
    assert (st.st_size, st.st_mtime_ns) == (old.st_size, old.st_mtime_ns)
    after = _ts(p)
    np.testing.assert_array_equal(after[:n], before[:n] + 1000)
    np.testing.assert_array_equal(after[n:], before[n:])


def test_shard_grown_by_append_is_read_afresh(tmp_path):
    p = str(tmp_path / "g.pack")
    ev = Trace.open(big_trace(str(tmp_path / "src"), nprocs=1,
                              events_per_proc=400, seed=3,
                              format="pack")[0]).events
    w = PackWriter.open_append(p, fsync=False)
    w.append(ev)
    w.commit()
    w.finalize()
    rows = len(_ts(p))
    assert len(_ts(p)) == rows         # kept record
    w = PackWriter.open_append(p, fsync=False)
    w.append(ev)
    w.commit()
    salvaged = Trace.open(p, streaming=True, cache=False,
                          on_error="salvage")
    assert sum(len(f) for f in salvaged._iter_frames()) == 2 * rows
    w.finalize()
    assert len(_ts(p)) == 2 * rows


def test_damaged_chunk_is_rediagnosed_every_pass(shards, tmp_path):
    from repro.testing.faults import bit_flip
    p = str(tmp_path / "bad.pack")
    write_pack(Trace.open(shards[0]), p, chunk_rows=200)
    victim = read_footer(p)["chunks"][0]
    bit_flip(p, p, offsets=[victim["offset"] + 5])
    st = Trace.open(p, streaming=True, cache=False, on_error="skip_chunk")
    for _ in range(2):
        packmod.reset_io_stats()
        s0 = tracer.snapshot()
        with pytest.warns(RuntimeWarning, match="quarantined 1 chunk"):
            st.flat_profile()
        assert packmod.io_stats()["chunks_quarantined"] == 1
        assert _opens(s0, tracer.snapshot()) == (0, 1)


def test_kept_records_hold_more_than_256_shards(tmp_path):
    t = Trace.open(big_trace(str(tmp_path / "src"), nprocs=1,
                             events_per_proc=60, seed=1, format="pack")[0])
    paths = [str(tmp_path / f"rank_{i}.pack") for i in range(300)]
    for i, p in enumerate(paths):
        ev = t.events.copy()
        ev[PROC] = np.full(len(ev), i, np.int32)
        write_pack(ev, p)
    st = Trace.open(paths, streaming=True, cache=False)
    s0 = tracer.snapshot()
    st.flat_profile()
    s1 = tracer.snapshot()
    st.flat_profile()
    assert _opens(s0, s1) == (0, 300)
    assert _opens(s1, tracer.snapshot()) == (300, 0)


def test_threads_share_the_kept_records(shards, monkeypatch):
    """Passes on many threads over a table smaller than the shard set (so
    records are evicted and rebuilt under contention) all give the same
    answer, count one open a shard a pass, and keep the table bounded."""
    import sys
    import threading
    from collections import OrderedDict
    monkeypatch.setattr(packmod, "_OPEN", OrderedDict())
    monkeypatch.setattr(packmod, "_OPEN_MAX", 2)
    want = np.asarray(Trace.open(shards, streaming=True, cache=False)
                      .flat_profile()["time.exc"])
    n_threads, passes = 12, 4
    errors, done = [], []

    def work():
        try:
            st = Trace.open(shards, streaming=True, cache=False)
            for _ in range(passes):
                got = np.asarray(st.flat_profile()["time.exc"])
                np.testing.assert_array_equal(got, want)
            done.append(1)
        except Exception as e:  # noqa: BLE001 — reported by the assert
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    s0 = tracer.snapshot()
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and len(done) == n_threads, errors
    assert sum(_opens(s0, tracer.snapshot())) == \
        n_threads * passes * len(shards)
    assert len(packmod._OPEN) <= 2
