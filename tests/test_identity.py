"""The sources' identity token (``plancache._paths_token``): its value for
every kind of source, and one ``os.stat`` a path to compute it.  The
token keys the plan cache, the service's cache and coalescing, and the
service's handle pool."""

import os
import shutil

import pytest

from repro.core import plancache
from repro.readers import pack
from repro.tracegen.big import big_trace


def _reference_stat_token(path):
    st = os.stat(path)
    try:
        cid = pack.read_footer(path).get("content_id")
    except (OSError, ValueError):
        cid = None
    if cid is not None:
        return ("pipitpack", cid)
    return (path, st.st_size, st.st_mtime_ns, st.st_ino)


def reference_token(paths):
    """The token by its definition, written out step by step: a directory
    is its files in walk order; a readable pack is its stored content id;
    any other file is ``(path, size, mtime_ns, inode)``."""
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, files in sorted(os.walk(p)):
                out.extend(_reference_stat_token(os.path.join(root, f))
                           for f in sorted(files))
        else:
            out.append(_reference_stat_token(p))
    return tuple(out)


@pytest.fixture(scope="module")
def sources(tmp_path_factory):
    """One source of each kind, by case name: ``(paths, stat'ed paths)``."""
    root = tmp_path_factory.mktemp("ident")
    packs = big_trace(str(root / "packs"), nprocs=3, events_per_proc=200,
                      calls_per_iter=10, seed=3, format="pack")
    text = big_trace(str(root / "text"), nprocs=1, events_per_proc=200,
                     calls_per_iter=10, seed=4, format="jsonl")
    damaged = str(root / "damaged.pack")
    shutil.copyfile(packs[0], damaged)
    with open(damaged, "r+b") as f:      # break the trailer's magic
        f.seek(-8, os.SEEK_END)
        f.write(b"\0" * 8)
    shards = root / "shards"
    (shards / "late").mkdir(parents=True)
    for i, p in enumerate(packs):
        shutil.copyfile(p, shards / f"rank_{i}.pack")
    shutil.copyfile(text[0], shards / "late" / "notes.jsonl")
    walked = [str(shards)] + sorted(
        os.path.join(r, f) for r, _d, fs in os.walk(shards) for f in fs)
    missing = str(root / "no_such.pack")
    return {
        "pack": ([packs[0]], [packs[0]]),
        "text": ([text[0]], [text[0]]),
        "damaged_pack": ([damaged], [damaged]),
        "directory": ([str(shards)], walked),
        "several": ([packs[1], text[0], str(shards)],
                    [packs[1], text[0]] + walked),
        "missing": ([missing], [missing]),
    }


@pytest.fixture()
def stats(monkeypatch):
    """Counts ``os.stat`` calls by path while the test runs."""
    seen = {}
    real = os.stat

    def spy(path, *a, **kw):
        key = os.fspath(path) if not isinstance(path, int) else path
        seen[key] = seen.get(key, 0) + 1
        return real(path, *a, **kw)
    monkeypatch.setattr(os, "stat", spy)
    return seen


def test_damaged_pack_has_no_content_id(sources):
    assert pack.content_id(sources["damaged_pack"][0][0]) is None
    assert pack.content_id(sources["pack"][0][0]) is not None


@pytest.mark.parametrize("case", ["pack", "text", "damaged_pack",
                                  "directory", "several", "missing"])
def test_token_equals_reference_with_one_stat_a_path(sources, case, stats):
    paths, stated = sources[case]
    try:
        want = reference_token(paths)
    except OSError as e:
        want = e
    for p in stated:
        pack._forget(p)
    # twice: the first parses each footer, the second reuses its record
    for _ in range(2):
        stats.clear()
        if isinstance(want, OSError):
            with pytest.raises(type(want)):
                plancache._paths_token(paths)
        else:
            assert plancache._paths_token(paths) == want
        assert {p: stats.get(p, 0) for p in stated} == \
            {p: 1 for p in stated}


def test_pack_token_is_its_content_id(sources):
    p = sources["pack"][0][0]
    assert plancache._paths_token([p]) == (
        ("pipitpack", pack.read_footer(p)["content_id"]),)
