"""Plan-result cache: terminal-op results memoized by content identity.

Repeated interactive analysis — the notebook workflow the paper's scripting
pitch targets — re-runs the same terminal ops over the same traces
constantly, and for out-of-core handles every re-run is a full re-read of
the on-disk stream.  This cache memoizes terminal-op results keyed by a
digest of

    (trace content identity, fused plan steps, op identity, args, kwargs)

so a repeated call returns the previous result object without touching the
data.  Entries are shared process-wide: two TraceSet members over the same
paths, or two handles opened on the same file, hit the same entry.

Content identity is what makes this safe:

* **streaming / scan sources** — the (path, size, mtime_ns, inode) of every
  input file plus the handle's read configuration; touching or rewriting a
  file changes the key, so stale hits are impossible.  On by default
  (``Trace.open(..., cache=False)`` or a per-call ``op(..., cache=False)``
  opts out).
* **in-memory traces** — a SHA-256 over the trace's base event columns
  (derived columns excluded: they are deterministic products of the base
  and materialize lazily).  Hashing is O(N) per call, so this layer is
  **opt-in** per call (``trace.query().flat_profile(cache=True)``); caching
  stays exact under mutation because a mutated frame hashes differently.

Anything that cannot be digested exactly — callable arguments, unknown
custom plan steps, exotic values — silently bypasses the cache rather than
risking a wrong hit.  ``clear()`` is the explicit invalidation hatch;
``configure(enabled=False)`` turns the whole layer off.

Like ``functools.lru_cache``, hits return the *same object* that was
stored: treat cached results as read-only, since mutating a returned
frame/array in place would be visible to every later hit.  Call with
``cache=False`` (or ``.copy()`` the result) when you intend to mutate.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import numpy as np

__all__ = ["lookup", "store", "plan_key", "clear", "configure", "stats",
           "live_lookup", "live_store", "live_invalidate", "live_plan_key"]

_MAX_ENTRIES = 128
_ENABLED = True
_TENANT_QUOTA: Optional[int] = None  # max entries per tenant; None = no cap
_CACHE: "OrderedDict[str, Any]" = OrderedDict()
_OWNER: Dict[str, str] = {}          # key -> tenant (tagged entries only)
_TENANT_KEYS: Dict[str, "OrderedDict[str, None]"] = {}  # tenant -> key LRU
_HITS = 0
_MISSES = 0
_EVICTIONS = 0
_TENANT_STATS: Dict[str, Dict[str, int]] = {}

# Live incremental partials (valid-up-to-row semantics): a live handle's
# plan keeps its running aggregation state here, keyed by live_plan_key —
# a re-query after the trace grows folds only the new rows into the
# stored partial instead of recomputing from row 0.  Validity is enforced
# by per-path prefix fingerprints stored *inside* the entry (group count,
# end offset, last CRC), not by the key: the same key deliberately
# matches across growth.  See core/streaming.py::execute_streaming.
_LIVE: "OrderedDict[str, Any]" = OrderedDict()
_LIVE_MAX = 32
_LIVE_HITS = 0
_LIVE_MISSES = 0
_LIVE_INVALIDATIONS = 0

# One process-wide reentrant lock guards every counter and both index maps:
# the trace-query service looks up / stores from worker threads while the
# asyncio loop reads stats(), and library calls can race them from the main
# thread.  All critical sections are tiny (dict ops), so a single lock
# cannot become the bottleneck next to the plan executions it memoizes.
_LOCK = threading.RLock()


class _Undigestable(Exception):
    """A key component has no exact digest; bypass the cache."""


def _tenant_stats(tenant: str) -> Dict[str, int]:
    st = _TENANT_STATS.get(tenant)
    if st is None:
        st = _TENANT_STATS[tenant] = {"entries": 0, "hits": 0, "misses": 0,
                                      "evictions": 0}
    return st


def _forget(key: str) -> None:
    """Drop ``key``'s tenant bookkeeping (caller already popped _CACHE)."""
    tenant = _OWNER.pop(key, None)
    if tenant is not None:
        keys = _TENANT_KEYS.get(tenant)
        if keys is not None:
            keys.pop(key, None)
        st = _tenant_stats(tenant)
        st["entries"] = max(st["entries"] - 1, 0)
        st["evictions"] += 1


def _evict_oldest() -> None:
    global _EVICTIONS
    key, _ = _CACHE.popitem(last=False)
    _forget(key)
    _EVICTIONS += 1


def configure(enabled: Optional[bool] = None,
              max_entries: Optional[int] = None,
              tenant_quota: Optional[int] = None) -> None:
    """Adjust the cache globally (``enabled=False`` disables lookups and
    stores; ``max_entries`` bounds the LRU; ``tenant_quota`` caps the
    entries any one tenant tag may hold — 0/negative removes the cap)."""
    global _ENABLED, _MAX_ENTRIES, _TENANT_QUOTA
    with _LOCK:
        if enabled is not None:
            _ENABLED = bool(enabled)
        if max_entries is not None:
            _MAX_ENTRIES = max(int(max_entries), 1)
            while len(_CACHE) > _MAX_ENTRIES:
                _evict_oldest()
        if tenant_quota is not None:
            _TENANT_QUOTA = int(tenant_quota) if tenant_quota > 0 else None
            if _TENANT_QUOTA is not None:
                for tenant in list(_TENANT_KEYS):
                    _shrink_tenant(tenant)


def _shrink_tenant(tenant: str) -> None:
    global _EVICTIONS
    keys = _TENANT_KEYS.get(tenant)
    if keys is None or _TENANT_QUOTA is None:
        return
    while len(keys) > _TENANT_QUOTA:
        key, _ = keys.popitem(last=False)
        _CACHE.pop(key, None)
        _OWNER.pop(key, None)
        st = _tenant_stats(tenant)
        st["entries"] = max(st["entries"] - 1, 0)
        st["evictions"] += 1
        _EVICTIONS += 1


def clear() -> None:
    """Drop every cached result (explicit invalidation), including live
    incremental partials.  Counters and per-tenant usage tallies survive;
    only the entries go."""
    with _LOCK:
        _CACHE.clear()
        _OWNER.clear()
        _TENANT_KEYS.clear()
        _LIVE.clear()
        for st in _TENANT_STATS.values():
            st["entries"] = 0


def stats() -> dict:
    """Cache counters: entries, hits, misses, evictions, limits, and — for
    entries stored under a tenant tag (the trace-query service does this) —
    per-tenant usage.  The service exposes this verbatim on ``/stats``."""
    with _LOCK:
        return {"entries": len(_CACHE), "hits": _HITS, "misses": _MISSES,
                "evictions": _EVICTIONS, "max_entries": _MAX_ENTRIES,
                "enabled": _ENABLED, "tenant_quota": _TENANT_QUOTA,
                "live_entries": len(_LIVE), "live_hits": _LIVE_HITS,
                "live_misses": _LIVE_MISSES,
                "live_invalidations": _LIVE_INVALIDATIONS,
                "tenants": {t: dict(st) for t, st in _TENANT_STATS.items()}}


def live_lookup(key: str) -> Any:
    """The stored incremental partial for ``key``, or None.  The caller
    owns validity checking (prefix fingerprints live in the entry)."""
    global _LIVE_HITS, _LIVE_MISSES
    with _LOCK:
        ent = _LIVE.get(key)
        if ent is not None:
            _LIVE.move_to_end(key)
            _LIVE_HITS += 1
            return ent
        _LIVE_MISSES += 1
        return None


def live_store(key: str, entry: Any) -> None:
    with _LOCK:
        _LIVE[key] = entry
        _LIVE.move_to_end(key)
        while len(_LIVE) > _LIVE_MAX:
            _LIVE.popitem(last=False)


def live_invalidate(key: Optional[str] = None) -> None:
    """Drop one live partial (or all of them) — used when a shard's
    committed prefix stops being a prefix extension (resume truncated a
    tail, a file was replaced) and on explicit invalidation."""
    global _LIVE_INVALIDATIONS
    with _LOCK:
        if key is None:
            _LIVE_INVALIDATIONS += len(_LIVE)
            _LIVE.clear()
        elif _LIVE.pop(key, None) is not None:
            _LIVE_INVALIDATIONS += 1


def lookup(key: str, tenant: Optional[str] = None) -> Tuple[bool, Any]:
    """(hit, value) for ``key``; a hit refreshes LRU order.  ``tenant``
    attributes the hit/miss to that tenant's usage counters."""
    global _HITS, _MISSES
    with _LOCK:
        if key in _CACHE:
            _CACHE.move_to_end(key)
            if tenant is not None:
                keys = _TENANT_KEYS.get(tenant)
                if keys is not None and key in keys:
                    keys.move_to_end(key)
                _tenant_stats(tenant)["hits"] += 1
            _HITS += 1
            return True, _CACHE[key]
        _MISSES += 1
        if tenant is not None:
            _tenant_stats(tenant)["misses"] += 1
        return False, None


def store(key: str, value: Any, tenant: Optional[str] = None) -> None:
    """Insert ``key``.  With a ``tenant`` tag the entry counts toward that
    tenant's quota (oldest tagged entry evicted beyond it); untagged
    entries (plain library calls) only face the global LRU bound."""
    with _LOCK:
        if key in _CACHE:
            _CACHE[key] = value
            _CACHE.move_to_end(key)
            return
        _CACHE[key] = value
        if tenant is not None:
            _OWNER[key] = tenant
            _TENANT_KEYS.setdefault(tenant, OrderedDict())[key] = None
            _tenant_stats(tenant)["entries"] += 1
            _shrink_tenant(tenant)
        while len(_CACHE) > _MAX_ENTRIES:
            _evict_oldest()


# ---------------------------------------------------------------------------
# key construction
# ---------------------------------------------------------------------------

def _norm(v) -> Any:
    """Normalize one argument value into a deterministic, repr-stable
    token; raise _Undigestable for anything without an exact digest."""
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return v
    if isinstance(v, (np.integer, np.floating, np.bool_)):
        return v.item()
    if isinstance(v, (list, tuple)):
        return tuple(_norm(x) for x in v)
    if isinstance(v, (set, frozenset)):
        return tuple(sorted((_norm(x) for x in v), key=repr))
    if isinstance(v, dict):
        return tuple(sorted(((str(k), _norm(x)) for k, x in v.items())))
    if isinstance(v, range):
        return ("range", v.start, v.stop, v.step)
    if isinstance(v, np.ndarray) and v.size <= 4096:
        return ("ndarray", v.dtype.str, v.shape, v.tobytes())
    raise _Undigestable(type(v).__name__)


def _filter_token(f) -> tuple:
    from .filters import _And, _Not, _Or
    if isinstance(f, _And):
        return ("and", _filter_token(f.a), _filter_token(f.b))
    if isinstance(f, _Or):
        return ("or", _filter_token(f.a), _filter_token(f.b))
    if isinstance(f, _Not):
        return ("not", _filter_token(f.a))
    if type(f).__name__ not in ("Filter",):
        raise _Undigestable(type(f).__name__)  # user Filter subclass
    return ("leaf", f.field, f.operator, _norm(f.value),
            getattr(f, "_trim", None))


def _steps_token(steps) -> tuple:
    from .query import FilterStep, ProcessStep, SliceTimeStep
    out = []
    for step in steps:
        if type(step) is FilterStep:
            out.append(("filter", _filter_token(step.filter)))
        elif type(step) is SliceTimeStep:
            out.append(("slice", float(step.start), float(step.end),
                        step.trim))
        elif type(step) is ProcessStep:
            out.append(("procs", tuple(int(p) for p in step.procs)))
        else:
            raise _Undigestable(type(step).__name__)
    return tuple(out)


def _stat_token(path: str, st=None) -> tuple:
    """Identity of one file from one ``os.stat`` (``st``, when the caller
    has taken it)."""
    import os
    if st is None:
        st = os.stat(path)
    # pack files carry a stored content id (SHA-256 over the column +
    # sidecar bytes): keying by it instead of (size, mtime, inode) means
    # copies and faithful rewrites of a pack share one cache entry, and a
    # re-pack with different content can never produce a stale hit
    from ..readers.pack import content_id
    cid = content_id(path, st)
    if cid is not None:
        return ("pipitpack", cid)
    return (path, st.st_size, st.st_mtime_ns, st.st_ino)


def _paths_token(paths) -> tuple:
    """Identity of ``paths``, one ``os.stat`` a path: a directory's files
    in walk order.  Raises ``OSError`` for a path that cannot be stat'ed."""
    import os
    import stat
    out = []
    for p in paths:
        st = os.stat(p)
        if stat.S_ISDIR(st.st_mode):
            for root, _dirs, files in sorted(os.walk(p)):
                out.extend(_stat_token(os.path.join(root, f))
                           for f in sorted(files))
        else:
            out.append(_stat_token(p, st))
    return tuple(out)


def _content_token(trace) -> tuple:
    """SHA-256 over the trace's base (non-derived) event columns."""
    from .frame import Categorical
    from .query import _strip
    ev = _strip(trace.events)
    h = hashlib.sha256()
    for name in ev.columns:
        col = ev.column(name)
        h.update(name.encode())
        if isinstance(col, Categorical):
            h.update(np.ascontiguousarray(col.codes).tobytes())
            h.update("\x00".join(map(str, col.categories)).encode())
        else:
            arr = np.asarray(col)
            if arr.dtype.kind == "O":
                raise _Undigestable(f"object column {name}")
            h.update(arr.dtype.str.encode())
            h.update(np.ascontiguousarray(arr).tobytes())
    return ("mem", len(ev), h.hexdigest())


def _source_token(source, cache_flag: Optional[bool]):
    """Identity token for a plan source, or None when this source should
    not be cached under the given per-call flag."""
    from .query import _ScanSource, _StreamSource, _TraceSource
    if isinstance(source, _StreamSource):
        h = source.handle
        if getattr(h, "is_live", False):
            # live handles execute over a pinned committed-prefix snapshot
            # — a stat-keyed entry would go stale the moment another
            # handle pins a newer snapshot of the same (unchanged) file.
            # They use the live incremental store instead.
            return None
        if cache_flag is None and not h.cache:
            return None
        return ("stream", _paths_token(h.paths), h.format, h.chunk_rows,
                h.executor, h.processes, _norm(h.reader_kwargs),
                _steps_token(h._steps))
    if isinstance(source, _ScanSource):
        return ("scan", _paths_token(source.paths), source.format)
    if isinstance(source, _TraceSource):
        # hashing an in-memory trace costs a full pass — only on request
        if not cache_flag:
            return None
        return _content_token(source.trace)
    return None  # unknown source kinds are never cached


def plan_key(source, steps, spec, args: tuple, kwargs: dict,
             cache_flag: Optional[bool]) -> Optional[str]:
    """Digest of one terminal-op execution, or None to bypass the cache.

    ``cache_flag`` is the per-call ``cache=`` argument: False forces a
    bypass, True opts an in-memory trace in, None applies the defaults
    (streaming/scan sources cached, in-memory not).
    """
    if not _ENABLED or cache_flag is False:
        return None
    try:
        src = _source_token(source, cache_flag)
        if src is None:
            return None
        fn = spec.fn
        op = (spec.name,
              f"{getattr(fn, '__module__', '')}."
              f"{getattr(fn, '__qualname__', '')}" if fn is not None else "")
        token = (src, _steps_token(steps), op, _norm(args), _norm(kwargs))
    except (_Undigestable, OSError):
        return None
    return hashlib.sha256(repr(token).encode()).hexdigest()


def live_plan_key(handle, steps, spec, args: tuple, kwargs: dict
                  ) -> Optional[str]:
    """Digest identifying one live plan *across growth*: the handle's
    paths and read configuration plus the plan/op/arguments — but
    deliberately **no** stat/content token, because the whole point is
    that the same key survives the file growing.  Validity (the new
    prefix really extends the one already folded) is checked against the
    fingerprints stored inside the live entry, never the key.  None when
    any component has no exact digest."""
    import os
    if not _ENABLED:
        return None
    try:
        rk = {k: v for k, v in handle.reader_kwargs.items()
              if k not in ("live", "upto_rows", "report")}
        fn = spec.fn
        op = (spec.name,
              f"{getattr(fn, '__module__', '')}."
              f"{getattr(fn, '__qualname__', '')}" if fn is not None else "")
        token = ("live",
                 tuple(os.path.abspath(p) for p in handle.paths),
                 handle.format, handle.chunk_rows, handle.processes,
                 _norm(rk), _steps_token(handle._steps),
                 _steps_token(steps), op, _norm(args), _norm(kwargs))
    except (_Undigestable, OSError):
        return None
    return hashlib.sha256(repr(token).encode()).hexdigest()
