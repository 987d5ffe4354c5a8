"""Pluggable op / reader registries — the extensibility backbone of the lazy
query layer (paper §IV-E, §VII: Pipit's claim is a *programmatic, extensible*
analysis API).

Two registries live here:

* **Op registry** — every §IV analysis operation registers itself with its
  declared prerequisites (``needs_structure``: enter/leave matching, parents,
  inc/exc; ``needs_messages``: send/recv matching).  The query engine
  (:mod:`repro.core.query`) reads these declarations to materialize each
  prerequisite *exactly once per plan* and users register custom analyses the
  same way the built-ins do::

      from repro.core.registry import register_op

      @register_op("send_count", needs_messages=True)
      def send_count(trace):
          ...

      trace.query().filter(f).send_count()   # chains like any built-in

* **Reader registry** — every trace format registers a reader plus an
  optional content sniffer and an optional per-shard process hint.
  ``Trace.open(path, format="auto")`` resolves the format here, and the
  parallel driver uses the shard hints to *skip shards before parsing* when
  the query plan restricts processes (predicate pushdown into readers).

This module is intentionally dependency-free (no imports from trace/query)
so both layers and all readers can import it without cycles.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, replace
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Set, Tuple)

__all__ = [
    "OpSpec", "register_op", "register_streaming", "get_op", "list_ops",
    "terminal_op",
    "register_backend", "get_backend", "op_backends", "list_backends",
    "ReaderSpec", "register_reader", "register_chunked", "register_units",
    "get_reader", "list_readers",
    "resolve_reader", "sniff_format", "rank_shard_procs", "PlanHints",
    "ByteSpan", "ProcSpan", "RowSpan", "even_edges", "even_groups",
]


@dataclass(frozen=True)
class PlanHints:
    """Pushdown hints a query plan hands to a chunked reader.

    Every field is advisory: a reader may drop rows/chunks that provably
    cannot satisfy the hints (cheaper than parsing then masking), or ignore
    any hint entirely — the streaming executor re-applies the full fused
    mask per chunk, so correctness never depends on reader cooperation.

    * ``procs`` — explicit set of process ids the plan restricts to;
    * ``proc_bounds`` — inclusive ``[lo, hi]`` bound on process ids;
    * ``time_window`` — inclusive ``[t0, t1]`` ns window such that every
      surviving row's own timestamp lies inside (only emitted for
      ``trim="within"`` windows — overlap windows extend past row
      timestamps and are never pushed down).
    """

    procs: Optional[frozenset] = None
    proc_bounds: Optional[Tuple[float, float]] = None
    time_window: Optional[Tuple[float, float]] = None

    def admits_proc(self, p: int) -> bool:
        if self.procs is not None and p not in self.procs:
            return False
        if self.proc_bounds is not None and not (
                self.proc_bounds[0] <= p <= self.proc_bounds[1]):
            return False
        return True


# ---------------------------------------------------------------------------
# parallel work units
# ---------------------------------------------------------------------------

def even_edges(lo: int, hi: int, n: int) -> List[int]:
    """n+1 monotone edges splitting [lo, hi) into ~equal integer spans —
    the one place the byte-range partition arithmetic lives (unit planners
    must not drift apart on span ownership)."""
    return [lo + (hi - lo) * i // n for i in range(n + 1)]


def even_groups(seq: Sequence, n: int) -> List[Tuple]:
    """Split ``seq`` into up to ``n`` contiguous non-empty tuples of ~equal
    length, preserving order — the shared group-partition arithmetic of the
    ProcSpan unit planners."""
    seq = list(seq)
    out = []
    for k in range(n):
        part = tuple(seq[len(seq) * k // n: len(seq) * (k + 1) // n])
        if part:
            out.append(part)
    return out


@dataclass(frozen=True)
class ByteSpan:
    """One byte range of a line/record-oriented trace file — a parallel work
    unit whose reader starts at the first record boundary at or after ``lo``
    and stops at the first boundary at or after ``hi``.  Spans planned over
    one file partition its records exactly: every record belongs to the span
    containing its first byte."""

    path: str
    lo: int
    hi: int


@dataclass(frozen=True)
class RowSpan:
    """One row range ``[lo, hi)`` of a random-access columnar trace file — a
    parallel work unit for formats whose footer index records exact row
    offsets (pipitpack).  Unlike :class:`ByteSpan` no boundary alignment is
    needed: the reader slices rows directly, so spans planned over one file
    partition its rows exactly by construction."""

    path: str
    lo: int
    hi: int


@dataclass(frozen=True)
class ProcSpan:
    """One process-subset work unit of a trace file: the rows of ``procs``
    only.  The executor *enforces* the subset with an explicit per-chunk
    mask (reader hints stay advisory) — spans over disjoint process sets
    therefore partition the rows exactly.  ``extra`` carries reader-specific
    keyword items (e.g. a pre-passed pid table) as a tuple of pairs."""

    path: str
    procs: Tuple[int, ...]
    extra: Tuple = ()


# ---------------------------------------------------------------------------
# op registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OpSpec:
    """A registered analysis operation.

    ``scope`` declares the op's input shape: a ``"trace"`` op is
    ``fn(trace, *args, **kwargs)`` and terminates a single-trace
    :class:`~repro.core.query.TraceQuery`; a ``"set"`` op is
    ``fn(traces, *args, **kwargs)`` over a sequence of traces and terminates
    a :class:`~repro.core.diff.TraceSet` query.  Either way ``fn`` runs with
    the declared prerequisites already materialized (on every member trace
    for set-scoped ops).
    """

    name: str
    fn: Callable[..., Any]
    needs_structure: bool = False
    needs_messages: bool = False
    scope: str = "trace"
    #: factory building a streaming aggregator (see
    #: :mod:`repro.core.streaming`) for out-of-core execution, or None when
    #: the op has no combinable partial-aggregate form and must run on a
    #: fully materialized trace.
    streaming: Optional[Callable[..., Any]] = None
    #: True when the streaming aggregator also declares a cross-worker merge
    #: (``supports_parallel`` + ``merge_from`` on the aggregator class) —
    #: the parallel executor (:mod:`repro.core.executor`) fans such ops over
    #: a process pool; others degrade to serial streaming with a warning.
    parallel_safe: bool = False

    @property
    def backends(self) -> Tuple[str, ...]:
        """Names of this op's registered execution backends (sorted).

        Empty for ops without a ``backend=`` kwarg; ops that accept one
        always register at least ``"numpy"`` (the exact reference
        implementation) and usually ``"pallas"`` (the accelerator kernel,
        interpret-mode on CPU).  See :func:`register_backend`.
        """
        return tuple(list_backends(self.name))


_OP_REGISTRY: Dict[str, OpSpec] = {}

#: per-op backend tables: ``_BACKENDS[op][backend_name] -> callable``.  The
#: callable's contract is op-specific (documented on each op) — what the
#: registry guarantees is uniform *resolution*: every op with a ``backend=``
#: kwarg looks its argument up here and fails loudly listing the options.
_BACKENDS: Dict[str, Dict[str, Callable[..., Any]]] = {}


def op_backends(op_name: str) -> Dict[str, Callable[..., Any]]:
    """The live backend table of ``op_name`` (created on first use).

    Mutating the returned dict *is* the registration mechanism —
    :func:`register_backend` writes into it, and deleting a key
    unregisters the backend.
    """
    return _BACKENDS.setdefault(op_name, {})


def register_backend(op_name: str, backend: str) -> Callable:
    """Decorator registering an execution backend for ``op_name``.

    Ops resolve their ``backend=`` kwarg through :func:`get_backend`;
    last registration wins, like the op registry itself::

        @register_backend("flat_profile", "my_accel")
        def _my_flat_profile(trace, *, metrics, groupby_column, per_process):
            ...

    The callable's signature is the op's own contract: trace-level ops
    take ``(trace, **op_kwargs)``; ``time_profile`` keeps its historical
    record-level contract ``fn(starts, ends, rate, name_codes, edges, nf)``
    (see docs/kernels.md).
    """

    def deco(fn: Callable) -> Callable:
        op_backends(op_name)[backend] = fn
        return fn

    return deco


def get_backend(op_name: str, backend: str) -> Callable[..., Any]:
    """Resolve ``backend`` for ``op_name`` or raise ValueError listing the
    registered names — the one lookup every ``backend=`` kwarg goes
    through (eager ops, streaming finalizers, and the serving layer)."""
    table = _BACKENDS.get(op_name)
    fn = table.get(backend) if table else None
    if fn is None:
        raise ValueError(
            f"unknown {op_name} backend {backend!r}; registered: "
            f"{sorted(table) if table else []}")
    return fn


def list_backends(op_name: str) -> List[str]:
    """Sorted backend names registered for ``op_name`` (empty when the op
    has no backend table)."""
    return sorted(_BACKENDS.get(op_name, ()))


def register_op(name: Optional[str] = None, *, needs_structure: bool = False,
                needs_messages: bool = False, scope: str = "trace") -> Callable:
    """Decorator registering an analysis op usable from ``TraceQuery``
    (``scope="trace"``, the default) or ``TraceSet`` (``scope="set"``).

    Re-registering a name overwrites the previous spec (last one wins), so
    user code can shadow a built-in analysis.
    """
    if scope not in ("trace", "set"):
        raise ValueError(f'scope must be "trace" or "set", got {scope!r}')

    def deco(fn: Callable) -> Callable:
        op_name = name or fn.__name__
        _OP_REGISTRY[op_name] = OpSpec(op_name, fn, needs_structure,
                                       needs_messages, scope)
        return fn

    return deco


def register_streaming(op_name: str) -> Callable:
    """Decorator declaring ``op_name``'s streaming (combinable) form.

    The decorated callable is an *aggregator factory*: called with the op's
    own ``(*args, **kwargs)`` it returns a streaming aggregator (see
    :class:`repro.core.streaming.StreamAgg`) whose mergeable partial results
    reproduce the in-memory op.  Ops without a registered factory raise a
    clear error under out-of-core execution instead of silently
    materializing the whole trace.

    Parallel safety is declared on the aggregator itself: a factory (class)
    carrying ``supports_parallel = True`` and a ``merge_from(other,
    code_map)`` method marks the op safe for multi-core execution, and the
    registry records that in :attr:`OpSpec.parallel_safe`.
    """

    def deco(factory: Callable) -> Callable:
        spec = _OP_REGISTRY.get(op_name)
        if spec is None:
            raise ValueError(
                f"cannot declare streaming form of unregistered op "
                f"{op_name!r}; register the op first")
        par = bool(getattr(factory, "supports_parallel", False)
                   and getattr(factory, "merge_from", None) is not None)
        _OP_REGISTRY[op_name] = replace(spec, streaming=factory,
                                        parallel_safe=par)
        return factory

    return deco


def get_op(name: str) -> Optional[OpSpec]:
    return _OP_REGISTRY.get(name)


def list_ops() -> List[str]:
    return sorted(_OP_REGISTRY)


def terminal_op(name: str, run: Callable[..., Any], owner: str) -> Callable:
    """Resolve ``name`` as a registered-op terminal bound to ``run`` — the
    shared ``__getattr__`` dispatch of TraceQuery, SetQuery and TraceSet.

    Raises AttributeError for dunder/private names and unknown ops so
    ``getattr``/``hasattr`` semantics stay intact on the owning object.
    """
    if name.startswith("_"):
        raise AttributeError(name)
    spec = get_op(name)
    if spec is None:
        raise AttributeError(
            f"{name!r} is neither a {owner} method nor a registered "
            f"analysis op (see repro.core.registry.list_ops())")

    def terminal(*args: Any, **kwargs: Any) -> Any:
        return run(name, *args, **kwargs)

    terminal.__name__ = name
    terminal.__qualname__ = f"{owner}.{name}"
    terminal.__doc__ = spec.fn.__doc__
    return terminal


# ---------------------------------------------------------------------------
# reader registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ReaderSpec:
    """A registered trace-format reader.

    ``read(path, **kw)`` must return a Trace.  ``sniff(path, head)`` gets the
    path and the first few KB of file text and returns True when the content
    is this format.  ``shard_procs(path)`` optionally returns the set of
    process ids a shard file contains (or None when unknown) — the parallel
    driver uses it to skip shards a process-restricted plan cannot need.

    ``iter_chunks(path, chunk_rows, hints)`` optionally yields successive
    EventFrames of at most ``chunk_rows`` events each without ever holding
    the whole trace — the out-of-core streaming executor
    (:mod:`repro.core.streaming`) drives it.  ``hints`` is a
    :class:`PlanHints` carrying the plan's predicate/process/time-window
    pushdown; applying it is optional (the executor re-masks every chunk).
    Formats without a chunked reader fall back to a whole-file read sliced
    into chunks (correct, but with no memory win).

    ``plan_units(path, n_units)`` optionally splits one file into up to
    ``n_units`` independent parallel work units (:class:`ByteSpan` byte
    ranges for line-oriented formats, :class:`ProcSpan` process subsets
    otherwise) for the multi-core executor (:mod:`repro.core.executor`);
    returning None (or a single unit) means the file cannot be split and is
    processed whole.
    """

    name: str
    read: Callable[..., Any]
    extensions: Tuple[str, ...] = ()
    sniff: Optional[Callable[[str, str], bool]] = None
    shard_procs: Optional[Callable[[str], Optional[Set[int]]]] = None
    priority: int = 0  # higher sniffs first
    iter_chunks: Optional[Callable[..., Iterator[Any]]] = None
    plan_units: Optional[Callable[[str, int], Optional[List[Any]]]] = None


_READER_REGISTRY: Dict[str, ReaderSpec] = {}


def register_reader(name: str, *, extensions: Sequence[str] = (),
                    sniff: Optional[Callable[[str, str], bool]] = None,
                    shard_procs: Optional[Callable[[str], Optional[Set[int]]]] = None,
                    priority: int = 0,
                    iter_chunks: Optional[Callable[..., Iterator[Any]]] = None
                    ) -> Callable:
    """Decorator registering a reader callable under ``name``."""

    def deco(fn: Callable) -> Callable:
        _READER_REGISTRY[name] = ReaderSpec(
            name, fn, tuple(e.lower() for e in extensions), sniff,
            shard_procs, priority, iter_chunks)
        return fn

    return deco


def register_chunked(name: str) -> Callable:
    """Decorator attaching a chunked reader to the already-registered
    format ``name`` (readers usually register ``read`` first, then the
    chunked variant right below it)."""

    def deco(fn: Callable) -> Callable:
        spec = _READER_REGISTRY.get(name)
        if spec is None:
            raise ValueError(
                f"cannot attach chunked reader to unregistered format "
                f"{name!r}; register the reader first")
        _READER_REGISTRY[name] = replace(spec, iter_chunks=fn)
        return fn

    return deco


def register_units(name: str) -> Callable:
    """Decorator attaching a parallel unit planner (``plan_units(path,
    n_units)``) to the already-registered format ``name``."""

    def deco(fn: Callable) -> Callable:
        spec = _READER_REGISTRY.get(name)
        if spec is None:
            raise ValueError(
                f"cannot attach unit planner to unregistered format "
                f"{name!r}; register the reader first")
        _READER_REGISTRY[name] = replace(spec, plan_units=fn)
        return fn

    return deco


def get_reader(name: str) -> ReaderSpec:
    try:
        return _READER_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown trace format {name!r}; registered: {list_readers()}"
        ) from None


def list_readers() -> List[str]:
    return sorted(_READER_REGISTRY)


def _read_head(path: str, nbytes: int = 8192) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return f.read(nbytes)
    except (OSError, IsADirectoryError):
        return ""


def sniff_format(path) -> Optional[str]:
    """Guess the registered format of ``path`` from its name and content."""
    path = os.fspath(path)
    specs = sorted(_READER_REGISTRY.values(), key=lambda s: -s.priority)
    if os.path.isdir(path):
        for spec in specs:
            if spec.sniff and spec.sniff(path, ""):
                return spec.name
        return None
    low = path.lower()
    ext_hit = [s for s in specs if any(low.endswith(e) for e in s.extensions)]
    head = _read_head(path)
    # content sniff wins over extension: ".json" is shared by three formats
    for spec in specs:
        if spec.sniff and spec.sniff(path, head):
            return spec.name
    # the extension is only trusted for formats without a content sniffer: a
    # sniffer that just *rejected* this content knows better than the file
    # name, and handing the path to its reader anyway ends in a bare KeyError
    # deep inside the parse
    for spec in ext_hit:
        if spec.sniff is None:
            return spec.name
    return None


def _describe_readers() -> str:
    """One line per registered format: extensions and content sniffer."""
    parts = []
    for name in list_readers():
        spec = _READER_REGISTRY[name]
        ext = "/".join(spec.extensions) if spec.extensions else "any"
        sniffer = spec.sniff.__name__ if spec.sniff else "extension only"
        parts.append(f"{name} (extensions: {ext}; sniffer: {sniffer})")
    return ", ".join(parts)


_RANK_RE = re.compile(r"^rank[_\-.](\d+)\.")


def rank_shard_procs(path: str) -> Optional[Set[int]]:
    """Default shard hint: per-location shard files named ``rank_<p>.*``
    (the layout split_jsonl_by_process writes) contain exactly one process.
    Anchored to the whole stem — a file merely *containing* "rank" (e.g.
    ``lowrank_2.csv``) gets no hint and is never skipped."""
    m = _RANK_RE.match(os.path.basename(path))
    return {int(m.group(1))} if m else None


def resolve_reader(path, format: str = "auto") -> ReaderSpec:
    """Resolve ``format`` (or sniff when "auto") to a ReaderSpec.

    ``path`` may be anything os.fspath accepts (str, pathlib.Path, ...).
    """
    if format and format != "auto":
        return get_reader(format)
    name = sniff_format(path)
    if name is None:
        try:
            size = (None if os.path.isdir(path)
                    else os.path.getsize(os.fspath(path)))
        except OSError:
            size = None
        if size == 0:
            from .errors import TraceReadError
            raise TraceReadError(
                os.fspath(path),
                f"empty file (0 bytes) — cannot determine trace format. "
                f"Sniffers tried: {_describe_readers()}")
        raise ValueError(
            f"cannot determine trace format of {path!r}: no registered "
            f"sniffer recognized the content.  Registered formats: "
            f"{_describe_readers()}.  Pass format=<name> to force one.")
    return get_reader(name)
