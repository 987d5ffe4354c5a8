"""What the plain reference reads: the calls and messages of the whole
trace, from the generator's ground truth (:class:`bench.gen.tracegen.Truth`)
as the generator drew them, and none of the program: no reader, no
structure matching, no streaming engine, no backend.

Each op of ``bench/ops`` computes its reference from a :class:`Selection`,
with exact sums: int64 where the values are integers, float64 elsewhere.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

EXC, INC = "time.exc", "time.inc"

# communication names, as the program's stragglers detector classifies them
_COMM_PREFIXES = ("MPI_", "mpi_", "nccl", "Nccl", "all-gather", "all-reduce",
                  "reduce-scatter", "all-to-all", "collective-permute",
                  "send", "recv", "Isend", "Irecv")
_COMM_SUBSTRINGS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "nccl", "send",
                    "recv")
_IDLE_NAMES = ("MPI_Wait", "MPI_Waitall", "MPI_Recv", "Idle", "MPI_Barrier")


def is_comm(name: str) -> bool:
    low = name.lower()
    return (name.startswith(_COMM_PREFIXES)
            or any(t in low for t in _COMM_SUBSTRINGS) or name in _IDLE_NAMES)


@dataclass
class Selection:
    """The calls and messages a whole-trace plan sees."""

    names: np.ndarray
    nprocs: int
    name: np.ndarray       # code of every call
    proc: np.ndarray
    start: np.ndarray      # int64
    end: np.ndarray        # int64
    exc: np.ndarray        # int64
    m_proc: np.ndarray
    m_partner: np.ndarray
    m_size: np.ndarray
    t_first: int           # earliest row
    t_last: int            # latest row
    rank_first: np.ndarray
    rank_last: np.ndarray


def select(truth) -> Selection:
    t = truth
    return Selection(
        names=t.names, nprocs=t.ranks, name=t.call_name, proc=t.call_proc,
        start=t.call_start, end=t.call_end, exc=t.call_exc,
        m_proc=t.msg_proc, m_partner=t.msg_partner, m_size=t.msg_size,
        t_first=int(t.rank_first.min()), t_last=int(t.rank_last.max()),
        rank_first=t.rank_first, rank_last=t.rank_last)


def metric(s: Selection, name: str) -> np.ndarray:
    return s.exc if name == EXC else s.end - s.start


def name_rank_totals(s: Selection, name: str = EXC) -> np.ndarray:
    """Per (name, rank) sums of a metric, float64."""
    nf = len(s.names)
    tot = np.zeros(nf * s.nprocs)
    np.add.at(tot, s.name * s.nprocs + s.proc,
              metric(s, name).astype(np.float64))
    return tot.reshape(nf, s.nprocs)
