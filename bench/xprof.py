"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time over the traced window, device
time per jit program, the device operations that took most time, and the
longest idle gaps, each named by the benchmark span the host had open.

The window is the host event ``bench:window`` that the harness opens around
its measured window.  Device planes are ``/device:TPU:<n>``; on each, the
``XLA Modules`` line holds one event per program execution (``XLA Ops``
when there is no module line).  Busy time is the union of those events
inside the window, averaged over the device planes.  The device timeline
is placed on the host's clock to about a millisecond (a v5e trace showed
its programs 1.2 ms before the host spans that launched them), so device
events are taken ``SLACK_NS`` either side of the window.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
SLACK_NS = 10_000_000


def xplane_path(logdir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return found[-1] if found else None


def _merge(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


class Profile:
    """One reduced trace.  Times are seconds; ``None`` where the trace holds
    no device plane (a run that never reached a TPU)."""

    def __init__(self, planes):
        self.spans: List[Tuple[str, int, int]] = []
        self.devices: Dict[str, Dict[str, list]] = {}
        lo_all, hi_all = None, None
        for plane in planes:
            name = plane.name
            if _DEVICE.match(name):
                lines = {ln.name: ln for ln in plane.lines}
                line = lines.get("XLA Modules") or lines.get("XLA Ops")
                mods, ops = [], []
                if line is not None:
                    mods = [(_SUFFIX.sub("", e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events]
                if "XLA Ops" in lines:
                    ops = [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in lines["XLA Ops"].events]
                self.devices[name] = {"modules": mods, "ops": ops or mods}
            elif name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        a = int(e.start_ns)
                        b = int(e.start_ns + e.duration_ns)
                        lo_all = a if lo_all is None else min(lo_all, a)
                        hi_all = b if hi_all is None else max(hi_all, b)
                        if e.name.startswith("bench:"):
                            self.spans.append((e.name[6:], a, b))
        win = [(a, b) for n, a, b in self.spans if n == "window"]
        if win:
            self.window = win[0]
        else:
            self.window = (lo_all or 0, hi_all or 0)
        lo, hi = self.window[0] - SLACK_NS, self.window[1] + SLACK_NS
        self._dev_window = (lo, hi)
        self._busy = {d: _merge(_clip([(a, b) for _, a, b in v["modules"]],
                                      lo, hi))
                      for d, v in self.devices.items()}

    # -- whole window ------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> Optional[float]:
        if not self.devices:
            return None
        tot = [sum(b - a for a, b in iv) for iv in self._busy.values()]
        return sum(tot) / len(tot) / 1e9

    def idle_pct(self) -> Optional[float]:
        busy = self.busy_s
        if busy is None or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - busy / self.window_s)

    # -- programs and operations ------------------------------------------
    def program_s(self, prefix: str) -> float:
        """Device seconds of the programs whose name starts with
        ``prefix`` (e.g. ``jit_segment_sum_matrix``), inside the window,
        summed over the device planes."""
        lo, hi = self._dev_window
        return sum(max(0, min(b, hi) - max(a, lo))
                   for v in self.devices.values()
                   for n, a, b in v["modules"] if n.startswith(prefix)) / 1e9

    def device_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operations with the most device time, each named
        ``<program>/<operation>`` (the HLO name before its ``=``)."""
        lo, hi = self._dev_window
        tot: Dict[str, int] = defaultdict(int)
        for v in self.devices.values():
            mods = sorted(v["modules"], key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for n, a, b in v["ops"]:
                i = bisect.bisect_right(starts, a) - 1
                prog = mods[i][0] if i >= 0 and a < mods[i][2] else "?"
                op = n.split(" = ", 1)[0].lstrip("%")
                tot[f"{prog}/{op}"] += max(0, min(b, hi) - max(a, lo))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in top if t > 0]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the first device, each named by
        the innermost benchmark span open at its middle."""
        if not self._busy:
            return []
        lo, hi = self.window
        busy = _clip(next(iter(self._busy.values())), lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) // 2
            open_ = [(s1 - s0, n) for n, s0, s1 in self.spans
                     if s0 <= mid <= s1 and n != "window"]
            label = min(open_)[1] if open_ else "no benchmark span"
            out.append([label, (b - a) / 1e9])
        return out


def load(path: str) -> Profile:
    from jax.profiler import ProfileData
    return Profile(ProfileData.from_file(path).planes)
