"""Closed-loop whole-trace scans: ``clients`` callers, each waiting for its
answer before it sends the next, cycling through the mix's ops in order.

A caller's job is one pass through the mix's ops.  It starts jobs until
``seconds`` have passed and lets the job in flight finish, so every window
holds whole jobs, each with every op once: the ops take different times,
and a window that ended on whichever op was running would weigh them
differently from run to run.  ``scan_events_per_s`` is every event of
every completed scan over the time from the first scan's start to the last
one's end.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

from ..service import Request


def _rotation(traffic: Dict) -> List[Request]:
    return [Request(op, dict(kw)) for op, kw in traffic["ops"]]


def warm(served, traffic: Dict, plan) -> None:
    """One scan of each op: compiles every program the window runs."""
    with served.client() as c:
        for req in _rotation(traffic):
            served.call(c, req, traffic)
            if not req.ok:
                raise RuntimeError(f"warm-up {req.op} failed: {req.error}")


def plan(traffic: Dict, truth, seed: int, seconds: float):
    return None


def measure(served, traffic: Dict, plan, seconds: float) -> List[Request]:
    done: List[Request] = []
    lock = threading.Lock()
    t_end = time.perf_counter() + seconds

    def caller():
        ops = traffic["ops"]
        with served.client() as c:
            while time.perf_counter() < t_end:
                for op, kw in ops:
                    req = Request(op, dict(kw), due=time.perf_counter())
                    served.call(c, req, traffic)
                    with lock:
                        done.append(req)

    threads = [threading.Thread(target=caller, name=f"bench-scan-{i}")
               for i in range(int(traffic.get("clients", 1)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return done


def end_to_end(reqs: List[Request], truth) -> Dict[str, float]:
    ok = [r for r in reqs if r.ok]
    if not ok:
        return {}
    span = max(r.done for r in ok) - min(r.sent for r in reqs)
    return {"scan_events_per_s": truth.n_events * len(ok) / span}
