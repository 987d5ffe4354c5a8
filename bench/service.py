"""The system under test in this process: the program's ``TraceServer``
on a loopback port, driven by the program's ``ServiceClient``.

Only this process touches JAX, so the chip has one owner.  The server runs
on an event loop in a thread of its own; every request goes over HTTP and
passes ``backend="pallas"``, with the lane, the plan cache and streaming
as the traffic file sets them.
"""

from __future__ import annotations

import asyncio
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional


@dataclass
class Request:
    """One request of a traffic plan and what became of it (host clock,
    ``time.perf_counter`` seconds)."""

    op: str
    kwargs: Dict[str, Any]
    tenant: Optional[str] = None
    due: float = 0.0
    sent: float = 0.0
    done: float = 0.0
    result: Any = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.done > 0


class Served:
    """Start/stop the in-process service; issue requests against it."""

    def __init__(self, paths: List[str], service_cfg: Dict, probes):
        self.paths = [str(p) for p in paths]
        self.cfg = service_cfg
        self.probes = probes
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.thread: Optional[threading.Thread] = None
        self.server = None
        self.scheduler = None
        self.service = None

    def start(self) -> "Served":
        from repro.core.scheduler import Scheduler
        from repro.serving.tracequery import TraceServer, TraceService

        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       name="bench-server", daemon=True)
        self.thread.start()
        self.scheduler = Scheduler(
            workers=int(self.cfg["workers"]),
            interactive_workers=int(self.cfg["interactive_workers"]))

        async def boot():
            svc = TraceService(scheduler=self.scheduler,
                               per_tenant=int(self.cfg["per_tenant"]),
                               max_active=int(self.cfg.get("max_active", 64)))
            self.probes.wrap_handles(svc.handles)
            self.service = svc
            return await TraceServer(svc, port=0).start()

        self.server = asyncio.run_coroutine_threadsafe(
            boot(), self.loop).result(timeout=60)
        return self

    def stop(self) -> None:
        if self.server is not None:
            asyncio.run_coroutine_threadsafe(
                self.server.shutdown(grace=30), self.loop).result(timeout=90)
            self.server = None
        if self.loop is not None:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(timeout=30)
            self.loop.close()
            self.loop = None
        if self.scheduler is not None:
            self.scheduler.shutdown(wait=True)
            self.scheduler = None

    def client(self, tenant: Optional[str] = None):
        from repro.serving.client import ServiceClient
        return ServiceClient("127.0.0.1", self.server.port, tenant=tenant,
                             timeout=600, retries=0)

    def stats(self) -> Dict[str, Any]:
        """The service's ``/stats`` counters, read in this process."""
        return self.service.stats() if self.service is not None else {}

    def call(self, client, req: Request, traffic: Dict) -> Request:
        """Issue ``req`` as the traffic file says (``lane``, ``cache``,
        ``streaming``) and wait for its answer; fills ``sent``, ``done`` and
        ``result`` or ``error``."""
        from repro.serving.client import RemoteError
        q = client.open(self.paths,
                        streaming=bool(traffic["streaming"])).query()
        req.sent = time.perf_counter()
        with self.probes.span("request:" + req.op):
            try:
                req.result = q.run(req.op, backend="pallas",
                                   cache=bool(traffic["cache"]),
                                   lane=traffic["lane"], **req.kwargs)
            except RemoteError as e:
                req.error = f"{e.status} {e.code}: {e}"
            except OSError as e:
                req.error = f"transport: {e}"
        req.done = time.perf_counter()
        return req
