"""Drives one Pod with closed-loop query streams.

One thread ticks the Pod (`TickLoop.run`).  Each stream is a thread that
runs the program's query functions against a `PodClient`, whose `.scan`
submits under the loop's lock and waits for its ticket, so the scans of
all streams sit in the Pod's queue together and the scheduler can stack
them.  A query's latency runs from its issue to the return of its answer
on the host.

The host spans that name what the host was doing in the profiler's trace
are put around the calls into each layer here, in the benchmark's own
code: `Pod.tick`, `Pod.submit`, `scan.wait` and `query.<name>`.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from bench import traffic


@dataclasses.dataclass
class Record:
    stream: int
    name: str
    params: Dict[str, int]
    t_issue: float
    t_done: float = 0.0
    answer: object = None
    error: Optional[BaseException] = None


def build_pod(cfg: dict):
    """The Pod a configuration file describes."""
    from repro.core.cache import BlockCache
    from repro.core.engine import DatapathEngine
    from repro.datapath.policy import AdaptiveOffloadPolicy, StaticPolicy
    from repro.datapath.service import Pod

    p = cfg["pod"]
    engine = DatapathEngine(backend=p["backend"],
                            cache=BlockCache(capacity_bytes=p["store_bytes"]))
    pol = p["policy"]
    policy = StaticPolicy(pol["mode"]) if pol["kind"] == "static" else AdaptiveOffloadPolicy()
    return Pod(engine=engine, policy=policy, **p.get("settings", {}))


class TickLoop:
    """Ticks the Pod whenever its queue holds work.  Also keeps running
    totals of the decode work the ticks did, from the ScanStats of the
    requests in the queue before and after each tick."""

    def __init__(self, pod):
        self.pod = pod
        self.cv = threading.Condition()
        self.closing = False
        self.error: Optional[BaseException] = None
        self.encoded_bytes = 0
        self.decode_work: Dict[str, int] = {}

    # -- client side -----------------------------------------------------
    def scan(self, tenant: str, reader, plan, blooms=None):
        with self.cv:
            with TraceAnnotation("Pod.submit"):
                ticket = self.pod.submit(tenant, reader, plan, blooms)
            self.cv.notify_all()
            with TraceAnnotation("scan.wait"):
                while ticket.status == "queued" and self.error is None:
                    self.cv.wait()
        if ticket.status == "queued":
            raise RuntimeError("the tick loop stopped") from self.error
        if ticket.status == "error":
            raise ticket.error
        return ticket.result

    # -- the loop --------------------------------------------------------
    def run(self) -> None:
        with self.cv:
            while True:
                while not self.pod.queue and not self.closing:
                    self.cv.wait()
                if not self.pod.queue:
                    return
                try:
                    self._tick()
                except BaseException as e:  # noqa: BLE001 — handed to every waiting scan
                    self.error = e
                    return
                finally:
                    self.cv.notify_all()

    def _tick(self) -> None:
        before = [(r, _work(r)) for r in self.pod.queue]
        with TraceAnnotation("Pod.tick"):
            self.pod.tick()
        for r, (enc0, work0) in before:
            enc1, work1 = _work(r)
            self.encoded_bytes += enc1 - enc0
            for e, b in work1.items():
                if b != work0.get(e, 0):
                    self.decode_work[e] = self.decode_work.get(e, 0) + b - work0.get(e, 0)

    def close(self) -> None:
        with self.cv:
            self.closing = True
            self.cv.notify_all()

    def counters(self) -> dict:
        """Running totals of the program's counters; call under `cv`."""
        from repro.kernels import ops

        tel = self.pod.telemetry.counters
        tiers = self.pod.store.stats()["tiers"]
        return {
            "xreq_groups": tel.get("xreq_groups", 0.0),
            "xreq_requests": tel.get("xreq_requests", 0.0),
            "store_hits": sum(tiers[t]["hits"] for t in ("decoded", "prefiltered")),
            "store_misses": sum(tiers[t]["misses"] for t in ("decoded", "prefiltered")),
            "dispatches": ops.dispatch_count(),
            "encoded_bytes": self.encoded_bytes,
            "decode_work": dict(self.decode_work),
        }


def _work(req):
    rs = req.rs
    if rs is None:
        return 0, {}
    return rs.stats.encoded_bytes, dict(rs.stats.decode_work)


def delta(a: dict, b: dict) -> dict:
    """b - a for two `counters()` readings."""
    out = {k: b[k] - a[k] for k in b if k != "decode_work"}
    out["decode_work"] = {e: v - a["decode_work"].get(e, 0)
                          for e, v in b["decode_work"].items()}
    return out


class PodClient:
    """Engine-compatible client (`.scan(reader, plan, blooms)`) of one
    tenant, for the program's query functions."""

    def __init__(self, loop: TickLoop, tenant: str):
        self.loop = loop
        self.tenant = tenant

    def scan(self, reader, plan, blooms=None):
        return self.loop.scan(self.tenant, reader, plan, blooms)


class Streams:
    """The mix's streams, each a thread in a closed loop."""

    def __init__(self, loop: TickLoop, mix: dict, seed: int, readers: dict,
                 queries: Dict[str, Callable]):
        self.loop = loop
        self.mix = mix
        self.readers = readers
        self.queries = queries
        self.records: List[Record] = []
        self.done = [0] * mix["streams"]
        self.current: List[Optional[Record]] = [None] * mix["streams"]
        self.cv = threading.Condition()
        self.stop = False
        self.abandoned = False
        self.threads = [
            threading.Thread(target=self._stream, args=(k, traffic.stream(mix, seed, k)),
                             name=f"stream{k}", daemon=True)
            for k in range(mix["streams"])]

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def _stream(self, k: int, seq) -> None:
        client = PodClient(self.loop, f"stream{k}")
        for name, params in seq:
            if self.stop:
                return
            rec = self.current[k] = Record(k, name, params, time.perf_counter())
            answer = error = None
            try:
                with TraceAnnotation(f"query.{name}"):
                    answer = self.queries[name](client, self.readers, **params)
            except Exception as e:  # noqa: BLE001 — a failed query is a result
                error = e
            t_done = time.perf_counter()
            with self.cv:
                if self.abandoned:
                    return
                rec.answer, rec.error, rec.t_done = answer, error, t_done
                self.records.append(rec)
                self.done[k] += 1
                self.cv.notify_all()

    def wait_passes(self, passes: int, timeout: float) -> bool:
        """Block until every stream has finished `passes` passes."""
        n = passes * len(self.mix["queries"])
        with self.cv:
            return self.cv.wait_for(lambda: min(self.done) >= n, timeout)

    def finish(self, timeout: float) -> None:
        """Stop issuing and wait for the queries in flight; one that has not
        come back by the timeout is recorded as unanswered."""
        self.stop = True
        end = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(0.0, end - time.perf_counter()))
        with self.cv:
            self.abandoned = True
            for t, rec in zip(self.threads, self.current):
                if t.is_alive():
                    rec.t_done = math.inf
                    rec.error = TimeoutError(f"no answer {timeout} s after the window closed")
                    self.records.append(rec)
