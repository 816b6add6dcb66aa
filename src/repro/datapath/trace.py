"""Datapath tracing: one span API for every layer of the served path,
and the per-request flight recorder.

`span(name, **counts)` is the one way a layer marks its work, from
`Pod.submit` down to the kernel API:

    with trace.span("engine.storage_read") as sp:
        pages = reader.read_encoded(rg, columns)
        if sp is not None:
            sp.set(pages=len(pages), bytes=nbytes)

It makes one check: is the JAX profiler collecting
(`TraceAnnotation.is_enabled()`), or is a flight-recorder slice live
(`_CUR`)?  When neither, it returns a shared null context whose `as`
value is None, so a call site builds no counts and reads no clock.  When
the profiler collects, the span

  - enters a `jax.profiler.TraceAnnotation`, so it lands in the trace's
    host plane on the same clock as the device's `XLA Ops` line, with its
    counts (and the request id of the slice, `req`) as metadata;
  - appends (name, thread, t0_ns, t1_ns, counts) to `LOG`, a bounded
    in-memory log of the latest profiler session, which per-layer metrics
    read.  Its times are `time.perf_counter_ns()`, and it counts the spans
    it drops when full.

When a recorder slice is live the span is also a node of that request's
span tree.  Span names are `<layer>.<phase>`: `pod.*` (service front),
`sched.*` (scheduler), `engine.*` (scan engine) and `ops.dispatch` (one
counted launch of the kernel API).

The flight recorder keeps a span tree per admitted request (subject to
`sample_rate`) —

    request                     submit() -> terminal ticket status
      admission                 metadata-only estimate + quota checks
      wfq_wait | hold_window    queued ticks, by WHY the request waited
      slice_dispatch            one per scheduler slice (run_tick)
        <layer spans>           engine.storage_read, engine.decode, ...
        store_hit / evict / sim_fetch   zero-duration instant events

— and the completed trees live in a bounded ring (`FlightRecorder`,
last-N requests, fixed memory).  Exporters: Chrome/Perfetto
`trace_event` JSON (one pid per tenant, one tid per request) and a
deterministic stage-attribution report in seconds per stage.

All recorder times are HOST time (`time.perf_counter`).  Kernel launches
are asynchronous on an accelerator, so a stage's seconds are the host
work of that stage plus whatever device wait happens to fall in it (a
launch span times the enqueue, not the kernel); device time comes from
the profiler's trace.  Tracing must never perturb results: bit-identity
of scan output with the recorder and the profiler on or off is
property-tested in tests/test_trace_props.py.
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

# span name -> attribution stage.  Children of a mapped span are NOT
# recursed into (a store_hit inside a storage read must not double-bill),
# so stage seconds over one trace can never exceed the root wall time.
STAGE_OF = {
    "admission": "admission",
    "hold_window": "hold_window",
    "wfq_wait": "wfq_wait",
    "engine.storage_read": "fetch",
    "engine.decode": "decode",
    "engine.mask": "filter",
    "engine.bloom": "filter",
    "engine.compact": "filter",
    "sched.reconcile": "reconcile",
}
STAGES = ("admission", "hold_window", "wfq_wait", "fetch", "decode",
          "filter", "reconcile")


def _node(name: str, t0: float, attrs: dict) -> dict:
    return {"name": name, "t0": t0, "t1": None, "args": attrs, "children": []}


class RequestTrace:
    """One request's span tree while in flight.  Spans are plain dicts
    (name/t0/t1/args/children); `stack` enforces strict nesting — the
    scheduler and engine call begin/end in stack discipline, and
    `Tracer.finish` force-closes anything an error path left open."""

    __slots__ = ("req_id", "tenant", "table", "status", "root", "stack",
                 "n_spans", "dropped_spans", "drop_depth", "wait_kind",
                 "summary")

    def __init__(self, req_id: int, tenant: str, table: str, t0: float,
                 attrs: dict):
        attrs = dict(attrs)
        attrs.update(req_id=req_id, tenant=tenant, table=table)
        self.req_id = req_id
        self.tenant = tenant
        self.table = table
        self.status = "queued"
        self.root = _node("request", t0, attrs)
        self.stack: List[dict] = [self.root]
        self.n_spans = 1
        self.dropped_spans = 0  # spans refused by the max_spans cap
        self.drop_depth = 0  # open-but-dropped begins awaiting their end
        self.wait_kind: Optional[str] = None  # open wfq_wait / hold_window
        self.summary: Optional[dict] = None  # filled at finish()


class FlightRecorder:
    """Bounded ring of the last `capacity` COMPLETED request traces.
    Always on, fixed memory: an old trace falls off the back, its spans
    garbage-collected with it."""

    def __init__(self, capacity: int = 64):
        self.capacity = max(1, int(capacity))
        self._ring: collections.deque = collections.deque(maxlen=self.capacity)
        self.completed = 0  # total finishes ever, including evicted ones

    def add(self, rt: RequestTrace) -> None:
        self._ring.append(rt)
        self.completed += 1

    def traces(self) -> List[RequestTrace]:
        return list(self._ring)

    # -- stage attribution -------------------------------------------------
    def report(self) -> dict:
        """Deterministic stage-attribution report over the ring: one
        summary per recorded request (ring order), fleet host seconds per
        stage and a per-tenant rollup.  Every dict is key-sorted; values
        are plain floats/ints."""
        traces = list(self._ring)
        stage_s = {s: 0.0 for s in STAGES}
        wall = 0.0
        by_tenant: Dict[str, dict] = {}
        for rt in traces:
            sm = rt.summary or {}
            wall += sm.get("wall_s", 0.0)
            bt = by_tenant.setdefault(
                rt.tenant, {"n": 0, "wall_s": 0.0,
                            "stage_s": {s: 0.0 for s in STAGES}})
            bt["n"] += 1
            bt["wall_s"] += sm.get("wall_s", 0.0)
            for s, v in sm.get("stages_s", {}).items():
                stage_s[s] += v
                bt["stage_s"][s] += v
        for bt in by_tenant.values():
            bt["stage_s"] = dict(sorted(bt["stage_s"].items()))
        return {
            "capacity": self.capacity,
            "completed": self.completed,
            "recorded": len(traces),
            "requests": [rt.summary for rt in traces if rt.summary],
            "wall_s": wall,
            "stage_s": dict(sorted(stage_s.items())),
            "by_tenant": dict(sorted(by_tenant.items())),
        }

    # -- Chrome/Perfetto export --------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Chrome `trace_event` JSON (load in ui.perfetto.dev or
        chrome://tracing): one process per tenant, one thread per request,
        "X" complete events for spans, "i" instants for zero-duration
        events.  Timestamps are microseconds relative to the earliest
        recorded request, so the export is position-independent."""
        traces = list(self._ring)
        events: List[dict] = []
        if not traces:
            return {"displayTimeUnit": "ms", "traceEvents": events}
        base = min(rt.root["t0"] for rt in traces)
        tenants = sorted({rt.tenant for rt in traces})
        pid_of = {t: i + 1 for i, t in enumerate(tenants)}
        for t in tenants:
            events.append({"args": {"name": t}, "name": "process_name",
                           "ph": "M", "pid": pid_of[t], "tid": 0})
        for rt in sorted(traces, key=lambda r: r.req_id):
            pid, tid = pid_of[rt.tenant], rt.req_id
            events.append({"args": {"name": f"req-{rt.req_id}"},
                           "name": "thread_name", "ph": "M",
                           "pid": pid, "tid": tid})
            stack = [rt.root]
            while stack:
                sp = stack.pop()
                stack.extend(reversed(sp["children"]))
                if sp["t1"] is None:
                    continue
                args = {
                    k: (v if isinstance(v, (str, int, float, bool)) else str(v))
                    for k, v in sorted(sp["args"].items())
                }
                ts = (sp["t0"] - base) * 1e6
                dur = (sp["t1"] - sp["t0"]) * 1e6
                if dur <= 0.0:
                    events.append({"args": args, "name": sp["name"],
                                   "ph": "i", "pid": pid, "s": "t",
                                   "tid": tid, "ts": ts})
                else:
                    events.append({"args": args, "dur": dur,
                                   "name": sp["name"], "ph": "X",
                                   "pid": pid, "tid": tid, "ts": ts})
        return {"displayTimeUnit": "ms", "traceEvents": events}

    def save_chrome_trace(self, path: str) -> int:
        """Write the Chrome-trace JSON to `path`; returns event count."""
        doc = self.to_chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f, sort_keys=True)
        return len(doc["traceEvents"])


class Tracer:
    """Per-request span recorder.  `sample_rate` in [0, 1] picks requests
    DETERMINISTICALLY (a fractional accumulator, no RNG — rate 0.5 traces
    every second request, run-to-run stable); `max_spans` bounds one
    request's tree (overflow increments `dropped_spans`, stack discipline
    preserved); completed trees land in `recorder` (bounded ring).  The
    clock is injectable so property tests can drive a counter clock and
    assert exact nesting."""

    def __init__(self, capacity: int = 64, sample_rate: float = 1.0,
                 max_spans: int = 4096, clock=time.perf_counter):
        self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
        self.max_spans = max_spans
        self.clock = clock
        self.recorder = FlightRecorder(capacity)
        self._live: Dict[int, RequestTrace] = {}
        self._acc = 0.0  # deterministic sampling accumulator
        self.sampled = 0
        self.skipped = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self, req_id: int, tenant: str, table: str,
              t0: Optional[float] = None, **attrs) -> Optional[RequestTrace]:
        """Open a request's root span at admission; None when the sampler
        skips this request (all later lookups no-op on None)."""
        self._acc += self.sample_rate
        if self._acc < 1.0:
            self.skipped += 1
            return None
        self._acc -= 1.0
        rt = RequestTrace(req_id, tenant, table,
                          self.clock() if t0 is None else t0, attrs)
        self._live[req_id] = rt
        self.sampled += 1
        return rt

    def live(self, req_id: int) -> Optional[RequestTrace]:
        return self._live.get(req_id)

    def has_live(self) -> bool:
        return bool(self._live)

    def finish(self, req_id: int, status: str, **attrs) -> Optional[RequestTrace]:
        """Close the root span at the request's terminal tick, force-close
        anything an error path left open, compute the stage-attribution
        summary and push the trace into the flight recorder."""
        rt = self._live.pop(req_id, None)
        if rt is None:
            return None
        self.end_wait(rt)
        while len(rt.stack) > 1:  # error paths may leave spans open
            self.end(rt)
        now = self.clock()
        root = rt.root
        root["t1"] = max(now, root["t0"])
        root["args"].update(attrs)
        root["args"]["status"] = status
        rt.status = status
        rt.summary = self._summarize(rt)
        self.recorder.add(rt)
        return rt

    # -- span ops (all take the RequestTrace; None-safe at call sites) -----
    def begin(self, rt: RequestTrace, name: str, **attrs) -> None:
        if rt.n_spans >= self.max_spans:
            rt.dropped_spans += 1
            rt.drop_depth += 1  # the matching end() must not pop a real span
            return
        sp = _node(name, self.clock(), attrs)
        rt.stack[-1]["children"].append(sp)
        rt.stack.append(sp)
        rt.n_spans += 1

    def end(self, rt: RequestTrace, name: Optional[str] = None, **attrs) -> None:
        """Close the innermost open span.  With `name`, pop (and close at
        the same instant) any deeper spans an exception left open until
        that span is closed — keeps the tree well-formed on error paths."""
        if rt.drop_depth > 0:
            rt.drop_depth -= 1
            return
        now = self.clock()
        while len(rt.stack) > 1:
            sp = rt.stack.pop()
            sp["t1"] = max(now, sp["t0"])
            if name is None or sp["name"] == name:
                sp["args"].update(attrs)
                return
        # underflow (unmatched end): ignore rather than corrupt the root

    def event(self, rt: RequestTrace, name: str, **attrs) -> None:
        """Zero-duration instant (store_hit / evict / sim_fetch) attached
        to the innermost open span."""
        if rt.n_spans >= self.max_spans:
            rt.dropped_spans += 1
            return
        now = self.clock()
        sp = _node(name, now, attrs)
        sp["t1"] = now
        rt.stack[-1]["children"].append(sp)
        rt.n_spans += 1

    def add_span(self, rt: RequestTrace, name: str, t0: float, t1: float,
                 **attrs) -> None:
        """Attach an already-closed span (e.g. admission, timed inline)."""
        if rt.n_spans >= self.max_spans:
            rt.dropped_spans += 1
            return
        sp = _node(name, t0, attrs)
        sp["t1"] = max(t1, t0)
        rt.stack[-1]["children"].append(sp)
        rt.n_spans += 1

    # -- wait-state machine (queued time, attributed by WHY) ---------------
    def wait(self, rt: RequestTrace, kind: str, **attrs) -> None:
        """The request is waiting this tick — `kind` is "wfq_wait" or
        "hold_window".  Consecutive same-kind ticks extend the open span
        (its `ticks` arg counts them); a kind switch closes the old span
        and opens the new one."""
        if rt.wait_kind == kind:
            top = rt.stack[-1]
            if top["name"] == kind:
                top["args"]["ticks"] = top["args"].get("ticks", 0) + 1
            return
        self.end_wait(rt)
        self.begin(rt, kind, ticks=1, **attrs)
        rt.wait_kind = kind

    def end_wait(self, rt: RequestTrace) -> None:
        """Close any open wait span — the scheduler calls this right
        before dispatching a slice, so wait time and slice time can never
        overlap (the stage-sum <= wall invariant depends on it)."""
        if rt.wait_kind is not None:
            self.end(rt, name=rt.wait_kind)
            rt.wait_kind = None

    # -- attribution -------------------------------------------------------
    def _summarize(self, rt: RequestTrace) -> dict:
        stages = {s: 0.0 for s in STAGES}

        def walk(sp: dict) -> None:
            stage = STAGE_OF.get(sp["name"])
            if stage is not None and sp["t1"] is not None:
                stages[stage] += sp["t1"] - sp["t0"]
                return  # never double-bill a mapped span's children
            for c in sp["children"]:
                walk(c)

        for c in rt.root["children"]:
            walk(c)
        wall = rt.root["t1"] - rt.root["t0"]
        args = rt.root["args"]
        return {
            "req_id": rt.req_id,
            "tenant": rt.tenant,
            "table": rt.table,
            "status": rt.status,
            "submitted_tick": args.get("submitted_tick", 0),
            "done_tick": args.get("done_tick", 0),
            "mode": args.get("mode", ""),
            "held_ticks": args.get("held_ticks", 0),
            "wall_s": wall,
            "stages_s": dict(sorted(stages.items())),
            "attributed_s": sum(stages.values()),
            "spans": rt.n_spans,
            "dropped_spans": rt.dropped_spans,
        }

    def report(self) -> dict:
        """The recorder's stage-attribution report plus sampler state."""
        out = {
            "enabled": True,
            "sample_rate": self.sample_rate,
            "sampled": self.sampled,
            "skipped": self.skipped,
            "live": len(self._live),
        }
        out.update(self.recorder.report())
        return dict(sorted(out.items()))


# ---------------------------------------------------------------------------
# module-level slice context and the span API
# ---------------------------------------------------------------------------
# The scheduler publishes the slice that is executing — its request id,
# and its recorder trace when the request is sampled — around each slice,
# so engine, store and kernel code need no plumbed-through tracer.  A Pod
# runs its ticks on one thread at a time (DESIGN.md §7), so one slot
# suffices.
_CUR: Optional[RequestTrace] = None
_CUR_TRACER: Optional[Tracer] = None
_REQ: Optional[int] = None

profiling = TraceAnnotation.is_enabled  # is the JAX profiler collecting?

NULL = contextlib.nullcontext()  # what `span` returns when nothing records


def set_slice(tracer: Optional[Tracer], rt: Optional[RequestTrace],
              req: Optional[int] = None) -> None:
    """Publish (or clear, with Nones) the slice that is executing: the
    request's recorder trace, if sampled, and its id."""
    global _CUR, _CUR_TRACER, _REQ
    _CUR, _CUR_TRACER, _REQ = rt, tracer, req


class SpanLog:
    """The spans of the latest profiler session, for per-layer metrics:
    (name, thread id, t0_ns, t1_ns, counts) tuples on the host's
    `perf_counter_ns` clock.  A session opens at the first span that sees
    the profiler collecting and closes at the first that sees it stopped
    (or at `span_log()`); opening clears the log.  Past `capacity` spans
    it drops new ones and counts them."""

    def __init__(self, capacity: int = 1 << 16):
        self.capacity = capacity
        self.spans: List[tuple] = []
        self.dropped = 0
        self.session = 0  # sessions opened so far in this process
        self.active = False  # the session is still collecting
        self._lock = threading.Lock()

    def sync(self, on: bool) -> None:
        """Follow the profiler: open a fresh session or close the one open."""
        with self._lock:
            if on and not self.active:
                self.spans = []
                self.dropped = 0
                self.session += 1
            self.active = on

    def add(self, name: str, t0: int, t1: int, counts: dict) -> None:
        if len(self.spans) >= self.capacity:
            self.dropped += 1
        else:
            self.spans.append((name, threading.get_ident(), t0, t1, counts))


LOG = SpanLog()


def span_log() -> SpanLog:
    """The log of the latest profiler session; `active` while it collects."""
    _logging()
    return LOG


def _logging() -> bool:
    """Is the profiler collecting?  Keeps the log's session in step."""
    on = profiling()
    if on != LOG.active:
        LOG.sync(on)
    return on


class Span:
    """One live span; see `span`.  `set(**counts)` adds counts known only
    once the work is done (they reach the profiler as metadata too)."""

    __slots__ = ("name", "counts", "_ann", "_t0", "_rt", "_tracer")

    def __init__(self, name: str, counts: dict):
        self.name = name
        self.counts = counts
        self._ann = None
        self._rt = None

    def set(self, **counts) -> None:
        self.counts.update(counts)
        if self._ann is not None:
            self._ann.set_metadata(**counts)

    def __enter__(self) -> "Span":
        rt = _CUR
        if rt is not None:
            self._rt, self._tracer = rt, _CUR_TRACER
            self._tracer.begin(rt, self.name, **self.counts)
        if _logging():
            meta = self.counts if _REQ is None else dict(self.counts, req=_REQ)
            self._ann = TraceAnnotation(self.name, **meta)
            self._ann.__enter__()
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        ann = self._ann
        if ann is not None:
            t1 = time.perf_counter_ns()
            ann.__exit__(*exc)
            LOG.add(self.name, self._t0, t1, self.counts)
        if self._rt is not None:
            self._tracer.end(self._rt, name=self.name, **self.counts)
        return False


def span(name: str, **counts):
    """A span around one phase of one layer: a `Span` when the profiler
    collects or a recorder slice is live, else `NULL`.  Use it as a
    context manager; its `as` value is None when nothing records."""
    if _CUR is None and not profiling():
        return NULL
    return Span(name, counts)


def interval(name: str, seconds: float, **counts) -> None:
    """Log a span that ends now and began `seconds` ago (a wait measured
    after the fact, such as `pod.queued`).  Log only: the profiler cannot
    take a span back-dated.  A no-op unless the profiler collects."""
    if _logging():
        t1 = time.perf_counter_ns()
        LOG.add(name, t1 - int(seconds * 1e9), t1, counts)


def event(name: str, **attrs) -> None:
    """A zero-duration instant in the live recorder slice (store_hit,
    evict, fault, ...); recorder only."""
    if _CUR is not None:
        _CUR_TRACER.event(_CUR, name, **attrs)
