"""One run of one cell: set-up, the measured window, the check.

Set-up generates the tables from the seed, writes them as lake files,
builds the Pod the configuration describes and runs the mix's own streams
until each has finished its warm-up passes, which compiles (or loads from
the persistent cache) every shape the traffic uses.  The window then
measures for `seconds`.  Queries still in flight at its close are
finished and checked but not counted.  After the window the device's
memory peak is read, the program's state is dropped, and every query
finished since the window opened is compared with the reference.

With `trace`, the run reports the cell's per-layer metrics instead of its
end-to-end ones, and traces a few seconds in the middle of the window.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import statistics
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import datagen, driver, reference, spec, trace_reduce
from bench.compiles import Compiles

TRACE_SECONDS = 4.0
WARMUP_TIMEOUT_S = 900.0
FINISH_TIMEOUT_S = 60.0  # a minute past the close for the queries in flight


class Readings:
    """What the per-layer metric readers read."""

    def __init__(self, queries: int, window: dict, traced: Optional[dict],
                 trace: Optional[trace_reduce.Reduction], peaks: Optional[dict]):
        self.queries = queries  # completed in the window
        self.window = window  # counter deltas over the window
        self.traced = traced  # counter deltas over the traced ticks
        self.trace = trace
        self.peaks = peaks


def _by_query(records) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for r in records:
        out.setdefault(r.name, []).append(r.t_done - r.t_issue)
    return out


def _profile_options():
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        bench_spec: dict, log: Callable[[str], None] = print,
        queries: Optional[Dict[str, Callable]] = None, cfg: Optional[dict] = None,
        compile_cache: bool = True) -> dict:
    """Run `cell` once; returns the result object (without printing it).
    `queries` and `cfg` stand in for the program's query functions and the
    cell's configuration file: tests run small tables, and break the timed
    path, with them."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core.queries import QUERIES
    from repro.lakeformat.reader import LakeReader
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache() if compile_cache else None
    compiles = Compiles()
    cfg = cfg or spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    limits = spec.limits()
    devices = jax.devices()[:cell["chips"]]
    dev = devices[0]
    peaks = spec.peaks(dev.device_kind) if dev.platform == "tpu" else None
    sync = jax.jit(lambda x: x + 1)
    sync(np.int32(0)).block_until_ready()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}")

    t = time.perf_counter()
    data = cfg["data"]
    tables = datagen.gen_tables(data["generator_sf"], seed, data["row_group_size"])
    lake_dir = tempfile.mkdtemp(prefix="bench_lake_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        paths = datagen.write_lake(tables, lake_dir, data["row_group_size"])
        readers = {k: LakeReader(p) for k, p in paths.items()}
        log(f"set-up: tables generated and written in {time.perf_counter() - t:.3f} s "
            f"({readers['lineitem'].n_rows} lineitem rows, "
            f"{readers['lineitem'].n_row_groups} row groups)")

        pod = driver.build_pod(cfg)
        loop = driver.TickLoop(pod)
        ticker = threading.Thread(target=loop.run, name="tick", daemon=True)
        ticker.start()
        streams = driver.Streams(loop, mix, seed, readers, queries or QUERIES)
        t = time.perf_counter()
        streams.start()
        if not streams.wait_passes(mix["warmup_passes"], WARMUP_TIMEOUT_S):
            raise RuntimeError(f"warm-up did not finish in {WARMUP_TIMEOUT_S} s")

        # -- the window ----------------------------------------------------
        with loop.cv:
            c0 = loop.counters()
            n_compiles0, t_w0 = compiles.n, time.perf_counter()
        setup_s = t_w0 - t_start
        log(f"set-up: warm-up ({mix['warmup_passes']} pass(es) of every stream) "
            f"{t_w0 - t:.3f} s; {compiles.n} compiles so far ({compiles.cache_hits} "
            f"from the persistent cache, {compiles.seconds:.3f} s compile or load)")
        traced = trace_file = None
        if trace:
            traced, trace_file = _traced_middle(jax, TraceAnnotation, loop, sync, t_w0,
                                                seconds, trace_dir)
        time.sleep(max(0.0, t_w0 + seconds - time.perf_counter()))
        with loop.cv:
            c1 = loop.counters()
            t_w1 = time.perf_counter()
            n_compiles1 = compiles.n
        streams.finish(FINISH_TIMEOUT_S)
        loop.close()
        ticker.join(FINISH_TIMEOUT_S)
        window_s = t_w1 - t_w0
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
        records = [r for r in streams.records if r.t_done >= t_w0]
        in_window = [r for r in records if r.t_done <= t_w1]
        log(f"window: {window_s:.3f} s, {len(in_window)} queries completed in it, "
            f"{len(records) - len(in_window)} finished after its close; "
            f"{n_compiles1 - n_compiles0} compiles inside the window")
        del pod, loop, streams, readers
        gc.collect()
        shutil.rmtree(lake_dir, ignore_errors=True)

        # -- the check ----------------------------------------------------
        t = time.perf_counter()
        ok, checks = reference.judge(records, reference.Reference(tables), limits)
        log(f"check: {len(records)} answers compared with the reference in "
            f"{time.perf_counter() - t:.3f} s")
        reduction = _read_trace(trace_file, log) if trace_file else None
    finally:
        shutil.rmtree(lake_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    wanted = spec.metrics_for(bench_spec, cell["name"], kind)
    if trace:
        rd = Readings(len(in_window), driver.delta(c0, c1), traced, reduction, peaks)
        for m in wanted:
            v = spec.metric_reader(m["name"]).read(rd)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        lat = [r.t_done - r.t_issue for r in in_window]
        e2e = {"setup_s": setup_s}
        if lat:
            e2e.update(queries_per_s=len(in_window) / window_s,
                       query_p50_s=float(np.percentile(lat, 50)),
                       query_p90_s=float(np.percentile(lat, 90)))
            log(f"latency over {len(lat)} queries: mean {statistics.fmean(lat):.6f} s, "
                f"max {max(lat):.6f} s; by query: " + ", ".join(
                    f"{q} {len(v)}x {statistics.fmean(v):.4f} s" for q, v in sorted(
                        _by_query(in_window).items())))
        for m in wanted:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    result = {
        "correct": ok,
        "attempted": len(records),
        "failed": checks["unanswered"]["value"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if reduction is not None:
        result["device"].update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        result["breakdown"] = {
            "device_ops": [[n, ns * 1e-9] for n, ns in
                           sorted(reduction.ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in reduction.gaps[:10]],
        }
    result["checks"] = checks
    return result


def _traced_middle(jax, TraceAnnotation, loop, sync, t_w0, seconds, trace_dir):
    """Trace TRACE_SECONDS in the middle of the window.  The trace starts
    and stops between ticks, with the device drained, so the decode work
    of the traced ticks and their device time fall inside it.  Returns the
    counter deltas over the traced ticks and the trace file."""
    span = min(TRACE_SECONDS, seconds / 2)
    time.sleep(max(0.0, t_w0 + (seconds - span) / 2 - time.perf_counter()))
    with loop.cv:
        sync(np.int32(0)).block_until_ready()
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
        c0 = loop.counters()
        ann = TraceAnnotation(trace_reduce.WINDOW_SPAN)
        ann.__enter__()
    time.sleep(span)
    with loop.cv:
        sync(np.int32(0)).block_until_ready()
        c1 = loop.counters()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return driver.delta(c0, c1), files[0]


def _read_trace(path: str, log) -> trace_reduce.Reduction:
    t = time.perf_counter()
    reduction = trace_reduce.load(path)
    log(f"trace: {os.path.getsize(path)} bytes, read in {time.perf_counter() - t:.3f} s")
    return reduction
