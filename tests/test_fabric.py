"""Pod-sharded scan fabric: bit-identity vs the single-node engine across
pod counts / offload modes / schedulers / batched-vs-sequential decode,
catalog snapshot isolation, peer block-store fetch priced into WFQ,
fleet-wide fairness re-leveling, and mid-scan pod failure (explicit and
silent-heartbeat) with bit-identical replay.

Fixed configuration grids always run; a hypothesis sweep widens the
bit-identity net when hypothesis is installed (same policy as
tests/test_recon_props.py).
"""

import functools

import numpy as np
import pytest

from repro.core import BlockCache, Cmp, DatapathEngine, ScanPlan, tpch
from repro.datapath import (
    AdaptiveOffloadPolicy,
    Catalog,
    Pod,
    ScanFabric,
    StaticPolicy,
)
from repro.datapath.costmodel import CostModel
from repro.lakeformat.reader import LakeReader

# 2048-row groups -> lineitem at sf=0.05 spans ~15 row groups, so every
# multi-pod split actually exercises routing, and tick_bytes below keeps
# scans multi-tick (preemptable mid-flight for the failure tests)
RG_ROWS = 2048
TICK_BYTES = 1 << 14


@pytest.fixture(scope="module")
def lakes(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch_fabric")
    return tpch.write_tables(str(d), sf=0.05, seed=0, row_group_size=RG_ROWS)


@pytest.fixture(scope="module")
def readers(lakes):
    return {k: LakeReader(p) for k, p in lakes.items()}


PLANS = [
    ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
             Cmp("l_shipdate", "between", (365, 729))),  # zone-map pruned
    ScanPlan("lineitem", ["l_extendedprice", "l_quantity"],
             Cmp("l_quantity", "le", 25)),  # unprunable: every rg survives
    ScanPlan("lineitem", ["l_quantity"], Cmp("l_quantity", "le", 3),
             compact=True),  # global compaction over the merged stream
    ScanPlan("part", ["p_partkey", "p_size"], Cmp("p_size", "le", 10)),
]


def _assert_identical(got, want):
    assert int(got.count) == int(want.count)
    assert np.array_equal(np.asarray(got.mask), np.asarray(want.mask))
    assert set(got.columns) == set(want.columns)
    for name in want.columns:
        assert np.array_equal(
            np.asarray(got.columns[name]), np.asarray(want.columns[name])
        ), name


@functools.lru_cache(maxsize=None)
def _direct_cache():
    return {}


def _direct(readers, idx):
    memo = _direct_cache()
    if idx not in memo:
        plan = PLANS[idx]
        memo[idx] = DatapathEngine(backend="ref").scan(readers[plan.table], plan)
    return memo[idx]


# ---------------------------------------------------------------------------
# bit-identity sweep: N pods x offload mode x scheduler x batch decode
# ---------------------------------------------------------------------------

SWEEP = [
    # (n_pods, policy factory, scheduler, batch_decode)
    (1, None, "wfq", True),  # degenerate fabric == one pod
    (2, None, "wfq", True),
    (4, None, "wfq", True),
    (2, lambda: StaticPolicy("raw"), "fifo", False),
    (2, lambda: StaticPolicy("preloaded"), "wfq", True),
    (4, lambda: StaticPolicy("prefiltered"), "wfq", True),
    (4, lambda: AdaptiveOffloadPolicy(), "fifo", True),
    (3, lambda: StaticPolicy("raw"), "wfq", True),
    (2, lambda: AdaptiveOffloadPolicy(), "wfq", False),
]


@pytest.mark.parametrize("n_pods,policy,sched,batch", SWEEP)
def test_fabric_bit_identical_to_single_node(readers, n_pods, policy, sched, batch):
    kw = {"policy": policy()} if policy else {}
    fab = ScanFabric(n_pods=n_pods, scheduler=sched, batch_decode=batch, **kw)
    for idx, plan in enumerate(PLANS):
        # twice: the second pass may serve from preloaded/prefiltered tiers
        for _ in range(2):
            got = fab.scan(readers[plan.table], plan)
            _assert_identical(got, _direct(readers, idx))


def test_fabric_merged_stats_cover_whole_table(readers):
    fab = ScanFabric(n_pods=4)
    plan = PLANS[1]  # unprunable
    got = fab.scan(readers["lineitem"], plan)
    want = _direct(readers, 1)
    assert got.stats.row_groups_total == readers["lineitem"].n_row_groups
    assert got.stats.rows_total == readers["lineitem"].n_rows
    assert got.stats.row_groups_scanned == want.stats.row_groups_scanned
    assert got.stats.rows_out == int(want.count)


def test_fabric_routing_is_ring_derived(readers):
    fab = ScanFabric(n_pods=4)
    r = readers["lineitem"]
    t = fab.submit("t0", r, PLANS[1])
    for sub in t.subs.values():
        for rg in sub.rgs:
            assert fab.owner_of(r.path, rg) == sub.pod_id
    fab.drain()
    assert t.status == "done"


def test_fabric_all_pruned_is_engine_empty(readers):
    plan = ScanPlan("lineitem", ["l_extendedprice"], Cmp("l_quantity", "lt", -1))
    fab = ScanFabric(n_pods=2)
    got = fab.scan(readers["lineitem"], plan)
    want = DatapathEngine(backend="ref").scan(readers["lineitem"], plan)
    _assert_identical(got, want)
    assert got.mask.shape == (0,)
    assert not fab.active  # nothing lingers (zero-sub tickets merge at submit)


def test_fabric_concurrent_tenants_interleaved(readers):
    fab = ScanFabric(n_pods=2, tick_bytes=TICK_BYTES)
    tickets = [fab.submit(f"t{i % 3}", readers[PLANS[i].table], PLANS[i])
               for i in range(len(PLANS))]
    fab.drain()
    for i, t in enumerate(tickets):
        _assert_identical(t.result, _direct(readers, i))


# ---------------------------------------------------------------------------
# pod failure: explicit kill and silent heartbeat death, mid-scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("silent", [False, True])
@pytest.mark.parametrize("batch", [True, False])
def test_fabric_pod_failure_mid_scan_replays_bit_identical(
        readers, silent, batch):
    fab = ScanFabric(n_pods=3, tick_bytes=TICK_BYTES, batch_decode=batch,
                     heartbeat_timeout_ticks=2)
    r = readers["lineitem"]
    t = fab.submit("t0", r, PLANS[1])  # unprunable -> subs on several pods
    assert len(t.subs) >= 2
    fab.tick()  # some slices land; victim must still have queued work
    victims = [s.pod_id for s in t.subs.values() if s.ticket.status == "queued"]
    assert victims
    fab.fail_pod(victims[0], silent=silent)
    fab.drain()
    assert t.status == "done"
    assert t.replays >= 1
    assert victims[0] not in fab.live_pods
    rep = fab.report()
    assert rep["drains"] and rep["drains"][-1]["dead"] == victims[0]
    assert rep["drains"][-1]["replayed"] >= 1
    _assert_identical(t.result, _direct(readers, 1))
    # the fleet still works after the drain
    _assert_identical(fab.scan(r, PLANS[1]), _direct(readers, 1))


def test_fabric_last_pod_failure_raises(readers):
    fab = ScanFabric(n_pods=1)
    with pytest.raises(RuntimeError):
        fab.fail_pod("pod0")


# ---------------------------------------------------------------------------
# catalog: shared registry, snapshot isolation for in-flight scans
# ---------------------------------------------------------------------------

def test_catalog_versioning_and_pins():
    cat = Catalog()
    assert cat.version == 0 and cat.tables() == []
    v1 = cat.register("t", "readerA")
    snap = cat.pin()
    assert snap.version == v1 and snap.table("t") == "readerA"
    v2 = cat.register("t", "readerB")
    assert v2 == v1 + 1
    assert cat.resolve("t") == "readerB"  # latest...
    assert snap.table("t") == "readerA"  # ...but the pin still reads v1
    assert cat.pinned_versions() == [v1]
    cat.release(snap)
    assert cat.pinned_versions() == []
    cat.release(None)  # tolerated
    with pytest.raises(RuntimeError):
        cat.release(snap)  # double release is a bug
    cat.drop("t")
    with pytest.raises(KeyError):
        cat.resolve("t")
    with pytest.raises(KeyError):
        cat.drop("t")


def test_fabric_snapshot_isolation_mid_scan(readers, tmp_path_factory):
    # second lake with different data, same schema
    d = tmp_path_factory.mktemp("tpch_v2")
    paths2 = tpch.write_tables(str(d), sf=0.05, seed=1, row_group_size=RG_ROWS)
    r1, r2 = readers["lineitem"], LakeReader(paths2["lineitem"])
    eng = DatapathEngine(backend="ref")
    want1, want2 = eng.scan(r1, PLANS[1]), eng.scan(r2, PLANS[1])

    fab = ScanFabric(n_pods=2, tick_bytes=TICK_BYTES)
    fab.catalog.register("lineitem", r1)
    t_old = fab.submit("t0", "lineitem", PLANS[1])
    fab.tick()  # in flight...
    assert fab.catalog.pinned_versions() == [1]
    fab.catalog.register("lineitem", r2)  # ...when the table is swapped
    t_new = fab.submit("t0", "lineitem", PLANS[1])
    fab.drain()
    _assert_identical(t_old.result, want1)  # pinned: pre-swap data
    _assert_identical(t_new.result, want2)  # post-swap submission sees v2
    assert fab.catalog.pinned_versions() == []  # merge released the pins


def test_fabric_unknown_table_releases_pin(readers):
    fab = ScanFabric(n_pods=2)
    with pytest.raises(KeyError):
        fab.submit("t0", "nope", PLANS[0])
    assert fab.catalog.pinned_versions() == []


# ---------------------------------------------------------------------------
# peer fetch: warm siblings beat the storage hop, and the tenant pays
# ---------------------------------------------------------------------------

def test_peer_fetch_cheaper_than_storage_at_any_size():
    cm = CostModel()
    for nb in (1, 4096, 1 << 20, 1 << 28):
        assert cm.peer_fetch_seconds(nb) < cm.link_model().fetch_seconds(nb)


def test_fabric_scale_out_peer_fetches_from_warm_owners(readers):
    fab = ScanFabric(n_pods=2, policy=StaticPolicy("preloaded"))
    r = readers["lineitem"]
    got = fab.scan(r, PLANS[1])  # warm the original owners' decoded tiers
    _assert_identical(got, _direct(readers, 1))
    new_pid = fab.add_pod()
    got = fab.scan(r, PLANS[1])  # stolen arcs pull from old owners
    _assert_identical(got, _direct(readers, 1))
    store = fab.pods[new_pid].store
    assert store.peer_hits > 0 and store.peer_hit_bytes > 0
    assert got.stats.peer_bytes == store.peer_hit_bytes
    # ...and the hop was billed to the tenant that missed
    tel = fab.pods[new_pid].telemetry
    assert tel.tenant_peer_bytes.get("default", 0) > 0
    assert tel.counters.get("peer_fetch_seconds", 0) > 0
    # someone served it: fleet-wide serves match hits
    serves = sum(fab.pods[p].store.peer_serves for p in fab.live_pods)
    assert serves == store.peer_hits


def test_fabric_peer_fetch_disabled_is_isolated(readers):
    fab = ScanFabric(n_pods=2, policy=StaticPolicy("preloaded"),
                     peer_fetch=False)
    fab.scan(readers["lineitem"], PLANS[1])
    fab.add_pod()
    got = fab.scan(readers["lineitem"], PLANS[1])
    _assert_identical(got, _direct(readers, 1))  # identical, just pricier
    assert all(fab.pods[p].store.peer_hits == 0 for p in fab.live_pods)
    assert got.stats.peer_bytes == 0


# ---------------------------------------------------------------------------
# fleet fairness: a tenant cannot dodge its backlog across pod clocks
# ---------------------------------------------------------------------------

def test_fleet_vtime_releveling_charges_cross_pod_consumption(readers):
    fab = ScanFabric(n_pods=2, tick_bytes=TICK_BYTES)
    r = readers["lineitem"]
    # the hog has multi-tick work queued on BOTH pods at once, so while it
    # consumes on one pod the other must charge its local clock
    t_hog = [fab.submit("hog", r, PLANS[1]) for _ in range(2)]
    t_mouse = fab.submit("mouse", readers["part"], PLANS[3])
    fab.drain()
    for t in t_hog:
        _assert_identical(t.result, _direct(readers, 1))
    _assert_identical(t_mouse.result, _direct(readers, 3))
    charges = sum(fab.pods[p].telemetry.counters.get("fleet_vtime_charges", 0)
                  for p in fab.live_pods)
    assert charges > 0
    # and the re-level never touches fifo pods
    fifo = ScanFabric(n_pods=2, scheduler="fifo", tick_bytes=TICK_BYTES)
    for _ in range(2):
        fifo.submit("hog", r, PLANS[1])
    fifo.drain()
    assert all(
        p.telemetry.counters.get("fleet_vtime_charges", 0) == 0
        for p in fifo.pods.values()
    )


# ---------------------------------------------------------------------------
# cross-request bucket stacking (satellite: same-tick same-table requests
# decode through ONE bucket pass)
# ---------------------------------------------------------------------------

def _stacking_pod(**kw):
    return Pod(engine=DatapathEngine(backend="ref", cache=BlockCache(1 << 30)),
               policy=StaticPolicy("raw"), **kw)


def test_cross_request_stacking_bit_identical_and_fewer_launches(readers):
    r = readers["lineitem"]
    p1 = ScanPlan("lineitem", ["l_extendedprice", "l_discount"],
                  Cmp("l_quantity", "le", 25))
    p2 = ScanPlan("lineitem", ["l_extendedprice", "l_quantity"],
                  Cmp("l_quantity", "le", 10))
    eng = DatapathEngine(backend="ref")
    want = [eng.scan(r, p) for p in (p1, p2)]

    stacked = _stacking_pod(batch_decode=True)
    tks = [stacked.submit("a", r, p1), stacked.submit("b", r, p2)]
    stacked.drain()
    for tk, w in zip(tks, want):
        _assert_identical(tk.result, w)
    tel = stacked.telemetry.counters
    assert tel.get("xreq_groups", 0) >= 1
    assert tel.get("xreq_requests", 0) >= 2
    assert tel.get("xreq_fallback", 0) == 0

    seq = _stacking_pod(batch_decode=False)
    for p in (p1, p2):
        seq.submit("a", r, p)
    seq.drain()
    assert (stacked.telemetry.counters["decode_launches"]
            < seq.telemetry.counters["decode_launches"])


def test_cross_request_pass_raises_errors_no_request_caused(readers, monkeypatch):
    """Only one request's own fault reruns the group per request; an error
    of the launch itself (a kernel that fails to lower, a device fault)
    raises out of the tick instead of hiding behind the fallback."""
    pod = _stacking_pod()

    def broken(*args, **kwargs):
        raise RuntimeError("kernel failed to lower")

    monkeypatch.setattr(pod.engine, "scan_group_batched", broken)
    for tenant, plan in zip("ab", PLANS[:2]):
        pod.submit(tenant, readers["lineitem"], plan)
    with pytest.raises(RuntimeError, match="failed to lower"):
        pod.drain()
    assert pod.telemetry.counters.get("xreq_fallback", 0) == 0


def test_fabric_stacks_across_requests_and_stays_identical(readers):
    fab = ScanFabric(n_pods=2, policy=StaticPolicy("raw"))
    r = readers["lineitem"]
    t1 = fab.submit("a", r, PLANS[0])
    t2 = fab.submit("b", r, PLANS[1])
    fab.drain()
    _assert_identical(t1.result, _direct(readers, 0))
    _assert_identical(t2.result, _direct(readers, 1))
    groups = sum(fab.pods[p].telemetry.counters.get("xreq_groups", 0)
                 for p in fab.live_pods)
    assert groups >= 1


# ---------------------------------------------------------------------------
# drain windows: a pod dies while a request is parked in the coalescing
# hold window, or mid-flight while peer fetches are feeding survivors —
# and the breaker-drain path (fetch faults, not heartbeats) replays too
# ---------------------------------------------------------------------------

def test_drain_while_request_parked_in_hold_window(readers):
    """A sub-scan still parked in its pod's coalescing hold window when
    the pod dies replays bit-identically on survivors — held requests
    are queued, undispatched state and must never be lost."""
    fab = ScanFabric(n_pods=3, tick_bytes=TICK_BYTES, hold_ticks=4,
                     heartbeat_timeout_ticks=2)
    r = readers["lineitem"]
    t = fab.submit("t0", r, PLANS[1])
    fab.tick()  # every sub is now HELD (a lone request has no partner)
    parked = [
        (pid, s) for pid, s in t.subs.items()
        if s.ticket.status == "queued"
        and any(q.held_ticks > 0 and not q.started
                for q in fab.pods[s.pod_id].queue
                if q.ticket is s.ticket)
    ]
    assert parked, "expected at least one sub parked in the hold window"
    fab.fail_pod(parked[0][1].pod_id, silent=True)
    fab.drain()
    assert t.status == "done" and t.replays >= 1
    _assert_identical(t.result, _direct(readers, 1))


def test_drain_mid_peer_fetch_falls_back_to_storage(readers):
    """Kill a warm pod SILENTLY mid-scan: until the heartbeat timeout
    expires, survivors' peer fetches still list the dead pod as a
    sibling, hit its dead store (ConnectionError), and must fall back to
    the next peer / storage — then the drain replays the dead pod's own
    work.  End state: bit-identical, no propagated peer error."""
    fab = ScanFabric(n_pods=3, tick_bytes=TICK_BYTES,
                     heartbeat_timeout_ticks=3)
    r = readers["lineitem"]
    fab.scan(r, PLANS[1])  # warm every pod's store
    t = fab.submit("t0", r, PLANS[1])
    fab.tick()
    victims = [s.pod_id for s in t.subs.values()
               if s.ticket.status == "queued"]
    assert victims
    victim = victims[0]
    assert fab.pods[victim].store.dead is False
    fab.fail_pod(victim, silent=True)
    assert fab.pods[victim].store.dead is True
    with pytest.raises(ConnectionError):
        fab.pods[victim].store.peek(("page", r.path, 0, "l_quantity"))
    fab.drain()
    assert t.status == "done"
    _assert_identical(t.result, _direct(readers, 1))
    # the fleet stays healthy for the next scan
    _assert_identical(fab.scan(r, PLANS[1]), _direct(readers, 1))


def test_breaker_open_pod_is_drained_and_replayed(readers):
    """A pod whose storage fetches trip its circuit breaker is treated
    like a heartbeat-silent pod: drained, its sub-scans replayed
    bit-identically on survivors whose storage paths are healthy."""
    from repro.datapath import FaultPlan, RetryPolicy

    fab = ScanFabric(n_pods=3, tick_bytes=TICK_BYTES)
    r = readers["lineitem"]
    t = fab.submit("t0", r, PLANS[1])
    victim = next(s.pod_id for s in t.subs.values())
    fab.inject_faults(victim, FaultPlan(transient_rate=1.0,
                                        fail_forever=True),
                      RetryPolicy(max_attempts=5))
    fab.drain()
    assert t.status == "done" and t.replays >= 1
    assert victim not in fab.live_pods
    assert fab.report()["breaker_drains"] >= 1
    _assert_identical(t.result, _direct(readers, 1))


def test_breaker_drain_never_takes_the_last_pod(readers):
    """A one-pod fleet with a tripped breaker degrades in place (typed
    error) rather than draining itself out of existence."""
    from repro.datapath import FaultPlan, FetchFailed, RetryPolicy

    fab = ScanFabric(n_pods=1, tick_bytes=TICK_BYTES)
    r = readers["lineitem"]
    fab.inject_faults("pod0", FaultPlan(transient_rate=1.0,
                                        fail_forever=True),
                      RetryPolicy(max_attempts=5))
    t = fab.submit("t0", r, PLANS[1])
    fab.drain()
    assert t.status == "error"
    assert isinstance(t.error, FetchFailed)
    assert fab.live_pods == ["pod0"]
    assert fab.report()["breaker_drains"] == 0


# ---------------------------------------------------------------------------
# hypothesis sweep (skips without hypothesis; the fixed grid above always
# runs, so bit-identity is never unguarded)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=12)
    @given(
        n_pods=st.sampled_from([1, 2, 4]),
        mode=st.sampled_from(["adaptive", "raw", "preloaded", "prefiltered"]),
        scheduler=st.sampled_from(["wfq", "fifo"]),
        batch=st.booleans(),
        kill=st.booleans(),
        idx=st.integers(0, len(PLANS) - 1),
    )
    def _hyp_fabric_identity(readers, n_pods, mode, scheduler, batch, kill, idx):
        policy = (AdaptiveOffloadPolicy() if mode == "adaptive"
                  else StaticPolicy(mode))
        fab = ScanFabric(n_pods=n_pods, policy=policy, scheduler=scheduler,
                         batch_decode=batch, tick_bytes=TICK_BYTES)
        plan = PLANS[idx]
        t = fab.submit("t0", readers[plan.table], plan)
        if kill and n_pods > 1:
            fab.tick()
            queued = [s.pod_id for s in t.subs.values()
                      if s.ticket.status == "queued"]
            if queued:
                fab.fail_pod(queued[0])
        fab.drain()
        _assert_identical(t.result, _direct(readers, idx))

    def test_fabric_identity_hypothesis_sweep(readers):
        _hyp_fabric_identity(readers)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_fabric_identity_hypothesis_sweep():
        pass
