"""datapath — the SmartNIC as a shared, scheduled, multi-tenant service.

service.py    Pod (née DatapathService): bounded queue, admission control,
              quotas, per-tenant WFQ virtual time + actual-cost
              reconciliation, auto-tuned coalescing hold window
fabric.py     ScanFabric: N pods behind consistent-hash row-group
              ownership — routed sub-scans, bit-identical global merge,
              peer block-store fetch over the inter-pod link, fleet WFQ
              re-leveling, heartbeat-driven drain/replay
catalog.py    shared table registry with per-scan snapshot pins
              (monotonic version; mid-scan DDL is invisible in flight)
blockstore.py unified tiered BlockStore (encoded pages / decoded columns
              / prefiltered results): one byte ledger, cost-aware
              eviction priced by the cost model, window-scoped decode
              pins that survive hold_ticks
scheduler.py  fair-share batch formation (wfq/fifo, row-group preemption,
              cross-tick coalescing holds) + shared decode windows +
              batched dispatch (each WFQ slice = one bucketed batch
              decode, reconciled by actual kernel launches)
costmodel.py  calibrated per-encoding decode rates (GB/s table with a
              nominal fallback), decode-seconds estimates from footer
              metadata — the WFQ virtual-time currency AND the store's
              eviction pricing
netsim.py     storage->NIC bandwidth/latency model, prefetch overlap
              (decode priced by the same calibrated table; store hits
              never enter the simulated fetch)
policy.py     adaptive raw/preloaded/prefiltered choice per request
              (residency read per tier from the store), hold-window
              footprint compatibility
telemetry.py  queue depth, decoded-bytes-saved, per-tenant p50/p99/p99.9,
              fair-share metrics (Jain index, held-request latency,
              window-retained bytes), estimated-vs-actual decode-cost
              ledger, per-tier store ledger
trace.py      the span API every layer marks its work with (profiler
              annotations + a span log for per-layer metrics) and the
              flight recorder: per-request span trees, bounded ring of
              completed traces, Chrome-trace export, host-time stage
              attribution
faults.py     storage fault plane: seedable deterministic fault schedules
              (FaultPlan), bounded retry/backoff/timeout/hedge policy
              (RetryPolicy + FaultInjector on the engine's storage-read
              seam), per-target circuit breaker with degraded mode and
              typed Overloaded load-shed — every extra modeled second
              reconciled into WFQ virtual time

See DESIGN.md §8–§9 and §11.  The synchronous per-caller path
(core/engine.py) remains the substrate; the service schedules it — at
row-group granularity, so no scan occupies the device longer than one
preemption quantum.
"""

from repro.datapath.blockstore import (  # noqa: F401
    TIERS,
    BlockEntry,
    BlockStore,
    DecodePool,
    PeerFetcher,
    StoreView,
)
from repro.datapath.catalog import Catalog, Snapshot  # noqa: F401
from repro.datapath.costmodel import (  # noqa: F401
    NOMINAL_RATES_GBPS,
    CostModel,
    RowGroupCost,
    measure_rates,
)
from repro.datapath.netsim import (  # noqa: F401
    DecodeModel,
    LinkModel,
    PrefetchPipeline,
    SliceClock,
)
from repro.datapath.policy import (  # noqa: F401
    AdaptiveOffloadPolicy,
    StaticPolicy,
    coalesce_compatible,
)
from repro.datapath.fabric import FabricTicket, ScanFabric  # noqa: F401
from repro.datapath.faults import (  # noqa: F401
    CircuitBreaker,
    FaultInjector,
    FaultPlan,
    FetchFailed,
    FetchTimeout,
    Overloaded,
    Quarantined,
    RetryPolicy,
    StorageFault,
    TransientFetchError,
)
from repro.datapath.scheduler import form_batch, run_tick  # noqa: F401
from repro.datapath.service import (  # noqa: F401
    DatapathService,
    Pod,
    QueueFull,
    QuotaExceeded,
    ScanRequest,
    ServiceClient,
    TenantQuota,
    Ticket,
)
from repro.datapath.telemetry import Telemetry, jain_index, quantile  # noqa: F401
from repro.datapath.trace import (  # noqa: F401
    STAGES,
    FlightRecorder,
    RequestTrace,
    Tracer,
)
