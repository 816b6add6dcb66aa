"""DatapathEngine — the paper's data-processing SmartNIC, TPU edition.

Pipeline per scan (DESIGN.md §2):

    footer zone maps ──► row-group pruning (metadata only, host)
         │
    encoded bytes ────► on-device decode (Pallas kernels / jnp ref)
         │                    │
         │              pushed-down predicate (+ bloom semijoin)
         │                    │
         │              optional stream compaction (survivors packed)
         ▼                    ▼
    BlockStore  ◄──── pre-filtered columns + mask + count ──► consumer
    (tiered: encoded pages / decoded columns / prefiltered results)

Offload configurations reproduce the paper's Figure 1:
  'raw'         — decode + filter on every scan (query on Parquet)
  'preloaded'   — decoded row groups served from the store's decoded
                  tier (encoded pages cached too, so even an evicted
                  decode skips the storage->NIC re-fetch)
  'prefiltered' — whole filtered scans served from the prefiltered tier

Backends: 'ref' (pure jnp — also the multi-pod dry-run path), 'pallas'
(Pallas kernels; interpret off-TPU), 'host' (numpy on the host CPU — the
"no SmartNIC, the CPU does everything" baseline), 'auto' ('pallas' on TPU,
'ref' elsewhere — resolved per kernel call in kernels/ops.py).

The engine is also drivable at row-group granularity (`scan_row_group`)
by the shared service scheduler (repro.datapath): a tick-level decode
pool lets N concurrent scans over the same row groups decode each
(row group, column) pair once ("shared-scan coalescing", DESIGN.md §8).
`scan_row_groups_batched` is the batched form of the same contract
(DESIGN.md §12): a whole dispatch slice's pages are bucketed by
(encoding, k, dtype) and decoded in ONE kernel launch per bucket —
bit-identical results and accounting, ~an order of magnitude fewer
device dispatches.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import operator
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import agg as agg_merge
from repro.core.cache import BlockCache
from repro.core.plan import (
    And,
    BloomProbe,
    Cmp,
    Expr,
    InSet,
    Or,
    ScanPlan,
    bind_expr,
    expr_columns,
    pred_int_bounds,
)
from repro.core.zonemap import estimate_selectivity, prune_row_groups
from repro.kernels import ops
from repro.lakeformat.encodings import (
    PACK_BLOCK,
    RLE_OUT_BLOCK,
    EncodedColumn,
    Encoding,
    decode_column_host,
    padded_rows,
)
from repro.lakeformat.integrity import CorruptPageError, page_checksum

# Span hook: the repro.datapath.trace MODULE, installed by the datapath
# scheduler at its import time (engine cannot import datapath — that
# would close an import cycle through the package __init__).  None for
# library users who never touch the service.
TRACE = None
_NO_SPAN = contextlib.nullcontext()


def _span(name: str):
    """`trace.span(name)` once the datapath has installed its trace
    module, else a null context.  Its `as` value is None when nothing
    records, so call sites build their counts only under
    `if sp is not None`."""
    t = TRACE
    return _NO_SPAN if t is None else t.span(name)


_SPLIT_TRACES = [0]  # times `_split_program`'s body ran: once per new trace


@functools.partial(jax.jit, static_argnames=("L",))
def _split_program(out, starts, sizes, L: int):
    """Every page's (L,) column out of one bucket's stacked output, in ONE
    dispatch: page i is the `sizes[i]` elements of the flattened output
    from `starts[i]`, zero-filled to L (an RLE page's 1024-row blocks can
    fall short of L) and truncated to L.  Starts and sizes are runtime
    arrays, so the trace keys only on the output's shape and dtype, L and
    the page count (which the caller pads to the `ops.bucket_blocks`
    ladder): another row-group order replays the same program."""
    _SPLIT_TRACES[0] += 1  # Python runs here only while jit traces
    # L zeros past the end keep every slice in bounds: dynamic_slice
    # would clamp a start that runs off the end, not fill
    flat = jnp.concatenate([out.reshape(-1), jnp.zeros((L,), out.dtype)])
    row = jnp.arange(L, dtype=jnp.int32)
    zero = jnp.zeros((), out.dtype)
    return tuple(
        jnp.where(row < sizes[i], lax.dynamic_slice(flat, (starts[i],), (L,)), zero)
        for i in range(starts.shape[0])
    )


_MASK_TRACES = [0]  # times `_mask_program`'s body ran: once per new trace

@functools.partial(jax.jit, static_argnames=("shape", "L"))
def _mask_program(cols, lits, hits, n, shape, L: int):
    """A row group's (L,) mask, `predicate & (iota(L) < n)`, in ONE
    dispatch.  `shape` is the predicate with its literals taken out
    (`_mask_shape`), or None for validity alone; its leaves index `cols`,
    `lits` (device scalars, weakly typed as the eager comparison's
    literals are, so each comparison computes in the same type) and
    `hits` (bloom hit masks, or the fused scan's mask).  Every value is a
    runtime operand, so a new parameter draw replays the program: the
    trace keys on the shape, L and the operands' shapes and dtypes."""
    _MASK_TRACES[0] += 1  # Python runs here only while jit traces

    def ev(node):
        kind = node[0]
        if kind == "cmp":
            _, c, op, slots = node
            v = cols[c]
            if op == "between":
                return (v >= lits[slots[0]]) & (v <= lits[slots[1]])
            return getattr(operator, op)(v, lits[slots[0]])  # Cmp's ops are operator's
        if kind == "in":
            m = jnp.zeros((L,), jnp.bool_)
            for s in node[2]:
                m = m | (cols[node[1]] == lits[s])
            return m
        if kind == "hit":
            return hits[node[1]].reshape(-1)[:L]
        m = ev(node[1][0])
        for c in node[1][1:]:
            m = (m & ev(c)) if kind == "and" else (m | ev(c))
        return m

    valid = lax.iota(jnp.int32, L) < n
    return valid if shape is None else ev(shape) & valid


def _mask_shape(pred: Expr):
    """`pred` as `_mask_program` runs it: (shape, columns, literals,
    probes).  Leaves become ("cmp", column slot, op, literal slots),
    ("in", column slot, literal slots) and ("hit", probe slot), inner
    nodes ("and"|"or", children); `columns`, `literals` (the values) and
    `probes` (the BloomProbe leaves) list the slots in order.  Not
    cached on `pred`: `==` on predicates takes 1 for 1.0, whose
    comparisons compute in other types."""
    columns: List[str] = []
    literals: List = []
    probes: List[BloomProbe] = []

    def slot(column):
        if column not in columns:
            columns.append(column)
        return columns.index(column)

    def lit(value):
        literals.append(value)
        return len(literals) - 1

    def walk(e):
        if isinstance(e, Cmp):
            vals = e.value if e.op == "between" else (e.value,)
            return ("cmp", slot(e.column), e.op, tuple(lit(v) for v in vals))
        if isinstance(e, InSet):
            return ("in", slot(e.column), tuple(lit(v) for v in e.values))
        if isinstance(e, BloomProbe):
            probes.append(e)
            return ("hit", len(probes) - 1)
        if isinstance(e, (And, Or)):
            return ("and" if isinstance(e, And) else "or",
                    tuple(walk(c) for c in e.children))
        raise TypeError(e)

    shape = walk(pred)
    return shape, columns, literals, probes


@functools.lru_cache(maxsize=1024, typed=True)
def _operand(value) -> jax.Array:
    """A literal or row count as a device scalar, built once and not once
    per row group; a Python number stays weakly typed, as in eager jnp."""
    return jax.device_put(value)


_GIVEN = ("hit", 0)  # `_mask_program`'s shape for a mask computed elsewhere


@dataclasses.dataclass
class ScanStats:
    row_groups_total: int = 0
    row_groups_scanned: int = 0
    encoded_bytes: int = 0
    decoded_bytes: int = 0  # decode output materialized for this scan
    decoded_bytes_fresh: int = 0  # subset actually decoded now (no pool/cache hit)
    # Fresh decode WORK by encoding, in output bytes — ground truth for the
    # service's cost reconciliation.  Keyed by the encoding of the buffers
    # actually read (not footer claims), it covers materializing decodes
    # AND the fused predicate column (processed at L*4 virtual output bytes
    # but never materialized); pool/cache hits do no decode work and are
    # excluded.
    decode_work: Dict[str, int] = dataclasses.field(default_factory=dict)
    pool_hits: int = 0  # (rg, column) decodes served by a shared decode pool
    pool_hit_bytes: int = 0
    page_hits: int = 0  # encoded pages served by the store's encoded tier
    page_hit_bytes: int = 0  # encoded bytes that skipped the storage->NIC hop
    rows_total: int = 0
    rows_out: int = 0
    # Bytes the scan's RESULT hands to the consumer (the result-DMA size):
    # projection columns + survivor mask for row scans; the finalized
    # (n_groups,) accumulator arrays for pushed-down aggregations — the
    # number operator pushdown exists to shrink (DESIGN.md §16).
    result_bytes: int = 0
    fused: bool = False
    cache_hit: bool = False
    # Device dispatches on the DECODE path only (column decodes, PLAIN device
    # puts, fused scans) — predicate eval and compaction launch identically
    # on both paths and are excluded.  The sequential path counts one per
    # fresh (row group, column); the batched path one per bucket launch.
    # This is the one ScanStats field batching is ALLOWED to change; the
    # cost model prices it via `launch_overhead_s` and reconciliation
    # refunds the batched path's savings.
    kernel_launches: int = 0
    # Batched-path shape telemetry: blocks of pure padding added to reach
    # each bucket's power-of-two size (the price of shape-stable jit).
    batch_pad_blocks: int = 0
    # Fabric peer fetches: bytes this scan pulled from a sibling pod's
    # block store instead of storage (cache.BlockCache.get threads the
    # stats object down to the PeerFetcher).  Priced per slice over the
    # inter-pod link at WFQ reconcile; always 0 on single-node services.
    peer_bytes: int = 0
    # Fault plane (datapath/faults.py).  `fault_wait_s` is MODELED extra
    # seconds the storage hop cost this scan beyond clean transfers —
    # failed attempts, retry backoff, latency spikes survived, hedge
    # exposure — billed into WFQ vtime at slice reconcile so a faulty
    # tenant's retries charge that tenant, not the fleet.  The counters
    # mirror telemetry but per-scan, and _merge_stats sums them across
    # fabric sub-scans like every other numeric field.
    retry_fetches: int = 0     # fetch attempts that failed and were retried
    fetch_timeouts: int = 0    # attempts abandoned at the policy timeout
    hedged_fetches: int = 0    # attempts that launched a hedged second read
    hedge_wins: int = 0        # hedges that beat the straggling primary
    corrupt_pages: int = 0     # checksum-detected pages (quarantined)
    fault_wait_s: float = 0.0  # modeled seconds of fault-plane delay


@dataclasses.dataclass
class ScanResult:
    columns: Dict[str, jax.Array]  # decoded (compacted iff plan.compact), padded
    mask: jax.Array  # (L,) bool — predicate & row-validity
    count: jax.Array  # scalar int32 — surviving rows
    stats: ScanStats
    # Operator pushdown (plans with `aggregates`): `aggregates` maps each
    # AggSpec.out_name() to its finalized (n_groups,) array; `agg_partials`
    # keeps the per-row-group ColPartials (core/agg.py) so the scan fabric
    # can merge pod sub-results in global row-group order bit-identically.
    # Both None for ordinary row scans; `columns`/`mask` are empty for
    # aggregate scans (nothing row-shaped crosses the result DMA).
    aggregates: Optional[Dict[str, np.ndarray]] = None
    agg_partials: Optional[Dict[int, dict]] = None


def group_domain(reader, column: str) -> int:
    """Dense group-id domain size for a pushed-down GROUP BY column,
    from footer metadata alone.  String DICT columns decode to globally
    stable int codes (the writer grows one map across row groups), so the
    dictionary length IS the domain; int columns use the zone-map maximum
    (values must be small non-negative ids — asserted, not assumed)."""
    d = reader.string_dicts.get(column)
    if d is not None:
        return max(len(d), 1)
    zms = reader.zonemaps(column)
    lo = min(zm["min"] for zm in zms)
    hi = max(zm["max"] for zm in zms)
    assert lo >= 0, (
        f"group_by column {column!r} has negative values (min {lo}); "
        "pushdown grouping needs a dense non-negative id domain"
    )
    return int(hi) + 1


class DatapathEngine:
    def __init__(
        self,
        backend: str = "auto",
        offload: str = "raw",
        cache: Optional[BlockCache] = None,
    ):
        assert backend in ("ref", "pallas", "host", "auto")
        # 'pre-aggregated' (DESIGN.md §16) is the fourth offload mode: an
        # aggregate plan's tiny accumulator result is cached whole (same
        # tier as prefiltered), but decoded row-group columns are NOT —
        # pushdown exists to avoid materializing them, so seeding the
        # decoded tier with them would waste the store.
        assert offload in ("raw", "preloaded", "prefiltered", "pre-aggregated")
        self.backend = backend
        self.offload = offload
        self.cache = cache if cache is not None else BlockCache()
        # Storage fault plane (datapath/faults.FaultInjector), installed by
        # the service like the TRACE hook — duck-typed because core cannot
        # import datapath.  None = clean reads, still checksum-verified.
        self.faults = None
        self.verify_checksums = True

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _decode_device(self, col: EncodedColumn, L: int) -> jax.Array:
        """Decode one encoded column on-device, padded to L rows."""
        be = self.backend if self.backend != "host" else "ref"
        e = col.encoding
        if e == Encoding.PLAIN:
            arr = ops.device_put(col.buffers["plain"])
        elif e == Encoding.BITPACK:
            arr = ops.bitunpack(jnp.asarray(col.buffers["packed"]), col.k, backend=be)
            arr = arr.reshape(-1)
        elif e == Encoding.DICT:
            d = col.buffers["dictionary"]
            d = jnp.asarray(d.astype(np.int32) if d.dtype.kind in "iu" else d)
            arr = ops.dict_decode(
                jnp.asarray(col.buffers["packed"]), d, col.k, backend=be
            ).reshape(-1)
        elif e == Encoding.DELTA:
            arr = ops.delta_decode(
                jnp.asarray(col.buffers["packed"]),
                jnp.asarray(col.buffers["bases"].astype(np.int32)),
                col.k,
                backend=be,
            ).reshape(-1)
        elif e == Encoding.RLE:
            arr = ops.rle_decode(
                jnp.asarray(col.buffers["rle_values"]),
                jnp.asarray(col.buffers["rle_ends"]),
                backend=be,
            ).reshape(-1)
        else:
            raise ValueError(e)
        if arr.shape[0] < L:
            arr = jnp.pad(arr, (0, L - arr.shape[0]))
        return arr[:L]

    def _decode_host(self, col: EncodedColumn, L: int) -> jax.Array:
        """Host (numpy) decode — the traditional 'CPU decodes' baseline."""
        arr = decode_column_host(col)
        out = np.zeros(L, dtype=arr.dtype)
        out[: arr.shape[0]] = arr
        return jnp.asarray(out)

    def rg_cache_key(self, reader, rg: int, name: str):
        """Decoded-tier / decode-pool key for one decoded row-group column."""
        return ("rg", reader.path, rg, name, self.backend)

    def page_cache_key(self, reader, rg: int, name: str):
        """Encoded-tier key for one column's raw encoded page.  No backend
        component: encoded bytes are backend-independent."""
        return ("page", reader.path, rg, name)

    @staticmethod
    def _pool_put(pool, key, arr, encoding: Optional[str] = None) -> None:
        """Insert into a shared decode pool.  Store-backed views take the
        source encoding so the window pin is priced honestly; a plain dict
        (legacy callers) just stores the array."""
        put = getattr(pool, "put", None)
        if put is not None:
            put(key, arr, encoding=encoding)
        else:
            pool[key] = arr

    def _decode_column(
        self,
        reader,
        rg: int,
        name: str,
        col: EncodedColumn,
        L: int,
        offload: Optional[str] = None,
        pool: Optional[Dict] = None,
        stats: Optional[ScanStats] = None,
        precomputed: Optional[jax.Array] = None,
    ):
        """Serve one decoded row-group column: pool hit, cache hit, or a
        fresh decode.  `precomputed` is the batched path's already-launched
        bucket slice for this (rg, column) — it substitutes for the kernel
        call only; every hit lookup, stats increment, and pool/cache put
        runs identically, which is what keeps batched ≡ sequential."""
        offload = offload or self.offload
        key = self.rg_cache_key(reader, rg, name)
        if pool is not None:
            hit = pool.get(key)
            if hit is not None:
                if offload in ("preloaded", "prefiltered"):
                    # pool hits must still persist: promote the (possibly
                    # ephemeral window-pinned) entry to a cache-owned one,
                    # carrying the pool's recorded encoding so the promoted
                    # decode keeps its honest eviction price
                    enc_of = getattr(pool, "encoding_of", None)
                    self.cache.promote(key, hit,
                                       encoding=enc_of(key) if enc_of else None)
                if stats is not None:
                    stats.decoded_bytes += int(hit.nbytes)
                    stats.pool_hits += 1
                    stats.pool_hit_bytes += int(hit.nbytes)
                return hit, True
        if offload in ("preloaded", "prefiltered"):
            hit = self.cache.get(key, stats=stats)
            if hit is not None:
                if pool is not None:
                    self._pool_put(pool, key, hit)
                if stats is not None:
                    stats.decoded_bytes += int(hit.nbytes)
                return hit, True
        if precomputed is not None:
            arr = precomputed  # bucket launch already counted by the caller
        else:
            with _span("engine.decode") as sp:
                arr = (self._decode_host(col, L) if self.backend == "host"
                       else self._decode_device(col, L))
                if sp is not None:
                    sp.set(rg=rg, column=name, encoding=col.encoding.value,
                           pages=1, rows=L, nbytes=int(arr.nbytes))
            if stats is not None:
                stats.kernel_launches += 1
        enc_name = col.encoding.value if col is not None else None
        if offload in ("preloaded", "prefiltered"):
            # demote payload: under pressure the decoded column falls back
            # to its encoded page (re-decode only) instead of dropping to
            # zero (re-fetch AND re-decode)
            self.cache.put(
                key, arr, encoding=enc_name,
                demote=(self.page_cache_key(reader, rg, name), col)
                if col is not None else None,
            )
        if pool is not None:
            self._pool_put(pool, key, arr, encoding=enc_name)
        if stats is not None:
            stats.decoded_bytes += int(arr.nbytes)
            stats.decoded_bytes_fresh += int(arr.nbytes)
            e = col.encoding.value
            stats.decode_work[e] = stats.decode_work.get(e, 0) + int(arr.nbytes)
        return arr, False

    # ------------------------------------------------------------------
    # predicate and row validity (on decoded device columns)
    # ------------------------------------------------------------------
    def _row_mask(self, pred: Optional[Expr], cols, blooms, n: int, L: int, rg: int,
                  bmasks: Optional[Dict] = None, given: Optional[jax.Array] = None):
        """A row group's (L,) mask, `pred & (iota(L) < n)`, in one
        `_mask_program` call.  `given` is a mask computed elsewhere (the
        fused scan's) that stands in for `pred`; with neither the mask is
        validity alone, and neither is filter work, so neither opens a
        span.  `bmasks` maps (bloom name, column) -> this row group's
        pre-probed (L,) membership mask from the batched path's stacked
        probe; a BloomProbe leaf without one is probed here."""
        if given is not None or pred is None:
            return _mask_program((), (), () if given is None else (given,), _operand(int(n)),
                                 shape=None if given is None else _GIVEN, L=L)
        with _span("engine.mask") as sp:
            traces0 = _MASK_TRACES[0] if sp is not None else 0
            shape, columns, literals, probes = _mask_shape(pred)
            mask = _mask_program(
                tuple(cols[c] for c in columns), tuple(_operand(v) for v in literals),
                tuple(self._bloom_hits(p, cols, blooms, bmasks) for p in probes),
                _operand(int(n)), shape=shape, L=L)
            if sp is not None:
                sp.set(rg=rg, rows=L, programs=1, traces=_MASK_TRACES[0] - traces0)
        return mask

    def _bloom_hits(self, e: BloomProbe, cols, blooms, bmasks: Optional[Dict]):
        """A BloomProbe leaf's (L,) hit mask: the batched bucket pass
        pre-probes every slice page's keys in ONE stacked ops.bloom_probe
        per filter (`_batch_bloom_probe`) and hands the per-row-group slice
        down here — bit-identical (the probe is elementwise per key), one
        dispatch instead of one per row group."""
        if bmasks is not None:
            hit = bmasks.get((e.name, e.column))
            if hit is not None:
                return hit
        keys = cols[e.column].astype(jnp.int32)
        L = keys.shape[0]
        pad = (-L) % RLE_OUT_BLOCK
        if pad:
            keys = jnp.pad(keys, (0, pad))
        m = ops.bloom_probe(
            keys.reshape(-1, RLE_OUT_BLOCK),
            blooms[e.name],
            e.n_hashes,
            backend=self.backend if self.backend != "host" else "ref",
        )
        return m.reshape(-1)[:L]

    # ------------------------------------------------------------------
    # fused decode+filter fast path
    # ------------------------------------------------------------------
    @staticmethod
    def _fusable(pred: Optional[Expr], enc: Dict[str, EncodedColumn], projected: List[str]):
        """Single int range/eq predicate on a BITPACK or int-DICT column not in
        the projection -> the filter column need never be materialized.

        For DICT columns the predicate is rewritten onto the *codes*: the
        dictionary is sorted (np.unique), so a value range maps to a code
        range via two host-side binary searches — the decode step then
        operates on packed codes only and the dictionary is never touched.
        """
        if not isinstance(pred, Cmp) or pred.column in projected:
            return None
        col = enc.get(pred.column)
        if col is None or col.encoding not in (Encoding.BITPACK, Encoding.DICT):
            return None
        if col.encoding == Encoding.DICT and col.buffers["dictionary"].dtype.kind not in "iu":
            return None
        bounds = pred_int_bounds(pred)
        if bounds is None:
            return None
        lo, hi = bounds
        if col.encoding == Encoding.DICT:
            d = col.buffers["dictionary"]
            lo = int(np.searchsorted(d, lo, side="left"))
            hi = int(np.searchsorted(d, hi, side="right")) - 1
            if hi < lo:
                lo, hi = 1, 0  # empty range, still valid
        return lo, hi

    def _storage_read(self, reader, rg: int, columns,
                      stats: ScanStats) -> Dict[str, EncodedColumn]:
        """The ONLY path encoded pages take from storage into the engine —
        both fetch seams (`_prepare_row_group`, `_serve_resident`) route
        here.  With a fault injector installed (datapath/faults.py, set on
        `self.faults` by the service) the read runs the full retry /
        verify / quarantine / hedge loop.  Without one, pages are STILL
        checksum-verified against the footer before they can reach a
        decode kernel; a mismatch quarantines the page key in the block
        store and raises typed — never returns garbage.  Legacy footers
        without checksums verify trivially (unverified fallback)."""
        with _span("engine.storage_read") as sp:
            got = self._read_verified(reader, rg, columns, stats)
            if sp is not None:
                sp.set(rg=rg, pages=len(got),
                       bytes=sum(c.encoded_bytes() for c in got.values()))
        return got

    def _read_verified(self, reader, rg: int, columns,
                       stats: ScanStats) -> Dict[str, EncodedColumn]:
        if self.faults is not None:
            return self.faults.read(self, reader, rg, columns, stats)
        got = reader.read_encoded(rg, columns)
        if self.verify_checksums:
            meta = getattr(reader, "page_checksum_meta", None)
            if meta is not None:
                for name, col in got.items():
                    expect = meta(rg, name)
                    if expect is not None and page_checksum(col) != expect:
                        stats.corrupt_pages += 1
                        store = getattr(self.cache, "store", None)
                        if store is not None and hasattr(store, "quarantine"):
                            store.quarantine(
                                self.page_cache_key(reader, rg, name))
                        raise CorruptPageError(
                            f"{reader.path} rg={rg} column={name}: page "
                            "failed checksum verification",
                            table=reader.path, rg=rg, column=name)
        return got

    def _prepare_row_group(self, reader, rg: int, plan: ScanPlan,
                           pred: Optional[Expr], mode: str, stats: ScanStats,
                           pool: Optional[Dict] = None):
        """The per-row-group front half shared VERBATIM by the sequential
        and batched dispatch paths (bit-identity by construction, not by
        mirroring): the fully-resident shortcut probe, the encoded-page
        tier lookups + storage->NIC fetch, and fusability.

        Returns (n, L, resident, enc, fuse, fetched).  When `resident` the
        remaining fields are empty — no encoded byte moves.  Fusable plans
        never take the shortcut (their predicate column is never decoded,
        so its key can never be resident), which keeps the resident mask
        a `_row_mask` over exactly the arrays a direct scan would produce.
        """
        need = plan.all_columns()
        n = reader.row_group_meta(rg)["n"]
        L = padded_rows(n)
        if pool is not None or mode in ("preloaded", "prefiltered"):
            keys = [self.rg_cache_key(reader, rg, name) for name in need]
            if (pool is not None and all(k in pool for k in keys)) or (
                mode in ("preloaded", "prefiltered")
                and all(k in self.cache for k in keys)
            ):
                return n, L, True, {}, None, False

        # Encoded-page tier: under preloaded/prefiltered the store keeps
        # raw encoded pages too, so a repeat scan whose decoded columns
        # were evicted (or never fit) at least skips the storage->NIC
        # re-fetch.  Page hits contribute no `encoded_bytes` — nothing
        # crossed the hop — which is also what keeps them out of netsim's
        # fetch simulation.
        enc: Dict[str, EncodedColumn] = {}
        missing = list(need)
        if mode in ("preloaded", "prefiltered"):
            missing = []
            for name in need:
                page = self.cache.get(self.page_cache_key(reader, rg, name),
                                      stats=stats)
                if page is None:
                    missing.append(name)
                else:
                    enc[name] = page
                    stats.page_hits += 1
                    stats.page_hit_bytes += page.encoded_bytes()
        fetched = False
        if missing:
            got = self._storage_read(reader, rg, missing, stats)
            stats.encoded_bytes += sum(c.encoded_bytes() for c in got.values())
            enc.update(got)
            fetched = True
            if mode in ("preloaded", "prefiltered"):
                for name, col in got.items():
                    self.cache.put(self.page_cache_key(reader, rg, name), col,
                                   tier="encoded")
        fuse = None
        if self.backend in ("ref", "pallas", "auto"):
            fuse = self._fusable(pred, enc, plan.materialized_columns())
        return n, L, False, enc, fuse, fetched

    def _agg_skip(self, plan: ScanPlan, pred: Optional[Expr],
                  enc: Dict[str, EncodedColumn]) -> frozenset:
        """Aggregate value columns eligible for the fully-fused
        decode→aggregate kernel (ops.fused_agg_batch): BITPACK pages whose
        decoded values nothing else consumes — not projected, not the
        group key, not referenced by the predicate.  Those pages skip the
        decode bucket entirely; the unpack happens inside the aggregate
        kernel and the value column never exists outside VMEM.  Ungrouped
        plans only (the fused kernel has no group-id input), device
        backends only — the host baseline decodes then reduces."""
        if not plan.aggregates or plan.group_by is not None:
            return frozenset()
        if self.backend not in ("ref", "pallas", "auto"):
            return frozenset()
        keep = set(plan.columns) | set(expr_columns(pred))
        out = set()
        for spec in plan.aggregates:
            c = spec.column
            if c is None or c in keep:
                continue
            col = enc.get(c)
            if col is not None and col.encoding == Encoding.BITPACK:
                out.add(c)
        return frozenset(out)

    def _agg_skip_meta(self, plan: ScanPlan, pred: Optional[Expr],
                       meta_cols: Dict) -> frozenset:
        """`_agg_skip` predicted from footer metadata alone — the cost
        estimator's mirror (decode_footprint), column for column."""
        if not plan.aggregates or plan.group_by is not None:
            return frozenset()
        if self.backend not in ("ref", "pallas", "auto"):
            return frozenset()
        keep = set(plan.columns) | set(expr_columns(pred))
        out = set()
        for spec in plan.aggregates:
            c = spec.column
            if c is None or c in keep:
                continue
            cm = meta_cols.get(c)
            if cm is not None and cm.get("encoding") == "bitpack":
                out.add(c)
        return frozenset(out)

    @staticmethod
    def _fused_width(reader, rg: int, pred) -> int:
        """Footer dtype width of the fused predicate column — the honest
        per-row charge for its processed-but-unmaterialized decode work
        (mirrors decode_footprint's `L * itemsize` sizing; the old code
        hardcoded 4)."""
        cm = reader.row_group_meta(rg)["columns"][pred.column]
        return np.dtype(cm["dtype"]).itemsize

    @staticmethod
    def _charge_agg_page(stats: ScanStats, col: EncodedColumn, L: int) -> None:
        """Book a fused-aggregate page's processed-but-never-materialized
        decode work — the in-kernel unpack, charged at the decoded int32
        width under the page's encoding, exactly like the fused predicate
        column.  No decode launch: the aggregate launch is counted where
        it happens (ResumableScan._fold_agg)."""
        e = col.encoding.value
        stats.decode_work[e] = stats.decode_work.get(e, 0) + L * 4

    # ------------------------------------------------------------------
    # service hooks (metadata only — used by repro.datapath for admission
    # control and the adaptive offload policy)
    # ------------------------------------------------------------------
    def plan_cache_key(self, reader, plan: ScanPlan, blooms: Optional[Dict] = None,
                       tag=None):
        """Prefiltered-cache key for a whole scan: plan signature + backend +
        a digest of any probe-side bloom filters.  Blooms are per-caller
        state that the plan signature cannot see — leaving them out would
        let one tenant's semijoin result answer another tenant's probe.

        `tag` scopes the key beyond the plan: the scan fabric tags each
        pod sub-request with its owned row-group subset, so a cached
        sub-result can never answer a DIFFERENT subset of the same plan
        (e.g. after a drain re-hashes ownership).  None (every single-node
        caller) leaves the key exactly as before."""
        key = ("scan", reader.path, plan.signature(), self.backend)
        if blooms:
            digest = tuple(
                sorted(
                    (name, hashlib.sha1(np.asarray(bits).tobytes()).hexdigest()[:16])
                    for name, bits in blooms.items()
                )
            )
            key += (digest,)
        if tag is not None:
            key += (tag,)
        return key

    def estimate_selectivity(self, reader, plan: ScanPlan) -> float:
        """Estimated fraction of rows surviving the plan's predicate, from
        zone maps alone (uniform-within-row-group assumption)."""
        pred = bind_expr(plan.predicate, reader)
        return estimate_selectivity(reader, pred)

    def estimate_scan_bytes(self, reader, plan: ScanPlan, row_groups=None) -> int:
        """Encoded bytes the scan would pull over the storage->NIC hop,
        after zone-map pruning.  Metadata only.  Pass `row_groups` when the
        caller already pruned (the service does, at admission)."""
        if row_groups is None:
            pred = bind_expr(plan.predicate, reader)
            row_groups = prune_row_groups(reader, pred)
        need = plan.all_columns()
        total = 0
        for rg in row_groups:
            cols = reader.row_group_meta(rg)["columns"]
            total += sum(cols[c]["encoded_bytes"] for c in need if c in cols)
        return total

    def fused_column_meta(self, pred: Optional[Expr], meta_cols: Dict, projected) -> Optional[str]:
        """Predict, from footer metadata alone, the predicate column the
        fused decode+filter fast path would skip materializing — or None
        when the scan will not fuse.  Mirrors `_fusable` (which needs the
        encoded buffers) column for column: single integer Cmp on a
        BITPACK/int-DICT column outside the projection, device backends
        only.  `pred` must already be bound (string constants folded)."""
        if self.backend not in ("ref", "pallas", "auto"):
            return None
        if not isinstance(pred, Cmp) or pred.column in projected:
            return None
        cm = meta_cols.get(pred.column)
        if cm is None or cm.get("encoding") not in ("bitpack", "dict"):
            return None
        if cm["encoding"] == "dict" and np.dtype(cm["dtype"]).kind not in "iu":
            return None
        if pred_int_bounds(pred) is None:
            return None
        return pred.column

    def decode_footprint(self, reader, plan: ScanPlan, row_groups, pred=None) -> List[dict]:
        """Honest per-row-group decode footprint, metadata only: what the
        engine will MATERIALIZE (PACK_BLOCK-padded rows, true dtype widths,
        fused predicate column skipped) and what it will merely process.

        Returns one dict per row group:
            {"rg", "n", "rows": L, "columns": {name: {
                "nbytes": L * itemsize,   # decoded output if materialized
                "encoded_bytes": int,     # storage->NIC fetch size
                "encoding": str,          # footer encoding (cost-model key)
                "materialized": bool,     # False for the fused pred column
            }}}
        The datapath cost model (datapath/costmodel.py) prices this in
        decode-seconds; the scheduler's fetch simulation sizes transfers
        with it.  No data bytes move."""
        if pred is None:
            pred = bind_expr(plan.predicate, reader)
        need = plan.all_columns()
        proj = plan.materialized_columns()
        pred_cols = set(expr_columns(pred))
        # aggregate pushdown eligibility is metadata-visible: a group-by
        # domain over the kernels' MAX_GROUPS ceiling falls back to
        # scan-then-host-aggregate, which does no in-datapath agg work
        agg_push = bool(plan.aggregates) and (
            plan.group_by is None
            or group_domain(reader, plan.group_by) <= ops.MAX_GROUPS
        )
        agg_srcs = agg_merge.agg_sources(plan.aggregates) if agg_push else []
        out = []
        for rg in row_groups:
            meta = reader.row_group_meta(rg)
            cols = meta["columns"]
            L = padded_rows(meta["n"])
            fused_col = self.fused_column_meta(pred, cols, proj)
            askip = self._agg_skip_meta(plan, pred, cols) if agg_push else frozenset()
            fp = {}
            for c in need:
                if c not in cols:
                    continue
                cm = cols[c]
                if c == plan.group_by:
                    role = "group-key"
                elif c in {s for s in agg_srcs if s is not None}:
                    role = "agg-source"
                elif c in plan.columns:
                    role = "output"
                else:
                    role = "pred"  # decoded for the mask, dropped pre-DMA
                fp[c] = {
                    "nbytes": L * np.dtype(cm["dtype"]).itemsize,
                    "encoded_bytes": cm.get("encoded_bytes", 0),
                    "encoding": cm.get("encoding", "plain"),
                    # fused predicate columns and fused-aggregate pages are
                    # processed in-kernel, never materialized
                    "materialized": c != fused_col and c not in askip,
                    "role": role,
                }
            # one aggregate-launch pseudo-column per DECODED source (the
            # fused `askip` pages' reduction rides their entry above):
            # encoded_bytes 0 (nothing crosses the hop), nbytes L*4 of
            # processed-not-materialized work at the 'agg' rate + one
            # launch — exactly what ResumableScan._fold_agg books per
            # source per row group on the sequential path
            for src in agg_srcs:
                if src in askip:
                    continue
                if src is not None and src not in cols:
                    continue
                fp[f"agg:{src or '*'}"] = {
                    "nbytes": L * 4,
                    "encoded_bytes": 0,
                    "encoding": "agg",
                    "materialized": False,
                    "role": "agg",
                }
            out.append({"rg": rg, "n": meta["n"], "rows": L, "columns": fp})
        return out

    # ------------------------------------------------------------------
    # scan
    # ------------------------------------------------------------------
    def scan_row_group(
        self,
        reader,
        rg: int,
        plan: ScanPlan,
        pred: Optional[Expr],
        blooms: Dict[str, jax.Array],
        stats: ScanStats,
        pool: Optional[Dict] = None,
        offload: Optional[str] = None,
    ):
        """Decode + filter ONE row group; the entry point the service
        scheduler drives.  `pred` must already be bound (bind_expr).

        Returns (cols, mask): `cols` maps each needed column to its decoded
        array — None for a predicate-only column skipped under fusion, or
        the raw EncodedColumn for an aggregate value page the fused
        decode→aggregate kernel consumes without decoding (`_agg_skip`) —
        and `mask` is (L,) bool including row validity.  `pool` is an
        optional tick-level decode pool shared across coalesced scans.
        """
        need = plan.all_columns()
        proj = plan.materialized_columns()
        mode = offload or self.offload
        # front half (resident probe / page tier / fetch / fusability) is
        # the exact code the batched path runs — _prepare_row_group
        n, L, resident, enc, fuse, _fetched = self._prepare_row_group(
            reader, rg, plan, pred, mode, stats, pool=pool
        )
        if resident:
            # fully resident: every needed column already decoded in the
            # tick pool (coalescing) or, under preloaded/prefiltered, in
            # the BlockCache -> no encoded fetch at all
            cols = {}
            for name in need:
                arr, _ = self._decode_column(
                    reader, rg, name, None, L, offload=offload, pool=pool, stats=stats
                )
                cols[name] = arr
            return cols, self._row_mask(pred, cols, blooms, n, L, rg)

        askip = self._agg_skip(plan, pred, enc)
        cols: Dict[str, Optional[jax.Array]] = {}
        if fuse is not None:
            stats.fused = True
            lo, hi = fuse
            fe = enc[pred.column].encoding.value
            # processed-but-never-materialized decode work, charged at the
            # column's TRUE footer dtype width (decode_footprint sizes the
            # estimate the same way, so estimate == actual stays exact for
            # fused scans whatever the predicate column's dtype)
            stats.decode_work[fe] = (
                stats.decode_work.get(fe, 0) + L * self._fused_width(reader, rg, pred)
            )
            stats.kernel_launches += 1
            with _span("engine.decode") as sp:
                fmask, _ = ops.fused_scan(
                    jnp.asarray(enc[pred.column].buffers["packed"]),
                    enc[pred.column].k,
                    lo,
                    hi,
                    backend=self.backend,
                )
                if sp is not None:
                    sp.set(rg=rg, encoding=fe, fused=True, pages=1, rows=L)
            for name in proj:
                if name in askip:
                    self._charge_agg_page(stats, enc[name], L)
                    cols[name] = enc[name]
                    continue
                arr, _ = self._decode_column(
                    reader, rg, name, enc[name], L, offload=offload, pool=pool, stats=stats
                )
                cols[name] = arr
            mask = self._row_mask(pred, cols, blooms, n, L, rg, given=fmask)
        else:
            for name in need:
                if name in askip:
                    self._charge_agg_page(stats, enc[name], L)
                    cols[name] = enc[name]
                    continue
                arr, _ = self._decode_column(
                    reader, rg, name, enc[name], L, offload=offload, pool=pool, stats=stats
                )
                cols[name] = arr
            mask = self._row_mask(pred, cols, blooms, n, L, rg)

        for name in need:
            cols.setdefault(name, None)  # predicate-only column under fusion
        return cols, mask

    # ------------------------------------------------------------------
    # batched multi-row-group scan (bucketed kernel launches)
    # ------------------------------------------------------------------
    def scan_row_groups_batched(
        self,
        reader,
        rgs,
        plan: ScanPlan,
        pred: Optional[Expr],
        blooms: Dict[str, jax.Array],
        stats: ScanStats,
        pool: Optional[Dict] = None,
        offload: Optional[str] = None,
    ):
        """Decode + filter MANY row groups with bucketed batch launches —
        bit-identical to calling `scan_row_group` per group, in order.

        Compatible pages are stacked along the block axis and decoded in
        ONE kernel launch per (encoding, k, dtype) bucket (`kernels.ops`
        `*_batch`), bucket-padded to power-of-two block counts so jit
        traces are reused across slices.  Everything that is NOT the
        kernel launch — residency lookups, page-tier fetches, stats
        increments, pool/cache puts — runs through the exact sequential
        code in strict (row group, column) order, so pool budgets and
        accounting cannot drift.  (The one documented divergence: all
        encoded fetches happen before any decoded put, so a cache evicting
        PRE-RESIDENT entries mid-slice can shift hit/fresh counters; the
        results stay bit-identical — a vanished entry is re-fetched and
        re-decoded singly.)

        Returns (per_rg, fetched): `per_rg` is [(cols, mask)] in `rgs`
        order with the same contract as `scan_row_group`; `fetched` lists
        the row groups that pulled encoded bytes over the storage->NIC hop
        (the scheduler feeds exactly these to the netsim pipeline).
        """
        rgs = list(rgs)
        mode = offload or self.offload
        if self.backend == "host" or len(rgs) <= 1:
            # the host baseline decodes on the CPU (nothing to batch-launch)
            # and a single group has nothing to bucket: the sequential path
            # IS the batched path
            per_rg, fetched = [], []
            for rg in rgs:
                enc0 = stats.encoded_bytes
                per_rg.append(self.scan_row_group(
                    reader, rg, plan, pred, blooms, stats, pool=pool, offload=offload
                ))
                if stats.encoded_bytes > enc0:
                    fetched.append(rg)
            return per_rg, fetched

        need = plan.all_columns()
        proj = plan.materialized_columns()

        # -- phase A: residency, page-tier fetch, fusability (rg order) ----
        # the front half is _prepare_row_group — the SAME code the
        # sequential scan_row_group runs, so the two paths cannot drift
        with _span("engine.prepare") as sp:
            slots = []
            fetched: List[int] = []
            for rg in rgs:
                n, L, resident, enc, fuse, did_fetch = self._prepare_row_group(
                    reader, rg, plan, pred, mode, stats, pool=pool
                )
                askip = self._agg_skip(plan, pred, enc) if not resident else frozenset()
                slot = {"rg": rg, "n": n, "L": L, "resident": resident,
                        "enc": enc, "fuse": fuse, "askip": askip, "decode": []}
                slots.append(slot)
                if did_fetch:
                    fetched.append(rg)
                if resident:
                    continue
                # columns needing a fresh decode — non-mutating residency peek
                # (presence checks touch no LRU order and count no hits; the
                # counting lookups run in the finalize pass, in order).  Fused-
                # aggregate pages (`askip`) never enter the decode buckets: the
                # aggregate kernel unpacks them in VMEM.
                for name in (proj if fuse is not None else need):
                    if name in askip:
                        continue
                    key = self.rg_cache_key(reader, rg, name)
                    if pool is not None and key in pool:
                        continue
                    if mode in ("preloaded", "prefiltered") and key in self.cache:
                        continue
                    slot["decode"].append(name)
            if sp is not None:
                sp.set(rgs=len(rgs), fetched=len(fetched))

        # -- phase B: bucket compatible pages, one launch per bucket -------
        decoded, fmasks = self._launch_buckets(slots, pred, stats)

        # bloom semijoin probes ride the batched pass too: every slice
        # page's keys probe in ONE stacked launch per bloom filter
        bloom_by_rg = self._batch_bloom_probe(slots, pred, blooms, decoded)

        # -- finalize (strict rg order): hits, puts, stats, masks ----------
        with _span("engine.finalize") as sp:
            per_rg = []
            for slot in slots:
                rg, n, L = slot["rg"], slot["n"], slot["L"]
                if slot["resident"]:
                    cols = {}
                    for name in need:
                        cols[name] = self._serve_resident(
                            reader, rg, name, L, mode, offload, pool, stats, fetched
                        )
                    per_rg.append((cols, self._row_mask(pred, cols, blooms, n, L, rg)))
                    continue
                enc = slot["enc"]
                askip = slot["askip"]
                cols = {}
                if slot["fuse"] is not None:
                    stats.fused = True
                    fe = enc[pred.column].encoding.value
                    stats.decode_work[fe] = (
                        stats.decode_work.get(fe, 0)
                        + L * self._fused_width(reader, rg, pred)
                    )
                    for name in proj:
                        if name in askip:
                            self._charge_agg_page(stats, enc[name], L)
                            cols[name] = enc[name]
                            continue
                        arr, _ = self._decode_column(
                            reader, rg, name, enc[name], L, offload=offload,
                            pool=pool, stats=stats, precomputed=decoded.get((0, rg, name)),
                        )
                        cols[name] = arr
                    mask = self._row_mask(pred, cols, blooms, n, L, rg,
                                          given=fmasks[(0, rg)])
                else:
                    for name in need:
                        if name in askip:
                            self._charge_agg_page(stats, enc[name], L)
                            cols[name] = enc[name]
                            continue
                        arr, _ = self._decode_column(
                            reader, rg, name, enc[name], L, offload=offload,
                            pool=pool, stats=stats, precomputed=decoded.get((0, rg, name)),
                        )
                        cols[name] = arr
                    mask = self._row_mask(pred, cols, blooms, n, L, rg,
                                          bmasks=bloom_by_rg.get((0, rg)))
                for name in need:
                    cols.setdefault(name, None)
                per_rg.append((cols, mask))
            if sp is not None:
                sp.set(rgs=len(rgs))
        return per_rg, fetched

    def _batch_bloom_probe(self, slots, pred, blooms, decoded) -> Dict[tuple, Dict]:
        """Stack every freshly-decoded slice page's keys and probe each
        bloom filter in ONE `ops.bloom_probe` dispatch (the semijoin leg
        of the fused bucket pass).  Returns {(item, rg): {(name, column):
        (L,) mask}} for `_row_mask` to consume; pages served from the pool or
        cache at finalize time are absent and fall back to the per-row-
        group probe — bit-identical either way, the probe is elementwise.
        """
        if pred is None or self.backend == "host" or not blooms:
            return {}
        probes = {(p.name, p.column): p for p in _mask_shape(pred)[3]
                  if p.name in blooms}
        out: Dict[tuple, Dict] = {}
        with _span("engine.bloom") as sp:
            for (name, column), probe in sorted(probes.items()):
                entries = []  # (item, rg, L, nblk)
                keys = []
                for slot in slots:
                    if slot["resident"] or slot["fuse"] is not None:
                        continue
                    item = slot.get("item", 0)
                    arr = decoded.get((item, slot["rg"], column))
                    if arr is None:
                        continue  # pool/cache-served at finalize: per-rg probe
                    L = slot["L"]
                    entries.append((item, slot["rg"], L, L // RLE_OUT_BLOCK))
                    keys.append(arr.astype(jnp.int32).reshape(-1, RLE_OUT_BLOCK))
                if not entries:
                    continue
                m = ops.bloom_probe(
                    jnp.concatenate(keys, axis=0), blooms[name], probe.n_hashes,
                    backend=self.backend,
                )
                s = 0
                for item, rg, L, nblk in entries:
                    out.setdefault((item, rg), {})[(name, column)] = (
                        m[s:s + nblk].reshape(-1)[:L]
                    )
                    s += nblk
            if sp is not None:
                sp.set(filters=len(probes))
        return out

    def _serve_resident(self, reader, rg, name, L, mode, offload, pool, stats,
                        fetched):
        """Finalize-time lookup for a phase-A-resident column.  If the
        entry was evicted between the phases (cache pressure from this
        slice's own puts), fall back to a fetch + single decode — the
        sequential path would have seen the same miss at its later
        residency check, so results stay identical."""
        key = self.rg_cache_key(reader, rg, name)
        still = (pool is not None and key in pool) or (
            mode in ("preloaded", "prefiltered") and key in self.cache
        )
        col = None
        if not still:
            # same lookup ladder as _prepare_row_group: the encoded-page
            # tier first — a page still resident contributes page_hit
            # bytes, NOT encoded_bytes (nothing re-crosses the hop, so
            # netsim must not price a transfer)
            if mode in ("preloaded", "prefiltered"):
                col = self.cache.get(self.page_cache_key(reader, rg, name),
                                     stats=stats)
                if col is not None:
                    stats.page_hits += 1
                    stats.page_hit_bytes += col.encoded_bytes()
            if col is None:
                col = self._storage_read(reader, rg, [name], stats)[name]
                stats.encoded_bytes += col.encoded_bytes()
                if rg not in fetched:
                    fetched.append(rg)
                if mode in ("preloaded", "prefiltered"):
                    self.cache.put(self.page_cache_key(reader, rg, name), col,
                                   tier="encoded")
        arr, _ = self._decode_column(
            reader, rg, name, col, L, offload=offload, pool=pool, stats=stats
        )
        return arr

    def _launch_buckets(self, slots, pred, stats):
        """Group every pending (row group, column) page by its launch
        signature and decode each bucket in ONE device dispatch.  Returns
        ({(item, rg, name): decoded (L,) array}, {(item, rg): fused mask}).

        Slots from a single scan leave `item`/`pred`/`stats` unset (they
        default to 0 and the arguments).  The cross-request group path
        (`scan_group_batched`) sets all three per slot: pages from MANY
        requests stack into the same buckets, each slot's fusability uses
        its own predicate, and a bucket's launch/pad counters are charged
        to the stats of its first contributing request (reconciliation
        refunds the others their share — kernel_launches is the one field
        batching is allowed to move)."""
        buckets: Dict[tuple, List[dict]] = {}
        fused_items: Dict[int, List[dict]] = {}
        for slot in slots:
            if slot["resident"]:
                continue
            rg, L = slot["rg"], slot["L"]
            item = slot.get("item", 0)
            spred = slot.get("pred", pred)
            sstats = slot.get("stats", stats)
            if slot["fuse"] is not None:
                col = slot["enc"][spred.column]
                lo, hi = slot["fuse"]
                fused_items.setdefault(col.k, []).append(
                    {"rg": rg, "L": L, "packed": col.buffers["packed"],
                     "lo": lo, "hi": hi, "item": item, "stats": sstats}
                )
            for name in slot["decode"]:
                col = slot["enc"][name]
                e = col.encoding
                if e == Encoding.PLAIN:
                    bkey = ("plain", str(col.buffers["plain"].dtype))
                elif e == Encoding.BITPACK:
                    bkey = ("bitpack", col.k)
                elif e == Encoding.DICT:
                    d = col.buffers["dictionary"]
                    bkey = ("dict", col.k,
                            "int32" if d.dtype.kind in "iu" else str(d.dtype))
                elif e == Encoding.DELTA:
                    bkey = ("delta", col.k)
                else:
                    bkey = ("rle", str(col.buffers["rle_values"].dtype))
                buckets.setdefault(bkey, []).append(
                    {"rg": rg, "name": name, "col": col, "L": L,
                     "item": item, "stats": sstats}
                )

        be = self.backend
        decoded: Dict[tuple, jax.Array] = {}
        for bkey, items in buckets.items():
            bstats = items[0]["stats"]
            with _span("engine.decode") as sp:
                if sp is not None:
                    launches0 = bstats.kernel_launches
                    pad0 = bstats.batch_pad_blocks
                decoded.update(self._decode_bucket(bkey, items, be, bstats))
                if sp is not None:
                    sp.set(bucket="/".join(str(p) for p in bkey), pages=len(items),
                           launches=bstats.kernel_launches - launches0,
                           pad_blocks=bstats.batch_pad_blocks - pad0)
        fmasks: Dict[tuple, jax.Array] = {}
        for k, items in sorted(fused_items.items()):
            bstats = items[0]["stats"]
            with _span("engine.decode") as sp:
                pad0 = bstats.batch_pad_blocks
                with _span("engine.stack") as st:
                    packed = np.concatenate([it["packed"] for it in items], axis=0)
                    blocks = [it["packed"].shape[0] for it in items]
                    lo = np.concatenate(
                        [np.full(b, it["lo"], np.int32) for b, it in zip(blocks, items)])
                    hi = np.concatenate(
                        [np.full(b, it["hi"], np.int32) for b, it in zip(blocks, items)])
                    if st is not None:
                        st.set(bucket=f"fused/k{k}", pages=len(items))
                mask = ops.fused_scan_batch(packed, k, lo, hi, backend=be)
                bstats.kernel_launches += 1
                bstats.batch_pad_blocks += ops.bucket_blocks(packed.shape[0]) - packed.shape[0]
                cols = self._split(mask, blocks, [it["L"] for it in items])
                for it, m in zip(items, cols):
                    fmasks[(it["item"], it["rg"])] = m
                if sp is not None:
                    sp.set(bucket=f"fused/k{k}", pages=len(items), fused=True,
                           launches=1, pad_blocks=bstats.batch_pad_blocks - pad0)
        return decoded, fmasks

    @staticmethod
    def _split(out, blocks, lengths) -> List[jax.Array]:
        """Cut one bucket's stacked output back into per-page (L,) columns,
        bit-identical to the sequential pad-to-L / truncate-to-L: page i
        owns the next `blocks[i]` rows of `out`'s leading axis and is
        `lengths[i]` long.  One `_split_program` call per distinct L."""
        per = out.size // out.shape[0]  # elements per leading-axis row
        sizes = np.asarray(blocks, np.int64) * per
        starts = np.cumsum(sizes) - sizes
        by_len: Dict[int, List[int]] = {}
        for i, L in enumerate(lengths):
            by_len.setdefault(L, []).append(i)
        cols: List[jax.Array] = [None] * len(lengths)
        with _span("engine.split") as sp:
            traces0 = _SPLIT_TRACES[0] if sp is not None else 0
            for L, idx in by_len.items():
                n = ops.bucket_blocks(len(idx))
                st = np.zeros(n, np.int32)
                sz = np.zeros(n, np.int32)
                st[:len(idx)] = starts[idx]
                sz[:len(idx)] = sizes[idx]
                for i, col in zip(idx, _split_program(out, st, sz, L=L)):
                    cols[i] = col
            if sp is not None:
                sp.set(pages=len(lengths), programs=len(by_len),
                       traces=_SPLIT_TRACES[0] - traces0)
        return cols

    def _split_items(self, out, items, blocks) -> Dict[tuple, jax.Array]:
        """`_split`, keyed (item, rg, column) as `decoded` holds pages."""
        cols = self._split(out, blocks, [it["L"] for it in items])
        return {(it.get("item", 0), it["rg"], it["name"]): c for it, c in zip(items, cols)}

    def _decode_bucket(self, bkey, items, be, stats) -> Dict[tuple, jax.Array]:
        """One bucket's launch: the host stacks its pages (`engine.stack`),
        one counted launch decodes them (`ops.dispatch`), and the output is
        sliced back into pages (`engine.split`)."""
        kind = bkey[0]
        if kind == "plain":
            # one host gather + ONE device put for the whole bucket, padded
            # to the bucket ladder in PACK_BLOCK rows (every L is a multiple)
            # like the kernels' block axis, so the split program keys on the
            # bucket and not on the raw page mix
            with _span("engine.stack") as sp:
                lengths = [it["L"] for it in items]
                nblk = sum(lengths) // PACK_BLOCK
                buf = np.zeros((ops.bucket_blocks(nblk) * PACK_BLOCK,), dtype=np.dtype(bkey[1]))
                s = 0
                for it in items:
                    v = it["col"].buffers["plain"]
                    buf[s:s + v.shape[0]] = v
                    s += it["L"]
                if sp is not None:
                    sp.set(bucket="/".join(str(p) for p in bkey), pages=len(items))
            out = ops.device_put(buf)
            stats.kernel_launches += 1
            stats.batch_pad_blocks += ops.bucket_blocks(nblk) - nblk
            return self._split_items(out, items, lengths)
        stats.kernel_launches += 1
        with _span("engine.stack") as sp:
            if kind == "rle":
                values = np.concatenate(
                    [it["col"].buffers["rle_values"] for it in items], axis=0)
                ends = np.concatenate(
                    [it["col"].buffers["rle_ends"] for it in items], axis=0)
                blocks = [it["col"].buffers["rle_values"].shape[0] for it in items]
            else:
                packed = np.concatenate(
                    [it["col"].buffers["packed"] for it in items], axis=0)
                blocks = [it["col"].buffers["packed"].shape[0] for it in items]
            if kind == "dict":
                dicts_np = [
                    d.astype(np.int32) if d.dtype.kind in "iu" else d
                    for d in (it["col"].buffers["dictionary"] for it in items)
                ]
                # the dictionary axis is bucket-padded like the block axis:
                # a raw per-call max width would re-trace the jitted batch
                # decode once per distinct cardinality mix (per-block clip
                # bounds make the zero padding unreachable, so this is free
                # bit-wise)
                dmax = ops.bucket_blocks(max(d.shape[0] for d in dicts_np))
                dicts = np.zeros((len(items), dmax), dtype=np.dtype(bkey[2]))
                sizes = np.zeros((len(items),), np.int32)
                for i, d in enumerate(dicts_np):
                    dicts[i, : d.shape[0]] = d
                    sizes[i] = d.shape[0]
                page = np.concatenate(
                    [np.full(b, i, np.int32) for i, b in enumerate(blocks)])
            elif kind == "delta":
                bases = np.concatenate(
                    [it["col"].buffers["bases"].astype(np.int32) for it in items])
            if sp is not None:
                sp.set(bucket="/".join(str(p) for p in bkey), pages=len(items))
        if kind == "bitpack":
            out = ops.bitunpack_batch(packed, bkey[1], backend=be)
        elif kind == "dict":
            out = ops.dict_decode_batch(packed, dicts, sizes, page, bkey[1], backend=be)
        elif kind == "delta":
            out = ops.delta_decode_batch(packed, bases, bkey[1], backend=be)
        else:  # rle
            out = ops.rle_decode_batch(values, ends, backend=be)
        stats.batch_pad_blocks += ops.bucket_blocks(sum(blocks)) - sum(blocks)
        return self._split_items(out, items, blocks)

    # ------------------------------------------------------------------
    # cross-request bucket stacking (DESIGN.md §15)
    # ------------------------------------------------------------------
    def scan_group_batched(self, items, pool=None):
        """Decode the slices of SEVERAL coalesced scans over one table in
        a single bucketed launch pass.

        Each item is one request's slice: {"reader", "rgs", "plan",
        "pred", "blooms", "stats", "offload", "owner", "trace"} — "trace"
        being the (tracer, request trace, request id) the scheduler
        publishes through `trace.set_slice` — the
        per-request state `ResumableScan.advance_batched` would have
        passed to `scan_row_groups_batched`.  Returns [(per_rg, fetched)]
        aligned with items, each element carrying that request's own
        columns/masks and fetched row groups, ready for
        `ResumableScan.ingest_batched`.

        Where this beats per-request batching: before this entry point,
        same-tick requests over the same table launched their buckets
        separately and shared decodes only through pool hits at finalize
        time.  Here every request's pages stack into ONE set of buckets
        (fewer dispatches), and a page two requests both need decodes
        exactly once — the later request skips it in phase A (`pending`)
        and serves it as a pool hit at its finalize, which is precisely
        the accounting the sequential order would have produced.

        Attribution rules: `pool.owner` and the trace slice context are
        rebound per item around its phase-A and finalize work, so window
        billing and the flight recorder see per-request activity; a
        stacked bucket's launch is charged to its first contributor's
        stats (WFQ reconciliation refunds the difference)."""
        tr_mod = TRACE

        def _ctx(it):
            if tr_mod is not None:
                t = it.get("trace")
                tr_mod.set_slice(*(t if t else (None, None)))

        def _owner(it):
            if pool is not None and hasattr(pool, "owner"):
                pool.owner = it.get("owner", pool.owner)

        if self.backend == "host":
            # the host baseline has no device launches to stack: run each
            # request through the normal batched entry (which itself falls
            # back to sequential on host), sharing only the pool
            out = []
            for it in items:
                _owner(it)
                _ctx(it)
                out.append(self.scan_row_groups_batched(
                    it["reader"], it["rgs"], it["plan"], it["pred"],
                    it["blooms"], it["stats"], pool=pool, offload=it["offload"],
                ))
            if tr_mod is not None:
                tr_mod.set_slice(None, None)
            return out

        # -- phase A per item, in order: residency / page tier / fetch ----
        slots_by_item: List[List[dict]] = []
        fetched_by_item: List[List[int]] = [[] for _ in items]
        pending: set = set()  # keys an EARLIER item decodes in this pass
        with _span("engine.prepare") as sp:
            for i, it in enumerate(items):
                reader, plan, pred = it["reader"], it["plan"], it["pred"]
                mode = it["offload"] or self.offload
                stats = it["stats"]
                need = plan.all_columns()
                proj = plan.materialized_columns()
                _owner(it)
                _ctx(it)
                slots = []
                for rg in it["rgs"]:
                    keys = [self.rg_cache_key(reader, rg, name) for name in need]
                    if (pool is not None
                            and all(k in pool or k in pending for k in keys)
                            and any(k in pending for k in keys)):
                        # every needed column is pooled or scheduled by an
                        # earlier request in THIS pass: by this item's
                        # finalize (strict item order) they are pool entries
                        # — the same full residency the sequential order
                        # would have seen after the earlier request's puts
                        n = reader.row_group_meta(rg)["n"]
                        slots.append({"rg": rg, "n": n, "L": padded_rows(n),
                                      "resident": True, "enc": {}, "fuse": None,
                                      "askip": frozenset(), "decode": [],
                                      "item": i, "pred": pred, "stats": stats})
                        continue
                    n, L, resident, enc, fuse, did_fetch = self._prepare_row_group(
                        reader, rg, plan, pred, mode, stats, pool=pool
                    )
                    askip = self._agg_skip(plan, pred, enc) if not resident else frozenset()
                    slot = {"rg": rg, "n": n, "L": L, "resident": resident,
                            "enc": enc, "fuse": fuse, "askip": askip, "decode": [],
                            "item": i, "pred": pred, "stats": stats}
                    slots.append(slot)
                    if did_fetch:
                        fetched_by_item[i].append(rg)
                    if resident:
                        continue
                    for name in (proj if fuse is not None else need):
                        if name in askip:
                            continue  # fused-aggregate page: unpacked in-kernel
                        key = self.rg_cache_key(reader, rg, name)
                        if pool is not None and key in pool:
                            continue
                        if mode in ("preloaded", "prefiltered") and key in self.cache:
                            continue
                        if pool is not None and key in pending:
                            continue  # an earlier request decodes it; our
                            # finalize serves it as a pool hit
                        slot["decode"].append(name)
                        pending.add(key)
                slots_by_item.append(slots)
            if sp is not None:
                sp.set(rgs=sum(len(it["rgs"]) for it in items), requests=len(items))

        # -- phase B: ONE bucket pass across every request's pages --------
        # (bucket launch spans attribute to the first item the recorder
        # traces, else to the first item)
        if tr_mod is not None:
            ctxs = [it["trace"] for it in items if it.get("trace")]
            first = next((t for t in ctxs if t[1] is not None),
                         ctxs[0] if ctxs else None)
            tr_mod.set_slice(*(first if first else (None, None)))
        all_slots = [s for slots in slots_by_item for s in slots]
        decoded, fmasks = self._launch_buckets(all_slots, None, None)
        if tr_mod is not None:
            tr_mod.set_slice(None, None)

        # -- finalize per item, in order: hits, puts, stats, masks --------
        with _span("engine.finalize") as sp:
            out = []
            for i, it in enumerate(items):
                reader, plan, pred = it["reader"], it["plan"], it["pred"]
                blooms, stats = it["blooms"], it["stats"]
                mode = it["offload"] or self.offload
                offload = it["offload"]
                need = plan.all_columns()
                proj = plan.materialized_columns()
                _owner(it)
                _ctx(it)
                per_rg = []
                for slot in slots_by_item[i]:
                    rg, n, L = slot["rg"], slot["n"], slot["L"]
                    if slot["resident"]:
                        cols = {}
                        for name in need:
                            cols[name] = self._serve_resident(
                                reader, rg, name, L, mode, offload, pool, stats,
                                fetched_by_item[i],
                            )
                        per_rg.append((cols, self._row_mask(pred, cols, blooms, n, L, rg)))
                        continue
                    enc = slot["enc"]
                    askip = slot["askip"]
                    cols = {}
                    if slot["fuse"] is not None:
                        stats.fused = True
                        fe = enc[pred.column].encoding.value
                        stats.decode_work[fe] = (
                            stats.decode_work.get(fe, 0)
                            + L * self._fused_width(reader, rg, pred)
                        )
                        for name in proj:
                            if name in askip:
                                self._charge_agg_page(stats, enc[name], L)
                                cols[name] = enc[name]
                                continue
                            arr, _ = self._decode_column(
                                reader, rg, name, enc[name], L, offload=offload,
                                pool=pool, stats=stats,
                                precomputed=decoded.get((i, rg, name)),
                            )
                            cols[name] = arr
                        mask = self._row_mask(pred, cols, blooms, n, L, rg,
                                              given=fmasks[(i, rg)])
                    else:
                        for name in need:
                            if name in askip:
                                self._charge_agg_page(stats, enc[name], L)
                                cols[name] = enc[name]
                                continue
                            arr, _ = self._decode_column(
                                reader, rg, name, enc[name], L, offload=offload,
                                pool=pool, stats=stats,
                                precomputed=decoded.get((i, rg, name)),
                            )
                            cols[name] = arr
                        mask = self._row_mask(pred, cols, blooms, n, L, rg)
                    for name in need:
                        cols.setdefault(name, None)
                    per_rg.append((cols, mask))
                out.append((per_rg, fetched_by_item[i]))
            if sp is not None:
                sp.set(rgs=sum(len(it["rgs"]) for it in items), requests=len(items))
        if tr_mod is not None:
            tr_mod.set_slice(None, None)
        return out

    def scan(
        self,
        reader,
        plan: ScanPlan,
        blooms: Optional[Dict[str, jax.Array]] = None,
        offload: Optional[str] = None,
        pool: Optional[Dict] = None,
        row_groups=None,
        batched: bool = False,
    ) -> ScanResult:
        """Full pushed-down scan.  `offload` overrides the engine-wide mode
        for this call (the adaptive policy's per-request knob); `pool` is a
        tick-level decode pool shared across coalesced scans; `row_groups`
        skips re-pruning when the caller already did it (service admission).

        Implemented as a ResumableScan driven to completion in one shot, so
        a scan the service slices across ticks is structurally guaranteed to
        produce the same result as a direct call.  `batched=True` routes the
        row-group work through `scan_row_groups_batched` (bucketed batch
        kernel launches) instead of one launch per (row group, column)."""
        rs = ResumableScan(
            self, reader, plan, blooms=blooms, offload=offload, row_groups=row_groups
        )
        if rs.result is None:
            if batched:
                rs.advance_batched(tuple(rs.pending), pool=pool)
            else:
                rs.advance(tuple(rs.pending), pool=pool)
        return rs.result

    # ------------------------------------------------------------------
    def resumable_scan(
        self,
        reader,
        plan: ScanPlan,
        blooms: Optional[Dict[str, jax.Array]] = None,
        offload: Optional[str] = None,
        row_groups=None,
    ) -> "ResumableScan":
        """A scan that can be advanced a few row groups at a time — the
        service scheduler's preemption point (DESIGN.md §9)."""
        return ResumableScan(
            self, reader, plan, blooms=blooms, offload=offload, row_groups=row_groups
        )

    # ------------------------------------------------------------------
    def _compact(self, cols: Dict[str, jax.Array], mask: jax.Array):
        """Global stream compaction: per-block kernel compaction + stitch."""
        L = mask.shape[0]
        nblk = L // RLE_OUT_BLOCK
        m2 = mask.reshape(nblk, RLE_OUT_BLOCK)
        out = {}
        counts = None
        for name, arr in cols.items():
            comp, counts = ops.filter_compact(
                arr.reshape(nblk, RLE_OUT_BLOCK), m2, backend=self.backend if self.backend != "host" else "ref"
            )
            offs = jnp.cumsum(counts) - counts
            slot = jnp.arange(RLE_OUT_BLOCK, dtype=jnp.int32)[None, :]
            valid = slot < counts[:, None]
            tgt = jnp.where(valid, offs[:, None] + slot, L)
            flat = jnp.zeros((L,), arr.dtype).at[tgt.reshape(-1)].set(
                comp.reshape(-1), mode="drop"
            )
            out[name] = flat
        total = jnp.sum(counts)
        new_mask = jnp.arange(L) < total
        return out, new_mask, total


class ResumableScan:
    """One pushed-down scan, resumable at row-group granularity.

    The service's fair scheduler slices big scans across ticks: each tick it
    calls `advance(next_few_row_groups, pool=tick_pool)` and, once the last
    group lands, `result` holds the assembled ScanResult.  The per-row-group
    work and the final assembly (concatenate → count → optional compaction →
    prefiltered-cache put) are the exact code path `DatapathEngine.scan`
    runs, so sliced results are bit-identical to single-shot scans no matter
    where the preemption points fall.

    `result` is non-None immediately after construction when no row-group
    work is needed: a prefiltered-cache hit, or every group pruned.
    """

    def __init__(
        self,
        engine: DatapathEngine,
        reader,
        plan: ScanPlan,
        blooms: Optional[Dict[str, jax.Array]] = None,
        offload: Optional[str] = None,
        row_groups=None,
        scan_tag=None,
    ):
        assert offload in (None, "raw", "preloaded", "prefiltered",
                           "pre-aggregated"), offload
        self.engine = engine
        self.reader = reader
        self.plan = plan
        self.offload = offload or engine.offload
        self.blooms = blooms or {}
        # fabric sub-requests tag their prefiltered key with the owned
        # row-group subset (plan_cache_key `tag`): identical subsets hit,
        # different subsets (e.g. post-drain re-hash) can never collide
        self.scan_tag = scan_tag
        self.stats = ScanStats(row_groups_total=reader.n_row_groups, rows_total=reader.n_rows)
        self.result: Optional[ScanResult] = None

        # operator pushdown (DESIGN.md §16): the scan reduces to per-group
        # accumulators instead of rows.  Beyond the kernels' MAX_GROUPS
        # ceiling it falls back to accumulating the decoded value rows and
        # reducing host-side at finish — through the same block math and
        # fold order, so results stay bit-identical either way.
        self._agg = bool(plan.aggregates)
        if self._agg:
            assert not plan.compact, "aggregate scans return no rows to compact"
            self._n_groups = (
                group_domain(reader, plan.group_by)
                if plan.group_by is not None else 1
            )
            self._agg_push = self._n_groups <= ops.MAX_GROUPS
            # src -> {rg: ColPartial}; None source = bare count(*)
            self._agg_parts: Dict[Optional[str], Dict[int, object]] = {}
        else:
            self._agg_push = False
        if self.offload in ("prefiltered", "pre-aggregated"):
            key = engine.plan_cache_key(reader, plan, self.blooms, tag=scan_tag)
            hit = engine.cache.get(key)
            if hit is not None:
                self.stats.cache_hit = True
                self.stats.rows_out = int(hit.count)
                self.stats.result_bytes = hit.stats.result_bytes
                self._pending: List[int] = []
                self.result = ScanResult(
                    hit.columns, hit.mask, hit.count, self.stats,
                    aggregates=hit.aggregates, agg_partials=hit.agg_partials,
                )
                return

        self.pred = bind_expr(plan.predicate, reader)
        rgs = list(row_groups) if row_groups is not None else prune_row_groups(reader, self.pred)
        self.stats.row_groups_scanned = len(rgs)
        self._rgs = rgs
        self._pending = list(rgs)
        self._need = plan.all_columns()
        self._per_rg_cols: Dict[str, List[Optional[jax.Array]]] = {c: [] for c in self._need}
        self._per_rg_mask: List[jax.Array] = []
        if not self._pending:  # everything pruned: assemble the empty result
            self._finish()

    @property
    def pending(self) -> tuple:
        """Row groups not yet scanned, in scan order."""
        return tuple(self._pending)

    def advance(self, row_groups, pool: Optional[Dict] = None) -> Optional[ScanResult]:
        """Scan the given row groups (must be the next groups in order) and
        fold them into the accumulated partial result.  `pool` is the
        current tick's shared DecodePool.  Returns the final ScanResult once
        the last group is folded in, else None."""
        assert self.result is None, "scan already complete"
        for rg in row_groups:
            assert self._pending and rg == self._pending[0], (
                f"row group {rg} dispatched out of order (next is "
                f"{self._pending[0] if self._pending else None})"
            )
            self._pending.pop(0)
            cols, mask = self.engine.scan_row_group(
                self.reader, rg, self.plan, self.pred, self.blooms, self.stats,
                pool=pool, offload=self.offload,
            )
            self._fold([rg], [(cols, mask)])
        if not self._pending:
            self._finish()
        return self.result

    def advance_batched(self, row_groups, pool: Optional[Dict] = None):
        """`advance`, but through the engine's bucketed batch-decode path:
        the slice's row groups are fetched, bucketed by (encoding, k,
        dtype) and decoded in one kernel launch per bucket — bit-identical
        fold-in, same preemption contract.  Returns (result-or-None,
        fetched): `fetched` lists the row groups that actually pulled
        encoded bytes, which is what the scheduler's netsim pipeline
        prices (store-resident groups fetch nothing)."""
        assert self.result is None, "scan already complete"
        rgs = list(row_groups)
        for rg in rgs:
            assert self._pending and rg == self._pending[0], (
                f"row group {rg} dispatched out of order (next is "
                f"{self._pending[0] if self._pending else None})"
            )
            self._pending.pop(0)
        per_rg, fetched = self.engine.scan_row_groups_batched(
            self.reader, rgs, self.plan, self.pred, self.blooms, self.stats,
            pool=pool, offload=self.offload,
        )
        self._fold(rgs, per_rg)
        if not self._pending:
            self._finish()
        return self.result, fetched

    def ingest_batched(self, row_groups, per_rg):
        """Fold in a slice the engine already scanned on this request's
        behalf via `scan_group_batched` (cross-request bucket stacking).
        Same preemption contract as `advance_batched` — the groups must be
        the next pending ones in order — but the per-row-group work
        happened inside the shared group pass, so this only does the
        fold + finish half.  Returns the final result once complete."""
        assert self.result is None, "scan already complete"
        for rg in row_groups:
            assert self._pending and rg == self._pending[0], (
                f"row group {rg} dispatched out of order (next is "
                f"{self._pending[0] if self._pending else None})"
            )
            self._pending.pop(0)
        self._fold(list(row_groups), per_rg)
        if not self._pending:
            self._finish()
        return self.result

    def _fold(self, rgs: List[int], per_rg) -> None:
        """Fold one advanced slice into the accumulated partial result.
        Row scans (and the >MAX_GROUPS aggregate fallback) stash decoded
        columns and masks per row group; pushed-down aggregates reduce the
        slice to (n_groups,) partials right here and keep nothing
        row-shaped."""
        if self._agg and self._agg_push:
            self._fold_agg(rgs, per_rg)
            return
        for cols, mask in per_rg:
            for name in self._need:
                self._per_rg_cols[name].append(cols[name])
            self._per_rg_mask.append(mask)

    def _fold_agg(self, rgs: List[int], per_rg) -> None:
        """Reduce an advanced slice to per-row-group ColPartials — ONE
        aggregate launch per value source per call.  Sequential `advance`
        passes single row groups (a launch per rg, mirroring its one-
        launch-per-page decodes); the batched paths pass whole slices, so
        every row group's blocks stack into one launch per source exactly
        like the decode buckets (WFQ reconciliation refunds the
        difference).  Splitting the stacked planes back per row group
        before folding keeps the canonical per-rg fold boundary, so both
        cadences produce bit-identical partials."""
        be = self.engine.backend if self.engine.backend != "host" else "ref"
        # per-rg block counts, 2-d group ids and survivor masks
        metas = []  # (nblk, gids2d, mask2d)
        for cols, mask in per_rg:
            L = int(mask.shape[0])
            nblk = L // PACK_BLOCK
            if self.plan.group_by is not None:
                gids = cols[self.plan.group_by].astype(jnp.int32).reshape(
                    nblk, PACK_BLOCK)
            else:
                gids = jnp.zeros((nblk, PACK_BLOCK), jnp.int32)
            metas.append((nblk, gids, mask.astype(jnp.int32).reshape(
                nblk, PACK_BLOCK)))
        for src in agg_merge.agg_sources(self.plan.aggregates):
            # partition the slice: decoded pages (and the gids-as-values
            # bare count) stack into one grouped launch; never-decoded
            # BITPACK pages (`_agg_skip`) into one in-kernel-unpack launch
            # per k.  Blocks reduce independently, so stacking cannot
            # change any per-block accumulator row.
            dec: List[int] = []
            fused: Dict[int, List[int]] = {}
            for i, (cols, _m) in enumerate(per_rg):
                v = cols[src] if src is not None else None
                if isinstance(v, EncodedColumn):
                    fused.setdefault(v.k, []).append(i)
                else:
                    dec.append(i)
            planes_by_i: Dict[int, tuple] = {}
            fdtype: Dict[int, np.dtype] = {}
            if dec:
                vals = jnp.concatenate([
                    (per_rg[i][0][src] if src is not None else metas[i][1])
                    .reshape(metas[i][0], PACK_BLOCK)
                    for i in dec
                ], axis=0)
                gids = jnp.concatenate([metas[i][1] for i in dec], axis=0)
                m2 = jnp.concatenate([metas[i][2] for i in dec], axis=0)
                with _span("engine.agg") as sp:
                    planes = ops.grouped_agg_batch(
                        vals, gids, m2, self._n_groups, backend=be)
                    if sp is not None:
                        sp.set(source=src or "*", pages=len(dec),
                               rows=int(vals.shape[0]) * PACK_BLOCK)
                self.stats.kernel_launches += 1
                nb = int(vals.shape[0])
                self.stats.batch_pad_blocks += ops.bucket_blocks(nb) - nb
                s = 0
                for i in dec:
                    planes_by_i[i] = tuple(p[s:s + metas[i][0]] for p in planes)
                    fdtype[i] = np.dtype(vals.dtype)
                    s += metas[i][0]
                    # the in-launch reduction processes the decoded values
                    # once more — ground-truth work the cost model prices
                    # under its own 'agg' rate (decode_footprint mirrors
                    # this as an agg:{src} pseudo-column)
                    self.stats.decode_work["agg"] = (
                        self.stats.decode_work.get("agg", 0)
                        + metas[i][0] * PACK_BLOCK * 4
                    )
            for k, idxs in sorted(fused.items()):
                packed = np.concatenate([
                    np.asarray(per_rg[i][0][src].buffers["packed"])
                    for i in idxs
                ], axis=0)
                m2 = jnp.concatenate([metas[i][2] for i in idxs], axis=0)
                with _span("engine.agg") as sp:
                    planes = ops.fused_agg_batch(packed, k, m2, backend=be)
                    if sp is not None:
                        sp.set(source=src, pages=len(idxs), fused=True,
                               rows=int(packed.shape[0]) * PACK_BLOCK)
                self.stats.kernel_launches += 1
                nb = int(packed.shape[0])
                self.stats.batch_pad_blocks += ops.bucket_blocks(nb) - nb
                s = 0
                for i in idxs:
                    planes_by_i[i] = tuple(p[s:s + metas[i][0]] for p in planes)
                    fdtype[i] = np.dtype(np.int32)
                    s += metas[i][0]
            parts = self._agg_parts.setdefault(src, {})
            for i, rg in enumerate(rgs):
                parts[rg] = agg_merge.fold_blocks(
                    planes_by_i[i],
                    np.issubdtype(fdtype[i], np.floating),
                )

    def _finish(self) -> None:
        """Assemble the result once the last row group is folded in; the
        `engine.finish` span covers it, and with it the wait for the
        device's count of survivors (`rows_out`)."""
        with _span("engine.finish") as sp:
            if self._agg:
                self._finish_agg()
            else:
                self._finish_rows()
            if sp is not None:
                sp.set(rows=self.stats.rows_out)

    def _finish_rows(self) -> None:
        proj = self.plan.columns
        if not self._rgs:  # everything pruned — never cached (nothing scanned)
            # Empty columns must keep the schema's decoded dtypes (float32
            # stays float32, ints/string codes stay int32): a jnp.zeros((0,))
            # default would force float32 and break the sliced ≡ single-shot
            # contract's dtype half for all-pruned scans.
            empty = {c: jnp.zeros((0,), self.reader.decoded_dtype(c)) for c in proj}
            z = jnp.zeros((0,), jnp.bool_)
            self.result = ScanResult(empty, z, jnp.int32(0), self.stats)
            return
        out_cols = {
            c: jnp.concatenate(v)
            for c, v in self._per_rg_cols.items()
            if v[0] is not None and c in proj
        }
        mask = jnp.concatenate(self._per_rg_mask)
        count = jnp.sum(mask.astype(jnp.int32))
        if self.plan.compact:
            with _span("engine.compact") as sp:
                out_cols, mask, count = self.engine._compact(out_cols, mask)
                if sp is not None:
                    sp.set(rows=int(mask.shape[0]))
        # result-DMA size: the projected columns + survivor mask actually
        # handed to the consumer (pred-only columns were dropped above —
        # decode→project)
        self.stats.result_bytes = (
            sum(int(a.nbytes) for a in out_cols.values()) + int(mask.nbytes)
        )
        result = ScanResult(out_cols, mask, count, self.stats)
        self.stats.rows_out = int(count)
        if self.offload == "prefiltered":
            # decode_work prices the entry's eviction rank by the ground-
            # truth work that produced it (re-creating the result costs at
            # least that much again)
            self.engine.cache.put(
                self.engine.plan_cache_key(self.reader, self.plan, self.blooms,
                                           tag=self.scan_tag),
                result, tier="prefiltered", decode_work=dict(self.stats.decode_work),
            )
        self.result = result

    def _finish_agg(self) -> None:
        """Assemble an aggregate scan's result: merge per-row-group
        partials in global row-group order (the canonical fold), finalize
        to (n_groups,) arrays, and hand over ONLY the accumulators — the
        result DMA is their footprint, not the value column's."""
        sources = agg_merge.agg_sources(self.plan.aggregates)
        if not self._rgs:
            # everything pruned: pure merge identities per source
            parts_by_rg: Dict[int, dict] = {}
            merged = {
                src: agg_merge.identity_partial(
                    self._n_groups,
                    self.reader.decoded_dtype(src) if src is not None
                    else np.int32,
                )
                for src in sources
            }
        elif self._agg_push:
            parts_by_rg = {
                rg: {src: self._agg_parts[src][rg] for src in sources}
                for rg in self._rgs
            }
            merged = {
                src: agg_merge.merge_partials(
                    [self._agg_parts[src][rg] for rg in self._rgs])
                for src in sources
            }
        else:
            # >MAX_GROUPS host fallback: the value rows were accumulated
            # like a row scan; reduce them through the same block math and
            # per-rg fold boundaries (segments) host-side
            cols = {
                c: jnp.concatenate(v)
                for c, v in self._per_rg_cols.items()
                if v and v[0] is not None
            }
            mask = jnp.concatenate(self._per_rg_mask)
            segments = [int(m.shape[0]) // PACK_BLOCK for m in self._per_rg_mask]
            by_src = agg_merge.rows_partials(
                cols, mask, self.plan.aggregates, self.plan.group_by,
                self._n_groups, segments=segments,
            )
            parts_by_rg = {
                rg: {src: by_src[src][j] for src in sources}
                for j, rg in enumerate(self._rgs)
            }
            merged = {
                src: agg_merge.merge_partials(parts)
                for src, parts in by_src.items()
            }
        aggs = agg_merge.finalize(self.plan.aggregates, merged, self._n_groups)
        count = int(next(iter(merged.values())).cnt.sum())
        self.stats.rows_out = count
        self.stats.result_bytes = sum(int(a.nbytes) for a in aggs.values())
        result = ScanResult(
            {}, jnp.zeros((0,), jnp.bool_), jnp.int32(count), self.stats,
            aggregates=aggs, agg_partials=parts_by_rg,
        )
        if self.offload in ("prefiltered", "pre-aggregated"):
            # the pre-aggregated tier caches the WHOLE accumulator result:
            # a few KB answering a scan that would otherwise re-read and
            # re-reduce every row group (DESIGN.md §16)
            self.engine.cache.put(
                self.engine.plan_cache_key(self.reader, self.plan, self.blooms,
                                           tag=self.scan_tag),
                result, tier="prefiltered", decode_work=dict(self.stats.decode_work),
            )
        self.result = result
