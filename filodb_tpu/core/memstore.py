"""TimeSeriesMemStore: per-dataset shards wiring ingest -> part-key index -> HBM store.

Reference: core/.../memstore/TimeSeriesMemStore.scala (shard map, ingestStream),
TimeSeriesShard.scala (the heart: partition set, Lucene index, ingest loop
:459/:1183, flush pipeline :771-:1048, recovery, eviction).

TPU-native shape of the same responsibilities:
  - partition lookup: host dict part-key-bytes -> part_id, resolved once per
    *distinct label set per container* (not per sample; the container's part_idx
    indirection makes sample->part_id a single vectorized numpy gather)
  - ingest: host staging buffers -> batched device scatter when the staging
    threshold is reached (one XLA call per flush, not per record)
  - flush groups & offset watermarks: group = part_id % num_groups; the group
    watermark advances when the group's staged samples land on device (and, once a
    ChunkSink is attached, when they are durably flushed) — recovery replays the
    bus from min(watermark), skipping below-watermark rows per group (ref:
    TimeSeriesShard.scala:180-184, doc/ingestion.md "Recovery and Persistence")
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from dataclasses import dataclass, field

import numpy as np

log = logging.getLogger(__name__)

# epoch-log sentinel: this visibility bump may have affected data at ANY
# timestamp (destructive mutations — purge, eviction, retention compaction,
# durable age-out). Fragments validated against a log containing it
# invalidate whole (query/incremental.py stable_before).
EPOCH_AFFECTS_ALL = -(1 << 62)

# The declared visibility surface (filolint epochcheck — analysis/
# epochcheck.py reads this dict from the AST; keep it a pure literal).
# Every function where query-visible store state changes must be named
# here, with the affected-timestamp class its bump records:
#   "batch_min_ts"      — the bump logs the minimum data timestamp the
#                         mutation touched (staged flush, recovery chunk
#                         load, purge end-time marks); per-step fragment
#                         validity survives for steps before it
#   "EPOCH_AFFECTS_ALL" — destructive: rows at arbitrary timestamps
#                         vanished (release/eviction, retention compaction,
#                         durable age-out); caches invalidate whole
#   "admit"             — series admission only: a partition with zero
#                         visible samples changes no query result, so no
#                         bump is required until the first staged flush
#                         lands its data (which bumps)
# ``visible_calls`` are the field-sensitive mutator shapes the checker
# hunts (self.<attr>.<method> / a local alias of self.<attr>): mutations
# of the arrays the query read path scans. ``admit_calls``/``admit_maps``
# are the admission-only shapes (declaration required, bump not).
# Undeclared mutation sites, bumps outside the shard lock, and
# EPOCH_AFFECTS_ALL bumps where a batch minimum is in scope are tier-1
# failures — see ANALYSIS.md "Epoch & visibility contracts".
EPOCH_SPEC = {
    "class": "TimeSeriesShard",
    "bump": "_bump_epoch_locked",
    "lock": "lock",
    "visible_calls": {
        "store": ("append", "compact", "free_rows"),
        "index": ("remove_part_keys", "update_end_time"),
        "sink": ("age_out", "age_out_commit"),
    },
    "admit_calls": {
        "index": ("add_part_key", "add_part_keys_bulk",
                  "add_part_keys_columnar"),
    },
    "admit_maps": ("_part_key_of_id", "_part_key_to_id"),
    "sites": {
        "staged_flush": {
            "fn": "TimeSeriesShard._flush_staged_locked",
            "affects": "batch_min_ts"},
        "partition_release": {
            "fn": "TimeSeriesShard._release_partitions_locked",
            "affects": "EPOCH_AFFECTS_ALL"},
        "purge_mark_ended": {
            "fn": "TimeSeriesShard.purge_expired_partitions",
            "affects": "batch_min_ts"},
        "compaction": {
            "fn": "TimeSeriesShard.flush",
            "affects": "EPOCH_AFFECTS_ALL"},
        "age_out": {
            "fn": "TimeSeriesShard.age_out_durable",
            "affects": "EPOCH_AFFECTS_ALL"},
        "recovery_chunk_load": {
            "fn": "TimeSeriesShard._recover_inner",
            "affects": "batch_min_ts"},
        "series_admit": {
            "fn": "TimeSeriesShard._create_series_locked",
            "affects": "admit"},
        "series_admit_bulk": {
            "fn": "TimeSeriesShard._bulk_create_locked",
            "affects": "admit"},
    },
}

from .chunkstore import SeriesStore
from .eviction import BloomFilter, CapacityEvictionPolicy, EvictionPolicy
from .filters import Filter
from .partkey_index import PartKeyIndex
from .record import RecordContainer
from .schemas import Schema, Schemas, part_key_bytes, part_key_of
from .selection import SelectionMemo, ShardSelection
from .store import (INDEX_FLAG_UNPARSEABLE, INDEX_GENESIS_BUCKET,
                    INDEX_RETIRE_BUCKET, INDEX_TOMBSTONE_BUCKET,
                    ChunkSetRecord, ChunkSink, encode_index_bucket,
                    labels_from_blob)
from ..utils.diagnostics import (TimedRLock, assert_owned, lock_hold_ns,
                                 lock_wait_ns)
from ..utils.metrics import (FILODB_INDEX_PERSISTED_BUCKETS,
                             FILODB_INDEX_RECOVER_MS,
                             FILODB_RETENTION_AGED_OUT_ROWS,
                             FILODB_RETENTION_ODP_ROWS,
                             FILODB_STORE_RESIDENCY_FALLBACK, registry)
from ..utils.tracing import SPAN_INGEST_FLUSH, SPAN_ODP_DURABLE, span

# _create_series_locked outcome distinct from "blocked, stage prefix first"
# (None): the tenant's cardinality quota shed this NEW series — the caller
# skips its samples (existing series are never affected)
SHED_PID = -2

# default granularity of persisted index time buckets (index.time_bucket)
DEFAULT_INDEX_BUCKET_MS = 6 * 3600 * 1000

# dense live runs at least this long load via ONE columnar bulk add at
# recovery; shorter runs stay per-key (bulk setup costs more than it saves)
RECOVER_BULK_MIN = 256


@dataclass
class StoreConfig:
    """Per-dataset store tuning (ref: core/.../store/IngestionConfig.scala + the
    store {} block of conf/timeseries-dev-source.conf)."""
    max_series_per_shard: int = 1 << 20
    samples_per_series: int = 1024          # device row capacity (ring via compaction)
    flush_batch_size: int = 65536           # staged samples triggering a device flush
    groups_per_shard: int = 16
    retention_ms: int = 3 * 3600 * 1000
    dtype: str = "float32"
    # narrow-RESIDENT: after each flush the f32 value block compresses to
    # i16 (q, vmin, scale) + a raw-f32 cohort pool for non-quantizable rows
    # and the f32 array is FREED — ~2x value-retention per HBM byte. Appends
    # rehydrate (write buffers stay raw, like the reference's); the fused
    # query path streams the i16 state directly; general paths decode a
    # transient. Scalar f32 single-column stores only. (Back-compat alias
    # for compressed_residency="gauge".)
    narrow_resident: bool = False
    # which store shapes adopt the compressed-resident form after flush
    # (server knob: config.py store.compressed_residency):
    #   "off"   — raw f32/i64 blocks stay resident
    #   "gauge" — scalar f32 single-column stores: the narrowest decode
    #             variant carrying the data bit-exactly (ops/decodereg.py:
    #             delta8 anchor+i8 deltas for counters, quant16, delta16)
    #             + ts elision
    #   "all"   — gauge AND [S, C, B] histogram stores (i8/i16 2D-delta bucket
    #             blocks — the reference keeps ALL in-memory data compressed,
    #             histograms most of all: doc/compression.md "Histograms")
    compressed_residency: str = "off"
    # cohort-pool gate for compressed residency: the fraction of live rows
    # allowed to fail the bit-exactness contract (kept raw in the cohort
    # pool) before the store declines compression — beyond it, raw f32 is
    # the cheaper residency and the decline counts a
    # filodb_store_residency_fallback
    narrow_cohort_gate: float = 0.25

    def __post_init__(self):
        if self.compressed_residency not in ("off", "gauge", "all"):
            raise ValueError(
                f"compressed_residency must be off|gauge|all, "
                f"got {self.compressed_residency!r}")
        if not 0.0 <= self.narrow_cohort_gate <= 1.0:
            raise ValueError(
                f"narrow_cohort_gate must be in [0, 1], "
                f"got {self.narrow_cohort_gate!r}")

    def residency_mode(self) -> str:
        """Effective residency mode ("off" | "gauge" | "all"), folding the
        legacy narrow_resident flag in."""
        if self.compressed_residency != "off":
            return self.compressed_residency
        return "gauge" if self.narrow_resident else "off"


@dataclass
class ShardStats:
    rows_ingested: int = 0
    series_created: int = 0
    unknown_schema_dropped: int = 0
    partitions_purged: int = 0
    partitions_evicted: int = 0
    evicted_part_key_reingests: int = 0
    # NEW series births shed by the per-tenant cardinality limiter (their
    # samples dropped WITH the birth; existing-series samples always land)
    series_quota_shed: int = 0


class TimeSeriesShard:
    """All state for one shard of one dataset."""

    def __init__(self, dataset: str, schema: Schema, shard_num: int, config: StoreConfig,
                 device=None, sink: ChunkSink | None = None,
                 eviction_policy: EvictionPolicy | None = None):
        import jax.numpy as jnp
        self.dataset = dataset
        self.schema = schema
        self.shard_num = shard_num
        self.config = config
        self.index = PartKeyIndex()
        self._part_key_to_id: dict[bytes, int] = {}
        self._part_key_of_id: dict[int, bytes] = {}
        # native open-addressing part-key table (ref: PartitionSet.scala) —
        # batch-probed once per container on the ingest hot path; the dicts
        # above remain the source of truth (and the fallback when no
        # toolchain). Mirrored on create/release/recover.
        from . import native as _native
        # native inserts for NEW series are deferred and batched: one ctypes
        # call per container instead of one per key (see _flush_native_locked)
        self._pending_native: list = []
        self._native_ps = (_native.NativePartSet(config.max_series_per_shard)
                           if _native.available() else None)
        # hash each pid was INSERTED under (container-supplied for ingest):
        # removal must use the same value — recomputing could diverge from a
        # frame whose trailer hash mismatches its key bytes, stranding a
        # stale native entry that resolves to a freed slot
        self._pid_hash = (np.zeros(config.max_series_per_shard, np.uint64)
                          if self._native_ps is not None else None)
        # bumped on every partition release: invalidates batch-resolved pids
        self._release_epoch = 0
        # O(1) data-time lead: the max sample timestamp ever staged or
        # recovered into this shard. The retention router consults it per
        # query — a full last_ts scan there would cost O(max_series) on
        # every query's hot path (monotonic: purge/compact never lower it)
        self.lead_ms = 0
        # ingest/mutation watermark: bumped (under the shard lock) whenever
        # query-visible data changes — rows staged, partitions released,
        # retention compaction. The query result cache records the cluster
        # vector of these counters per entry; a vector mismatch means a
        # cached result could diverge from re-execution (query/engine.py
        # QueryResultCache). Served over /api/v1/epochs for peer probes.
        self.data_epoch = 0
        # per-bump provenance for INCREMENTAL serving (query/incremental.py):
        # each data_epoch bump appends (new epoch, min affected data ts) —
        # an append-type bump records the minimum timestamp that became
        # visible, a destructive bump (purge/eviction/compaction/age-out)
        # records EPOCH_AFFECTS_ALL. A cached per-step fragment recorded at
        # epoch e stays provably valid for steps t < min(min_ts of every
        # bump after e): only data at timestamps <= t can influence step t
        # (windows and lookback reach strictly backward). Bounded ring; a
        # gap (too many bumps since e) reads as "unknown" and the fragment
        # fully invalidates — never a stale serve.
        self._epoch_log: deque[tuple[int, int]] = deque(maxlen=256)
        self._stage_min_ts: int | None = None
        self._stage_max_ts = 0
        # QUERY-VISIBLE data-time lead: advances when staged rows actually
        # land on the device store (or recovery loads chunks), unlike
        # lead_ms which advances at STAGE time. Streaming subscriptions
        # chase this one — an increment cut at the staged (not yet
        # visible) lead would serve a step without its samples and never
        # re-deliver it (the cursor only moves forward)
        self.visible_lead_ms = 0
        # True while recover() is rebuilding this shard (queries are
        # admitted during recovery, but an empty selection seen in the
        # window must not be CACHED as proof of emptiness — the negative
        # cache consults this; ref: RecoveryInProgress status)
        self.recovering = False
        # purged slots available for reuse + membership filter of evicted keys
        # (ref: TimeSeriesShard evictedPartKeys bloom :93-96, checked on ingest :1092)
        self._free_pids: list[int] = []
        self._evicted_keys = BloomFilter()
        # memoized RangeVectorKey per queried pid: the dict-encoded index
        # reconstructs labels on demand, so query leaves cache the key object
        # (built once per series lifetime, dropped on purge)
        self._rv_keys: dict[int, object] = {}
        # what the wide selectors and their groupings came to, kept while
        # the index says the same (core/selection.py has the rule)
        self._selections = SelectionMemo(self)
        self.eviction_policy = eviction_policy or CapacityEvictionPolicy()
        # guards the donating device append vs concurrent query dispatch: the
        # scatter invalidates (donates) the old store buffers, so query leaves
        # must capture arrays AND dispatch their kernels under this lock
        # (ref analog: per-shard single ingest thread + ChunkMap read locks)
        self.lock = TimedRLock(f"shard-{shard_num}-lock", order_class="shard",
                               order_index=shard_num)
        # per-slot release counters (purge/eviction): lazily materialized
        # query artifacts (LazyKeys) snapshot the epochs of THEIR pids and
        # detect slot reuse without being invalidated by unrelated releases
        self.slot_epoch = np.zeros(config.max_series_per_shard, np.uint32)
        self._device = device
        self._dtype = jnp.float64 if config.dtype == "float64" else jnp.float32
        self.bucket_les: np.ndarray | None = None
        if schema.is_histogram:
            # histogram stores are created lazily: the bucket scheme arrives with
            # the first container (ref: BinaryHistogram carries its bucket scheme)
            self.store = None
        else:
            self.store = self._make_store()
            self.store.owner_lock = self.lock
        # staging buffers (host)
        self._stage_pid: list[np.ndarray] = []
        self._stage_ts: list[np.ndarray] = []
        self._stage_val: list[np.ndarray] = []
        self._rehydrates_seen = 0    # the store's count at the last flush span
        self._staged = 0
        # per-group ingest offset watermarks (ref: checkpoint per flush group)
        self.group_watermarks = np.full(config.groups_per_shard, -1, np.int64)
        self._pending_offset = -1
        # persistence (ref: doFlushSteps — encode + sink write + checkpoint commit)
        self.sink = sink
        G = config.groups_per_shard
        self._pending_chunks: list[list] = [[] for _ in range(G)]   # per group (pids, ts, vals)
        self._pending_group_offset = np.full(G, -1, np.int64)
        # pids of chunk snapshots currently being written by a flush_group
        # call (token -> unique pids). While a snapshot is outside
        # _pending_chunks its pids are invisible to the release-time scrubs,
        # so eviction/purge must not release them: a release during the sink
        # write would persist a dead pid's samples after its tombstone and,
        # after slot reuse, attribute them to the slot's next owner on
        # recovery. Protected here; scrub-on-requeue stays as defense.
        self._inflight_flush: dict[object, np.ndarray] = {}
        # one flush at a time per group (ref: createFlushTask — a group's
        # flush task is singular). Beyond exactly-once, this gives callers a
        # happens-after guarantee: when flush_group(g) returns, any in-flight
        # flush of g that had already snapshotted the pending chunks has
        # finished its sink write AND its inline-downsample publish — without
        # it, a caller could see an empty pending list, return immediately,
        # and read the sink before the concurrent flusher published.
        # Ordered TimedRLocks (not bare threading.Lock): the global order
        # group_flush < sink < shard is asserted under FILODB_LOCK_DEBUG=1
        # (diagnostics.LOCK_ORDER) and checked statically by filolint.
        self._group_flush_locks = [
            TimedRLock(f"shard-{shard_num}-group-{g}-flush",
                       order_class="group_flush", order_index=g)
            for g in range(G)]
        # ordered part-key event log awaiting durable persist: creations
        # (pid, labels, start) and release tombstones (pid, {}, -1) in event
        # order, so recovery's last-entry-wins resolves slot reuse correctly
        # regardless of which thread drains the log
        self._partkey_log: list[tuple[int, dict, int]] = []
        # serializes drain+write batches (ordered: sink < shard)
        self._sink_lock = TimedRLock(f"shard-{shard_num}-sink-lock",
                                     order_class="sink",
                                     order_index=shard_num)
        self._meta_written = False
        # inline downsampling at flush (ref: ShardDownsampler + DownsamplePublisher):
        # (resolution_ms, callback(shard, {agg: (pids, ts, vals)}))
        self.downsample: tuple | None = None
        # ingest cardinality governance (core/cardinality.py): per-tenant
        # active-series accounting + birth limiter, shared per dataset; the
        # shard consults it under its own lock at every series creation
        self.governor = None
        # durable index time buckets (index.time_bucket; 0 disables): the
        # part-key log drain also appends columnar index frames so a
        # restarted shard recovers the index from the ring instead of
        # rebuilding per key (ref: persisted Lucene time-bucket blobs)
        self.index_bucket_ms = DEFAULT_INDEX_BUCKET_MS
        # True once index.log carries a GENESIS snapshot covering this
        # shard's full history (written at the first drain of a fresh
        # shard, or after a recovery that had to fall back to
        # partkeys.log) — recovery only trusts the log from its last
        # genesis marker, so an upgraded/toggled shard never loses series
        self._index_log_seeded = False
        self.stats = ShardStats()

    # -- partition resolution ----------------------------------------------

    def _resolve_segment_locked(self, container, mapping, first_ts, start) -> int:
        """Resolve label sets from ``start`` onward to dense part ids, creating
        new partitions (and index entries) as needed. Under slot pressure, evict
        least-recently-active partitions to make room (ref: TimeSeriesShard
        ``ensureFreeSpace``, :1315). Returns the index one past the last label
        set resolved: when a new slot is needed but every eviction candidate is
        a series resolved earlier in this same container (its samples not yet
        staged), resolution stops there so the caller can stage the prefix —
        which makes those series evictable — and re-enter.

        Hot path: the whole container probes the native part-key table in ONE
        call (ref: PartitionSet zero-alloc probes under
        getOrAddPartitionAndIngest, TimeSeriesShard.scala:1183); only misses
        (new series) take the per-set creation path. A release during the
        loop (eviction making room) invalidates the batch snapshot, so the
        remaining tail re-probes."""
        n_sets = len(container.label_sets)
        keys, hashes = container.resolved_keys()
        protected: set[int] = set()
        i = start
        while i < n_sets:
            if self._native_ps is not None:
                self._flush_native_locked()   # re-probes must see this batch
                pids = self._native_ps.resolve_batch(hashes[i:], keys[i:])
            else:
                g = self._part_key_to_id.get
                pids = np.fromiter((g(k, -1) for k in keys[i:]), np.int32,
                                   count=n_sets - i)
            if i == start and self._bulk_create_locked(container, mapping,
                                                       pids, i, first_ts):
                return n_sets
            epoch0 = self._release_epoch
            seg = i
            for j in range(seg, n_sets):
                pid = int(pids[j - seg])
                if pid < 0:
                    pid = self._create_series_locked(
                        container.label_sets[j], keys[j], int(hashes[j]),
                        first_ts, protected)
                    if pid is None:
                        return j   # blocked on this container's own series
                mapping[j] = pid
                if pid >= 0:       # SHED_PID: quota-shed birth, no slot
                    protected.add(pid)
                i = j + 1
                if self._release_epoch != epoch0 and i < n_sets:
                    break          # eviction ran: re-probe the tail
        return n_sets

    BULK_CREATE_MIN = 512      # below this, per-key creation wins

    def _bulk_create_locked(self, container, mapping, probe_pids,
                            seg: int, first_ts) -> bool:
        """Registration fast path: admit ALL of a probe's misses in one bulk
        pass — dense pid assignment, bulk index add from the container's
        canonical key bytes, one dict update for the key maps (ref:
        TimeSeriesShard.scala:1183 getOrAddPartitionAndIngest +
        PartKeyLuceneIndex.addPartKey; jmh IngestionBenchmark is the bar).

        Only when nothing per-key can happen: enough free capacity for every
        miss without eviction (and none ever evicted — the bloom re-ingest
        accounting stays exact), no reusable slots (dense append only), and
        no ignored shard-key tags (the index stores ALL labels; key bytes
        drop ignored ones). Returns False untouched otherwise."""
        miss = np.nonzero(probe_pids < 0)[0]
        if len(miss) < self.BULK_CREATE_MIN:
            return False
        if (self._free_pids or self.stats.partitions_evicted
                or self.schema.options.ignore_shard_key_tags
                or len(self.index) + len(miss) > self.config.max_series_per_shard):
            return False
        keys, hashes = container.resolved_keys()
        label_sets = container.label_sets
        n_sets = len(label_sets)
        base = len(self.index)
        new_pids = np.arange(base, base + len(miss), dtype=np.int64)
        new_keys = [keys[seg + j] for j in miss.tolist()]
        # builder interning makes label sets unique, but hand-built containers
        # may repeat a key — the per-key path dedups those; bulk cannot
        if len(set(new_keys)) != len(new_keys):
            return False
        gov_tenant = None
        if self.governor is not None and self.governor.limit is not None:
            # all-or-nothing block reservation; mixed-tenant batches (or a
            # batch that does not fit) take the per-key path, which sheds
            # series-precisely
            tenants = {self.governor.tenant_of(label_sets[seg + int(j)])
                       for j in miss}
            if len(tenants) != 1:
                return False
            gov_tenant = tenants.pop()
            if not self.governor.admit_block(gov_tenant, len(miss)):
                return False
        # columnar fast path: the builder's per-label columns skip pair-bytes
        # parsing entirely (one dict probe per value); only valid when the
        # whole container is new series (columns align 1:1 with the misses)
        added = False
        if (container.label_columns is not None and seg == 0
                and len(miss) == n_sets):
            fixed, vary, cols = container.label_columns
            added = self.index.add_part_keys_columnar(
                new_pids, fixed, vary, cols, first_ts)
        if not added:
            counts_hint = np.fromiter((len(label_sets[seg + j])
                                       for j in miss.tolist()), np.int64,
                                      count=len(miss))
            if not self.index.add_part_keys_bulk(new_pids, new_keys, first_ts,
                                                 counts_hint=counts_hint):
                if gov_tenant is not None:   # reservation rolls back with us
                    self.governor.retire(gov_tenant, len(miss))
                return False
        pid_list = new_pids.tolist()
        self._part_key_to_id.update(zip(new_keys, pid_list))
        self._part_key_of_id.update(zip(pid_list, new_keys))
        if self._native_ps is not None:
            # straight to the native table (array form, no per-entry tuples);
            # deferred inserts must land FIRST to keep insertion order sane
            hs = hashes[seg + miss]
            self._flush_native_locked()
            self._native_ps.insert_arrays(hs, new_keys, new_pids.astype(np.int32))
            self._pid_hash[new_pids] = hs
        if self.sink is not None:
            # 4-tuple form: labels stay a (sequence, index) reference so the
            # dicts build at flush time OUTSIDE the shard lock — a 1M-series
            # batch must not pay n dict builds in the locked ingest path
            self._partkey_log.extend(
                (pid, label_sets, seg + j, first_ts)
                for pid, j in zip(pid_list, miss.tolist()))
        self.stats.series_created += len(miss)
        seg_map = mapping[seg:seg + (n_sets - seg)]
        hit = probe_pids >= 0
        seg_map[hit] = probe_pids[hit]
        seg_map[miss] = new_pids
        return True

    def _flush_native_locked(self) -> None:
        """Land deferred part-key inserts in one native call. Must run
        before any native probe or removal: within a container, creations are
        visible through _part_key_to_id; across operations the native table
        is the source of truth."""
        if self._pending_native:
            self._native_ps.insert_batch(self._pending_native)
            self._pending_native.clear()

    def _create_series_locked(self, labels, pk: bytes, ph: int, first_ts,
                              protected) -> int | None:
        """Admit a new series: assign a slot (evicting under pressure), index
        it, and mirror the key into the native table. None when every
        eviction candidate is protected (caller stages its prefix first)."""
        S = self.config.max_series_per_shard
        # distinct label sets can share one part key (ignore_shard_key_tags):
        # an earlier creation in this same batch snapshot must win, not be
        # double-created (the batch probe predates it)
        pid = self._part_key_to_id.get(pk)
        if pid is not None:
            return pid
        gov_tenant = None
        if self.governor is not None:
            # series-birth limiter: a tenant at quota sheds the NEW part key
            # (and only it) — samples for existing series are unaffected,
            # which is the whole multi-tenant point (a noisy tenant's label
            # explosion must not evict everyone else's series). Checked
            # BEFORE eviction work so an over-quota birth never evicts
            # someone else's series to make room it will not use.
            gov_tenant = self.governor.tenant_of(labels)
            if not self.governor.admit(gov_tenant):
                self.stats.series_quota_shed += 1
                self.governor.count_shed("shard", gov_tenant)
                return SHED_PID
        if not self._free_pids and len(self.index) >= S:
            if not self._ensure_free_space_locked(protected):
                # creation BLOCKED (caller stages its prefix and retries,
                # re-admitting then): the reservation must roll back or
                # every blocked attempt permanently inflates the tenant's
                # active count
                if gov_tenant is not None:
                    self.governor.retire(gov_tenant)
                return None
        if pk in self._evicted_keys:
            self.stats.evicted_part_key_reingests += 1
        pid = self._free_pids.pop() if self._free_pids else len(self.index)
        self._part_key_to_id[pk] = pid
        self._part_key_of_id[pid] = pk
        if self._native_ps is not None:
            self._pending_native.append((ph, pk, pid))
            self._pid_hash[pid] = ph
        self.index.add_part_key(pid, labels, start_time=first_ts)
        if self.sink is not None:
            self._partkey_log.append((pid, labels, first_ts))
        self.stats.series_created += 1
        return pid

    def _ensure_free_space_locked(self, protected: set[int]) -> bool:
        """Evict the least-recently-active partitions so a new series can be
        admitted instead of erroring (ref: TimeSeriesShard.ensureFreeSpace
        :1315 + evictedPartKeys bloom :93-96). Eviction frees the HBM rows,
        tombstones the index entries, and records the part keys so a returning
        series is detected. Returns False when every occupied slot belongs to
        ``protected`` (series whose samples are still pending in the caller's
        container) and nothing can move."""
        self._flush_staged_locked()   # staged rows must land before slots move
        occupied = np.fromiter(self._part_key_of_id.keys(), np.int64,
                               count=len(self._part_key_of_id))
        if protected:
            occupied = occupied[~np.isin(
                occupied, np.fromiter(protected, np.int64, count=len(protected)))]
        if self._inflight_flush:
            # snapshots mid-write (see _inflight_flush): releasing these pids
            # would persist dead samples after their tombstone
            inflight = np.unique(np.concatenate(list(self._inflight_flush.values())))
            occupied = occupied[~np.isin(occupied, inflight)]
        if occupied.size == 0:
            return False
        # amortize: evict a small batch, least-recently-active first
        k = min(occupied.size, max(1, self.config.max_series_per_shard // 16))
        last = self.store.last_ts[occupied]
        victims = (occupied[np.argpartition(last, k - 1)[:k]]
                   if k < occupied.size else occupied)
        self._release_partitions_locked(victims.astype(np.int32))
        self.stats.partitions_evicted += int(victims.size)
        return True

    def _bump_epoch_locked(self, min_affected_ms: int) -> None:
        """Advance the visibility watermark (caller holds the shard lock),
        recording the minimum data timestamp the mutation can have touched
        — ``EPOCH_AFFECTS_ALL`` for destructive changes. EVERY data_epoch
        bump must route through here: the incremental-serving validity rule
        requires one log entry per bump (a gap reads as full
        invalidation)."""
        self.data_epoch += 1
        self._epoch_log.append((self.data_epoch, int(min_affected_ms)))

    def epoch_state(self) -> tuple[int, list[tuple[int, int]]]:
        """``(data_epoch, recent (epoch, min affected ts) entries)`` read
        coherently under the shard lock — the substrate of per-step
        fragment validity (local probes read this directly; peers serve it
        over ``/api/v1/epochs?log=1``)."""
        with self.lock:
            return self.data_epoch, list(self._epoch_log)

    def _release_partitions_locked(self, pids: np.ndarray) -> None:
        """Shared teardown for purge and eviction: drop id maps (recording the
        keys in the evicted-keys filter), tombstone index entries, free HBM
        rows, and make the slots reusable. Durable tombstones (queued here,
        written outside the lock by the next drain point) ensure recovery
        neither resurrects the series nor attributes its persisted chunks to a
        later owner of the reused slot."""
        pid_list = pids.tolist()
        self.slot_epoch[pids] += 1
        self._release_epoch += 1
        # result-cache watermark: data gone (destructive — a released
        # series held samples at arbitrary timestamps)
        self._bump_epoch_locked(EPOCH_AFFECTS_ALL)
        if self.governor is not None:
            # labels still resolve here (the index tombstones below):
            # churned-out series release their tenant's quota slots
            for pid in pid_list:
                self.governor.retire(
                    self.governor.tenant_of(self.index.labels_of(pid)))
        for pid in pid_list:
            pk = self._part_key_of_id.pop(pid, None)
            if pk is not None:
                del self._part_key_to_id[pk]
                self._evicted_keys.add(pk)
                if self._native_ps is not None:
                    self._flush_native_locked()
                    # remove under the hash it was INSERTED with (see
                    # _pid_hash) — never a recomputed one
                    self._native_ps.remove(int(self._pid_hash[pid]), pk)
        self.index.remove_part_keys(pids)
        self.store.free_rows(pids)
        for pid in pid_list:
            self._rv_keys.pop(pid, None)
        self._free_pids.extend(pid_list)
        # open downsample buckets of released partitions must never emit: the
        # slot's next owner would be attributed the dead series' data
        if self.downsample is not None and hasattr(self.downsample[1], "drop_pids"):
            self.downsample[1].drop_pids(pid_list)
        if self.sink is not None:
            # unpersisted samples of a released partition must never reach the
            # sink: a later flush_group would write them under a pid whose slot
            # may belong to a new owner by recovery time (the purge path avoids
            # this by refusing to purge pids with pending chunks; eviction
            # cannot refuse, so it scrubs them instead)
            gone_arr = np.asarray(pid_list, np.int32)
            for g, pending in enumerate(self._pending_chunks):
                if not pending:
                    continue
                kept = []
                for pids_, ts_, vals_ in pending:
                    m = ~np.isin(pids_, gone_arr)
                    if m.all():
                        kept.append((pids_, ts_, vals_))
                    elif m.any():
                        kept.append((pids_[m], ts_[m], vals_[m]))
                self._pending_chunks[g] = kept
            self._partkey_log.extend((pid, {}, -1) for pid in pid_list)

    def _flush_partkey_log(self) -> None:
        """Persist queued part-key events. The drain and the sink write happen
        inside one critical section (``_sink_lock``, NOT the shard lock — sink
        I/O must not stall ingest/query threads): two concurrent drains could
        otherwise write their batches out of event order, letting a released
        slot's tombstone land after its new owner's key and erase that series
        on recovery."""
        if self.sink is None:
            return
        with self._sink_lock:
            with self.lock:
                log, self._partkey_log = self._partkey_log, []
            if not log:
                return
            try:
                # rows are (pid, labels, start) or the bulk path's deferred
                # (pid, labels_seq, idx, start) — materialized here, off the
                # shard lock
                rows = []
                for e in log:
                    if len(e) == 3:
                        pid, labels, start = e
                    else:
                        pid, seq, i, start = e
                        labels = seq[i]
                    rows.append((int(pid), labels, int(start)))
                # index time buckets FIRST, then the JSON part-key log: a
                # crash between the two leaves index.log AHEAD (extra events
                # replay idempotently, latest-per-pid wins), never behind —
                # so recovery may trust the columnar log whenever present.
                # A failed write requeues the whole batch; the retry's
                # duplicate frames dedup the same way.
                self._persist_index_buckets(rows)
                self.sink.write_part_keys(self.dataset, self.shard_num, rows)
            except Exception:
                # transient sink failure: the events must survive for retry —
                # prepend (they predate anything queued meanwhile)
                with self.lock:
                    self._partkey_log = log + self._partkey_log
                raise

    @staticmethod
    def _index_entry(pid: int, labels: dict, start: int) -> tuple:
        """(pid, start, blob, flags) for one index.log entry. Labels the
        pair encoding cannot represent (NUL in a name/value, the pair
        separator in a name) get the UNPARSEABLE flag — recovery then
        refuses the whole frames path instead of loading split garbage."""
        for k, v in labels.items():
            if "\x00" in k or "\x00" in v or "\x01" in k:
                return (pid, start, b"", INDEX_FLAG_UNPARSEABLE)
        return (pid, start, part_key_bytes(sorted(labels.items()), ()), 0)

    def _write_index_genesis(self) -> None:
        """Append a GENESIS frame: a complete live-series snapshot, the
        trust anchor recovery applies the log from. Written once per shard
        lifetime — at the first drain of a fresh shard, or right after a
        recovery that had to rebuild from partkeys.log (upgraded shard,
        persistence toggled back on). Caller holds ``_sink_lock`` or is
        single-threaded recovery; takes the shard lock for the snapshot
        (sink < shard is the declared order)."""
        with self.lock:
            snapshot = [self._index_entry(pid, self.index.labels_of(pid),
                                          self.index.start_time(pid))
                        for pid in sorted(self._part_key_of_id)]
        self.sink.write_index_bucket(
            self.dataset, self.shard_num,
            encode_index_bucket(INDEX_GENESIS_BUCKET, snapshot))
        self._index_log_seeded = True

    def _persist_index_buckets(self, rows) -> None:
        """Append columnar index frames for one part-key drain batch,
        grouped into CONSECUTIVE same-bucket runs (dict-grouping could
        reorder a tombstone past a slot-reusing re-creation inside one
        batch — event order is what last-entry-wins recovery relies on).
        Creations bucket by their start time; tombstones ride the
        dedicated tombstone pseudo-bucket."""
        if not self.index_bucket_ms \
                or not hasattr(self.sink, "write_index_bucket"):
            return
        if not self._index_log_seeded:
            self._write_index_genesis()
        frames: list[bytes] = []
        cur_bucket: int | None = None
        cur: list[tuple] = []
        for pid, labels, start in rows:
            if labels:
                entry = self._index_entry(pid, labels, start)
                bucket = (start // self.index_bucket_ms) \
                    * self.index_bucket_ms
            else:
                entry = (pid, start, b"", 0)
                bucket = INDEX_TOMBSTONE_BUCKET
            if bucket != cur_bucket and cur:
                frames.append(encode_index_bucket(cur_bucket, cur))
                cur = []
            cur_bucket = bucket
            cur.append(entry)
        if cur:
            frames.append(encode_index_bucket(cur_bucket, cur))
        for frame in frames:
            self.sink.write_index_bucket(self.dataset, self.shard_num, frame)
        if frames:
            registry.counter(FILODB_INDEX_PERSISTED_BUCKETS,
                             {"dataset": self.dataset,
                              "shard": str(self.shard_num)}) \
                .increment(len(frames))

    # -- ingest -------------------------------------------------------------

    def _make_store(self, width_hint: int = 0) -> SeriesStore:
        """Device store shaped by the schema: multi-value-column schemas get
        one array per data column sharing ts/n (Schema.col_layout); legacy
        single-column schemas keep the flat scalar/histogram layout
        (``width_hint``: bucket count of a les-less 2-D container)."""
        nb = len(self.bucket_les) if self.bucket_les is not None else 0
        if not nb and not self.schema.is_multi_column:
            nb = width_hint
        layout = (self.schema.col_layout(nb)
                  if self.schema.is_multi_column else None)
        store = SeriesStore(self.config.max_series_per_shard,
                            self.config.samples_per_series,
                            dtype=self._dtype, device=self._device,
                            nbuckets=nb, layout=layout,
                            default_col=self.schema.value_column,
                            born_narrow=self.config.residency_mode() != "off")
        store.cohort_gate = self.config.narrow_cohort_gate
        return store

    def ingest(self, container: RecordContainer, offset: int = -1,
               recovery_watermarks: np.ndarray | None = None) -> None:
        """Ingest one container. During recovery replay, rows whose flush group
        already persisted past ``offset`` are skipped (ref: TimeSeriesShard
        recovery skips rows below the group watermark, :180-184)."""
        if container.schema.schema_id != self.schema.schema_id:
            with self.lock:   # stats are shard state: writers race otherwise
                self.stats.unknown_schema_dropped += len(container)
            return
        if self.store is None:
            # double-checked under the shard lock: two writer threads racing
            # the first container would each build a store and one's would be
            # silently dropped (with its bucket scheme)
            with self.lock:
                if self.store is None:
                    self.bucket_les = (np.asarray(container.bucket_les)
                                       if container.bucket_les is not None
                                       else None)
                    width = (container.values.shape[1]
                             if container.values.ndim == 2 else 0)
                    self.store = self._make_store(width_hint=width)
                    self.store.owner_lock = self.lock
        n_sets = len(container.label_sets)
        if n_sets == 0 or len(container) == 0:
            return
        mapping = np.empty(n_sets, np.int32)
        first_ts = int(container.ts.min())
        # resolution + staging share the shard lock: HTTP writers / gateways may
        # ingest from several threads, and query paths call flush(). Resolution
        # is segmented: when slot pressure forces eviction but every candidate
        # is a series from this very container, the resolved prefix is staged
        # and landed on device first so those series become evictable.
        with self.lock:
            start = 0
            while start < n_sets:
                done = self._resolve_segment_locked(container, mapping,
                                                    first_ts, start)
                self._stage_segment_locked(container, mapping, start, done,
                                           offset, recovery_watermarks)
                if done < n_sets:
                    self._flush_staged_locked()
                start = done
        if self._staged >= self.config.flush_batch_size:
            self.flush()

    def _stage_segment_locked(self, container, mapping, start, done, offset,
                              recovery_watermarks) -> None:
        """Stage the samples of label sets ``[start, done)`` (the common case —
        the whole container — avoids the mask)."""
        if start == 0 and done == len(container.label_sets):
            pids = mapping[container.part_idx]
            ts, vals = container.ts, container.values
        else:
            sel = (container.part_idx >= start) & (container.part_idx < done)
            pids = mapping[container.part_idx[sel]]
            ts, vals = container.ts[sel], container.values[sel]
        if len(pids) and pids.min() < 0:
            # quota-shed births (SHED_PID): drop exactly their samples —
            # every other series in the container lands normally
            keep = pids >= 0
            pids, ts, vals = pids[keep], ts[keep], vals[keep]
        if recovery_watermarks is not None:
            keep = recovery_watermarks[pids % self.config.groups_per_shard] < offset
            if not keep.all():
                pids, ts, vals = pids[keep], ts[keep], vals[keep]
        if len(pids) == 0:
            return
        self._stage_pid.append(pids)
        self._stage_ts.append(ts)
        self._stage_val.append(vals)
        # min staged ts feeds the epoch log at the flush visibility point:
        # steps older than it stay provably cacheable across the bump
        batch_min = int(ts.min())
        if self._stage_min_ts is None or batch_min < self._stage_min_ts:
            self._stage_min_ts = batch_min
        lead = int(ts.max())
        if lead > self._stage_max_ts:
            self._stage_max_ts = lead
        if lead > self.lead_ms:
            self.lead_ms = lead
        self._staged += len(ts)
        self._pending_offset = max(self._pending_offset, offset)
        self.stats.rows_ingested += len(ts)
        if self.sink is not None:
            # one stable argsort + split instead of a full-array mask per
            # group: the staging path runs per container on the ingest hot
            # loop, and G masks are G passes over the batch
            groups = pids % self.config.groups_per_shard
            order = np.argsort(groups, kind="stable")
            gs = groups[order]
            for idx in np.split(order, np.flatnonzero(np.diff(gs)) + 1):
                if not len(idx):
                    continue
                g = int(groups[idx[0]])
                self._pending_chunks[g].append((pids[idx], ts[idx], vals[idx]))
                self._pending_group_offset[g] = max(self._pending_group_offset[g], offset)

    def _flush_staged_locked(self) -> int:
        """Land staged samples on the device store (caller holds the lock)."""
        if not self._staged:
            return 0
        # result-cache watermark bumps at the VISIBILITY point: staged rows
        # are host-side until this scatter, so bumping at stage time would
        # let a query cached in the stage->flush window validate against a
        # vector that already includes the not-yet-visible rows — a stale
        # hit after the flush (review finding, PR 8)
        self._bump_epoch_locked(self._stage_min_ts
                                if self._stage_min_ts is not None
                                else EPOCH_AFFECTS_ALL)
        self._stage_min_ts = None
        # the staged rows become query-visible with this scatter
        if self._stage_max_ts > self.visible_lead_ms:
            self.visible_lead_ms = self._stage_max_ts
        pids = np.concatenate(self._stage_pid)
        ts = np.concatenate(self._stage_ts)
        vals = np.concatenate(self._stage_val, axis=0)
        self._stage_pid.clear(); self._stage_ts.clear(); self._stage_val.clear()
        self._staged = 0
        return self.store.append(pids, ts, vals)

    def flush(self) -> int:
        """Push staged samples to the device store; advance group watermarks.
        Applies device backpressure OUTSIDE the lock (SeriesStore.throttle):
        a hot ingest loop must run at the device's retirement rate, or its
        dispatch backlog starves concurrent query fetches."""
        if not self._staged:
            # unlocked peek, like ingest()'s batch-size check: an idle tick
            # opens no span (rows staged by another thread just now land
            # untraced)
            return self._flush({})
        waited, held = lock_wait_ns(), lock_hold_ns()
        with span(SPAN_INGEST_FLUSH, shard=self.shard_num) as tags:
            tags["rows"] = written = self._flush(tags)
            tags["lock_wait_ms"] = (lock_wait_ns() - waited) / 1e6
            tags["lock_hold_ms"] = (lock_hold_ns() - held) / 1e6
        return written

    def _flush(self, tags: dict) -> int:
        with self.lock:
            staged = bool(self._staged)
            written = self._flush_staged_locked() if staged else 0
            if staged:
                st = self.store
                tags["demoted"] = st.demoted_last_append
                tags["holes"] = st.holes_last_append
                tags["pooled"] = st.pooled_last_append
                # an append in place leaves the store as it found it
                tags["form"] = "narrow" if st._inplace else "raw"
        residency = self.config.residency_mode()
        if not staged:
            # nothing new — but a purge/compact since the last flush may have
            # rehydrated a compressed-resident store; re-adopt, else the
            # quiesced shard silently sits at raw 12B/sample residency
            if residency != "off":
                self._compress_resident_two_phase(residency)
            return 0
        self.store.throttle()   # the one wait of the write path for the device
        if self.sink is None and self._pending_offset >= 0:
            # without a durable sink, device residency is the only watermark
            with self.lock:
                self.group_watermarks[:] = self._pending_offset
        # capacity pressure -> compact out data older than retention
        # (policy pluggable; ref: PartitionEvictionPolicy.scala)
        if self.eviction_policy.should_evict(self.store, self.config):
            cutoff = int(self.store.last_ts.max(initial=0)) - self.config.retention_ms
            with self.lock:
                self.store.compact(cutoff)
                # result-cache watermark: rows aged out (destructive)
                self._bump_epoch_locked(EPOCH_AFFECTS_ALL)
        if residency != "off":
            # adopt/refresh the compressed-resident state AFTER any compact
            # (compact rehydrates — compressing first would be discarded
            # work). Two-phase: the streaming build + host fetches run
            # OUTSIDE the shard lock; only the swap takes it.
            if self._compress_resident_two_phase(residency):
                tags["form"] = "rebuilt"
        st = self.store
        tags["rehydrates"] = st.rehydrates - self._rehydrates_seen
        self._rehydrates_seen = st.rehydrates
        tags["sample_bytes"] = st.resident_bytes_per_sample()
        return written

    def _compress_resident_two_phase(self, mode: str = "gauge") -> bool:
        """Build the compressed-resident state without the shard lock, then
        swap under it iff nothing mutated meanwhile (a racing append donates
        the very buffers the build streams — detected and retried next
        flush). ``mode`` gates
        which store shapes compress (histograms only under "all"). A store
        that is narrow already — born so, or appended to in place — is
        left alone. True where a rebuild was committed."""
        st = self.store
        if st is None:
            return False
        if st.nbuckets and mode != "all":
            return False
        epoch0 = st.mutation_epoch()
        # idempotence: fully compressed already, or nothing mutated since the
        # last (possibly declined) attempt — a declined 25%-gate store must
        # not re-run the full-store build on every empty flush tick
        if st._val_compressed and (st._ts_elided
                                   or st.grid_info() is None):
            return False
        if getattr(self, "_last_compress_epoch", None) == epoch0:
            return False
        self._last_compress_epoch = epoch0
        try:
            prep = st.compress_prepare(hist=mode == "all")
        except RuntimeError:
            return False           # racing donation invalidated the build
        if prep is None:
            if st.residency_decline is not None:
                # the store WANTED compression and the data refused the
                # ok-contract: "tried and fell back" must be a visible
                # signal, not a silent raw-residency downgrade
                registry.counter(FILODB_STORE_RESIDENCY_FALLBACK,
                                 {"reason": st.residency_decline}).increment()
            return False
        with self.lock:
            if st.mutation_epoch() == epoch0:
                st.compress_commit(prep)
                return True
        return False

    # -- persistence flush pipeline (ref: TimeSeriesShard.doFlushSteps :814) --

    def flush_group(self, group: int) -> int:
        """Encode and persist one flush group's pending samples, then commit its
        checkpoint atomically after the write (ref: :989 writeChunks ->
        :1048 commitCheckpoint). Serialized per group — see
        ``_group_flush_locks``. Returns chunkset record count."""
        if self.sink is None:
            return 0
        with self._group_flush_locks[group]:
            return self._flush_group_serialized(group)

    def _flush_group_serialized(self, group: int) -> int:
        self.flush()                      # device state first
        token = object()
        with self.lock:
            pending = self._pending_chunks[group]
            self._pending_chunks[group] = []
            # per-sample-batch slot epochs: if the persist below fails and a
            # release ran meanwhile, the requeue scrubs exactly the released
            # (possibly reused) slots' samples
            pend_epochs = [self.slot_epoch[p].copy() for (p, _, _) in pending]
            if pending:
                self._inflight_flush[token] = np.unique(
                    np.concatenate([p for (p, _, _) in pending]))
        try:
            # part-key events (creations + tombstones, in order) land before
            # the chunks that reference them. Order matters: the chunk
            # snapshot is taken FIRST — every pid in it was resolved (and so
            # logged) before its samples were staged, hence this drain
            # necessarily covers it. A drain before the snapshot would let a
            # concurrently-created series slip its chunks into this flush
            # with its key still queued.
            self._flush_partkey_log()
            if not pending:
                return 0
            pids = np.concatenate([p for p, _, _ in pending])
            ts = np.concatenate([t for _, t, _ in pending])
            vals = np.concatenate([v for _, _, v in pending])
            order = np.argsort(pids, kind="stable")
            pids, ts, vals = pids[order], ts[order], vals[order]
            bounds = np.concatenate([[0], np.nonzero(np.diff(pids))[0] + 1,
                                     [len(pids)]])
            layout = None
            if self.schema.is_multi_column:
                nb = len(self.bucket_les) if self.bucket_les is not None else 0
                layout = tuple(self.schema.col_layout(nb))
            records = [
                ChunkSetRecord(int(pids[bounds[i]]), ts[bounds[i]:bounds[i + 1]],
                               vals[bounds[i]:bounds[i + 1]], layout)
                for i in range(len(bounds) - 1)
            ]
            if self.bucket_les is not None and not self._meta_written:
                if hasattr(self.sink, "write_meta"):
                    self.sink.write_meta(self.dataset, self.shard_num,
                                         {"bucket_les": list(map(float, self.bucket_les))})
                self._meta_written = True
            self.sink.write_chunkset(self.dataset, self.shard_num, group, records)
        except Exception:
            # transient sink failure must not lose the snapshot: requeue it
            # for the next flush attempt. A fully-written duplicate frame from
            # a partially-completed attempt is deduped at recovery replay by
            # the store's out-of-order drop; a torn tail frame is skipped by
            # the sink reader (WAL semantics). The requeue puts the pids back
            # in _pending_chunks where the release-time scrubs see them, so
            # the inflight token can be dropped with the snapshot re-queued.
            with self.lock:
                self._requeue_pending_locked(group, pending, pend_epochs)
                self._inflight_flush.pop(token, None)
            raise
        try:
            # inline downsample runs after the chunks are durably written; a
            # failure here must not kill the ingest thread — the streaming
            # downsampler retains its accumulators and retries next flush.
            # Still under the inflight token: a release between the sink
            # write and this add would otherwise let the dead pid's samples
            # rebuild an open bucket AFTER drop_pids scrubbed it, and the
            # claim-generation check cannot poison a claim taken later
            if self.downsample is not None and vals.ndim == 1:
                res_ms, target = self.downsample
                try:
                    if hasattr(target, "add"):    # streaming InlineDownsampler
                        target.add(self, pids, ts, vals)
                    else:                         # plain callback (tests)
                        from .downsample import downsample_records
                        target(self, downsample_records(pids, ts, vals, res_ms))
                except Exception:
                    log.exception("inline downsample publish failed; will retry")
        finally:
            with self.lock:
                self._inflight_flush.pop(token, None)
        off = int(self._pending_group_offset[group])
        if off >= 0:
            # a checkpoint failure does NOT requeue: the chunks are durable,
            # the watermark merely lags and recommits on the next flush
            self.sink.write_checkpoint(self.dataset, self.shard_num, group, off)
            with self.lock:
                self.group_watermarks[group] = off
        return len(records)

    def _requeue_pending_locked(self, group, pending, pend_epochs) -> None:
        """Return a failed flush's chunk snapshot to the pending queue (at the
        front, preserving order), scrubbing samples whose partition was
        released while the snapshot was outside ``_pending_chunks`` — the
        release-time scrub could not see them there. Caller holds the lock."""
        kept = []
        for (pids_, ts_, vals_), eps in zip(pending, pend_epochs):
            m = self.slot_epoch[pids_] == eps
            if m.all():
                kept.append((pids_, ts_, vals_))
            elif m.any():
                kept.append((pids_[m], ts_[m], vals_[m]))
        self._pending_chunks[group] = kept + self._pending_chunks[group]

    def flush_all_groups(self) -> None:
        for g in range(self.config.groups_per_shard):
            self.flush_group(g)

    def recover(self, bus=None, schemas: Schemas | None = None,
                on_chunks_loaded=None, accept=None) -> int:
        """Restore shard state from the sink + replay the bus from the minimum
        checkpointed offset (ref: TimeSeriesShard.recoverIndex :483 +
        TimeSeriesMemStore.recoverStream :148). Returns rows replayed.
        ``accept(container)`` filters replayed containers when several
        shards share one broker partition (IngestionConsumer demux)."""
        assert self.sink is not None and len(self.index) == 0
        # queries admitted mid-recovery see a PARTIAL shard: flagged so the
        # serving layer never caches an in-window empty selection as proof
        # of emptiness (the TTL negative cache would otherwise mask the
        # recovered data for its whole TTL — a restart-then-404 incident)
        self.recovering = True
        try:
            return self._recover_inner(bus, schemas, on_chunks_loaded, accept)
        finally:
            self.recovering = False

    def _recover_inner(self, bus, schemas, on_chunks_loaded, accept) -> int:
        if self.store is None and (self.schema.is_histogram
                                   or self.schema.is_multi_column):
            meta = self.sink.read_meta(self.dataset, self.shard_num) \
                if hasattr(self.sink, "read_meta") else {}
            # create early only when the bucket count is knowable: a
            # histogram schema without persisted les (crash before first
            # flush) must stay None so bus replay recreates it with the
            # bucket scheme its first container carries
            if meta.get("bucket_les") or not self.schema.is_histogram:
                # under the shard lock: queries are admitted while recovery
                # streams in, and they read self.store
                with self.lock:
                    self.bucket_les = (np.asarray(meta["bucket_les"])
                                       if meta.get("bucket_les") else None)
                    self.store = self._make_store()
                    self.store.owner_lock = self.lock
        # 1. part keys -> index (ids dense in creation order; a purged slot may
        #    have been re-persisted under a new series — the last entry wins).
        #    The durable index time buckets (index.log) are the FAST path:
        #    columnar frames load back through bulk array adds; partkeys.log
        #    (per-key JSON) stays the fallback for sinks/logs without them.
        #    Either way the duration lands in filodb_index_recover_ms.
        import time as _time
        t0_index = _time.perf_counter()
        # pid -> (labels | None, label blob | None, start); blobs parse
        # lazily — the bulk load consumes them as canonical key bytes
        latest: dict[int, tuple[dict | None, bytes | None, int]] = {}
        last_live: dict[int, tuple[dict | None, bytes | None]] = {}
        frames_reader = getattr(self.sink, "read_index_frames", None)
        used_frames = False
        if frames_reader is not None and self.index_bucket_ms:
            try:
                frames = list(frames_reader(self.dataset,
                                            self.shard_num) or ())
                # trust window: the log is authoritative only from its
                # LAST genesis snapshot, and only when no RETIRE marker
                # (a persistence-off recovery ran since) supersedes it —
                # an upgraded or toggled shard whose log misses history
                # must fall back, never silently lose series
                gen_at = retire_at = -1
                for fi, fr in enumerate(frames):
                    if fr[0] == INDEX_GENESIS_BUCKET:
                        gen_at = fi
                    elif fr[0] == INDEX_RETIRE_BUCKET:
                        retire_at = fi
                trusted = gen_at >= 0 and gen_at > retire_at
                for fr in (frames[gen_at:] if trusted else ()):
                    _bucket, fpids, fstarts, fblobs, fflags = fr
                    if len(fflags) \
                            and (fflags & INDEX_FLAG_UNPARSEABLE).any():
                        trusted = False     # placeholder entries: the pair
                        break               # encoding could not hold them
                    for pid, start, blob in zip(fpids.tolist(),
                                                fstarts.tolist(), fblobs):
                        latest[pid] = (None, blob, start)
                        if blob:
                            last_live[pid] = (None, blob)
                if trusted and latest:
                    used_frames = True
                    self._index_log_seeded = True
                else:
                    latest.clear()
                    last_live.clear()
            except Exception:
                log.warning("index.log recovery failed; rebuilding from "
                            "partkeys.log", exc_info=True)
                latest.clear()
                last_live.clear()
        if not used_frames:
            for pid, labels, start in self.sink.read_part_keys(
                    self.dataset, self.shard_num) or ():
                latest[pid] = (labels, None, start)
                if labels:
                    last_live[pid] = (labels, None)
        opts = self.schema.options

        def _pk_and_labels(labels, blob):
            if labels is None:
                labels = labels_from_blob(blob)
            if blob and not opts.ignore_shard_key_tags:
                return blob, labels      # full-label blob IS the part key
            return part_key_of(labels, opts), labels

        # queries are admitted while recovery streams in (the reference serves
        # partial data during RecoveryInProgress), so index and store
        # mutations take the shard lock like any ingest would — an unlocked
        # store.append would donate (delete) array buffers a concurrent query
        # has already captured
        with self.lock:
            recovered_keys: list[tuple[int, bytes]] = []
            items = [(pid,) + latest[pid] for pid in sorted(latest)]
            # bulk-loadable only when the blob doubles as the canonical key
            # (no ignored tags: add_part_keys_bulk derives index labels FROM
            # the key bytes, which must then carry every label)
            can_bulk = used_frames and not opts.ignore_shard_key_tags
            i = 0
            while i < len(items):
                pid, labels, blob, start = items[i]
                while len(self.index) < pid:   # gap: entry lost; free hole
                    hole = len(self.index)
                    self.index.add_part_key(hole, {}, 0, end_time=-1)
                    self._free_pids.append(hole)
                if not labels and not blob:    # tombstone won: slot is free
                    self.index.add_part_key(pid, {}, 0, end_time=-1)
                    self._free_pids.append(pid)
                    prev = last_live.get(pid)
                    if prev is not None:       # returning-series detection
                        self._evicted_keys.add(_pk_and_labels(*prev)[0])
                    i += 1
                    continue
                # dense live run -> ONE columnar bulk add (the recover-ms
                # lever: no per-key dict builds or python add loops)
                j = i
                while (can_bulk and j < len(items) and items[j][2]
                       and items[j][0] == pid + (j - i)):
                    j += 1
                if j - i >= RECOVER_BULK_MIN and \
                        len({items[k][2] for k in range(i, j)}) == j - i and \
                        self.index.add_part_keys_bulk(
                            np.arange(pid, pid + (j - i)),
                            [items[k][2] for k in range(i, j)], 0,
                            start_times=np.asarray(
                                [items[k][3] for k in range(i, j)],
                                np.int64)):
                    if self.governor is not None:
                        # batched adoption: one cheap key-bytes extraction
                        # per key and ONE adopt per distinct tenant — a
                        # per-key dict build + lock + gauge update would
                        # hand back much of the bulk path's win
                        tenants: dict[str, int] = {}
                        for k in range(i, j):
                            t = self.governor.tenant_from_key_bytes(
                                items[k][2])
                            tenants[t] = tenants.get(t, 0) + 1
                        for t, cnt in tenants.items():
                            self.governor.adopt(t, cnt)
                    for k in range(i, j):
                        rpid, _rl, rblob, _rs = items[k]
                        self._part_key_to_id[rblob] = rpid
                        self._part_key_of_id[rpid] = rblob
                        recovered_keys.append((rpid, rblob))
                    i = j
                    continue
                pk, labels = _pk_and_labels(labels, blob)
                self._part_key_to_id[pk] = pid
                self._part_key_of_id[pid] = pk
                recovered_keys.append((pid, pk))
                self.index.add_part_key(pid, labels, start)
                if self.governor is not None:
                    self.governor.adopt(self.governor.tenant_of(labels))
                i += 1
            if self._native_ps is not None and recovered_keys:
                # one native batch hash + ONE batch insert (per-key ctypes
                # calls cost ~10us each — material at 100k recovered series)
                from .native import fnv1a64_batch
                hashes = fnv1a64_batch([pk for _pid, pk in recovered_keys])
                self._native_ps.insert_batch(
                    [(int(h), pk, pid)
                     for (pid, pk), h in zip(recovered_keys, hashes)])
                for (pid, _pk), h in zip(recovered_keys, hashes):
                    self._pid_hash[pid] = h
        registry.gauge(FILODB_INDEX_RECOVER_MS,
                       {"dataset": self.dataset,
                        "shard": str(self.shard_num)}) \
            .update((_time.perf_counter() - t0_index) * 1000.0)
        if hasattr(self.sink, "write_index_bucket"):
            # re-anchor the index log's trust: a fallback rebuild appends a
            # fresh GENESIS snapshot (fast path restored next restart), a
            # persistence-off recovery appends a RETIRE marker so a later
            # persistence-on restart cannot trust the now-stale content.
            # Best-effort — a failed write just defers seeding to the next
            # drain (seeded stays False) or the next recovery
            try:
                if self.index_bucket_ms and not used_frames:
                    self._write_index_genesis()
                elif not self.index_bucket_ms:
                    self.sink.write_index_bucket(
                        self.dataset, self.shard_num,
                        encode_index_bucket(INDEX_RETIRE_BUCKET, []))
            except Exception:
                log.warning("index.log trust re-anchor failed; the next "
                            "drain or recovery retries", exc_info=True)
        # 2. chunks -> device store (batched appends, flush order == time order).
        #    Chunks of purged partitions are skipped; for a reused slot, samples
        #    older than the current owner's start time belong to the purged
        #    predecessor and are dropped.
        own_start = {pid: start
                     for pid, (labels, blob, start) in latest.items()
                     if labels or blob}
        start_of = np.full(len(self.index) + 1, 1 << 62, np.int64)
        for pid, start in own_start.items():
            start_of[pid] = start
        for group, records in self.sink.read_chunksets(self.dataset, self.shard_num) or ():
            keep = [r for r in records if r.part_id in own_start]
            if not keep:
                continue
            pids = np.concatenate([np.full(len(r.ts), r.part_id, np.int32) for r in keep])
            ts = np.concatenate([r.ts for r in keep])
            vals = np.concatenate([r.values for r in keep])
            owned = ts >= start_of[pids]
            if not owned.all():
                pids, ts, vals = pids[owned], ts[owned], vals[owned]
            if len(pids):
                with self.lock:   # append donates the store buffers
                    self.store.append(pids, ts, vals)
                    # loaded chunks change query-visible data exactly like
                    # a flush would: the epoch-validated caches must see
                    # the bump (a result cached mid-recovery would
                    # otherwise validate against a pre-load vector forever)
                    self._bump_epoch_locked(int(ts.min()))
                    lead = int(ts.max())
                    if lead > self.lead_ms:
                        self.lead_ms = lead
                    if lead > self.visible_lead_ms:
                        self.visible_lead_ms = lead   # loaded = visible
        # between chunk load and replay: replayed rows flow through the
        # normal flush pipeline, so state seeded here (e.g. the streaming
        # downsampler's open buckets) sees each sample exactly once
        if on_chunks_loaded is not None:
            on_chunks_loaded()
        # 3. checkpoints -> watermarks; replay the bus past them
        cps = self.sink.read_checkpoints(self.dataset, self.shard_num)
        with self.lock:   # _pending_group_offset is ingest-staging state
            for g, off in cps.items():
                self.group_watermarks[g] = off
                self._pending_group_offset[g] = off
        replayed = 0
        if bus is not None:
            wm = self.group_watermarks.copy()
            start_off = int(wm[wm >= 0].min()) if (wm >= 0).any() else 0
            next_off = start_off
            for off, container in bus.consume(schemas or Schemas(), start_off):
                next_off = off + 1
                if accept is not None and not accept(container):
                    continue
                before = self.stats.rows_ingested
                self.ingest(container, off, recovery_watermarks=wm)
                replayed += self.stats.rows_ingested - before
            self.flush()
            # the EXACT offset replay reached: the live consumer must resume
            # here, not at a later end_offset read — frames published between
            # the replay's end snapshot and that read would be skipped
            # forever (visible as a permanent gap on an adopted shard that
            # warms while its partition keeps taking writes)
            self.recovered_through = next_off
        return replayed

    # -- purge (ref: TimeSeriesShard.purgeExpiredPartitions :751) ------------

    def purge_expired_partitions(self, cutoff_ms: int) -> int:
        """Remove partitions whose last sample is older than ``cutoff_ms``:
        index entries tombstoned, HBM rows freed for reuse, part keys recorded
        in the evicted-keys filter so a returning series is detected. Returns
        the number of partitions purged."""
        self.flush()
        if self.store is None:
            return 0
        # the whole purge mutates index + store + id maps; query threads read the
        # same structures concurrently, so it all happens under the shard lock
        with self.lock:
            # mark end-times of inactive series (the reference persists endTime
            # when a partition goes quiet; the host last_ts mirror is authoritative)
            last = self.store.last_ts
            inactive = np.nonzero((self.store.n_host > 0) & (last < cutoff_ms))[0]
            ended = {pid: int(last[pid]) for pid in inactive.tolist()
                     if self.index.is_live(pid)}
            if ended:
                # the marks alone are query-visible — a series ended at T
                # drops out of selections for windows past T even when the
                # pending-flush filter below vetoes the actual purge — so
                # they need their own bump: steps at or before the earliest
                # mark are provably unaffected (batch_min_ts class). Bump
                # BEFORE applying the marks (the flush/release pattern): a
                # mid-loop fault can then never leave marks visible under a
                # stale epoch
                self._bump_epoch_locked(min(ended.values()))
                for pid, end_ts in ended.items():
                    self.index.update_end_time(pid, end_ts)
            purged = self.index.part_ids_ended_before(cutoff_ms)
            # never purge series with data still staged for a pending flush
            # group, nor pids of a snapshot currently being written
            if len(purged) and self.sink is not None:
                staged = [pids for chunks in self._pending_chunks
                          for (pids, _, _) in chunks]
                staged.extend(self._inflight_flush.values())
                if staged:
                    pending = np.unique(np.concatenate(staged))
                    purged = np.setdiff1d(purged, pending).astype(np.int32)
            if len(purged) == 0:
                return 0
            self._release_partitions_locked(purged)
            self.stats.partitions_purged += len(purged)
        self._flush_partkey_log()   # durable write happens outside the shard lock
        return len(purged)

    # -- on-demand paging (ref: OnDemandPagingShard.scala:26,58 +
    #    DemandPagedChunkStore.scala:35 — cold chunks paged in for queries) -----

    def needs_paging(self, pids: np.ndarray, start_ms: int) -> bool:
        """True when the query needs data older than what's resident for any
        selected series and a durable sink exists to page from."""
        if self.sink is None or len(pids) == 0 or self.store is None:
            return False
        first = self.store.first_ts[pids]
        return bool((first[first >= 0] > start_ms).any())

    def read_cold_for(self, pids: np.ndarray, start_ms: int, end_ms: int):
        """Sink-side cold chunks for the given pids: pid -> ([ts...], [vals...]).
        Needs NO shard lock — sink logs are append-only and torn-tolerant, so
        wide paged scans must not stall ingest while reading disk. The scan
        is traced and its paged samples counted per tier: a remote sink
        (StoreServer ring) is the cluster-wide durable-tier ODP path."""
        cold_ts: dict[int, list] = {int(p): [] for p in pids}
        cold_val: dict[int, list] = {int(p): [] for p in pids}
        reader = getattr(self.sink, "read_chunksets", None)
        if reader is not None:
            tier = ("remote" if getattr(self.sink, "remote_tier", False)
                    else "local")
            rows = 0
            with span(SPAN_ODP_DURABLE, shard=self.shard_num,
                      tier=tier) as tags:
                for _g, records in reader(self.dataset, self.shard_num,
                                          start_ms, end_ms) or ():
                    for r in records:
                        if r.part_id in cold_ts:
                            cold_ts[r.part_id].append(r.ts)
                            cold_val[r.part_id].append(np.asarray(r.values))
                            rows += len(r.ts)
                tags["rows"] = rows
            if rows:
                registry.counter(FILODB_RETENTION_ODP_ROWS,
                                 {"dataset": self.dataset,
                                  "tier": tier}).increment(rows)
        return cold_ts, cold_val

    def age_out_durable(self, cutoff_ms: int) -> int:
        """Durable raw retention (retention.raw_ttl): drop sink samples older
        than ``cutoff_ms`` and bump ``data_epoch`` so cached results over the
        aged-out range invalidate. The heavy read-decode-rewrite half runs
        with NO locks held (copy-out); only the commit — splicing the tail
        appended since the snapshot, bounded by one flush batch per group,
        then an atomic rename — runs under all group flush locks, so the
        rewrite can never lose a concurrent append yet flushes stall only
        for the splice. Sinks without the prepare/commit split (the remote
        store client, whose age_out is one deadline-bounded RPC) keep the
        single-call form under the locks — the declared LATENCY_SPEC
        sanction."""
        import contextlib
        sink = self.sink
        if sink is None or not hasattr(sink, "age_out"):
            return 0
        prepare = getattr(sink, "age_out_prepare", None)
        if prepare is not None:
            token = prepare(self.dataset, self.shard_num, cutoff_ms)
            if token is None:
                return 0
            with contextlib.ExitStack() as stack:
                for lk in self._group_flush_locks:   # ascending: in-order
                    stack.enter_context(lk)
                dropped = int(sink.age_out_commit(token))
        else:
            with contextlib.ExitStack() as stack:
                for lk in self._group_flush_locks:   # ascending: in-order
                    stack.enter_context(lk)
                dropped = int(sink.age_out(self.dataset, self.shard_num,
                                           cutoff_ms))
        if dropped:
            with self.lock:
                # result-cache watermark: rows aged out (destructive)
                self._bump_epoch_locked(EPOCH_AFFECTS_ALL)
            registry.counter(FILODB_RETENTION_AGED_OUT_ROWS,
                             {"dataset": self.dataset,
                              "shard": str(self.shard_num)}).increment(dropped)
        return dropped

    def read_with_paging(self, pids: np.ndarray, start_ms: int, end_ms: int,
                         cold=None, column=None):
        """Merged (ts [P, C'], val [P, C'], n [P]) host arrays combining paged
        cold chunks (from the sink) with resident device data, deduped on the
        per-series resident first-timestamp boundary. ``cold`` accepts a
        pre-fetched read_cold_for result (gathered outside the shard lock);
        ``column`` selects one scalar column of a multi-column store (cold
        multi-column records are sliced by the schema layout)."""
        from .chunkstore import TS_PAD
        cold_ts, cold_val = cold if cold is not None else \
            self.read_cold_for(pids, start_ms, end_ms)
        col_off = None
        if self.schema.is_multi_column:
            nb = len(self.bucket_les) if self.bucket_les is not None else 0
            name = column or self.store.default_col
            for nm, off, w, _ih in self.schema.col_layout(nb):
                if nm == name:
                    assert w == 1, "histogram columns do not page on demand"
                    col_off = off
                    break
        rows_ts, rows_val = [], []
        # ONE batched device->host transfer for the whole paged batch, and a
        # compressed-resident store decodes/derives ONLY the selected rows
        # (gather_rows — the whole-store f32/i64 temp never materializes).
        # The previous per-pid slice (`np.asarray(tsrc[p, :cnt])`) cost one
        # host sync (a dispatch round trip) per SERIES — the dominant term
        # of a wide cold scan
        from .chunkstore import _Deferred
        tsrc, vsrc, _n = self.store.arrays(column)
        if isinstance(tsrc, np.ndarray) and isinstance(vsrc, np.ndarray):
            ts_host, val_host = tsrc[pids], vsrc[pids]
        else:
            import jax
            import jax.numpy as jnp
            rid = jnp.asarray(np.asarray(pids, np.int32))
            ts_rows = (tsrc.gather_rows(rid) if isinstance(tsrc, _Deferred)
                       else jnp.take(jnp.asarray(tsrc), rid, axis=0))
            val_rows = (vsrc.gather_rows(rid) if isinstance(vsrc, _Deferred)
                        else jnp.take(jnp.asarray(vsrc), rid, axis=0))
            ts_host, val_host = jax.device_get((ts_rows, val_rows))
        for i, p in enumerate(pids):
            p = int(p)
            cnt = int(self.store.n_host[p])
            b = int(self.store.born[p])     # the cells before a birth: none
            hot_t = np.asarray(ts_host[i, b:cnt])
            hot_v = np.asarray(val_host[i, b:cnt])
            if self.store.hole_cells:       # a hole is no sample
                hot_t, hot_v = hot_t[hot_t < TS_PAD], hot_v[hot_t < TS_PAD]
            boundary = hot_t[0] if len(hot_t) else (1 << 62)
            if cold_ts[p]:
                ct = np.concatenate(cold_ts[p])
                cv = np.concatenate(cold_val[p])
                if col_off is not None and cv.ndim == 2:
                    cv = cv[:, col_off]
                # same slot-reuse rule as recovery (recover() step 2): sink
                # chunks older than the CURRENT owner's start time belong to
                # a released predecessor of the slot, not this series
                own_start = self.index.start_time(p)
                sel = (ct < boundary) & (ct >= own_start)
                order = np.argsort(ct[sel], kind="stable")
                st, sv = ct[sel][order], cv[sel][order]
                if len(st):
                    # keep-first timestamp dedup: a requeued flush after a
                    # partial sink failure (or a lost-response write) can
                    # leave duplicate frames in the log — recovery replay
                    # dedups via the store's out-of-order drop, and the
                    # paged read path must match it or duplicated samples
                    # double-count in sum/count_over_time
                    keep = np.concatenate([[True], np.diff(st) > 0])
                    st, sv = st[keep], sv[keep]
                rows_ts.append(np.concatenate([st, hot_t]))
                rows_val.append(np.concatenate([sv, hot_v]))
            else:
                rows_ts.append(hot_t)
                rows_val.append(hot_v)
        C = max((len(t) for t in rows_ts), default=1)
        P = len(pids)
        ts_arr = np.full((P, C), TS_PAD, np.int64)
        val_arr = np.zeros((P, C), np.float64)
        n_arr = np.zeros(P, np.int32)
        for i, (t, v) in enumerate(zip(rows_ts, rows_val)):
            ts_arr[i, :len(t)] = t
            val_arr[i, :len(t)] = v
            n_arr[i] = len(t)
        return ts_arr, val_arr, n_arr

    # -- queries ------------------------------------------------------------

    def rv_key_of(self, pid: int):
        """Memoized RangeVectorKey for a live pid (query-leaf hot path: avoids
        re-materializing the dict-encoded labels on every query). Call under
        the shard lock; purge drops cache entries for reused slots."""
        assert_owned(self.lock, "rv_key_of")   # caller-holds-lock contract
        k = self._rv_keys.get(pid)
        if k is None:
            from ..query.rangevector import RangeVectorKey
            k = self._rv_keys[pid] = RangeVectorKey.of(self.index.labels_of(pid))
        return k

    def selection(self, filters: list[Filter], start: int, end: int,
                  keep_over: int) -> tuple[ShardSelection, str]:
        """The query leaves' select: the series ``filters`` match in
        [start, end] and how they came — ``hit`` / ``miss`` of the selection
        memo, or ``bypass`` for what it does not keep (a selection of at most
        ``keep_over`` series, a time mask that bites, a recovering shard).
        Staged rows land first, as before every select: the memo holds what
        labels decide, never samples. The arrays handed out are shared and
        read-only."""
        self.flush()
        with self.lock:
            return self._selections.select(filters, start, end, keep_over)

    def part_ids_from_filters(self, filters: list[Filter], start: int, end: int,
                              limit: int | None = None) -> np.ndarray:
        self.flush()
        # under the shard lock: a concurrent purge mutates postings in place
        with self.lock:
            return self.index.part_ids_from_filters(filters, start, end, limit)

    def label_values(self, label: str, filters=None, top_k=None) -> list[str]:
        with self.lock:
            return self.index.label_values(label, filters, top_k=top_k)

    def label_value_counts(self, label: str, filters=None,
                           top_k=None) -> list[tuple[str, int]]:
        with self.lock:
            return self.index.label_value_counts(label, filters, top_k=top_k)

    def label_names(self, filters=None) -> list[str]:
        with self.lock:
            return self.index.label_names(filters)

    @property
    def num_series(self) -> int:
        return len(self._part_key_to_id)


class TimeSeriesMemStore:
    """Dataset -> shards facade (ref: MemStore.scala trait + TimeSeriesMemStore)."""

    def __init__(self, schemas: Schemas | None = None):
        self.schemas = schemas or Schemas()
        self._shards: dict[tuple[str, int], TimeSeriesShard] = {}
        self._configs: dict[str, StoreConfig] = {}
        self._dataset_schema: dict[str, Schema] = {}

    def setup(self, dataset: str, schema: Schema | str, shard: int,
              config: StoreConfig | None = None, device=None,
              sink: ChunkSink | None = None,
              eviction_policy: EvictionPolicy | None = None) -> TimeSeriesShard:
        if isinstance(schema, str):
            schema = self.schemas[schema]
        cfg = config or self._configs.get(dataset) or StoreConfig()
        self._configs[dataset] = cfg
        self._dataset_schema[dataset] = schema
        key = (dataset, shard)
        if key in self._shards:
            raise ValueError(f"shard {shard} of {dataset} already set up")
        s = TimeSeriesShard(dataset, schema, shard, cfg, device=device, sink=sink,
                            eviction_policy=eviction_policy)
        self._shards[key] = s
        return s

    def shard(self, dataset: str, shard: int) -> TimeSeriesShard:
        return self._shards[(dataset, shard)]

    def shards_of(self, dataset: str) -> list[TimeSeriesShard]:
        return [s for (d, _), s in sorted(self._shards.items()) if d == dataset]

    def shards(self) -> list[TimeSeriesShard]:
        """Every shard of every dataset (a snapshot: set-up may add one)."""
        return list(self._shards.values())

    def ingest(self, dataset: str, shard: int, container: RecordContainer,
               offset: int = -1) -> None:
        self._shards[(dataset, shard)].ingest(container, offset)

    def flush_all(self, dataset: str | None = None) -> None:
        for (d, _), s in self._shards.items():
            if dataset is None or d == dataset:
                s.flush()
