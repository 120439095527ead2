"""Metrics: counters/gauges/histograms + Prometheus text exposition.

Reference: Kamon instrumentation throughout the hot paths (TimeSeriesShardStats
TimeSeriesShard.scala:36-97, MemoryStats BlockManager.scala:63, ChunkSinkStats,
ShardHealthStats.scala) exported via the Prometheus embedded server / log
reporters (coordinator/.../KamonLogger.scala).

One process-global registry; the HTTP server exposes it at /metrics.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_right
from collections import defaultdict

# ---------------------------------------------------------------------------
# Declared metric surface.
#
# Every ``filodb_*`` series this process exports is named by ONE constant
# below and documented in METRICS_SPEC — filolint's surface-check family
# enforces it (a literal name at a registration site, an undeclared
# constant, a kind mismatch, or two constants sharing a name all fail
# tier-1), and the README "Metrics" table is generated from this dict so
# docs cannot drift from code.  A ``*`` suffix declares a dynamic family
# (names built with an f-string prefix).
# ---------------------------------------------------------------------------

FILODB_INGESTED_ROWS = "filodb_ingested_rows"
FILODB_GATEWAY_INGESTED_ROWS = "filodb_gateway_ingested_rows"
FILODB_GATEWAY_PARSE_ERRORS = "filodb_gateway_parse_errors"
FILODB_INGEST_DECODE_ERRORS = "filodb_ingest_decode_errors"
FILODB_INGEST_RETRIES = "filodb_ingest_retries"
FILODB_INGEST_FAILOVERS = "filodb_ingest_failovers"
FILODB_INGEST_REPLICATION_LAG = "filodb_ingest_replication_lag"
FILODB_INGEST_PUBLISH_SHED = "filodb_ingest_publish_shed"
FILODB_SWALLOWED_ERRORS = "filodb_swallowed_errors"
FILODB_SCHEDULER_WORKER_ERRORS = "filodb_scheduler_worker_errors"
FILODB_PEER_EXEC_REQUESTS = "filodb_peer_exec_requests"
FILODB_PEER_EXEC_LATENCY_MS = "filodb_peer_exec_latency_ms"
FILODB_PEER_BREAKER_OPEN = "filodb_peer_breaker_open"
FILODB_SHARD_STATUS = "filodb_shard_status"
FILODB_SHARD_NUM_SERIES = "filodb_shard_num_series"
FILODB_SHARD_LOCK_WAIT_SECONDS = "filodb_shard_lock_wait_seconds"
FILODB_SHARD_LOCK_HOLD_SECONDS = "filodb_shard_lock_hold_seconds"
FILODB_GROUPIDS = "filodb_groupids"
FILODB_SELECTION_MEMO = "filodb_selection_memo"
FILODB_QUERY_LEAF = "filodb_query_leaf"
FILODB_QUERY_LEAF_GATHER = "filodb_query_leaf_gather"
FILODB_INDEX_RESOLVE = "filodb_index_resolve"
FILODB_QUERY_LATENCY_MS = "filodb_query_latency_ms"
FILODB_QUERY_SLOW = "filodb_query_slow"
FILODB_QUERY_COMPILE_CACHE_HITS = "filodb_query_compile_cache_hits"
FILODB_QUERY_COMPILE_CACHE_MISSES = "filodb_query_compile_cache_misses"
FILODB_QUERY_COMPILE_CACHE_EVICTIONS = "filodb_query_compile_cache_evictions"
FILODB_QUERY_RESULT_CACHE_HITS = "filodb_query_result_cache_hits"
FILODB_QUERY_RESULT_CACHE_MISSES = "filodb_query_result_cache_misses"
FILODB_QUERY_RESULT_CACHE_EVICTIONS = "filodb_query_result_cache_evictions"
FILODB_QUERY_RESULT_CACHE_INVALIDATIONS = \
    "filodb_query_result_cache_invalidations"
FILODB_QUERY_ADMISSION_SHED = "filodb_query_admission_shed"
FILODB_QUERY_ADMISSION_OVERSIZED = "filodb_query_admission_oversized"
FILODB_QUERY_ADMISSION_COST = "filodb_query_admission_cost"
FILODB_QUERY_FUSED_SERVED = "filodb_query_fused_served"
FILODB_QUERY_FUSED_FALLBACK = "filodb_query_fused_fallback"
FILODB_QUERY_FUSED_FALL_TILES = "filodb_query_fused_fall_tiles"
FILODB_QUERY_MESH_SERVED = "filodb_query_mesh_served"
FILODB_QUERY_MESH_FALLBACK = "filodb_query_mesh_fallback"
FILODB_QUERY_MESH_PREPARED = "filodb_query_mesh_prepared"
FILODB_QUERY_NEGATIVE_CACHE_HITS = "filodb_query_negative_cache_hits"
FILODB_QUERY_NEGATIVE_CACHE_EVICTIONS = \
    "filodb_query_negative_cache_evictions"
FILODB_QUERY_FRAGMENT_CACHE_HITS = "filodb_query_fragment_cache_hits"
FILODB_QUERY_FRAGMENT_CACHE_MISSES = "filodb_query_fragment_cache_misses"
FILODB_QUERY_FRAGMENT_CACHE_EXTENSIONS = \
    "filodb_query_fragment_cache_extensions"
FILODB_QUERY_FRAGMENT_CACHE_EVICTIONS = \
    "filodb_query_fragment_cache_evictions"
FILODB_QUERY_FRAGMENT_CACHE_INVALIDATIONS = \
    "filodb_query_fragment_cache_invalidations"
FILODB_QUERY_FRAGMENT_CACHE_BYTES = "filodb_query_fragment_cache_bytes"
FILODB_QUERY_WINDOWS_WIDENED = "filodb_query_windows_widened"
FILODB_QUERY_SUBSCRIBE_INCREMENTS = "filodb_query_subscribe_increments"
FILODB_INGEST_PUBLISH_LATENCY_MS = "filodb_ingest_publish_latency_ms"
FILODB_DEVICE_INFLIGHT_PROGRAMS = "filodb_device_inflight_programs"
FILODB_RUNTIME_WAKEUP_SECONDS = "filodb_runtime_wakeup_seconds"
FILODB_RUNTIME_STALLS = "filodb_runtime_stalls"
FILODB_RETENTION_ROUTED_QUERIES = "filodb_retention_routed_queries"
FILODB_RETENTION_ODP_ROWS = "filodb_retention_odp_rows"
FILODB_RETENTION_REPLICA_FAILOVER = "filodb_retention_replica_failover"
FILODB_RETENTION_AGED_OUT_ROWS = "filodb_retention_aged_out_rows"
FILODB_STORE_RESIDENCY_FALLBACK = "filodb_store_residency_fallback"
FILODB_STORE_STAMP_FORM = "filodb_store_stamp_form"
FILODB_STORE_REHYDRATE = "filodb_store_rehydrate"
FILODB_STORE_RESIDENT_BYTES_PER_SAMPLE = \
    "filodb_store_resident_bytes_per_sample"
FILODB_QUERY_REFUSED = "filodb_query_refused"
FILODB_STORE_ROWS_DEMOTED = "filodb_store_rows_demoted"
FILODB_STORE_BIRTHS = "filodb_store_births"
FILODB_STORE_ROWS_OFF_LINE = "filodb_store_rows_off_line"
FILODB_STORE_HOLE_CELLS = "filodb_store_hole_cells"
FILODB_INGEST_STALE_MARKERS = "filodb_ingest_stale_markers"
FILODB_RULES_EVALUATIONS = "filodb_rules_evaluations"
FILODB_RULES_EVAL_FAILURES = "filodb_rules_eval_failures"
FILODB_RULES_EVAL_LATENCY_MS = "filodb_rules_eval_latency_ms"
FILODB_RULES_EVAL_LAG_MS = "filodb_rules_eval_lag_ms"
FILODB_RULES_DERIVED_ROWS = "filodb_rules_derived_rows"
FILODB_RULES_ALERTS_FIRING = "filodb_rules_alerts_firing"
FILODB_RULES_ALERT_TRANSITIONS = "filodb_rules_alert_transitions"
FILODB_RULES_NOTIFICATIONS = "filodb_rules_notifications"
FILODB_RULES_SPOOF_REJECTS = "filodb_rules_spoof_rejects"
FILODB_INDEX_RECOVER_MS = "filodb_index_recover_ms"
FILODB_INDEX_PERSISTED_BUCKETS = "filodb_index_persisted_buckets"
FILODB_TENANT_ACTIVE_SERIES = "filodb_tenant_active_series"
FILODB_TENANT_SERIES_SHED = "filodb_tenant_series_shed"
FILODB_CLUSTER_GOSSIP_ROUNDS = "filodb_cluster_gossip_rounds"
FILODB_CLUSTER_PEER_STATE = "filodb_cluster_peer_state"
FILODB_CLUSTER_EPOCH = "filodb_cluster_epoch"
FILODB_CLUSTER_FENCED_REJECTS = "filodb_cluster_fenced_rejects"
FILODB_CLUSTER_REBALANCES = "filodb_cluster_rebalances"
FILODB_CLUSTER_REJOIN_TRUNCATED = "filodb_cluster_rejoin_truncated"

METRICS_SPEC: dict[str, tuple[str, str]] = {
    FILODB_INGESTED_ROWS: (
        "counter", "Rows ingested per dataset/shard by the bus consumers."),
    FILODB_GATEWAY_INGESTED_ROWS: (
        "counter", "Samples accepted by the line-protocol gateway "
                   "(a line with F fields contributes F)."),
    FILODB_GATEWAY_PARSE_ERRORS: (
        "counter", "Malformed line-protocol lines dropped by the gateway "
                   "(latest offender sampled in last_parse_error)."),
    FILODB_INGEST_DECODE_ERRORS: (
        "counter", "Decode-ahead worker faults surfaced to the consumer "
                   "(the batch is re-fetched; a rising rate means a "
                   "corrupt bus segment)."),
    FILODB_INGEST_RETRIES: (
        "counter", "BrokerBus publish re-sends: reconnect replays of the "
                   "unacked window plus RETRY-shed backoffs (jittered "
                   "exponential, capped)."),
    FILODB_INGEST_FAILOVERS: (
        "counter", "BrokerBus leader re-resolutions: the client re-ranked "
                   "the replica set by watermark and switched brokers."),
    FILODB_INGEST_REPLICATION_LAG: (
        "gauge", "Frames the follower trails the leader, per partition and "
                 "peer (0 when fully replicated; grows while a follower "
                 "is down or out of the in-sync set)."),
    FILODB_INGEST_PUBLISH_SHED: (
        "counter", "Publishes the broker shed with RETRY: per-partition "
                   "queue-depth overload or a below-min_insync quorum "
                   "stall (clients back off and replay idempotently)."),
    FILODB_SWALLOWED_ERRORS: (
        "counter", "Errors intentionally dropped on non-critical paths, "
                   "tagged by site= — the observability replacement for "
                   "`except: pass` (filolint except-swallow)."),
    FILODB_SCHEDULER_WORKER_ERRORS: (
        "counter", "Query-scheduler worker-loop faults outside task "
                   "execution; the worker survives and the fault is "
                   "counted instead of killing the thread."),
    FILODB_PEER_EXEC_REQUESTS: (
        "counter", "Cross-node /exec dispatches per endpoint."),
    FILODB_PEER_EXEC_LATENCY_MS: (
        "gauge", "Last cross-node /exec round-trip latency per endpoint."),
    FILODB_PEER_BREAKER_OPEN: (
        "gauge", "1 while the per-peer circuit breaker is open (dispatches "
                 "shed fast as 503)."),
    FILODB_SHARD_STATUS: (
        "gauge", "Shard count per dataset and status "
                 "(Active/Assigned/Recovery/Down/Unassigned)."),
    FILODB_SHARD_NUM_SERIES: (
        "gauge", "Live series per shard."),
    FILODB_SHARD_LOCK_WAIT_SECONDS: (
        "gauge", "Seconds threads have blocked in contended acquires of the "
                 "shard lock, total since start."),
    FILODB_SHARD_LOCK_HOLD_SECONDS: (
        "gauge", "Seconds the shard lock has been held, total since start; "
                 "its rate is the lock's utilisation."),
    FILODB_GROUPIDS: (
        "counter", "by/without group-id computations under a shard lock, "
                   "tagged by route: index = gathers over the part-key "
                   "index's label columns (a selection still held as pids), "
                   "walk = one Python step a materialized series key."),
    FILODB_SELECTION_MEMO: (
        "counter", "Reads of a shard's selection memo (core/selection.py) "
                   "by part (select = a selector's part ids and slot "
                   "epochs, groupids = a by/without's group ids, keys and "
                   "device array) and outcome: hit, miss (built and kept; "
                   "reason = time_mask where the select ran the index's "
                   "time-masked pass: kept for its span of ranges), bypass "
                   "(not kept; reason = recovering, time_mask or narrow, "
                   "the first that holds)."),
    FILODB_QUERY_LEAF: (
        "counter", "Data-reading leaves by how they took their rows: route "
                   "= gather (a narrow selection: keys materialized, rows "
                   "gathered to a power of two), wide (the store's own "
                   "blocks, n zeroed outside the selection) or paged (cold "
                   "chunks merged in from the sink)."),
    FILODB_QUERY_LEAF_GATHER: (
        "counter", "Gathered leaves (route = gather, a row at least) by how "
                   "they reached the device under the shard lock: form = "
                   "one (the row gather, the window function, the step "
                   "slice and the aggregate's map phase dispatched as ONE "
                   "program, the host's scalars its arguments: a resident "
                   "scalar block whose stamps are the grid's or a resident "
                   "s64 block, one start cohort) or steps (gathered on its "
                   "own, the kernels after it one dispatch each: a "
                   "compressed-resident, line-form or histogram store, a "
                   "churned cohort, rows a fused kernel takes)."),
    FILODB_INDEX_RESOLVE: (
        "counter", "Leaf selects by whether the index had to resolve the "
                   "filter set: outcome = miss (matcher set algebra ran, "
                   "regex value sets among it) or hit (the part ids came "
                   "from the index's filter cache or the selection memo)."),
    FILODB_QUERY_LATENCY_MS: (
        "histogram", "End-to-end PromQL latency per dataset; the /metrics "
                     "rendering carries the last query's trace id as an "
                     "exemplar-style companion series."),
    FILODB_QUERY_SLOW: (
        "counter", "Queries that crossed query.slow_log_threshold_ms and "
                   "entered the slow-query ring "
                   "(/api/v1/debug/slow_queries)."),
    FILODB_QUERY_COMPILE_CACHE_HITS: (
        "counter", "Compiled-plan cache hits: the query's padded kernel "
                   "shape reused an already-traced XLA program."),
    FILODB_QUERY_COMPILE_CACHE_MISSES: (
        "counter", "Compiled-plan cache misses: a new (kernel, fn/op, "
                   "shape-bucket, dtype) key traced and compiled a fresh "
                   "program (the multi-second first-query cost warmup "
                   "exists to absorb)."),
    FILODB_QUERY_COMPILE_CACHE_EVICTIONS: (
        "counter", "Compiled programs dropped by the plan cache's LRU "
                   "capacity bound (query.plan_cache_size)."),
    FILODB_QUERY_RESULT_CACHE_HITS: (
        "counter", "Result-cache hits: a repeated range query answered "
                   "from the step-aligned fragment cache after its ingest "
                   "watermark vector validated."),
    FILODB_QUERY_RESULT_CACHE_MISSES: (
        "counter", "Result-cache misses (no entry for the query key)."),
    FILODB_QUERY_RESULT_CACHE_EVICTIONS: (
        "counter", "Result-cache entries dropped by the LRU capacity bound "
                   "(query.result_cache_size)."),
    FILODB_QUERY_RESULT_CACHE_INVALIDATIONS: (
        "counter", "Result-cache entries discarded because a shard's ingest "
                   "watermark advanced past the entry's recorded vector "
                   "(data changed; a hit would no longer equal "
                   "re-execution)."),
    FILODB_QUERY_ADMISSION_SHED: (
        "counter", "Queries shed by cost-based admission control (tagged by "
                   "tenant): estimated cost did not fit the in-flight "
                   "budget, answered 503 + Retry-After."),
    FILODB_QUERY_ADMISSION_OVERSIZED: (
        "counter", "Queries rejected outright because their estimated cost "
                   "exceeds the absolute budget or tenant quota (answered "
                   "non-retryable 422; never admissible at any load — NOT "
                   "an overload signal)."),
    FILODB_QUERY_ADMISSION_COST: (
        "gauge", "Estimated cost units currently admitted and executing "
                 "(bounded by query.max_concurrent_cost)."),
    FILODB_QUERY_FUSED_SERVED: (
        "counter", "Queries served by a fused compressed-resident kernel, "
                   "tagged by registry shape (rate_sum / window_reduce / "
                   "hist_quantile) and backend mode (query.fused_kernels: "
                   "xla / pallas)."),
    FILODB_QUERY_FUSED_FALLBACK: (
        "counter", "Queries that matched a fused shape but fell back to "
                   "the composed two-step path (shape gate, group cap, "
                   "off-grid store), tagged by shape."),
    FILODB_QUERY_FUSED_FALL_TILES: (
        "counter", "Row tiles of a fused kernel that telescopes a "
                   "window's delta and fell back to summing increments — "
                   "a counter reset or a series' last sample under a query "
                   "window — summed over the queries it served, tagged by "
                   "kernel (hist: the raw hist kernel's correction matmul; "
                   "line: the scalar kernel's band product on a line "
                   "store) and backend mode; 0 for counters that only "
                   "grow: each query then costs the fewest matmuls a "
                   "tile."),
    FILODB_QUERY_MESH_SERVED: (
        "counter", "Queries served by a mesh dist_* collective, tagged by "
                   "route (fused / fused-narrow / twostep / sketch / topk)."),
    FILODB_QUERY_MESH_FALLBACK: (
        "counter", "Mesh-eligible queries that fell back to the host "
                   "scatter-gather path after eligibility, tagged by reason "
                   "(paging / order_stat_caps / topk_caps)."),
    FILODB_QUERY_MESH_PREPARED: (
        "counter", "What a mesh leaf found ready as it took every shard's "
                   "lock, by part and outcome: plan (a fused program's "
                   "window operands) = ready (built before the locks for "
                   "the grid the last dispatch saw) or built (under them: "
                   "the first fused query, a grid or decode variant that "
                   "changed); gids (the shards' group-id rows on their "
                   "devices) = memo, built (a new selector or grouping, an "
                   "index that changed) or bypass (a selection the shards "
                   "do not keep). ready + memo: the locks were held for a "
                   "dispatch alone."),
    FILODB_QUERY_NEGATIVE_CACHE_HITS: (
        "counter", "Range queries answered from the TTL-bounded negative "
                   "result cache: a recent execution proved the selection "
                   "empty (typo'd metric), so plan+execute is skipped until "
                   "the TTL expires."),
    FILODB_QUERY_NEGATIVE_CACHE_EVICTIONS: (
        "counter", "Negative-cache entries dropped by TTL expiry or the "
                   "capacity bound (query.negative_cache_size)."),
    FILODB_QUERY_FRAGMENT_CACHE_HITS: (
        "counter", "Range queries that reused at least one provably-valid "
                   "cached per-step column from the incremental fragment "
                   "cache (query/incremental.py)."),
    FILODB_QUERY_FRAGMENT_CACHE_MISSES: (
        "counter", "Fragment-cache probes that reused nothing: no entry, "
                   "off-grid request, a coverage gap, or every cached step "
                   "past the stable-before bound."),
    FILODB_QUERY_FRAGMENT_CACHE_EXTENSIONS: (
        "counter", "Fragment entries extended by a delta evaluation: only "
                   "the new head/tail steps executed, the overlap served "
                   "from cache (the dashboard-refresh fast path)."),
    FILODB_QUERY_FRAGMENT_CACHE_EVICTIONS: (
        "counter", "Fragment entries dropped by the entry-count "
                   "(query.fragment_cache_size) or total-byte "
                   "(query.fragment_cache_bytes) bound."),
    FILODB_QUERY_FRAGMENT_CACHE_INVALIDATIONS: (
        "counter", "Fragment entries dropped because per-step validity "
                   "could not be proven: destructive mutation "
                   "(purge/eviction/age-out), an epoch-log gap, or a "
                   "topology change since the entry's vector."),
    FILODB_QUERY_FRAGMENT_CACHE_BYTES: (
        "gauge", "Resident bytes of the fragment cache's per-step value "
                 "columns (per-entry detail at "
                 "/api/v1/debug/fragment_cache)."),
    FILODB_QUERY_WINDOWS_WIDENED: (
        "counter", "Windowed functions auto-widened on retention-routed "
                   "queries because their window was narrower than the "
                   "serving family's resolution (tagged dataset + "
                   "resolution; also in per-query stats)."),
    FILODB_QUERY_SUBSCRIBE_INCREMENTS: (
        "counter", "Per-step increments served by the streaming "
                   "subscription surface (/api/v1/subscribe long-poll and "
                   "chunked modes), tagged by dataset."),
    FILODB_INGEST_PUBLISH_LATENCY_MS: (
        "histogram", "BrokerBus pipelined publish-group round trip per "
                     "partition, exemplar-tagged with the publish trace "
                     "id."),
    FILODB_DEVICE_INFLIGHT_PROGRAMS: (
        "gauge", "Fused query programs dispatched to the device whose "
                 "result no thread has fetched yet, process-wide, as of the "
                 "scrape (the flush's programs are not counted: no one "
                 "fetches them). A dispatch span's tag ahead is the same "
                 "count as its program entered."),
    FILODB_RUNTIME_WAKEUP_SECONDS: (
        "histogram", "How late the tracer's heartbeat thread came out of "
                     "each 20 ms sleep: what a thread pays to get the "
                     "interpreter back, as a worker coming out of a device "
                     "fetch does (the GIL-pressure gauge; recorded while a "
                     "server runs with trace.enabled)."),
    FILODB_RUNTIME_STALLS: (
        "counter", "Heartbeat wake-ups more than 1 s late: the whole "
                   "process stood still. Each logs one warning naming the "
                   "shard lock held, its holder, the oldest unfetched "
                   "dispatch and whether a full collection overlapped."),
    FILODB_RETENTION_ROUTED_QUERIES: (
        "counter", "Queries the retention router served from a downsample "
                   "family (tagged dataset + resolution; stitched raw+ds "
                   "queries count under the family's resolution)."),
    FILODB_RETENTION_ODP_ROWS: (
        "counter", "Samples paged in from the durable chunk tier by "
                   "on-demand paging, tagged tier=local|remote (remote = "
                   "the replicated StoreServer ring)."),
    FILODB_RETENTION_REPLICA_FAILOVER: (
        "counter", "Replica reads that failed and fell over to the next "
                   "backend of the ReplicatedColumnStore ring (tagged by "
                   "op; a rising rate means a dead or flapping "
                   "StoreServer)."),
    FILODB_RETENTION_AGED_OUT_ROWS: (
        "counter", "Raw samples aged out of the durable tier past "
                   "retention.raw_ttl (each pass also bumps the shard's "
                   "data_epoch so cached results invalidate)."),
    FILODB_STORE_RESIDENCY_FALLBACK: (
        "counter", "Flushes where a store configured for compressed "
                   "residency tried to compress and the data refused the "
                   "ok-contract (cohort gate breached), tagged "
                   "reason=resets|non-integer|range — or holds a row born "
                   "late in time-aligned cells (reason=births: the narrow "
                   "forms read every row from column 0) — distinguishes "
                   "\"compressed\" from \"tried and fell back to raw\"."),
    FILODB_STORE_REHYDRATE: (
        "counter", "Times a compressed-resident store was decoded back to "
                   "its raw f32 + s64 blocks, tagged cause=append|compact|"
                   "free (a mutation of a form that cannot take it: "
                   "quant16, delta16 off a grid, a histogram), off_grid "
                   "(the delta form's first stamp off the scrape grid), "
                   "births (a series born late into a delta form that was "
                   "adopted from time-aligned cells) or "
                   "cohort_gate (more rows in the raw pool than "
                   "store.narrow_cohort_gate allows). The delta form on a "
                   "grid appends, ages out and frees as it is: 0 there."),
    FILODB_STORE_RESIDENT_BYTES_PER_SAMPLE: (
        "gauge", "Resident HBM bytes of a shard's sample state (values + "
                 "stamps) over the cells it holds: 12 raw (f32 + s64), ~1 "
                 "in the delta8 form with elided stamps."),
    FILODB_QUERY_REFUSED: (
        "counter", "Queries refused by name rather than run, tagged "
                   "reason=decode_bytes: a wide selection whose decode of a "
                   "compressed-resident store to f32 [S, C] is more than "
                   "half the device's free memory."),
    FILODB_STORE_STAMP_FORM: (
        "gauge", "How a shard's store keeps time: 0 = grid (every stamp on "
                 "one common scrape grid, the s64 block resident), 1 = line "
                 "(a line a row plus a narrow residual a cell; turned on "
                 "once, by the first stamp off the grid)."),
    FILODB_STORE_ROWS_DEMOTED: (
        "counter", "Rows of a line-form store demoted from their line, "
                   "tagged reason=residual|gap|interval (a stamp too far "
                   "from the line for the residual's width, a run of "
                   "holes, staleness markers and skipped cells together, "
                   "past the bound a line keeps, a "
                   "row on no cell of the line); a demoted row is "
                   "answered by the general kernels."),
    FILODB_STORE_BIRTHS: (
        "counter", "Series that appeared after the oldest cell their store "
                   "holds, tagged aligned=true (a store in time-aligned "
                   "cells gave the row a birth cell past 0: one cohort, "
                   "the kernels' births mode) | false (a form without "
                   "birth cells took it as a minority start cohort, or "
                   "left its aligned cells for the line form)."),
    FILODB_STORE_ROWS_OFF_LINE: (
        "gauge", "Live rows of a shard's store that the line kernel skips "
                 "now: demoted rows and rows that start in another cell."),
    FILODB_STORE_HOLE_CELLS: (
        "gauge", "Cells of a shard's line-form store that hold no sample "
                 "among the cells its rows use: missed scrapes, kept as "
                 "holes (a staleness marker or a skipped cell); no "
                 "function reads one."),
    FILODB_INGEST_STALE_MARKERS: (
        "counter", "Rows ingested that carried Prometheus's staleness "
                   "marker (value.StaleNaN: the scrape failed), per shard; "
                   "a scalar store writes each as a hole."),
    FILODB_RULES_EVALUATIONS: (
        "counter", "Rule evaluations completed, tagged group= and rule= "
                   "(one per rule per scheduler tick)."),
    FILODB_RULES_EVAL_FAILURES: (
        "counter", "Rule evaluations that raised (bad data mid-flight, "
                   "admission shed after retries, publish fault), tagged "
                   "group= and rule=; the group keeps evaluating."),
    FILODB_RULES_EVAL_LATENCY_MS: (
        "histogram", "Wall time of one whole group evaluation (every rule "
                     "in the group, sequentially, derived publish "
                     "included), tagged group=."),
    FILODB_RULES_EVAL_LAG_MS: (
        "gauge", "How far the group's completed evaluation trails its "
                 "scheduled grid tick, per group — sustained growth means "
                 "the interval is shorter than the evaluation costs."),
    FILODB_RULES_DERIVED_ROWS: (
        "counter", "Derived samples published back through the ingest "
                   "plane by recording rules, tagged group=."),
    FILODB_RULES_ALERTS_FIRING: (
        "gauge", "Alert instances currently in the firing state, tagged "
                 "rule=."),
    FILODB_RULES_ALERT_TRANSITIONS: (
        "counter", "Alert state-machine transitions, tagged rule= and to= "
                   "(pending/firing/inactive)."),
    FILODB_RULES_NOTIFICATIONS: (
        "counter", "Webhook notifications attempted, tagged status=ok| "
                   "failed (failed = retries exhausted)."),
    FILODB_RULES_SPOOF_REJECTS: (
        "counter", "External writes rejected for carrying the reserved "
                   "__rule__ label (tagged site=remote-write|gateway): "
                   "derived-series provenance cannot be forged."),
    FILODB_INDEX_RECOVER_MS: (
        "gauge", "Wall milliseconds the last shard restart spent recovering "
                 "the part-key index (per dataset/shard): columnar load "
                 "from persisted index.log time buckets when available, "
                 "else the per-key partkeys.log rebuild."),
    FILODB_INDEX_PERSISTED_BUCKETS: (
        "counter", "Index time-bucket frames persisted to the durable tier "
                   "(CRC-verified appends to index.log; recovery loads "
                   "these columnar instead of rebuilding per key)."),
    FILODB_TENANT_ACTIVE_SERIES: (
        "gauge", "Active (resident) series per dataset and tenant — the "
                 "quantity index.max_series_per_tenant bounds; births "
                 "increment, purge/eviction/release decrement."),
    FILODB_TENANT_SERIES_SHED: (
        "counter", "NEW series births shed by the per-tenant cardinality "
                   "limiter, tagged site=shard|gateway|remote-write — "
                   "samples for existing series are never counted here "
                   "(they always land)."),
    FILODB_CLUSTER_GOSSIP_ROUNDS: (
        "counter", "Gossip probe rounds run by this node's membership agent "
                   "(the deterministic round counter suspicion is counted "
                   "in — no wall clock)."),
    FILODB_CLUSTER_PEER_STATE: (
        "gauge", "Membership state per peer: 0=alive, 1=suspect, 2=dead "
                 "(counted-not-timed transitions at cluster.suspect_after / "
                 "cluster.dead_after probe rounds)."),
    FILODB_CLUSTER_EPOCH: (
        "gauge", "Current leadership epoch per fenced scope (scope="
                 "partition|shard, id=): bumps on every claim/adoption — a "
                 "step means a failover or rebalance cutover happened."),
    FILODB_CLUSTER_FENCED_REJECTS: (
        "counter", "Writes refused by epoch fencing (tagged site=publish|"
                   "replicate|store): a deposed leader tried to ack a "
                   "publish, stream a replication batch, or flush/checkpoint "
                   "after deposition."),
    FILODB_CLUSTER_REBALANCES: (
        "counter", "Operator-triggered live shard rebalances completed by "
                   "this node (flush→handoff→catch-up→cutover, tagged "
                   "dataset=)."),
    FILODB_CLUSTER_REJOIN_TRUNCATED: (
        "counter", "Divergent log frames a restarted deposed leader "
                   "truncated on REJOIN before catching up from the current "
                   "leader (tagged partition=)."),
    "filodb_shard_*": (
        "gauge", "Per-shard ingest/eviction stats exported from the shard's "
                 "IngestStats dataclass fields on each /metrics scrape."),
}


def metrics_markdown_table() -> str:
    """The README 'Metrics' table, generated from METRICS_SPEC (verified
    against the checked-in README by tests/test_static_analysis.py)."""
    lines = ["| metric | kind | meaning |", "|---|---|---|"]
    for name, (kind, doc) in sorted(METRICS_SPEC.items()):
        lines.append(f"| `{name}` | {kind} | {doc} |")
    return "\n".join(lines)


class Counter:
    def __init__(self):
        self._v = 0.0
        self._lock = threading.Lock()

    def increment(self, by: float = 1.0):
        with self._lock:
            self._v += by

    @property
    def value(self) -> float:
        return self._v


class Gauge:
    """``update`` is a plain rebind (GIL-atomic — no lock needed); any
    read-modify-write MUST go through ``increment`` instead of
    ``g.value += x``, which loses updates under concurrent dispatch threads
    (filolint's lock-guard-inconsistent rule flags the latter)."""

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def update(self, v: float):
        self.value = float(v)

    def increment(self, by: float = 1.0):
        with self._lock:
            self.value += by


class Histogram:
    """Fixed-boundary histogram (ms-scale latencies by default).

    ``record(v, trace_id=...)`` keeps the LAST recorded observation's trace
    id as an exemplar: /metrics renders it as a companion
    ``<name>_exemplar{trace_id="..."}`` series carrying the exemplar value,
    so an operator can jump from a latency bucket straight to the trace in
    /api/v1/debug/traces (the 0.0.4 text format has no native exemplar
    syntax; a labeled companion series is the compatible encoding)."""

    DEFAULT_BOUNDS = (1, 2, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000)

    def __init__(self, bounds=DEFAULT_BOUNDS):
        self.bounds = list(bounds)
        self.buckets = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self.last_trace_id: str | None = None
        self.last_value = 0.0
        self._lock = threading.Lock()

    def record(self, v: float, trace_id: str | None = None):
        with self._lock:
            self.buckets[bisect_right(self.bounds, v)] += 1
            self.sum += v
            self.count += 1
            if trace_id:
                self.last_trace_id = trace_id
                self.last_value = v


class MetricsRegistry:
    def __init__(self):
        self._metrics: dict[tuple, object] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, tags: dict | None, *args):
        key = (name, tuple(sorted((tags or {}).items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = cls(*args)
            return m

    def counter(self, name: str, tags: dict | None = None) -> Counter:
        return self._get(Counter, name, tags)

    def gauge(self, name: str, tags: dict | None = None) -> Gauge:
        return self._get(Gauge, name, tags)

    def histogram(self, name: str, tags: dict | None = None,
                  bounds=Histogram.DEFAULT_BOUNDS) -> Histogram:
        """``bounds`` count when the series is first made (a unit other
        than milliseconds brings its own)."""
        return self._get(Histogram, name, tags, bounds)

    def expose_prometheus(self) -> str:
        """Prometheus text format 0.0.4."""
        lines = []
        for (name, tags), m in sorted(self._metrics.items()):
            tag_s = ",".join(f'{k}="{v}"' for k, v in tags)
            tag_s = "{" + tag_s + "}" if tag_s else ""
            if isinstance(m, Counter):
                lines.append(f"{name}_total{tag_s} {m.value:g}")
            elif isinstance(m, Gauge):
                lines.append(f"{name}{tag_s} {m.value:g}")
            elif isinstance(m, Histogram):
                cum = 0
                for b, c in zip(m.bounds, m.buckets):
                    cum += c
                    lt = (tag_s[:-1] + "," if tag_s else "{") + f'le="{b}"' + "}"
                    lines.append(f"{name}_bucket{lt} {cum}")
                lt = (tag_s[:-1] + "," if tag_s else "{") + 'le="+Inf"}'
                lines.append(f"{name}_bucket{lt} {m.count}")
                lines.append(f"{name}_sum{tag_s} {m.sum:g}")
                lines.append(f"{name}_count{tag_s} {m.count}")
                if m.last_trace_id:
                    # exemplar-style companion series: the last observation's
                    # trace id as a label, its value as the sample
                    et = (tag_s[:-1] + "," if tag_s else "{") \
                        + f'trace_id="{m.last_trace_id}"' + "}"
                    lines.append(f"{name}_exemplar{et} {m.last_value:g}")
        return "\n".join(lines) + "\n"


registry = MetricsRegistry()


class ShardHealthStats:
    """Ref: coordinator/.../ShardHealthStats.scala — gauges per dataset for
    active/recovering/down shard counts fed from ShardManager snapshots."""

    def __init__(self, dataset: str, reg: MetricsRegistry = registry):
        self.dataset = dataset
        self.reg = reg

    def update(self, snapshot: dict) -> None:
        counts = defaultdict(int)
        for info in snapshot.values():
            counts[info["status"]] += 1
        for status in ("Active", "Assigned", "Recovery", "Down", "Unassigned"):
            self.reg.gauge(FILODB_SHARD_STATUS,
                           {"dataset": self.dataset, "status": status}
                           ).update(counts.get(status, 0))
