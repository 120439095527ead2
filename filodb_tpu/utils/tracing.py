"""Tracing: distributed spans with context propagation, sampling, and an
in-process collector + Zipkin v2 exporter.

Reference: Kamon spans on hot paths (ODP span OnDemandPagingShard.scala:47-50,
query spans queryengine2/QueryEngine.scala:62-66) exported to Zipkin via the
custom reporter (core/.../zipkin/Zipkin.scala:24) and span log reporters
(KamonLogger.scala). Here: ``with span(SPAN_QUERY_EXECUTE, tags)`` records
timing into a ring buffer; context crosses threads via ``activate`` and
crosses the wire via ``current_context``/``activate`` pairs (the /exec HTTP
header and the broker PUBLISH_BATCH / OP_REPLICATE trace-header blocks), so
one query or one publish yields ONE trace id with spans from every
participating node.

Clock discipline: a span's start (``start_ns``) and its duration both come
from ``time.perf_counter_ns()``, so everything that consumes spans inside
one process (trace assembly, the benchmark's readers, a span that tags the
lock wait it contained) orders and subtracts them on ONE monotonic clock —
the same no-wall-clock rule the fault plans and broker follow (a stepped
system clock must never produce negative or million-second spans). The
wall clock is the EXPORTER's anchor only: it is read once per recorded
span, at close, and ``start_us`` (what Zipkin needs) is that reading minus
the monotonic time since the start.

The profiler's clock: every recorded span also runs inside a
``jax.profiler.TraceAnnotation`` of the same name (argument ``trace_id``),
so any ``jax.profiler`` trace of the process holds the program's spans in
its host planes, on the trace's own clock, beside the device operations.
An annotation outside a profiler session costs a fraction of a
microsecond; a sampled-out or disabled span enters none. Intervals handed
to ``record()`` after the fact (a queue wait, a garbage collection, the
heartbeat's ``runtime.beat``) cannot enter one and live in the ring only.

Sampling: the decision is made once at the trace ROOT (``sample_rate``) and
rides the context, so either every participating node records a trace or
none does — a half-sampled cross-node trace is useless. A remote context
that arrives sampled is recorded even on a node whose own tracer is
disabled (the root decided).
"""

from __future__ import annotations

import contextlib
import gc
import json
import logging
import os
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from . import diagnostics
from .metrics import (FILODB_RUNTIME_STALLS, FILODB_RUNTIME_WAKEUP_SECONDS,
                      FILODB_SWALLOWED_ERRORS, registry)

log = logging.getLogger("filodb_tpu.trace")

# ---------------------------------------------------------------------------
# Declared span surface.
#
# Every span name this process records is named by ONE constant below and
# documented in TRACE_SPEC — filolint's surface-check family enforces it
# exactly like CONFIG_SPEC / METRICS_SPEC (a literal name at a span() call
# site, an undeclared constant, and a declared-but-unused span all fail
# tier-1), and the ARCHITECTURE span-taxonomy table is generated from this
# dict so docs cannot drift from code.
# ---------------------------------------------------------------------------

SPAN_HTTP_REQUEST = "http.request"
SPAN_HTTP_RENDER = "http.render"
SPAN_QUERY_QUEUE = "query.queue"
SPAN_QUERY = "query"
SPAN_QUERY_PARSE = "query.parse"
SPAN_QUERY_PLAN = "query.plan"
SPAN_QUERY_EXECUTE = "query.execute"
SPAN_QUERY_LEAF = "query.exec.leaf"
SPAN_QUERY_SELECT = "query.exec.select"
SPAN_QUERY_GATHER = "query.exec.gather"
SPAN_QUERY_GROUPIDS = "query.exec.groupids"
SPAN_QUERY_KERNEL = "query.exec.kernel"
SPAN_QUERY_REDUCE = "query.exec.reduce"
SPAN_QUERY_DISPATCH = "query.exec.dispatch"
SPAN_QUERY_SERVE = "query.exec.serve"
SPAN_QUERY_ODP = "query.odp"
SPAN_QUERY_COMPILE = "query.compile"
SPAN_QUERY_ADMIT = "query.admission"
SPAN_REMOTE_READ = "query.remote_read"
SPAN_REMOTE_WRITE = "ingest.remote_write"
SPAN_GATEWAY_PUBLISH = "ingest.gateway.publish"
SPAN_INGEST_PUBLISH = "ingest.publish"
SPAN_BROKER_APPEND = "ingest.broker.append"
SPAN_REPLICATE = "ingest.replicate"
SPAN_REPLICATE_SERVE = "ingest.replicate.serve"
SPAN_INGEST_CONSUME = "ingest.consume"
SPAN_INGEST_FLUSH = "ingest.flush"
SPAN_QUERY_RETENTION = "query.retention"
SPAN_QUERY_FRAGMENT = "query.fragment"
SPAN_QUERY_SUBSCRIBE = "query.subscribe"
SPAN_ODP_DURABLE = "query.odp.durable"
SPAN_RULES_EVAL = "rules.eval"
SPAN_CLUSTER_GOSSIP = "cluster.gossip"
SPAN_CLUSTER_LEAD = "cluster.epoch.lead"
SPAN_CLUSTER_REJOIN = "cluster.rejoin"
SPAN_CLUSTER_REBALANCE = "cluster.rebalance"
SPAN_RUNTIME_GC = "runtime.gc"
SPAN_RUNTIME_BEAT = "runtime.beat"

TRACE_SPEC: dict[str, str] = {
    SPAN_HTTP_REQUEST: "Root span of one HTTP query_range/query request on "
                       "its handler thread: request parsed -> response "
                       "written (tags: route, status, bytes; an error "
                       "answer is written after it closes).",
    SPAN_HTTP_RENDER: "Answer of a query rendered and written: Prometheus "
                      "JSON shape + json.dumps + socket write (tags: "
                      "series, bytes).",
    SPAN_QUERY_QUEUE: "Wait of one scheduled query in the QueryScheduler's "
                      "heap, enqueue -> a worker starts it (recorded after "
                      "the fact; tags: priority).",
    SPAN_QUERY: "One PromQL query; the root unless an http.request opened "
                "first (tags: dataset, promql, start_ms, end_ms, step_ms, "
                "tenant; on close exec_path, status, lock_wait_ms = "
                "every wait of its thread for a shard lock, and "
                "lock_hold_ms = every hold of a shard lock its thread "
                "released inside it: the epoch probe's and the leaf's, so "
                "a leaf's hold is in the leaf's tag and in this one).",
    SPAN_QUERY_PARSE: "PromQL text -> LogicalPlan.",
    SPAN_QUERY_PLAN: "LogicalPlan -> ExecPlan materialization + remote "
                     "collapse.",
    SPAN_QUERY_EXECUTE: "ExecPlan execution (mesh, fused, or scatter-gather "
                        "path; tags: path).",
    SPAN_QUERY_LEAF: "One data-reading leaf under its shard lock; on the "
                     "mesh route one span under every shard's lock, on the "
                     "fused-hist route the engine's own (tags: shard, or "
                     "shard=all route=mesh; lock_wait_ms = what the thread "
                     "waited for shard locks inside it, lock_hold_ms = what "
                     "it held them for, counted as each is released; on the "
                     "mesh route that is the sum over the locks it took and "
                     "locks = how many; there also gids = memo | built | "
                     "bypass: whether the shards' group-id rows came from "
                     "the engine's memo, and on a fused program plan = "
                     "ready | built: whether its window plan was placed "
                     "before the locks were taken).",
    SPAN_QUERY_SELECT: "Index select + array capture of one leaf; per shard "
                       "on the mesh route (tags: shard, series, memo = hit "
                       "| miss | bypass of the shard's selection memo, "
                       "memo_why = recovering | time_mask | narrow: why "
                       "this select was no hit (time_mask = it ran the "
                       "index's pass over every matching series' start "
                       "and end time; wider than a gather it is a miss, "
                       "kept for the span of ranges that pass leaves the "
                       "same), "
                       "demoted = selected rows the fused kernel skips and "
                       "the general kernels answer; on a line store "
                       "hole_cells = the selected rows' cells without a "
                       "sample and used_cells = all the cells they use, "
                       "from the host's counts, kept with the selection; "
                       "matchers = the filters' kinds, eq | ne | in | re | "
                       "nre joined by +; resolve = miss where the index "
                       "had to resolve the filter set, regex value sets "
                       "among it, hit where its filter cache or the memo "
                       "had the part ids; route = gather | wide | paged: "
                       "how the leaf took its rows; the keys of a gather "
                       "are materialized inside this span).",
    SPAN_QUERY_GATHER: "A narrow selection's rows reaching the device (at "
                       "most GATHER_THRESHOLD series and under half the "
                       "index). programs = 1: the dispatch of the leaf's "
                       "ONE program, under the leaf span after the select "
                       "span: row gather padded to a power of two, window "
                       "function, step slice and the aggregate's map phase, "
                       "with the picked rows, steps, window, arguments and "
                       "group ids as host arguments of the call (packed: one "
                       "s64 and one f64 vector). programs "
                       "> 1: the stepwise form, the gather alone, inside the "
                       "select span (or where a fused kernel asked for the "
                       "rows), the count its form's dispatches at the "
                       "least. The stamps of a grid-form store are derived "
                       "from each row's first stamp, its s64 block is no "
                       "operand; a delta block's picked rows are decoded "
                       "inside the same program (tags: shard, rows, padded, "
                       "bytes = rows x a row's values and stamps as the "
                       "store holds them, what the gather needs, programs, "
                       "decode = raw | delta8 | delta16 | quant16: the form "
                       "the rows were read from).",
    SPAN_QUERY_GROUPIDS: "Group ids of the selected series for a "
                         "by/without aggregation: from the index's label "
                         "columns where the selection is still pids "
                         "(route=index), else one Python step a "
                         "materialized key (route=walk); a global "
                         "aggregate opens none (tags: keys, groups, "
                         "route; on route=index memo = hit | miss | bypass: "
                         "whether the selection memo had the grouping; the "
                         "mesh route opens ONE for every leaf, global "
                         "aggregates too — its shards' dense rows under the "
                         "shared numbering — with memo = memo | built | "
                         "bypass of the engine's row memo).",
    SPAN_QUERY_KERNEL: "Host side of one fused kernel: phase=dispatch is "
                       "the call under the shard lock, phase=fetch the "
                       "blocking fetch of its result outside it (dispatch "
                       "tags: ahead = fused query programs dispatched before "
                       "this one whose result no thread had fetched yet as "
                       "it entered, process-wide: what the device runs "
                       "first (the flush's programs are not in it: no one "
                       "fetches them); kernel, rows, c0, cols, steps, "
                       "groups, stamps "
                       "= grid | line, how the store keeps time, on grid "
                       "births = 0 | 1, whether the program's births mode "
                       "ran (the store holds a row born past its grid's "
                       "first cell), and born_late = the SELECTED rows born "
                       "so, and on "
                       "line packed = 1 | 2, the edge slots a 128-lane "
                       "block of the kernel's one-hot operand, and holes "
                       "= 0 | 1, whether the mode that reads around cells "
                       "without a sample ran; the "
                       "fused-hist route adds buckets and variant = "
                       "hist-raw | hist-int8 | hist-int16 | hist-untiled; "
                       "hist-raw adds packed = 1 where one weight narrower "
                       "than two bands carried both of a tile's products, "
                       "and on the fetch span fall_tiles = the tiles that "
                       "ran the correction matmul; the fetch span of a "
                       "rate program on a line store carries fall_tiles = "
                       "the row tiles that took the band product over "
                       "their increments, a counter having fallen under "
                       "some window or a row ending under one, and tiles "
                       "= the row tiles of the dispatch: the others took "
                       "a window's delta as the difference of its last "
                       "and first sample).",
    SPAN_QUERY_REDUCE: "Cross-shard reduce merge of child partials.",
    SPAN_QUERY_DISPATCH: "One cross-node /exec POST (tags: endpoint, "
                         "shards).",
    SPAN_QUERY_SERVE: "Peer side of /exec: subtree execution on the "
                      "shard-owning node (tags: node).",
    SPAN_QUERY_ODP: "On-demand page-in of cold chunks for one leaf batch "
                    "(tags: shard, series).",
    SPAN_QUERY_COMPILE: "First execution of a new compiled-plan-cache key: "
                        "XLA trace + compile + run (tags: kernel; absent on "
                        "warm shapes — its count IS the compile count).",
    SPAN_QUERY_ADMIT: "Cost-based admission decision for one query (tags: "
                      "cost, tenant, shed on rejection).",
    SPAN_REMOTE_READ: "Remote-read fan-out leg to one peer (tags: "
                      "endpoint).",
    SPAN_REMOTE_WRITE: "Remote-write batch accepted at the HTTP edge.",
    SPAN_GATEWAY_PUBLISH: "One built gateway container published to its "
                          "shard's bus (tags: shard).",
    SPAN_INGEST_PUBLISH: "One pipelined PUBLISH_BATCH group on the client "
                         "(tags: partition, failovers on a leader switch).",
    SPAN_BROKER_APPEND: "Broker-side publish append + quorum wait "
                        "(tags: partition, broker).",
    SPAN_REPLICATE: "Leader->follower replication push for one publish "
                    "(tags: partition, peer).",
    SPAN_REPLICATE_SERVE: "Follower side of OP_REPLICATE: CRC check + "
                          "append (tags: partition, broker).",
    SPAN_INGEST_CONSUME: "One consumer drain: bus containers scattered "
                         "into the shard store (tags: dataset, shard, rows, "
                         "lock_wait_ms, lock_hold_ms = the shard lock's "
                         "holds its thread released inside it, those of the "
                         "flushes nested in it too).",
    SPAN_INGEST_FLUSH: "One shard flush that had staged rows to land: "
                       "device scatter, backpressure, residency upkeep; an "
                       "idle flush opens none (tags: shard, rows, "
                       "lock_wait_ms, lock_hold_ms = the shard lock's holds "
                       "it released, also in the tag of a consume or query "
                       "span around it, demoted = rows this flush took off their "
                       "line, holes = cells it left without a sample: "
                       "staleness markers and skipped cells; form = narrow "
                       "| raw | rebuilt: the append wrote the delta form in "
                       "place, wrote raw blocks, or wrote raw blocks that "
                       "this flush re-encoded whole; pooled = rows the "
                       "append moved from the delta form to the raw pool; "
                       "rehydrates = times the store was decoded back to "
                       "raw since the flush before; sample_bytes = resident "
                       "bytes a cell, values + stamps, as the flush "
                       "left them).",
    SPAN_QUERY_RETENTION: "Downsample-aware routing of one query: the "
                          "resolution decision and its routed/stitched "
                          "leg queries hang under it (tags: dataset, "
                          "resolution, stitched).",
    SPAN_QUERY_FRAGMENT: "Incremental (delta) evaluation of one range "
                         "query off the fragment cache: reused per-step "
                         "columns + head/tail sub-executions hang under it "
                         "(tags: dataset, reused, computed).",
    SPAN_QUERY_SUBSCRIBE: "One streaming-subscription increment: the steps "
                          "newly covered by the ingest watermarks since "
                          "the subscriber's cursor (tags: dataset, steps).",
    SPAN_ODP_DURABLE: "Durable-tier chunk scan of one ODP page-in batch "
                      "(tags: shard, tier=local|remote, rows).",
    SPAN_RULES_EVAL: "One rule evaluation inside a scheduler tick (tags: "
                     "group, rule, eval_ts; its PromQL query and derived "
                     "publish spans hang under it).",
    SPAN_CLUSTER_GOSSIP: "One membership gossip probe round: digest "
                         "exchange with the scheduled peer (tags: peer, "
                         "round).",
    SPAN_CLUSTER_LEAD: "Leadership claim for one partition: read peer "
                       "epochs, bump, persist, announce (tags: partition, "
                       "epoch).",
    SPAN_CLUSTER_REJOIN: "REJOIN repair of a restarted deposed leader: "
                         "divergent-tail truncation + catch-up from the "
                         "current leader (tags: partition, owner).",
    SPAN_CLUSTER_REBALANCE: "Operator-triggered live shard move: "
                            "flush→handoff→catch-up→cutover (tags: dataset, "
                            "shard, to).",
    SPAN_RUNTIME_GC: "One full (generation 2) garbage collection of the "
                     "server process, every thread stopped (recorded after "
                     "the fact by the server's gc hook; tags: collected).",
    SPAN_RUNTIME_BEAT: "One second of the tracer's heartbeat, a thread that "
                       "sleeps 20 ms at a time while a server runs and "
                       "takes, as it wakes, how late it is: what a thread "
                       "pays to get the interpreter back. The interval is "
                       "that second's WORST wake-up, due -> woke, not the "
                       "second (recorded after the fact; tags: ticks, "
                       "late_ms = sum over the second's wake-ups, period_ms "
                       "= the time the counts cover, inflight = fused "
                       "programs dispatched and not fetched as it closed, "
                       "lock and lock_hold_ms = the busiest shard lock's "
                       "name and the growth of its hold_s over the period: "
                       "the LOCK's side, each hold once. A wake-up more "
                       "than 1 s late adds stall = 1 and what the thread "
                       "saw as it came back: held_lock, holder, held_ms = "
                       "the shard lock held longest, the thread that holds "
                       "it and since when; oldest_dispatch_ms = the age of "
                       "the oldest unfetched program; gc = 1 where a full "
                       "collection overlapped).",
}


def trace_markdown_table() -> str:
    """The ARCHITECTURE 'Span taxonomy' table, generated from TRACE_SPEC
    (verified against the checked-in ARCHITECTURE.md by
    tests/test_static_analysis.py)."""
    lines = ["| span | meaning |", "|---|---|"]
    for name, doc in sorted(TRACE_SPEC.items()):
        lines.append(f"| `{name}` | {doc} |")
    return "\n".join(lines)


@dataclass
class SpanRecord:
    trace_id: str
    span_id: str
    parent_id: str | None
    name: str
    start_us: int
    duration_us: int
    tags: dict = field(default_factory=dict)
    # monotonic record sequence (per tracer): exporters keep a watermark
    # against it instead of draining the shared ring
    seq: int = 0
    # the start on this process's monotonic clock (time.perf_counter_ns):
    # what orders and subtracts spans in-process; start_us is the same
    # instant on the wall clock, for the exporter
    start_ns: int = 0

    def to_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "start_us": self.start_us, "start_ns": self.start_ns,
                "duration_us": self.duration_us,
                "tags": {k: str(v) for k, v in self.tags.items()}}

    def to_zipkin(self) -> dict:
        """Zipkin v2 JSON shape (ref: Zipkin.scala converts Kamon spans)."""
        return {"traceId": self.trace_id, "id": self.span_id,
                "parentId": self.parent_id, "name": self.name,
                "timestamp": self.start_us, "duration": self.duration_us,
                "tags": {k: str(v) for k, v in self.tags.items()}}


class Tracer:
    """Process-global span recorder.

    The per-thread context stack holds ``(trace_id, span_id, sampled)``
    frames; ``span()`` parents under the innermost frame. ``activate``
    adopts a REMOTE (or cross-thread) parent frame; ``current_context`` is
    its wire-able counterpart — together they are the context-propagation
    pair every transport uses.
    """

    def __init__(self, capacity: int = 4096):
        self.spans: deque[SpanRecord] = deque(maxlen=capacity)
        # finished intervals from record(), which takes no lock (it runs
        # inside the gc hook, possibly on a thread that holds _lock); the
        # next commit, snapshot or drain moves them into the ring
        self._handoff: deque[SpanRecord] = deque()
        self._local = threading.local()
        self._lock = threading.Lock()
        self._seq = 0
        self._gc_users = 0
        self._gc_t0: int | None = None
        self._gc_last: tuple[int, int] | None = None    # (start, end)
        self._beat_probes: list = []
        self._beat: _Heartbeat | None = None
        self.log_spans = False
        self.enabled = True
        self.sample_rate = 1.0

    # -- context ------------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _new_id(self) -> str:
        """16-hex-char id from a per-thread PRNG seeded ONCE from
        os.urandom (never wall clock). uuid4 would syscall urandom per id —
        tens of µs on older kernels, which dominates a span; trace ids need
        uniqueness, not cryptographic strength."""
        rng = getattr(self._local, "rng", None)
        if rng is None:
            rng = self._local.rng = random.Random(
                int.from_bytes(os.urandom(16), "little"))
        return f"{rng.getrandbits(64):016x}"

    def current_context(self) -> dict | None:
        """The innermost active frame as a wire-able dict (None outside any
        span). The receiving side feeds it back through ``activate``."""
        st = self._stack()
        if not st:
            return None
        trace_id, span_id, sampled = st[-1]
        return {"trace_id": trace_id, "span_id": span_id,
                "sampled": bool(sampled)}

    def wrap(self, fn):
        """Bind the CURRENT thread's innermost context to ``fn``: the
        returned callable activates it wherever it runs. THE way to hand
        work to a thread pool without severing its spans from the trace
        (every fan-out site uses this one helper instead of hand-rolling
        capture + activate)."""
        ctx = self.current_context()

        def bound(*args, **kwargs):
            with self.activate(ctx):
                return fn(*args, **kwargs)
        return bound

    _ID_CHARS = frozenset("0123456789abcdef")

    @classmethod
    def _valid_id(cls, v) -> bool:
        """Wire-supplied ids must be lowercase hex, bounded length: they end
        up in span records, debug JSON, and /metrics exemplar LABELS — an
        unvalidated id with quotes/braces would corrupt the whole metrics
        exposition for every scraper."""
        return (isinstance(v, str) and 0 < len(v) <= 32
                and set(v) <= cls._ID_CHARS)

    @contextlib.contextmanager
    def activate(self, ctx: dict | None):
        """Adopt a remote/cross-thread parent frame on THIS thread: spans
        opened inside parent under ``ctx`` and join its trace. A None or
        malformed context — including non-hex ids from a hostile peer — is
        a no-op (the span() below it roots a fresh trace), so transports
        can pass whatever they extracted."""
        if not isinstance(ctx, dict) or not self._valid_id(
                ctx.get("trace_id")) or not self._valid_id(
                ctx.get("span_id")):
            yield
            return
        st = self._stack()
        st.append((ctx["trace_id"], ctx["span_id"],
                   bool(ctx.get("sampled", True))))
        try:
            yield
        finally:
            st.pop()

    # -- spans --------------------------------------------------------------

    def _root(self) -> tuple:
        """``(trace_id, None, sampled)``: a fresh trace and its sampling
        decision, made once here and inherited by everything under it."""
        trace_id = self._new_id()
        return (trace_id, None, self.sample_rate >= 1.0
                or self._local.rng.random() < self.sample_rate)

    def span(self, name: str, **tags) -> "_OpenSpan":
        """Record one span: ``with span(NAME, k=v) as tags``. Yields the
        TAGS dict so callers can attach outcome tags discovered mid-span
        (e.g. a publish that failed over leaders) — mutations land in the
        recorded span."""
        return _OpenSpan(self, name, tags)

    def _commit(self, rec: SpanRecord) -> None:
        with self._lock:
            self._seq += 1
            rec.seq = self._seq
            self.spans.append(rec)
        if self.log_spans:
            log.info("span %s %.1fms %s", rec.name, rec.duration_us / 1000,
                     rec.tags)
        if self._handoff:
            self._sync_handoff()

    def record(self, name: str, t0_ns: int, t1_ns: int, **tags) -> None:
        """Record a FINISHED interval (``time.perf_counter_ns`` readings)
        under the calling thread's current context, by span()'s sampling
        and ``enabled`` rules: for the waits no ``with`` block on one thread
        can bracket (the scheduler queue, a garbage collection, the
        heartbeat's second). Takes no lock — the gc hook calls it from
        wherever a collection happened to start — so the record reaches the
        ring with the next span, snapshot or drain."""
        stack = self._stack()
        if not stack and not self.enabled:
            return
        trace_id, parent_id, sampled = stack[-1] if stack else self._root()
        if sampled:
            self._handoff.append(self._finished(
                trace_id, self._new_id(), parent_id, name, t0_ns, t1_ns,
                time.perf_counter_ns(), tags))

    @staticmethod
    def _finished(trace_id, span_id, parent_id, name, t0_ns: int, t1_ns: int,
                  now_ns: int, tags: dict) -> SpanRecord:
        # the wall clock, read ONCE a span and only as the exporter's
        # anchor: "now" on it, less the monotonic time since the start
        start_us = int(time.time() * 1e6) - (now_ns - t0_ns) // 1000
        return SpanRecord(trace_id, span_id, parent_id, name, start_us,
                          (t1_ns - t0_ns) // 1000, tags, 0, t0_ns)

    def _sync_handoff(self) -> None:
        """Move what record() handed over into the ring.
        The deque is lock-free on both sides (appends and pops are atomic)."""
        late = []
        try:
            while True:
                late.append(self._handoff.popleft())
        except IndexError:
            if not late:
                return
        with self._lock:
            for r in late:
                self._seq += 1
                r.seq = self._seq
                self.spans.append(r)
        if self.log_spans:
            for r in late:
                log.info("span %s %.1fms %s", r.name, r.duration_us / 1000,
                         r.tags)

    # -- garbage collections ------------------------------------------------

    def install_gc_hook(self) -> None:
        """``runtime.gc`` spans from here on: one ``gc.callbacks`` hook for
        the process, shared by every server that asked (FiloServer.start /
        stop pair it with :meth:`remove_gc_hook`)."""
        with self._lock:
            self._gc_users += 1
            if self._gc_users == 1:
                gc.callbacks.append(self._on_gc)

    def remove_gc_hook(self) -> None:
        with self._lock:
            if self._gc_users:
                self._gc_users -= 1
                if not self._gc_users:
                    gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        """Full (generation 2) collections only: with ~10^7 index objects
        alive one of them stops every thread for a third of a second.
        Runs wherever the interpreter chose to collect — also on a thread
        inside this tracer's critical section — hence record(), lock-free."""
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0 is not None:
            t0, self._gc_t0 = self._gc_t0, None
            self._gc_last = (t0, time.perf_counter_ns())
            self.record(SPAN_RUNTIME_GC, *self._gc_last,
                        collected=info.get("collected", 0))

    def gc_overlapped(self, t0_ns: int, t1_ns: int) -> bool:
        """Whether a full collection ran (or still runs) inside the
        interval: the one in progress or the last finished one."""
        last = self._gc_last
        return (self._gc_t0 is not None
                or (last is not None and last[0] < t1_ns and last[1] > t0_ns))

    # -- heartbeat ----------------------------------------------------------

    def start_heartbeat(self, shard_locks) -> None:
        """``runtime.beat`` spans from here on: one heartbeat thread for the
        process, shared by every server that asked, as the gc hook is
        (FiloServer.start / shutdown pair it with :meth:`stop_heartbeat`).
        ``shard_locks`` is the server's callable returning its shards'
        ``TimedRLock``s; the beat reads them once a second."""
        with self._lock:
            self._beat_probes.append(shard_locks)
            if self._beat is None:
                self._beat = _Heartbeat(self).start()

    def stop_heartbeat(self, shard_locks) -> None:
        with self._lock:
            if shard_locks in self._beat_probes:
                self._beat_probes.remove(shard_locks)
            beat = None
            if not self._beat_probes:
                beat, self._beat = self._beat, None
        if beat is not None:
            beat.stop()

    # -- assembly / export --------------------------------------------------

    def snapshot(self) -> list[SpanRecord]:
        self._sync_handoff()
        with self._lock:
            return list(self.spans)

    def drain(self) -> list[SpanRecord]:
        self._sync_handoff()
        with self._lock:
            out = list(self.spans)
            self.spans.clear()
        return out

    def traces(self, limit: int = 50,
               trace_id: str | None = None) -> list[dict]:
        """Recent traces assembled parent -> child: newest trace first, each
        trace's spans ordered roots-first then DFS by parent links (orphans
        — parent span evicted from the ring — follow their trace's tree)."""
        spans = self.snapshot()
        by_trace: dict[str, list[SpanRecord]] = {}
        order: list[str] = []
        for s in spans:
            if trace_id is not None and s.trace_id != trace_id:
                continue
            if s.trace_id not in by_trace:
                order.append(s.trace_id)
            by_trace.setdefault(s.trace_id, []).append(s)
        out = []
        for tid in reversed(order[-limit:] if trace_id is None else order):
            members = by_trace[tid]
            ids = {s.span_id for s in members}
            children: dict[str | None, list[SpanRecord]] = {}
            roots = []
            for s in members:
                if s.parent_id in ids:
                    children.setdefault(s.parent_id, []).append(s)
                else:
                    roots.append(s)
            ordered: list[SpanRecord] = []
            stack = list(reversed(sorted(roots, key=lambda s: s.start_ns)))
            while stack:
                s = stack.pop()
                ordered.append(s)
                kids = sorted(children.get(s.span_id, ()),
                              key=lambda c: c.start_ns)
                stack.extend(reversed(kids))
            out.append({"trace_id": tid,
                        "duration_us": max((s.duration_us for s in roots),
                                           default=0),
                        "spans": [s.to_dict() for s in ordered]})
        return out

    def export_zipkin_json(self, trace_id: str | None = None) -> str:
        return json.dumps([s.to_zipkin() for s in self.snapshot()
                           if trace_id is None or s.trace_id == trace_id])

    def post_zipkin(self, endpoint: str,
                    spans: list[SpanRecord] | None = None) -> int:
        """POST spans (default: a non-destructive snapshot) to a Zipkin v2
        collector; returns the span count shipped (ref: the custom
        Zipkin.scala reporter). Never drains the ring — the debug plane
        (/api/v1/debug/traces, the slow-query trace pivot) reads the same
        ring and must keep working alongside an exporter."""
        import urllib.request
        spans = self.snapshot() if spans is None else spans
        if not spans:
            return 0
        body = json.dumps([s.to_zipkin() for s in spans]).encode()
        req = urllib.request.Request(
            endpoint, data=body, method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5.0) as r:
            r.read()
        return len(spans)


class _OpenSpan:
    """The context manager behind ``Tracer.span`` (a class, not a generator:
    a span is opened ~13 times a served query and a generator-based manager
    costs a microsecond more each)."""

    __slots__ = ("_tracer", "_name", "_tags", "_frame", "_stack", "_ann",
                 "_t0")

    def __init__(self, tracer_: Tracer, name: str, tags: dict):
        self._tracer, self._name, self._tags = tracer_, name, tags
        self._frame = None

    def __enter__(self) -> dict:
        tr = self._tracer
        stack = self._stack = tr._stack()
        if stack:
            trace_id, parent_id, sampled = stack[-1]
        elif not tr.enabled:
            # no active context and tracing off: stay out of the clocks
            return self._tags
        else:
            trace_id, parent_id, sampled = tr._root()
        # sampled-out spans skip id generation too: the frame still
        # propagates (children and peers must inherit the decision) but
        # nothing will ever reference its span id
        span_id = tr._new_id() if sampled else "0"
        self._frame = (trace_id, span_id, parent_id, sampled)
        stack.append((trace_id, span_id, sampled))
        if sampled:
            # the same interval on the profiler's clock, when one is tracing
            self._ann = TraceAnnotation(self._name, trace_id=trace_id)
            self._ann.__enter__()
            self._t0 = time.perf_counter_ns()
        return self._tags

    def __exit__(self, *exc) -> bool:
        if self._frame is None:
            return False
        trace_id, span_id, parent_id, sampled = self._frame
        self._stack.pop()
        if sampled:
            t1 = time.perf_counter_ns()
            self._ann.__exit__(None, None, None)
            self._tracer._commit(Tracer._finished(
                trace_id, span_id, parent_id, self._name, self._t0, t1, t1,
                self._tags))
        return False


class _Heartbeat:
    """The interpreter's wake-up, sampled: a daemon thread that sleeps
    ``PERIOD_NS`` at a time and, each time it wakes, takes ``late = woke -
    due``. A thread coming out of a sleep needs the GIL back exactly as a
    worker coming out of a device fetch does, so ``late`` is what a fetch
    pays, 50 times a second, without touching the fetch. Once a second it
    hands ONE ``runtime.beat`` span to ``Tracer.record()``; a wake-up more
    than ``STALL_NS`` late also logs one warning and counts one stall.
    All state below belongs to the beat thread (``tick`` is its body, and
    takes its clock readings as arguments: a test hands it its own)."""

    PERIOD_NS = 20_000_000
    SPAN_NS = 1_000_000_000
    STALL_NS = 1_000_000_000
    # wake-ups run from tens of microseconds to a stall's seconds
    WAKEUP_BOUNDS_S = (0.0001, 0.0002, 0.0005, 0.001, 0.002, 0.005, 0.01,
                       0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

    def __init__(self, tracer_: "Tracer"):
        self.tracer = tracer_
        self._halt = threading.Event()
        self._thread: threading.Thread | None = None
        self._wakeups = registry.histogram(
            FILODB_RUNTIME_WAKEUP_SECONDS, bounds=self.WAKEUP_BOUNDS_S)
        self._stalls = registry.counter(FILODB_RUNTIME_STALLS)
        self._new_period(time.perf_counter_ns(), self._locks())

    def start(self) -> "_Heartbeat":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="trace-heartbeat")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._halt.set()
        if self._thread is not None:
            self._thread.join(timeout=3)
            self._thread = None

    def _run(self) -> None:
        while True:
            due = time.perf_counter_ns() + self.PERIOD_NS
            if self._halt.wait(self.PERIOD_NS / 1e9):
                return
            try:
                self.tick(due, time.perf_counter_ns())
            except Exception:  # noqa: BLE001 — a probe that raises (a
                # shard map changing under it) must not end the heartbeat
                # for the life of the process; counted, logged, next tick
                registry.counter(FILODB_SWALLOWED_ERRORS,
                                 {"site": "trace-heartbeat"}).increment()
                log.warning("heartbeat tick failed", exc_info=True)

    def _locks(self) -> list:
        return [lk for probe in list(self.tracer._beat_probes)
                for lk in probe()]

    def _new_period(self, now_ns: int, locks: list) -> None:
        """Counts to zero, the locks' totals as they stand."""
        self._t0 = now_ns
        self._ticks = 0
        self._late_ns = 0
        self._worst = (0, 0)                # (due, woke) of the latest
        self._held = {id(lk): lk.hold_s for lk in locks}

    def tick(self, due_ns: int, woke_ns: int) -> None:
        """One wake-up, due at ``due_ns`` and come at ``woke_ns``."""
        if not self.tracer.enabled:
            self._new_period(woke_ns, [])   # off: no count, no span
            return
        late = max(0, woke_ns - due_ns)
        self._wakeups.record(late / 1e9)
        self._ticks += 1
        self._late_ns += late
        if late >= self._worst[1] - self._worst[0]:
            self._worst = (due_ns, woke_ns)
        stall = self._stalled(due_ns, woke_ns) if late >= self.STALL_NS \
            else {}
        if not stall and woke_ns - self._t0 < self.SPAN_NS:
            return
        tags = {"ticks": self._ticks, "late_ms": self._late_ns / 1e6,
                "period_ms": (woke_ns - self._t0) / 1e6,
                "inflight": diagnostics.inflight.count}
        locks = self._locks()
        if locks:
            # the lock's own total: every hold once, whoever held it
            growth = [(lk.hold_s - self._held.get(id(lk), lk.hold_s), lk)
                      for lk in locks]
            held_s, busiest = max(growth, key=lambda g: g[0])
            tags.update(lock=busiest.name, lock_hold_ms=held_s * 1e3)
        self.tracer.record(SPAN_RUNTIME_BEAT, *self._worst, **tags, **stall)
        self._new_period(woke_ns, locks)

    def _stalled(self, due_ns: int, woke_ns: int) -> dict:
        """A wake-up a second and more late: what this thread sees as it
        comes back, as the beat's tags and ONE warning (so an untraced
        run's log says what stood still). Racy reads of the locks, by
        design: the worst a torn one costs is a wrong name."""
        held = [lk for lk in self._locks() if lk._depth > 0]
        lk = min(held, key=lambda k: k._acquired_at) if held else None
        age_s = diagnostics.inflight.oldest_age_s()
        in_gc = self.tracer.gc_overlapped(due_ns, woke_ns)
        tags = {"stall": 1, "gc": int(in_gc)}
        what = since = "none"
        if lk is not None:
            held_ms = (time.monotonic() - lk._acquired_at) * 1e3
            tags.update(held_lock=lk.name, holder=lk.holder, held_ms=held_ms)
            what = f"{lk.name} by {lk.holder} for {held_ms:.0f} ms"
        if age_s is not None:
            tags["oldest_dispatch_ms"] = age_s * 1e3
            since = f"{age_s * 1e3:.0f} ms"
        self._stalls.increment()
        log.warning(
            "stall: the heartbeat woke %.0f ms late; shard lock held: %s; "
            "oldest unfetched dispatch: %s (%d in flight); full collection "
            "inside it: %s", (woke_ns - due_ns) / 1e6, what, since,
            diagnostics.inflight.count, "yes" if in_gc else "no")
        return tags


class ZipkinReporter:
    """Periodic Zipkin shipper (``trace.zipkin_endpoint``): snapshots the
    tracer's ring on a cadence and POSTs the spans newer than its seq
    watermark — the ring itself stays intact for the debug plane. A failed
    POST leaves the watermark, so those spans retry next tick (they can
    still age out of the bounded ring under pressure — bounded loss, never
    unbounded memory). Export faults are counted and logged, never fatal
    (the loop survives; filolint: resource-worker-silent-death)."""

    def __init__(self, tracer_: "Tracer", endpoint: str,
                 interval_s: float = 5.0):
        self.tracer = tracer_
        self.endpoint = endpoint
        self.interval_s = interval_s
        self._watermark = 0
        self._stop_ev = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "ZipkinReporter":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="zipkin-reporter")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop_ev.set()
        if self._thread is not None:
            self._thread.join(timeout=3)
            self._thread = None

    def tick(self) -> int:
        """One export pass: ship spans newer than the watermark, advance it
        only on success. Returns the count shipped."""
        fresh = [s for s in self.tracer.snapshot()
                 if s.seq > self._watermark]
        if not fresh:
            return 0
        n = self.tracer.post_zipkin(self.endpoint, fresh)
        self._watermark = fresh[-1].seq
        return n

    def _run(self) -> None:
        while not self._stop_ev.wait(self.interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — a dead collector must not
                # kill the reporter for the process lifetime; counted so a
                # persistently failing export is visible in /metrics
                registry.counter(FILODB_SWALLOWED_ERRORS,
                                 {"site": "zipkin-export"}).increment()
                log.warning("zipkin export to %s failed", self.endpoint,
                            exc_info=True)


tracer = Tracer()
span = tracer.span
