#!/usr/bin/env python
"""Benchmark suite — parity with the reference's jmh suites (SURVEY.md §6).

Each sub-benchmark mirrors the *workload definition* of one reference jmh suite
(jmh/src/main/scala/filodb.jmh/) and prints one JSON line per metric:

    {"suite": "...", "metric": "...", "value": N, "unit": "..."}

Suites (reference file in parens):

  ingestion     container build + memstore ingest hot path  (IngestionBenchmark.scala)
  encoding      delta-delta / NibblePack / XOR codec throughput, python + C++
                (EncodingBenchmark.scala, BasicFiloBenchmark.scala)
  partkey_index 1M-series tag index: add rate, equals/regex lookups, top-k
                (PartKeyIndexBenchmark.scala)
  hist_ingest   histogram container ingest + 2D-delta encode  (HistogramIngestBenchmark.scala)
  hist_query    sum(rate(hist[5m])) + histogram_quantile  (HistogramQueryBenchmark.scala)
  query_hicard  8000-series single-shard sum(rate) query throughput
                (QueryHiCardInMemoryBenchmark.scala: 15m @ 10s, quarter queried)
  query_ingest  interleaved ingest + query  (QueryAndIngestBenchmark.scala)
  gateway       Influx line-protocol parse throughput  (GatewayBenchmark.scala)
  elastic       kill-a-node soak, live rebalance under load, split-brain
                zero-duplicate audit  (ISSUE 12; ClusterRecoverySpec analog)
  mesh_query    one-program mesh vs host shard loop dispatch floor, bit
                parity + warmup compile-count audit  (ISSUE 16)
  scalar_residency  delta8/quant16/delta16 ladder: retention at fixed HBM,
                fused bytes/sample A/B, encode-at-flush cost  (ISSUE 17)

``--full`` uses reference-scale sizes (1M index series etc.); default sizes are
CI-friendly. ``--suite name`` runs one suite. The north-star query benchmark
stays in /root/repo/bench.py (QueryInMemoryBenchmark equivalent).
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def emit(suite: str, metric: str, value: float, unit: str) -> None:
    print(json.dumps({"suite": suite, "metric": metric,
                      "value": round(float(value), 3), "unit": unit}), flush=True)


def timed(fn, *, min_s: float = 0.3, max_iters: int = 50) -> tuple[float, int]:
    """Run fn repeatedly for >= min_s; return (total seconds, iterations)."""
    fn()                                # warmup (jit compile / cache fill)
    t0 = time.perf_counter()
    iters = 0
    while True:
        fn()
        iters += 1
        dt = time.perf_counter() - t0
        if dt >= min_s or iters >= max_iters:
            return dt, iters


# ---------------------------------------------------------------- fixtures

BASE = 1_700_000_000_000
IV = 10_000


def _gauge_containers(n_series: int, n_samples: int, per_container: int = 1000):
    """linearMultiSeries-style data grouped into ~1000-record containers
    (ref IngestionBenchmark: 100k records in 1000-record containers)."""
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    containers = []
    b = RecordBuilder(GAUGE)
    count = 0
    for t in range(n_samples):
        for s in range(n_series):
            b.add({"_metric_": "heap_usage", "_ws_": "demo", "_ns_": "app",
                   "host": f"h{s}", "job": f"App-{s % 8}"},
                  BASE + t * IV, float(s * 100 + t))
            count += 1
            if count % per_container == 0:
                containers.append(b.build())
                b = RecordBuilder(GAUGE)
    if count % per_container:
        containers.append(b.build())
    return containers


# ---------------------------------------------------------------- suites

def bench_ingestion(full: bool) -> None:
    """Ref IngestionBenchmark: RecordBuilder build + the partition-resolve +
    ingest hot loop into a memstore with a null sink."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.schemas import GAUGE

    # full scale: 500k records — the cold path's fixed per-flush device sync
    # must amortize, as it does at the
    # reference's 815k-record scale (IngestionBenchmark ingests large blocks)
    n_series, n_samples = (1000, 500) if full else (500, 40)
    t0 = time.perf_counter()
    containers = _gauge_containers(n_series, n_samples)
    build_s = time.perf_counter() - t0
    n_records = n_series * n_samples
    emit("ingestion", "record_build_throughput", n_records / build_s, "records/s")
    # bulk path: one add_batch per series (backfills/CSV/generators)
    from filodb_tpu.core.record import RecordBuilder
    import numpy as np
    ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV
    t0 = time.perf_counter()
    b = RecordBuilder(GAUGE)
    for s in range(n_series):
        b.add_batch({"_metric_": "heap_usage", "_ws_": "demo", "_ns_": "app",
                     "host": f"h{s}", "job": f"App-{s % 8}"},
                    ts_arr, np.full(n_samples, float(s)))
    b.build()
    emit("ingestion", "record_build_batch_throughput",
         n_records / (time.perf_counter() - t0), "records/s")

    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=n_samples + 8,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ms.setup("bench", GAUGE, 0, cfg)
    t0 = time.perf_counter()
    for c in containers:
        ms.ingest("bench", 0, c)
    ms.flush_all()
    ingest_s = time.perf_counter() - t0
    emit("ingestion", "ingest_throughput", n_records / ingest_s, "records/s")

    # re-ingest = pure hot path (every partition already exists: the
    # PartitionSet-probe side of ref ingestBinaryRecords)
    t0 = time.perf_counter()
    for c in containers:
        ms.ingest("bench", 0, c)
    ms.flush_all()
    emit("ingestion", "ingest_hot_throughput",
         n_records / (time.perf_counter() - t0), "records/s")


def bench_encoding(full: bool) -> None:
    """Ref EncodingBenchmark/BasicFiloBenchmark: codec encode/decode speeds."""
    from filodb_tpu.memory import deltadelta, native, nibblepack

    n = 100_000 if full else 20_000
    rng = np.random.default_rng(7)
    ts = BASE + np.arange(n, dtype=np.int64) * IV + rng.integers(-50, 50, n)
    doubles = np.cumsum(rng.exponential(5.0, n))

    for name, enc, dec, data, nbytes in [
        ("deltadelta_ts", deltadelta.encode, lambda b: deltadelta.decode(b),
         ts, n * 8),
        ("nibblepack_doubles", nibblepack.pack_doubles,
         lambda b: nibblepack.unpack_doubles(b, n), doubles, n * 8),
    ]:
        buf = enc(data)
        dt, it = timed(lambda: enc(data))
        emit("encoding", f"{name}_encode", nbytes * it / dt / 1e6, "MB/s")
        dt, it = timed(lambda: dec(buf))
        emit("encoding", f"{name}_decode", nbytes * it / dt / 1e6, "MB/s")
        emit("encoding", f"{name}_ratio", nbytes / len(buf), "x")

    if native.available():
        u = doubles.view(np.uint64)
        buf = native.pack_doubles(doubles)
        dt, it = timed(lambda: native.pack_doubles(doubles))
        emit("encoding", "native_pack_doubles", n * 8 * it / dt / 1e6, "MB/s")
        dt, it = timed(lambda: native.unpack_doubles(buf, n))
        emit("encoding", "native_unpack_doubles", n * 8 * it / dt / 1e6, "MB/s")


class _PurePythonIndex:
    """The seed-era index shape — dicts of sets, per-value regex loops — the
    baseline the columnar engine's >= 10x acceptance bar measures against
    (bit-identical results asserted)."""

    def __init__(self):
        self.inv: dict = {}              # name -> value -> set(pid)

    def add(self, pid, labels):
        for k, v in labels.items():
            self.inv.setdefault(k, {}).setdefault(v, set()).add(pid)

    def query(self, filters):
        import re

        from filodb_tpu.core import filters as F
        result = None
        for f in filters:
            vals = self.inv.get(f.label, {})
            if isinstance(f, F.Equals):
                ids = set(vals.get(f.value, ()))
            elif isinstance(f, F.EqualsRegex):
                pat = re.compile(f.pattern)
                ids = set()
                for v, s in vals.items():
                    if pat.fullmatch(v):
                        ids |= s
            elif isinstance(f, F.NotEquals):
                ids = set()
                for v, s in vals.items():
                    if v != f.value:
                        ids |= s
            else:
                raise TypeError(f)
            result = ids if result is None else (result & ids)
        return np.asarray(sorted(result or ()), np.int32)

    def topk(self, label, k):
        from collections import Counter
        c = Counter({v: len(s) for v, s in self.inv.get(label, {}).items()})
        return [v for v, _ in c.most_common(k)]


def bench_partkey_index(full: bool) -> None:
    """Ref PartKeyIndexBenchmark: the columnar index at 100k (and 1M with
    --full) — build rate, equals/regex/multi-matcher select latency with
    COLD select caches (the filter/union/match caches cleared per batch, so
    the rows measure the columnar set algebra, not a memo), top-k
    label_values, recover-ms from a 2-replica durable ring, ingest p99 with
    the cardinality limiter armed, and the >= 10x bar vs the pure-Python
    dicts-of-sets baseline at bit-identical results."""
    from filodb_tpu.core import filters as F
    from filodb_tpu.core.partkey_index import PartKeyIndex

    def labels_of(i):
        return {"_metric_": "heap_usage", "_ws_": "demo", "_ns_": "app",
                "job": f"App-{i % 100}", "host": f"H{i % 1000}",
                "instance": f"I{i:07d}"}

    def build_columnar(n):
        idx = PartKeyIndex()
        t0 = time.perf_counter()
        ok = idx.add_part_keys_columnar(
            np.arange(n),
            {"_metric_": "heap_usage", "_ws_": "demo", "_ns_": "app"},
            ["job", "host", "instance"],
            [[f"App-{i % 100}" for i in range(n)],
             [f"H{i % 1000}" for i in range(n)],
             [f"I{i:07d}" for i in range(n)]], BASE)
        assert ok
        # readers fold the staged columns: include it in the build cost
        idx.part_ids_from_filters([F.Equals("_metric_", "heap_usage")],
                                  0, 1 << 62)
        return idx, time.perf_counter() - t0

    def filter_batches():
        return [
            ("equals", [[F.Equals("job", f"App-{i}"), F.Equals("host", "H0"),
                         F.Equals("_metric_", "heap_usage")]
                        for i in range(20)]),
            ("regex", [[F.Equals("_metric_", "heap_usage"),
                        F.EqualsRegex("instance", f"I00000{i % 10}.*")]
                       for i in range(20)]),
            ("multi_matcher", [[F.Equals("_metric_", "heap_usage"),
                                F.EqualsRegex("host", f"H{i % 10}.*"),
                                F.NotEquals("job", "App-0")]
                               for i in range(20)]),
            # every operand dense (covers most of the pid space): the
            # u64-word bitmap AND/ANDNOT plane
            ("dense_multi", [[F.Equals("_metric_", "heap_usage"),
                              F.Equals("_ws_", "demo"),
                              F.NotEquals("job", f"App-{i % 100}")]
                             for i in range(20)]),
        ]

    def cold(idx):
        # measure the select plane, not the memo layer: dashboards DO hit
        # these caches, but the acceptance bar is the cold set algebra
        idx._filter_cache.clear()
        idx._regex_union_cache.clear()
        idx._regex_cache.clear()

    sizes = [100_000, 1_000_000] if full else [100_000]
    results_100k: dict[str, list] = {}
    for n in sizes:
        tag = "1m" if n >= 1_000_000 else "100k"
        idx, build_s = build_columnar(n)
        emit("partkey_index", f"build_columnar_rate_{tag}", n / build_s,
             "keys/s")
        for name, batches in filter_batches():
            def run(idx=idx, batches=batches):
                cold(idx)
                for flt in batches:
                    idx.part_ids_from_filters(list(flt), 0, 1 << 62)
            dt, it = timed(run, max_iters=20)
            emit("partkey_index", f"{name}_ms_{tag}",
                 dt / (it * len(batches)) * 1000, "ms")
            if n == 100_000:
                cold(idx)
                results_100k[name] = [
                    idx.part_ids_from_filters(list(flt), 0, 1 << 62)
                    for flt in batches]
        dt, it = timed(lambda idx=idx: idx.label_value_counts("job",
                                                              top_k=10),
                       max_iters=50)
        emit("partkey_index", f"labelvalues_topk_ms_{tag}", dt / it * 1000,
             "ms")
        filt = [F.EqualsRegex("host", "H1.*")]
        dt, it = timed(lambda idx=idx, filt=filt: idx.label_value_counts(
            "job", list(filt), top_k=10), max_iters=20)
        emit("partkey_index", f"labelvalues_topk_filtered_ms_{tag}",
             dt / it * 1000, "ms")
        emit("partkey_index", f"label_storage_{tag}",
             idx.arena_bytes() / n, "bytes/series")
        emit("partkey_index", f"postings_storage_{tag}",
             idx.postings_bytes() / n, "bytes/series")
        if n == 100_000:
            idx_100k = idx

    # ---- >= 10x bar vs the pure-Python baseline (100k, bit-identical) ----
    n = 100_000
    pure = _PurePythonIndex()
    t0 = time.perf_counter()
    for i in range(n):
        pure.add(i, labels_of(i))
    emit("partkey_index", "pure_build_rate_100k",
         n / (time.perf_counter() - t0), "keys/s")
    for name, batches in filter_batches():
        def run_pure(batches=batches):
            for flt in batches:
                pure.query(list(flt))
        dt, it = timed(run_pure, min_s=0.5, max_iters=5)
        pure_ms = dt / (it * len(batches)) * 1000
        emit("partkey_index", f"pure_{name}_ms_100k", pure_ms, "ms")
        # bit-identical results: same sorted pid arrays per batch entry
        parity = all(
            np.array_equal(got, pure.query(list(flt)))
            for got, flt in zip(results_100k[name], batches))
        emit("partkey_index", f"{name}_parity_vs_pure", float(parity), "bool")

    # ---- recover-ms from the durable ring --------------------------------
    import shutil
    import tempfile

    from filodb_tpu.core.diststore import (RemoteStore,
                                           ReplicatedColumnStore,
                                           StoreServer)
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.utils.metrics import FILODB_INDEX_RECOVER_MS, registry
    for n in sizes:
        tag = "1m" if n >= 1_000_000 else "100k"
        root = tempfile.mkdtemp(prefix="pkib-")
        servers = [StoreServer(f"{root}/n{i}").start() for i in range(2)]
        try:
            ring = ReplicatedColumnStore(
                [RemoteStore(f"127.0.0.1:{s.port}") for s in servers],
                replication=2)
            cfg = StoreConfig(max_series_per_shard=max(n, 1 << 20),
                              samples_per_series=4, flush_batch_size=10**9,
                              dtype="float64")
            ms = TimeSeriesMemStore()
            sh = ms.setup("pkib", GAUGE, 0, cfg, sink=ring)
            step = 200_000
            for base_i in range(0, n, step):
                b = RecordBuilder(GAUGE)
                m = min(step, n - base_i)
                b.add_series_batch(
                    {"_metric_": "heap_usage", "_ws_": "demo", "_ns_": "app",
                     "job": [f"App-{(base_i + i) % 100}" for i in range(m)],
                     "host": [f"H{(base_i + i) % 1000}" for i in range(m)],
                     "instance": [f"I{base_i + i:07d}" for i in range(m)]},
                    BASE, 1.0)
                sh.ingest(b.build())
            sh.flush_all_groups()
            ms2 = TimeSeriesMemStore()
            sh2 = ms2.setup("pkib", GAUGE, 0, cfg, sink=ring)
            t0 = time.perf_counter()
            sh2.recover()
            total_s = time.perf_counter() - t0
            assert sh2.num_series == n
            idx_ms = registry.gauge(FILODB_INDEX_RECOVER_MS,
                                    {"dataset": "pkib", "shard": "0"}).value
            emit("partkey_index", f"recover_index_ms_{tag}", idx_ms, "ms")
            emit("partkey_index", f"recover_total_ms_{tag}", total_s * 1000,
                 "ms")
            emit("partkey_index", f"recover_rate_{tag}",
                 n / max(idx_ms / 1000.0, 1e-9), "keys/s")
        finally:
            for s in servers:
                try:
                    s.stop()
                except Exception:
                    pass
            shutil.rmtree(root, ignore_errors=True)

    # ---- ingest p99 with the limiter armed -------------------------------
    from filodb_tpu.core.cardinality import CardinalityGovernor
    p99s = {}
    for governed in (False, True):
        cfg = StoreConfig(max_series_per_shard=1 << 16,
                          samples_per_series=256, flush_batch_size=10**9,
                          dtype="float64")
        ms = TimeSeriesMemStore()
        sh = ms.setup("pkg", GAUGE, 0, cfg)
        if governed:
            sh.governor = CardinalityGovernor(50_000, dataset="pkg")
        n_series, per = 5000, 1000
        b = RecordBuilder(GAUGE)
        b.add_series_batch(
            {"_metric_": "m", "_ws_": "demo", "_ns_": "app",
             "host": [f"h{i}" for i in range(n_series)]}, BASE, 1.0)
        sh.ingest(b.build())          # registration: every later row exists
        lat = []
        for t in range(60):
            b = RecordBuilder(GAUGE)
            b.add_series_batch(
                {"_metric_": "m", "_ws_": "demo", "_ns_": "app",
                 "host": [f"h{i}" for i in range(per)]},
                BASE + (t + 1) * 10_000, float(t))
            c = b.build()
            t0 = time.perf_counter()
            sh.ingest(c)
            lat.append((time.perf_counter() - t0) * 1000)
        p99 = sorted(lat)[int(len(lat) * 0.99) - 1]
        p99s[governed] = p99
        emit("partkey_index",
             "ingest_p99_governed_ms" if governed else "ingest_p99_plain_ms",
             p99, "ms")
    emit("partkey_index", "ingest_p99_governed_ratio",
         p99s[True] / max(p99s[False], 1e-9), "x")


def bench_hist_ingest(full: bool) -> None:
    """Ref HistogramIngestBenchmark: ingest native-histogram records."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    from filodb_tpu.memory import hist as H

    n_series, n_samples, B = (100, 300, 64) if full else (50, 100, 64)
    rng = np.random.default_rng(3)
    les = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])
    counts = [np.cumsum(np.cumsum(rng.poisson(0.3, (n_samples, B)), axis=0), axis=1)
              .astype(np.float64) for _ in range(n_series)]
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=n_samples + 8,
                      flush_batch_size=10**9, dtype="float64")
    ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV

    def ingest_all():
        ms = TimeSeriesMemStore()
        ms.setup("bench", PROM_HISTOGRAM, 0, cfg)
        for s in range(n_series):
            b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
            # the reference benchmark ships pre-built containers into the
            # shard; add_batch is the equivalent bulk build path
            b.add_batch({"_metric_": "req_latency", "host": f"h{s}"},
                        ts_arr, counts[s])
            ms.ingest("bench", 0, b.build())
        ms.flush_all()
        return ms

    ingest_all()                      # warm the jit caches (jmh warmup)
    t0 = time.perf_counter()
    ms = ingest_all()
    total = n_series * n_samples
    emit("hist_ingest", "ingest_throughput",
         total / (time.perf_counter() - t0), "hist_records/s")
    # per-record build path (one b.add per sample, 64-bucket rows)
    t0 = time.perf_counter()
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
    for t in range(n_samples):
        b.add({"_metric_": "req_latency", "host": "h0"}, BASE + t * IV,
              counts[0][t])
    b.build()
    emit("hist_ingest", "record_build_throughput",
         n_samples / (time.perf_counter() - t0), "hist_records/s")

    one = counts[0]
    dt, it = timed(lambda: H.encode_hist_series(one))
    emit("hist_ingest", "encode_2d_delta", n_samples * it / dt, "hists/s")


def bench_hist_query(full: bool) -> None:
    """Ref HistogramQueryBenchmark: quantile-of-rate over native hists."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    from filodb_tpu.query.engine import QueryEngine

    n_series, n_samples, B = (100, 300, 64) if full else (40, 120, 64)
    rng = np.random.default_rng(4)
    les = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=n_samples + 8,
                      flush_batch_size=10**9, dtype="float64")
    ms = TimeSeriesMemStore()
    ms.setup("bench", PROM_HISTOGRAM, 0, cfg)
    for s in range(n_series):
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
        c = np.cumsum(np.cumsum(rng.poisson(0.3, (n_samples, B)), axis=0),
                      axis=1).astype(np.float64)
        for t in range(n_samples):
            b.add({"_metric_": "req_latency", "host": f"h{s}"},
                  BASE + t * IV, c[t])
        ms.ingest("bench", 0, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "bench")
    start, end = BASE + 600_000, BASE + (n_samples - 10) * IV

    def q(_=None):
        eng.query_range('histogram_quantile(0.9, sum(rate(req_latency[5m])))',
                        start, end, 60_000)

    dt, it = timed(q, max_iters=30)
    emit("hist_query", "quantile_of_sum_rate", it / dt, "queries/s")
    emit("hist_query", "quantile_of_sum_rate_p50", dt / it * 1000, "ms")
    # concurrent throughput (the jmh methodology: queries in flight). 64
    # workers so the ~100ms session floor amortizes below the device cost —
    # the FALSIFIABLE form of the latency bar is the device-marginal
    # ms/query below, not the floor-bound p50 above (BASELINE.md "Bars")
    from concurrent.futures import ThreadPoolExecutor
    n_q = 128
    with ThreadPoolExecutor(64) as ex:
        list(ex.map(q, range(16)))
        t0 = time.perf_counter()
        list(ex.map(q, range(n_q)))
        cdt = time.perf_counter() - t0
    emit("hist_query", "quantile_of_sum_rate_concurrent", n_q / cdt, "queries/s")
    emit("hist_query", "device_marginal_ms_per_query", cdt / n_q * 1000, "ms")


def bench_query_hicard(full: bool) -> None:
    """Ref QueryHiCardInMemoryBenchmark: 8000 series, 15m @ 10s, a quarter
    queried per sum(rate) query."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.query.engine import QueryEngine

    n_series = 8000 if full else 2000
    n_samples = 90                       # 15 minutes @ 10s
    rng = np.random.default_rng(11)
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ms.setup("bench", PROM_COUNTER, 0, cfg)
    per_job = 4                           # -> n_series/4 match one job filter
    for s in range(n_series):
        b = RecordBuilder(PROM_COUNTER)
        vals = np.cumsum(rng.exponential(5.0, n_samples))
        for t in range(n_samples):
            b.add({"_metric_": "request_total", "job": f"J{s % per_job}",
                   "instance": f"i{s}"}, BASE + t * IV, float(vals[t]))
        ms.ingest("bench", 0, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "bench")
    start, end = BASE + 300_000, BASE + (n_samples - 1) * IV

    def q():
        eng.query_range('sum(rate(request_total{job="J0"}[1m]))',
                        start, end, 60_000)

    dt, it = timed(q, max_iters=30)
    emit("query_hicard", "sum_rate_quarter_series", it / dt, "queries/s")
    emit("query_hicard", "sum_rate_p50", dt / it * 1000, "ms")


def bench_query_ingest(full: bool) -> None:
    """Ref QueryAndIngestBenchmark: an ingest thread keeps streaming
    containers (with per-batch flushes) while concurrent query threads run —
    the reference likewise measures queries DURING ingestion (the shard's
    single ingest thread + concurrent query scheduler model,
    TimeSeriesShard.scala:258-260 + FiloSchedulers)."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.query.engine import QueryEngine

    n_series, n_samples = (1000, 100) if full else (400, 60)
    containers = _gauge_containers(n_series, n_samples)
    # capacity 1024 keeps the fused single-pass path (its VMEM row-tile cap);
    # longer retention would compact, as in production
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=1024,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ms.setup("bench", GAUGE, 0, cfg)
    sh = ms.shard("bench", 0)
    for c in containers[: len(containers) // 2]:
        ms.ingest("bench", 0, c)
    ms.flush_all()
    eng = QueryEngine(ms, "bench")
    start = BASE + 120_000
    end = BASE + (n_samples // 2 - 1) * IV

    def run_query(_=None):
        eng.query_range('sum(rate(heap_usage[1m]))', start, end, 30_000)

    run_query()   # compile
    # idle baseline: 16 queries in flight — a bounded dashboard load. 16 (not
    # 64) on purpose: this host has ONE core, and an unbounded query pool
    # measures GIL starvation of the ingest thread, not the store (a 64-pool
    # probe measured ingest collapsing 25k->4k rec/s with device work
    # unchanged). 16 in flight still amortizes the ~100ms session floor to
    # ~6ms/query, below-or-near the device cost, so the marginal is
    # device-falsifiable (BASELINE.md "Bars")
    n_q = 128
    POOL = 16
    with ThreadPoolExecutor(POOL) as ex:
        list(ex.map(run_query, range(16)))   # thread warm
        t0 = time.perf_counter()
        list(ex.map(run_query, range(n_q)))
        idle_qps = n_q / (time.perf_counter() - t0)
    emit("query_ingest", "idle_query_throughput", idle_qps, "queries/s")
    emit("query_ingest", "idle_device_marginal_ms", 1000.0 / idle_qps, "ms")

    stop = threading.Event()
    ingested = [0]
    # the SLO question: sustain a FIXED scrape rate (the reference benchmark
    # likewise drives a fixed producer) and measure what concurrent queries
    # keep. A scrape stream is paced by wall clock and SKIPS missed ticks —
    # pacing that "catches up" with back-to-back bursts after any stall
    # creates a starvation feedback loop (a stalled query delays ingest,
    # whose burst stalls more queries) that measures the pathology of the
    # pacer, not of the store
    # 12k/s at --full: the highest scrape rate this ONE-core host co-
    # schedules with a 16-in-flight dashboard load without the pacer
    # saturating the core (at 35k the ingest thread spins permanently
    # behind, and the measurement becomes GIL starvation, not the store —
    # a multi-core host raises the target, not the design)
    target_rps = 12_000 if full else 8_000

    def ingest_loop():
        # one template container per tick (1 sample per series, timestamps
        # shifted per tick — container building is the producer/gateway's
        # job, measured by its own suites); ~20 ticks staged per device
        # flush; SeriesStore.throttle applies backpressure on the flush path
        import numpy as np

        from filodb_tpu.core.record import RecordBuilder, RecordContainer
        b = RecordBuilder(GAUGE)
        for s in range(n_series):
            b.add({"_metric_": "heap_usage", "_ws_": "demo", "_ns_": "app",
                   "host": f"h{s}", "job": f"App-{s % 8}"}, 0, float(s))
        tpl = b.build()
        k = 0
        period = n_series / target_rps
        base = BASE + (n_samples // 2) * IV   # contiguous with the preload
        while not stop.is_set():
            t0 = time.perf_counter()
            ts = np.full(len(tpl.ts), base + k * IV, np.int64)
            c = RecordContainer(tpl.schema, ts, tpl.values, tpl.part_hash,
                                tpl.shard_hash, tpl.part_idx,
                                tpl.label_sets, tpl.bucket_les,
                                tpl.part_keys, tpl.set_hashes)
            ms.ingest("bench", 0, c)
            ingested[0] += n_series
            k += 1
            if k % 20 == 0:
                sh.flush()
            wait = period - (time.perf_counter() - t0)
            if wait > 0:
                stop.wait(wait)

    t = threading.Thread(target=ingest_loop, daemon=True)
    t.start()
    time.sleep(0.3)
    # best of 2 rounds: the best round is the closest estimate of what the
    # STORE design costs under interleaved streams
    best = None
    for _ in range(2):
        # snapshot-delta instead of resetting: the ingest thread's += isn't
        # atomic against a cross-thread reset (a lost reset would carry a
        # whole round's count into the next round's throughput)
        snap = ingested[0]
        with ThreadPoolExecutor(POOL) as ex:
            t0 = time.perf_counter()
            list(ex.map(run_query, range(n_q)))
            dt = time.perf_counter() - t0
        if best is None or n_q / dt > best[0]:
            best = (n_q / dt, (ingested[0] - snap) / dt)
    stop.set()
    t.join(timeout=10)
    emit("query_ingest", "mixed_ingest_target", target_rps, "records/s")
    emit("query_ingest", "mixed_ingest_throughput", best[1], "records/s")
    emit("query_ingest", "mixed_query_throughput", best[0], "queries/s")
    emit("query_ingest", "mixed_device_marginal_ms", 1000.0 / best[0], "ms")
    emit("query_ingest", "mixed_vs_idle_query_ratio",
         best[0] / idle_qps, "x")


def bench_ingest(full: bool) -> None:
    """Ingest-plane pipeline (ISSUE 4): end-to-end gateway lines/s (per-
    connection builders + route memo + per-shard publish locks) vs the
    serial per-line baseline (one global lock, per-line key hashing — the
    pre-batching gateway hot path), broker publish rows/s with the windowed
    PUBLISH_BATCH publisher vs one frame per round trip, and consume-side
    replay rows/s. Bit-parity: per-shard row multisets of the two gateway
    paths must match, and the batched-published partition must replay
    byte-identical to the serial one."""
    import shutil
    import socket
    import tempfile
    import threading
    from collections import Counter

    from filodb_tpu.core.record import RecordBuilder, fnv1a64
    from filodb_tpu.core.schemas import GAUGE, Schemas, part_key_of, \
        shard_key_of
    from filodb_tpu.ingest.broker import BrokerBus, BrokerServer
    from filodb_tpu.ingest.gateway import GatewayServer, parse_influx_line
    from filodb_tpu.parallel.shardmapper import ShardMapper

    n_lines, n_conns = (100_000, 8) if full else (20_000, 4)
    n_series = 500
    lines = [f"cpu,host=h{i % n_series},dc=us-east usage={i % 97}.5 "
             f"{(BASE + i) * 1_000_000}" for i in range(n_lines)]

    # -- gateway: serial per-line baseline (the pre-PR-4 ingest_line shape:
    # parse, rebuild labels, hash shard+part key PER LINE, one global lock)
    mapper = ShardMapper(4, 0)
    glock = threading.Lock()
    builders: dict[int, RecordBuilder] = {}
    serial_out: list[tuple[int, object]] = []

    def serial_line(line: str) -> None:
        measurement, tags, fields, ts_ns = parse_influx_line(line)
        ts_ms = ts_ns // 1_000_000 if ts_ns else 0
        with glock:
            for fname, fval in fields.items():
                metric = measurement if fname == "value" \
                    else f"{measurement}_{fname}"
                labels = dict(tags)
                labels["_metric_"] = metric
                labels.setdefault("_ws_", "default")
                labels.setdefault("_ns_", "default")
                opts = GAUGE.options
                shard = mapper.shard_of(
                    fnv1a64(shard_key_of(labels, opts)) & 0xFFFFFFFF,
                    fnv1a64(part_key_of(labels, opts)))
                b = builders.get(shard)
                if b is None:
                    b = builders[shard] = RecordBuilder(GAUGE)
                b.add(labels, ts_ms, fval)

    t0 = time.perf_counter()
    for ln in lines:
        serial_line(ln)
    for shard, b in builders.items():
        serial_out.append((shard, b.build()))
    serial_s = time.perf_counter() - t0
    emit("ingest", "gateway_lines_serial", n_lines / serial_s, "lines/s")

    # -- gateway: batched/pipelined path, end to end over N TCP connections
    got: list[tuple[int, object]] = []
    gw = GatewayServer(lambda s, c: got.append((s, c)), num_shards=4,
                       flush_lines=2048, flush_interval_ms=200, port=0).start()
    slices = [lines[k::n_conns] for k in range(n_conns)]

    def send(sl):
        with socket.create_connection(("127.0.0.1", gw.port)) as s:
            s.sendall(("\n".join(sl) + "\n").encode())

    t0 = time.perf_counter()
    threads = [threading.Thread(target=send, args=(sl,)) for sl in slices]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    deadline = time.time() + 120
    while sum(len(c) for _, c in got) < n_lines and time.time() < deadline:
        time.sleep(0.002)
    gw_s = time.perf_counter() - t0
    gw.stop()
    assert sum(len(c) for _, c in got) == n_lines, "gateway lost lines"
    emit("ingest", "gateway_lines_batched", n_lines / gw_s, "lines/s")
    emit("ingest", "gateway_speedup", serial_s / gw_s, "x")
    emit("ingest", "gateway_connections", n_conns, "count")

    def multiset(pairs):
        out: dict[int, Counter] = {}
        for shard, c in pairs:
            keys, _ = c.resolved_keys()
            ms = out.setdefault(shard, Counter())
            for i in range(len(c)):
                ms[(keys[int(c.part_idx[i])], int(c.ts[i]),
                    float(c.values[i]))] += 1
        return out

    assert multiset(got) == multiset(serial_out), \
        "batched gateway diverged from the serial path"

    # -- broker publish: one frame per round trip vs windowed PUBLISH_BATCH
    rows_per, n_conts, window = (100, 400, 32) if full else (50, 200, 32)
    conts = []
    for i in range(n_conts):
        b = RecordBuilder(GAUGE)
        b.add_batch({"_metric_": "pub", "host": f"h{i}"},
                    BASE + np.arange(rows_per, dtype=np.int64) * IV,
                    np.arange(rows_per, dtype=np.float64))
        conts.append(b.build())
    total_rows = rows_per * n_conts
    tmp = tempfile.mkdtemp(prefix="filodb_ingest_bench_")
    try:
        broker = BrokerServer(tmp, 2).start()
        bus = BrokerBus(f"127.0.0.1:{broker.port}", 0, publish_window=window)
        t0 = time.perf_counter()
        for c in conts:
            bus.publish(c)                     # serial: 1 round trip / frame
        serial_pub_s = time.perf_counter() - t0
        emit("ingest", "broker_publish_rows_serial",
             total_rows / serial_pub_s, "rows/s")
        bus2 = BrokerBus(f"127.0.0.1:{broker.port}", 1, publish_window=window)
        before = bus2.requests
        t0 = time.perf_counter()
        bus2.publish_batch(conts)              # ceil(n/W) pipelined trips
        batch_pub_s = time.perf_counter() - t0
        emit("ingest", "broker_publish_rows_batched",
             total_rows / batch_pub_s, "rows/s")
        emit("ingest", "broker_publish_speedup",
             serial_pub_s / batch_pub_s, "x")
        emit("ingest", "broker_publish_round_trips",
             bus2.requests - before, "count")
        emit("ingest", "broker_publish_window", window, "count")
        # replay: consume-side decode throughput (FETCH already batches)
        t0 = time.perf_counter()
        replayed = list(bus2.consume(Schemas()))
        replay_s = time.perf_counter() - t0
        emit("ingest", "replay_rows_per_s",
             sum(len(c) for _, c in replayed) / replay_s, "rows/s")
        # bit parity: the batched partition's log replays identical to the
        # per-round-trip partition's
        serial_frames = [c.to_bytes() for _, c in bus.consume(Schemas())]
        batch_frames = [c.to_bytes() for _, c in replayed]
        assert serial_frames == batch_frames, \
            "batched publish log diverged from serial publish log"
        bus.close(), bus2.close()
        broker.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    emit("ingest", "bit_parity", 1.0, "bool")


def bench_ingest_soak(full: bool) -> None:
    """Replicated multi-partition ingest soak (ISSUE 6): 2 gateways x 3
    partitions x replication 2 over two broker nodes. The leader of
    partition 1 is KILLED mid-stream (deterministic kill-at-offset fault);
    gateways fail over to the survivor and replay their unacked windows.
    Audit: pub-id reconciliation of every gateway's acked-id ledger against
    the survivor's journals — zero lost, zero duplicated — plus end-to-end
    row-count parity. Overload phase: queue cap 1 + response-delay faults
    shed RETRY at the wire while client backoff lands every publish."""
    import shutil
    import socket as socketmod
    import tempfile
    import threading

    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE, Schemas
    from filodb_tpu.ingest.broker import BrokerBus, BrokerServer
    from filodb_tpu.ingest.faults import FaultPlan, FaultRule
    from filodb_tpu.ingest.gateway import GatewayServer
    from filodb_tpu.utils.metrics import (FILODB_INGEST_FAILOVERS,
                                          FILODB_INGEST_PUBLISH_SHED,
                                          FILODB_INGEST_RETRIES, registry)

    n_lines = 30_000 if full else 6_000          # per gateway
    n_parts, n_shards, kill_at = 3, 4, 10

    def reserve():
        with socketmod.socket() as s:
            s.setsockopt(socketmod.SOL_SOCKET, socketmod.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    pa, pb = reserve(), reserve()
    peers = [f"127.0.0.1:{pa}", f"127.0.0.1:{pb}"]
    tmp = tempfile.mkdtemp(prefix="filodb_soak_")
    retries0 = registry.counter(FILODB_INGEST_RETRIES).value
    failovers0 = registry.counter(FILODB_INGEST_FAILOVERS).value
    try:
        # leader(p) = peers[p % 2]: partition 1 leads on node B — the kill
        # target; A survives and leads/follows everything afterwards
        a = BrokerServer(f"{tmp}/a", n_parts, port=pa, peers=peers,
                         node_index=0, replication=2).start()
        plan = FaultPlan([FaultRule("append", "kill_server", partition=1,
                                    at_offset=kill_at)])
        b = BrokerServer(f"{tmp}/b", n_parts, port=pb, peers=peers,
                         node_index=1, replication=2, fault_plan=plan).start()

        gateways = []
        for g in range(2):
            buses = {s: BrokerBus(peers, s % n_parts, publish_window=16,
                                  retry_backoff_ms=5, max_retries=12,
                                  seed=100 + g, track_acks=True)
                     for s in range(n_shards)}
            gw = GatewayServer(
                lambda s, c, _bs=buses: _bs[s].publish_async(c),
                num_shards=n_shards, flush_lines=64, flush_interval_ms=100,
                port=0).start()
            gw.bus_drain = (lambda _bs=buses:
                            [bus.flush_publishes() for bus in _bs.values()])
            gateways.append((gw, buses))

        def send(gw_idx):
            gw, _ = gateways[gw_idx]
            lines = [f"cpu,host=g{gw_idx}h{i % 400},dc=east usage={i % 97}.5 "
                     f"{(BASE + i) * 1_000_000}" for i in range(n_lines)]
            with socketmod.create_connection(("127.0.0.1", gw.port)) as s:
                s.sendall(("\n".join(lines) + "\n").encode())

        t0 = time.perf_counter()
        threads = [threading.Thread(target=send, args=(g,)) for g in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for gw, _ in gateways:
            gw.stop()           # flush builders + drain publish windows
        soak_s = time.perf_counter() - t0
        assert plan.fired, "leader kill never fired"

        # -- pub-id reconciliation against the SURVIVOR (node A) ----------
        acked: dict[int, set] = {p: set() for p in range(n_parts)}
        for _gw, buses in gateways:
            for s, bus in buses.items():
                acked[s % n_parts].update(bus.acked_ids)
        lost = dup = frames = rows = 0
        for p in range(n_parts):
            items = a._journals[p].items()
            offsets = [o for o, _pid in items]
            pids = [pid for _o, pid in items]
            assert offsets == list(range(len(offsets))), "journal not dense"
            dup += len(pids) - len(set(pids))
            lost += len(acked[p] - set(pids))
            # every logged frame was acked to SOME gateway (drain completed)
            dup += len(set(pids) - acked[p])
            frames += len(pids)
            rows += sum(len(c) for _off, c in
                        BrokerBus([peers[0]], p).consume(Schemas()))
        emit("ingest_soak", "soak_lines_per_s", 2 * n_lines / soak_s,
             "lines/s")
        emit("ingest_soak", "frames_on_survivor", frames, "count")
        emit("ingest_soak", "rows_on_survivor", rows, "rows")
        emit("ingest_soak", "rows_expected", 2 * n_lines, "rows")
        emit("ingest_soak", "pubid_lost", lost, "count")
        emit("ingest_soak", "pubid_duplicated", dup, "count")
        emit("ingest_soak", "row_parity",
             float(rows == 2 * n_lines), "bool")
        emit("ingest_soak", "kill_offset", kill_at, "offset")
        emit("ingest_soak", "client_retries",
             registry.counter(FILODB_INGEST_RETRIES).value - retries0,
             "count")
        emit("ingest_soak", "client_failovers",
             registry.counter(FILODB_INGEST_FAILOVERS).value - failovers0,
             "count")
        assert lost == 0 and dup == 0 and rows == 2 * n_lines
        for _gw, buses in gateways:
            for bus in buses.values():
                bus.close()
        a.stop()
        with __import__("contextlib").suppress(Exception):
            b.stop()

        # -- overload: queue cap 1 + delayed responses -> RETRY shed, then
        # client backoff lands every publish (bounded in-flight by design:
        # client windows <= _MAX_UNACKED_FRAMES, server admits <= max_queue)
        shed0 = registry.counter(FILODB_INGEST_PUBLISH_SHED).value
        oplan = FaultPlan([FaultRule("serve", "delay", nth=1, count=40,
                                     delay_s=0.02)])
        o = BrokerServer(f"{tmp}/o", 1, max_queue=1, fault_plan=oplan).start()
        n_pub, n_threads = (400, 8) if full else (120, 6)

        def hammer(k):
            bus = BrokerBus([f"127.0.0.1:{o.port}"], 0, retry_backoff_ms=10,
                            max_retries=16, seed=k)
            for i in range(n_pub // n_threads):
                bld = RecordBuilder(GAUGE)
                bld.add({"_metric_": "ov", "t": f"{k}-{i}"}, BASE, 1.0)
                bus.publish(bld.build())
            bus.close()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=hammer, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        odt = time.perf_counter() - t0
        n_expected = (n_pub // n_threads) * n_threads
        end = o._parts[0].end_offset
        sheds = registry.counter(FILODB_INGEST_PUBLISH_SHED).value - shed0
        emit("ingest_soak", "overload_publishes", n_expected, "count")
        emit("ingest_soak", "overload_landed", end, "count")
        emit("ingest_soak", "overload_sheds", sheds, "count")
        emit("ingest_soak", "overload_publish_rate", n_expected / odt,
             "frames/s")
        emit("ingest_soak", "overload_queue_cap", 1, "count")
        emit("ingest_soak", "overload_zero_loss",
             float(end == n_expected), "bool")
        assert end == n_expected and sheds > 0
        o.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_gateway(full: bool) -> None:
    """Ref GatewayBenchmark: Influx line-protocol parse + shard-hash rate."""
    from filodb_tpu.ingest.gateway import parse_influx_line

    n = 50_000 if full else 10_000
    lines = [
        f"cpu,host=h{i % 100},dc=us-east usage_user={i % 90}.5,usage_sys=1.25 "
        f"{(BASE + i) * 1_000_000}" for i in range(n)
    ]

    def parse_all():
        for ln in lines:
            parse_influx_line(ln)

    dt, it = timed(parse_all, max_iters=10)
    emit("gateway", "influx_parse", n * it / dt, "lines/s")


def bench_narrow_resident(full: bool) -> None:
    """Compressed-resident store (StoreConfig.narrow_resident): retention per
    HBM byte vs the raw f32 store, decode bit-parity, and the fused-path
    device-marginal ms/dispatch ratio (bar: <= ~1.3x of the f32 path).
    Ref: doc/compression.md + DoubleVector.scala — the reference's read path
    keeps values only compressed; here i16 quantized values + grid-derived
    timestamps replace the 12B/sample raw blocks."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.core.chunkstore import TS_PAD
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.query.engine import QueryEngine

    S = (1 << 20) if full else (1 << 14)
    C = 768 if full else 256
    NS = 720 if full else 200

    def build(narrow: bool):
        ms = TimeSeriesMemStore()
        cfg = StoreConfig(max_series_per_shard=S, samples_per_series=C,
                          flush_batch_size=10**9, dtype="float32",
                          narrow_resident=narrow)
        sh = ms.setup("prometheus", "gauge", 0, cfg)
        # register a handful of series through the real path to seed the
        # index, then install integer-valued (quantizable) bulk data
        from filodb_tpu.core.record import RecordBuilder
        from filodb_tpu.core.schemas import GAUGE
        b = RecordBuilder(GAUGE)
        b.add_series_batch({"_metric_": "m",
                            "host": [f"h{i}" for i in range(S)]}, BASE, 0.0)
        sh.ingest(b.build())
        with sh.lock:
            sh._stage_pid.clear(); sh._stage_ts.clear()
            sh._stage_val.clear(); sh._staged = 0
        st = sh.store
        st.ts = st.val = st.n = None

        @jax.jit
        def mk(key):
            inc = jax.random.randint(key, (S, NS), 1, 50).astype(jnp.float32)
            v = jnp.cumsum(inc, axis=1)
            return jnp.zeros((st.S, C), jnp.float32).at[:S, :NS].set(v)

        st.val = mk(jax.random.PRNGKey(3))
        ts_row = np.full(C, TS_PAD, np.int64)
        ts_row[:NS] = BASE + np.arange(NS, dtype=np.int64) * IV
        st.ts = jnp.tile(jnp.asarray(ts_row), (st.S, 1))
        st.n = jnp.full(st.S, NS, jnp.int32)
        st.n_host = np.full(st.S, NS, np.int32)
        st.first_ts = np.full(st.S, BASE, np.int64)
        st.last_ts = np.full(st.S, BASE + (NS - 1) * IV, np.int64)
        st.grid_base, st.grid_interval, st.grid_ok = BASE, IV, True
        st._cohorts = None
        if narrow:
            with sh.lock:
                assert st.compress_resident(), "quantizable data must compress"
        return ms, sh

    start = BASE + 300_000
    end = BASE + (NS - 1) * IV
    q = "sum(rate(m[5m]))"

    def marginal_ms(eng, K=24, reps=3):
        """Device-marginal per-dispatch: K pipelined queries, median of
        reps (robust to host latency spikes, same methodology as
        bench.py)."""
        eng.query_range(q, start, end, 150_000)       # warm compile
        outs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(K):
                eng.query_range(q, start, end, 150_000)
            outs.append((time.perf_counter() - t0) / K * 1000)
        return sorted(outs)[len(outs) // 2]

    ms_f32, sh_f32 = build(False)
    e_f32 = QueryEngine(ms_f32, "prometheus")
    f32_ms = marginal_ms(e_f32)
    f32_bytes = sh_f32.store.resident_sample_bytes()
    r_f32 = e_f32.query_range(q, start, end, 150_000)
    (_k, _t, a), = list(r_f32.matrix.iter_series())
    a = np.asarray(a).copy()
    # release the f32 store's HBM before building the narrow one: at full
    # scale (1M x 768) the two residencies do not fit together
    st0 = sh_f32.store
    st0.ts = st0.val = st0.n = None
    del ms_f32, sh_f32, e_f32, r_f32, st0

    ms_nr, sh_nr = build(True)
    st = sh_nr.store
    assert st.is_narrow_resident and st.val is None and st.ts is None
    e_nr = QueryEngine(ms_nr, "prometheus")
    nr_ms = marginal_ms(e_nr)
    nr_bytes = st.resident_sample_bytes()
    r_nr = e_nr.query_range(q, start, end, 150_000)

    # bit parity of the flagship aggregate between residencies
    (_k, _t, b), = list(r_nr.matrix.iter_series())
    assert np.array_equal(a, b), "narrow-resident query diverged"

    retention = f32_bytes / max(nr_bytes, 1)
    emit("narrow_resident", "resident_bytes_f32", f32_bytes, "bytes")
    emit("narrow_resident", "resident_bytes_narrow", nr_bytes, "bytes")
    emit("narrow_resident", "retention_multiple_at_fixed_hbm", retention, "x")
    emit("narrow_resident", "fused_ms_f32", f32_ms, "ms/query")
    emit("narrow_resident", "fused_ms_narrow", nr_ms, "ms/query")
    emit("narrow_resident", "fused_ratio_narrow_vs_f32", nr_ms / f32_ms, "x")
    emit("narrow_resident", "bit_parity", 1.0, "bool")


def bench_scalar_residency(full: bool) -> None:
    """Scalar narrow residency v2 (ISSUE 17): the delta8/quant16/delta16
    preference ladder on gauge/counter stores. Measures retention at fixed
    HBM for the counter-shaped delta8 path (bar: >= 3x vs the 12B/sample
    raw f32+i64 store), the fused query's device-marginal ms A/B (the
    bytes/sample effect on the streamed operand), per-kind resident
    bytes/sample, and the encode-at-flush device cost (compress_prepare —
    the donated flush-path encode)."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.core.chunkstore import TS_PAD
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.query.engine import QueryEngine

    S = (1 << 20) if full else (1 << 14)
    C = 768 if full else 256
    NS = 720 if full else 200

    def build(shape: str, narrow: bool):
        ms = TimeSeriesMemStore()
        cfg = StoreConfig(max_series_per_shard=S, samples_per_series=C,
                          flush_batch_size=10**9, dtype="float32",
                          narrow_resident=narrow)
        sh = ms.setup("prometheus", "gauge", 0, cfg)
        from filodb_tpu.core.record import RecordBuilder
        from filodb_tpu.core.schemas import GAUGE
        b = RecordBuilder(GAUGE)
        b.add_series_batch({"_metric_": "m",
                            "host": [f"h{i}" for i in range(S)]}, BASE, 0.0)
        sh.ingest(b.build())
        with sh.lock:
            sh._stage_pid.clear(); sh._stage_ts.clear()
            sh._stage_val.clear(); sh._staged = 0
        st = sh.store
        st.ts = st.val = st.n = None

        @jax.jit
        def mk(key):
            if shape == "counter":      # small int increments -> delta8
                inc = jax.random.randint(key, (S, NS), 1, 50)
                v = jnp.cumsum(inc, axis=1).astype(jnp.float32)
            elif shape == "halfint":    # 0.5 steps: non-integral -> quant16
                a0 = jax.random.randint(key, (S, 1), 0, 1000)
                v = a0.astype(jnp.float32) + 0.5 * jnp.arange(NS)
            else:                       # big odd increments -> delta16
                inc = jax.random.randint(key, (S, NS), 100, 3000) * 2 + 1
                v = jnp.cumsum(inc, axis=1).astype(jnp.float32)
            return jnp.zeros((st.S, C), jnp.float32).at[:S, :NS].set(v)

        st.val = mk(jax.random.PRNGKey(17))
        ts_row = np.full(C, TS_PAD, np.int64)
        ts_row[:NS] = BASE + np.arange(NS, dtype=np.int64) * IV
        st.ts = jnp.tile(jnp.asarray(ts_row), (st.S, 1))
        st.n = jnp.full(st.S, NS, jnp.int32)
        st.n_host = np.full(st.S, NS, np.int32)
        st.first_ts = np.full(st.S, BASE, np.int64)
        st.last_ts = np.full(st.S, BASE + (NS - 1) * IV, np.int64)
        st.grid_base, st.grid_interval, st.grid_ok = BASE, IV, True
        st._cohorts = None
        if narrow:
            with sh.lock:
                assert st.compress_resident(hist=False), \
                    f"{shape} data must compress"
        return ms, sh

    def teardown(ms, sh):
        st = sh.store
        st.ts = st.val = st.n = None
        st._narrow = None

    start = BASE + 300_000
    end = BASE + (NS - 1) * IV
    q = "sum(rate(m[5m]))"

    def marginal_ms(eng, K=24, reps=3):
        eng.query_range(q, start, end, 150_000)       # warm compile
        outs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(K):
                eng.query_range(q, start, end, 150_000)
            outs.append((time.perf_counter() - t0) / K * 1000)
        return sorted(outs)[len(outs) // 2]

    # ---- raw f32 A-side: fused ms, bytes, parity sample, encode cost
    ms_f32, sh_f32 = build("counter", False)
    st0 = sh_f32.store
    f32_ms = marginal_ms(QueryEngine(ms_f32, "prometheus"))
    f32_bytes = st0.resident_sample_bytes()
    r = QueryEngine(ms_f32, "prometheus").query_range(q, start, end, 150_000)
    (_k, _t, a), = list(r.matrix.iter_series())
    a = np.asarray(a).copy()
    # encode-at-flush: compress_prepare is the lock-free device encode the
    # flush path pays; time it hot (prep discarded, store stays raw)
    dt, it = timed(lambda: jax.block_until_ready(
        st0.compress_prepare(hist=False)), min_s=0.5, max_iters=20)
    enc_ms = dt / it * 1000
    emit("scalar_residency", "encode_flush_ms", enc_ms, "ms")
    emit("scalar_residency", "encode_flush_throughput",
         st0.val.size * 4 / (dt / it) / 1e9, "GB/s")
    teardown(ms_f32, sh_f32)
    del ms_f32, sh_f32, st0, r

    # ---- narrow B-side: counter data lands on delta8 (1B/sample values)
    ms_nr, sh_nr = build("counter", True)
    st = sh_nr.store
    assert st.is_narrow_resident and st.val is None and st.ts is None
    kind = st.narrow_operands()[0]
    assert kind == "delta8", f"counter data must land on delta8, got {kind}"
    e_nr = QueryEngine(ms_nr, "prometheus")
    nr_ms = marginal_ms(e_nr)
    nr_bytes = st.resident_sample_bytes()
    r = e_nr.query_range(q, start, end, 150_000)
    (_k, _t, bvals), = list(r.matrix.iter_series())
    assert np.array_equal(a, bvals), "delta8-resident query diverged"
    teardown(ms_nr, sh_nr)
    del ms_nr, sh_nr, st, e_nr, r

    retention = f32_bytes / max(nr_bytes, 1)
    assert retention >= 3.0, f"retention multiple {retention:.2f} < 3x"
    emit("scalar_residency", "resident_bytes_f32", f32_bytes, "bytes")
    emit("scalar_residency", "resident_bytes_delta8", nr_bytes, "bytes")
    emit("scalar_residency", "retention_multiple_at_fixed_hbm", retention, "x")
    emit("scalar_residency", "fused_ms_f32", f32_ms, "ms/query")
    emit("scalar_residency", "fused_ms_delta8", nr_ms, "ms/query")
    emit("scalar_residency", "fused_ratio_delta8_vs_f32", nr_ms / f32_ms, "x")
    emit("scalar_residency", "bit_parity", 1.0, "bool")

    # ---- the rest of the ladder: adopted kind + resident bytes/sample
    for shape, want in (("halfint", "quant16"), ("bigodd", "delta16")):
        ms_k, sh_k = build(shape, True)
        stk = sh_k.store
        kind = stk.narrow_operands()[0]
        assert kind == want, f"{shape} data must land on {want}, got {kind}"
        emit("scalar_residency", f"bytes_per_sample_{want}",
             stk.resident_sample_bytes() / (S * NS), "B/sample")
        teardown(ms_k, sh_k)
        del ms_k, sh_k, stk
    emit("scalar_residency", "bytes_per_sample_delta8",
         nr_bytes / (S * NS), "B/sample")
    emit("scalar_residency", "bytes_per_sample_f32",
         f32_bytes / (S * NS), "B/sample")


def bench_hist_retention(full: bool) -> None:
    """Compressed-resident HISTOGRAM store (compressed_residency="all"):
    series-at-fixed-HBM retention vs the raw f32 [S, C, B] store, plus
    quantile-of-sum-of-rate parity and ms between residencies. Ref:
    doc/compression.md "Histograms" — the reference's in-memory histogram
    vectors are 2D-delta compressed; this is the device-resident analog
    (i8/i16 dd blocks + first-frame deltas, ops/narrow.build_narrow_hist)."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    from filodb_tpu.query.engine import QueryEngine

    n_series, n_samples, B = (2000, 300, 64) if full else (64, 120, 32)
    rng = np.random.default_rng(12)
    les = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])
    ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV
    data = [np.cumsum(np.cumsum(rng.poisson(0.3, (n_samples, B)), axis=0),
                      axis=1).astype(np.float64) for _ in range(n_series)]

    def build(mode: str):
        ms = TimeSeriesMemStore()
        cfg = StoreConfig(max_series_per_shard=n_series,
                          samples_per_series=n_samples + 8,
                          flush_batch_size=10**9, dtype="float32",
                          compressed_residency=mode)
        sh = ms.setup("bench", PROM_HISTOGRAM, 0, cfg)
        for s in range(n_series):
            b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
            b.add_batch({"_metric_": "req_latency", "host": f"h{s}"},
                        ts_arr, data[s])
            ms.ingest("bench", 0, b.build())
        ms.flush_all()
        return ms, sh

    start, end = BASE + 600_000, BASE + (n_samples - 10) * IV
    q = 'histogram_quantile(0.9, sum(rate(req_latency[5m])))'

    def series_result(eng):
        r = eng.query_range(q, start, end, 60_000)
        (_k, _t, v), = list(r.matrix.iter_series())
        return np.asarray(v).copy()

    ms_raw, sh_raw = build("off")
    e_raw = QueryEngine(ms_raw, "bench")
    raw_bytes = sh_raw.store.resident_sample_bytes()
    dt, it = timed(lambda: series_result(e_raw), max_iters=20)
    raw_ms = dt / it * 1000
    a = series_result(e_raw)
    del ms_raw, sh_raw, e_raw

    ms_c, sh_c = build("all")
    st = sh_c.store
    assert st.is_narrow_resident and st.val is None and st.ts is None, \
        "hist store must adopt compressed residency"
    e_c = QueryEngine(ms_c, "bench")
    dt, it = timed(lambda: series_result(e_c), max_iters=20)
    nr_ms = dt / it * 1000
    b = series_result(e_c)
    assert np.array_equal(a, b), "hist-resident quantile diverged"
    nr_bytes = st.resident_sample_bytes()

    retention = raw_bytes / max(nr_bytes, 1)
    emit("hist_retention", "resident_bytes_f32", raw_bytes, "bytes")
    emit("hist_retention", "resident_bytes_compressed", nr_bytes, "bytes")
    emit("hist_retention", "retention_multiple_at_fixed_hbm", retention, "x")
    emit("hist_retention", "series_at_fixed_hbm_multiple", retention, "x")
    emit("hist_retention", "dd_dtype_bits",
         st._nhist[0].dtype.itemsize * 8, "bits")
    emit("hist_retention", "quantile_of_sum_rate_ms_f32", raw_ms, "ms")
    emit("hist_retention", "quantile_of_sum_rate_ms_compressed", nr_ms, "ms")
    emit("hist_retention", "fused_ratio_compressed_vs_f32",
         nr_ms / max(raw_ms, 1e-9), "x")
    emit("hist_retention", "bit_parity", 1.0, "bool")


def bench_odp(full: bool) -> None:
    """Ref QueryOnDemandBenchmark: evict resident data, then query a COLD
    range — every query merges sink chunks with the resident tail through
    read_with_paging (one batched device upload per paged batch). Reports
    first-touch latency (compile + page-in), steady cold-query page-in ms /
    qps, and the resident-range baseline for contrast."""
    import shutil
    import tempfile

    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.core.store import FileColumnStore
    from filodb_tpu.query.engine import QueryEngine

    n_series, n_samples = (2000, 240) if full else (400, 120)
    tmp = tempfile.mkdtemp(prefix="filodb_odp_")
    try:
        cfg = StoreConfig(max_series_per_shard=n_series,
                          samples_per_series=n_samples + 8,
                          flush_batch_size=10**9, dtype="float32")
        ms = TimeSeriesMemStore()
        sh = ms.setup("bench", GAUGE, 0, cfg, sink=FileColumnStore(tmp))
        ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV
        rng = np.random.default_rng(9)
        b = RecordBuilder(GAUGE)
        for s in range(n_series):
            b.add_batch({"_metric_": "m_odp", "host": f"h{s}"},
                        ts_arr, np.cumsum(rng.exponential(2.0, n_samples)))
        ms.ingest("bench", 0, b.build())
        ms.flush_all()
        # evict the early two thirds: resident data starts at `cut`, the
        # cold range below it pages from the sink on every query
        cut = BASE + (2 * n_samples // 3) * IV
        sh.store.compact(cut)
        eng = QueryEngine(ms, "bench")
        cold_start, cold_end = BASE + 120_000, cut - IV
        hot_start, hot_end = cut + 60_000, BASE + (n_samples - 1) * IV

        def q_cold(_=None):
            eng.query_range('sum(rate(m_odp[1m]))', cold_start, cold_end,
                            60_000)

        def q_hot(_=None):
            eng.query_range('sum(rate(m_odp[1m]))', hot_start, hot_end,
                            60_000)

        t0 = time.perf_counter()
        q_cold()
        emit("odp", "cold_first_touch_ms",
             (time.perf_counter() - t0) * 1000, "ms")   # compile + page-in
        dt, it = timed(q_cold, max_iters=20)
        emit("odp", "cold_query_page_in_ms", dt / it * 1000, "ms")
        emit("odp", "cold_query_qps", it / dt, "queries/s")
        emit("odp", "paged_series_per_s", n_series * it / dt, "series/s")
        dt, it = timed(q_hot, max_iters=20)
        emit("odp", "resident_query_ms", dt / it * 1000, "ms")
        emit("odp", "series", n_series, "count")
        emit("odp", "cold_samples_per_series",
             (cold_end - BASE) // IV, "samples")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_retention(full: bool) -> None:
    """PR 10 retention tiering: a (scaled) year of synthetic data answered
    at three resolutions through the retention router (latency + qps per
    resolution), a cold month-long rate() over evicted series paged from
    the replicated durable StoreServer tier at measured qps, and a
    kill-one-replica run proving reads AND writes continue (ref: the
    reference's downsample cluster + Cassandra chunk store)."""
    import shutil
    import tempfile

    from filodb_tpu.core.diststore import (RemoteStore,
                                           ReplicatedColumnStore,
                                           StoreServer)
    from filodb_tpu.core.downsample import ds_family
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.jobs.batch_downsampler import (load_downsampled,
                                                   run_batch_downsample)
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.retention import (RetentionPolicy, RetentionRouter,
                                            resolution_label)
    from filodb_tpu.utils.metrics import (FILODB_RETENTION_REPLICA_FAILOVER,
                                          registry)

    RAW_IV = 300_000                       # 5m raw scrape interval
    H1, H6 = 3_600_000, 21_600_000
    DAY = 86_400_000
    days, n_series = (365, 16) if full else (60, 8)
    n_samples = days * DAY // RAW_IV
    tmp = tempfile.mkdtemp(prefix="filodb_retention_")
    servers = [StoreServer(f"{tmp}/node{i}").start() for i in range(2)]
    stores = [RemoteStore(f"127.0.0.1:{s.port}", timeout_s=5.0,
                          connect_timeout_s=2.0) for s in servers]
    repl = ReplicatedColumnStore(stores, replication=2)
    try:
        cfg = StoreConfig(max_series_per_shard=n_series,
                          samples_per_series=1 << (n_samples - 1).bit_length(),
                          flush_batch_size=10**9, groups_per_shard=4,
                          dtype="float64")
        ms = TimeSeriesMemStore()
        sh = ms.setup("bench", GAUGE, 0, cfg, sink=repl)
        ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * RAW_IV
        rng = np.random.default_rng(13)
        t0 = time.perf_counter()
        b = RecordBuilder(GAUGE)
        for s in range(n_series):
            b.add_batch({"_metric_": "m", "host": f"h{s}"}, ts_arr,
                        np.cumsum(rng.exponential(2.0, n_samples)))
        sh.ingest(b.build(), offset=0)
        sh.flush_all_groups()
        emit("retention", "ingest_flush_s", time.perf_counter() - t0, "s")
        emit("retention", "span_days", days, "days")
        emit("retention", "series", n_series, "count")
        emit("retention", "raw_samples", n_series * n_samples, "samples")
        t0 = time.perf_counter()
        for res in (H1, H6):
            run_batch_downsample(repl, "bench", 0, res)
        emit("retention", "downsample_build_s", time.perf_counter() - t0, "s")
        fams = {}
        for res in (H1, H6):
            fms = TimeSeriesMemStore()
            load_downsampled(repl, "bench", 0, res, "dAvg", fms)
            fams[res] = QueryEngine(fms, ds_family("bench", res))
        eng = QueryEngine(ms, "bench")
        eng.retention = RetentionRouter(
            RetentionPolicy([H1, H6], raw_window_ms=7 * DAY),
            lambda r: fams.get(r), dataset="bench")
        lead = int(ts_arr[-1])
        # the same year-long question at each resolution (step = 6h so the
        # three answers are comparable; the override pins the tier)
        q = "sum(avg_over_time(m[6h]))"
        for res_ms, lbl in ((0, "raw"), (H1, "1h"), (H6, "6h")):
            def q_res(_lbl=lbl):
                eng.query_range(q, BASE + H6, lead, H6, resolution=_lbl)
            dt, it = timed(q_res, max_iters=10)
            emit("retention", f"latency_{lbl}_ms", dt / it * 1000, "ms")
            emit("retention", f"qps_{lbl}", it / dt, "queries/s")
        # auto-routing over the full span stitches ds body + raw tail
        auto = eng.query_range(q, BASE + H6, lead, H6)
        emit("retention", "auto_resolution_is_stitched",
             float(auto.stats.resolution.endswith("+raw")), "bool")
        # cold month-long rate(): evict everything older than 7 days from
        # memory, then force raw over a month far past the horizon — every
        # query pages from the replicated durable tier
        with sh.lock:
            sh.store.compact(lead - 7 * DAY)
        cold_lo = lead - min(40, days - 10) * DAY
        cold_hi = cold_lo + 30 * DAY

        from filodb_tpu.utils.metrics import FILODB_RETENTION_ODP_ROWS
        odp_rows = registry.counter(FILODB_RETENTION_ODP_ROWS,
                                    {"dataset": "bench", "tier": "remote"})
        odp_before = odp_rows.value

        def q_cold(_=None):
            return eng.query_range("sum(rate(m[1h]))", cold_lo, cold_hi,
                                   H6, resolution="raw")
        first = q_cold()
        emit("retention", "cold_paged_series",
             first.stats.rows_paged_in, "series")
        emit("retention", "cold_paged_samples_per_query",
             odp_rows.value - odp_before, "samples")
        dt, it = timed(q_cold, max_iters=8)
        emit("retention", "cold_month_rate_ms", dt / it * 1000, "ms")
        emit("retention", "cold_month_rate_qps", it / dt, "queries/s")
        # kill one replica holding the shard: reads fail over, writes land
        # on the survivor (consistency ONE), failovers are counted
        holders = [i for i, st in enumerate(stores)
                   if st.chunk_log_size("bench", 0) > 0]
        fo = registry.counter(FILODB_RETENTION_REPLICA_FAILOVER,
                              {"op": "read_chunksets"})
        fo_before = fo.value
        servers[holders[0]].stop()
        stores[holders[0]].close()
        after_kill = q_cold()
        emit("retention", "reads_after_kill_ok",
             float(np.array_equal(np.asarray(after_kill.matrix.values),
                                  np.asarray(first.matrix.values),
                                  equal_nan=True)), "bool")
        b2 = RecordBuilder(GAUGE)
        ts2 = lead + RAW_IV + np.arange(4, dtype=np.int64) * RAW_IV
        for s in range(n_series):
            b2.add_batch({"_metric_": "m", "host": f"h{s}"}, ts2,
                         np.full(4, 1.0))
        sh.ingest(b2.build(), offset=1)
        sh.flush_all_groups()
        emit("retention", "writes_after_kill_ok", 1.0, "bool")
        emit("retention", "replica_failovers", fo.value - fo_before, "count")
        emit("retention", "resolutions",
             float(len([resolution_label(r) for r in (H1, H6)]) + 1), "count")
    finally:
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 — one was killed mid-run
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def bench_count_values(full: bool) -> None:
    """Mesh count_values closure (VERDICT weak 4 / item 7): count_values is
    the one aggregation whose reduce stays a HOST merge (partial state keyed
    by rendered value strings — no fixed-size device layout to gather).
    Measure the host merge's share of total query time at bench scale over 8
    shards; the mesh exclusion stands while the fraction is small."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.query.engine import QueryEngine

    n_series, n_samples, nshards = (8192, 120, 8) if full else (1024, 60, 8)
    per = n_series // nshards
    cfg = StoreConfig(max_series_per_shard=per,
                      samples_per_series=n_samples + 8,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV
    rng = np.random.default_rng(21)
    for s in range(nshards):
        ms.setup("bench", GAUGE, s, cfg)
        b = RecordBuilder(GAUGE)
        for i in range(per):
            # small-int values: the realistic count_values shape (status
            # codes, bucketed levels) — distinct-value count stays bounded
            vals = rng.integers(0, 20, n_samples).astype(np.float64)
            b.add_batch({"_metric_": "m_cv", "host": f"h{s}-{i}"},
                        ts_arr, vals)
        ms.ingest("bench", s, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "bench")
    start, end = BASE + 120_000, BASE + (n_samples - 1) * IV

    def q(_=None):
        eng.query_range('count_values("v", m_cv)', start, end, 60_000)

    dt, it = timed(q, max_iters=20)
    total_ms = dt / it * 1000
    emit("count_values", "query_ms", total_ms, "ms")

    # isolate the host merge: per-shard map-phase partials captured once,
    # then the reduce (merge + present) timed on its own
    from filodb_tpu.promql import parser as promql
    from filodb_tpu.query.exec import _merge_heterogeneous
    plan = promql.query_to_logical_plan('count_values("v", m_cv)', start, end,
                                        60_000)
    ep = eng.planner.materialize(plan)
    ctx = eng._ctx()
    partials = [c.execute(ctx) for c in ep.children]
    presenter = ep.transformers[0]

    def merge(_=None):
        presenter.apply(_merge_heterogeneous(
            partials, "count_values", ("v",), (), ()), ctx)

    dt, it = timed(merge, max_iters=50)
    merge_ms = dt / it * 1000
    emit("count_values", "host_merge_ms", merge_ms, "ms")
    emit("count_values", "host_merge_fraction", merge_ms / total_ms, "x")
    emit("count_values", "series", n_series, "count")


def bench_observability(full: bool) -> None:
    """PR 7: tracing + per-query-stats overhead on the query hot path.
    Exactly the query_hicard workload (same fixture, same query), measured
    with tracing OFF (one flag check per root span; QueryStats accounting
    is always on), SAMPLED at 0.01, and FULL — so ``query_p50_off`` is
    directly comparable to ``query_hicard.sum_rate_p50`` of the previous
    round's BENCH_SUITE (the <2% tracing-off acceptance bar)."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.utils.tracing import tracer

    n_series = 8000 if full else 2000
    n_samples = 90                       # 15 minutes @ 10s
    rng = np.random.default_rng(11)
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ms.setup("bench", PROM_COUNTER, 0, cfg)
    per_job = 4
    for s in range(n_series):
        b = RecordBuilder(PROM_COUNTER)
        vals = np.cumsum(rng.exponential(5.0, n_samples))
        for t in range(n_samples):
            b.add({"_metric_": "request_total", "job": f"J{s % per_job}",
                   "instance": f"i{s}"}, BASE + t * IV, float(vals[t]))
        ms.ingest("bench", 0, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "bench")
    start, end = BASE + 300_000, BASE + (n_samples - 1) * IV

    def q():
        eng.query_range('sum(rate(request_total{job="J0"}[1m]))',
                        start, end, 60_000)

    modes = (("off", False, 1.0), ("sampled_1pct", True, 0.01),
             ("full", True, 1.0))
    was = (tracer.enabled, tracer.sample_rate)
    runs: dict[str, list[float]] = {m: [] for m, _, _ in modes}
    spans_full = iters_full = 0
    try:
        for _ in range(5):
            q()                          # warm: compile + caches settled
        # INTERLEAVE modes across rounds and take each mode's best run:
        # machine noise between rounds would otherwise swamp a few-percent
        # overhead (the thing this suite exists to measure)
        for _ in range(3):
            for mode, enabled, rate in modes:
                tracer.enabled, tracer.sample_rate = enabled, rate
                tracer.drain()
                dt, it = timed(q, max_iters=30)
                runs[mode].append(dt / it * 1000)
                if mode == "full":
                    # +1: timed() runs one warmup call before the clock
                    spans_full, iters_full = len(tracer.drain()), it + 1
    finally:
        tracer.enabled, tracer.sample_rate = was
    p50 = {m: min(v) for m, v in runs.items()}
    for mode in p50:
        emit("observability", f"query_p50_{mode}", p50[mode], "ms")
    spans_per_query = spans_full / max(iters_full, 1)
    emit("observability", "spans_per_query_full", spans_per_query, "spans")
    emit("observability", "overhead_sampled_vs_off",
         p50["sampled_1pct"] / p50["off"] - 1, "x")
    emit("observability", "overhead_full_vs_off",
         p50["full"] / p50["off"] - 1, "x")

    # tight-loop span cost: the wall-clock A/B above carries the box's
    # multi-percent run-to-run noise, so also publish the noise-immune
    # per-span cost and the overhead it implies at this query shape
    def span_cost_us(n: int = 20000) -> float:
        with tracer.span("query"):      # warm the per-thread rng
            pass
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tracer.span("query"):
                pass
        return (time.perf_counter_ns() - t0) / n / 1000.0
    try:
        tracer.enabled = False
        off_us = span_cost_us()
        emit("observability", "span_cost_us_off", off_us, "us")
        tracer.enabled, tracer.sample_rate = True, 1.0
        full_us = span_cost_us()
        emit("observability", "span_cost_us_full", full_us, "us")
    finally:
        tracer.enabled, tracer.sample_rate = was
        tracer.drain()
    emit("observability", "est_overhead_off_pct",
         spans_per_query * off_us / (p50["off"] * 1000) * 100, "%")
    emit("observability", "est_overhead_full_pct",
         spans_per_query * full_us / (p50["off"] * 1000) * 100, "%")


def bench_serving(full: bool) -> None:
    """ISSUE 8: the query-serving fast path. Three phases on the hicard
    fixture: (a) cold-vs-warm compile latency — the compiled-plan cache is
    cleared to re-measure a cold process, then a config-style warmup
    pre-traces the shape; (b) repeated-dashboard serving with the result
    cache on vs off (hit must be >= 5x faster at bit parity); (c) overload:
    a cost budget that admits ~2 queries at a time under 8 honored-backoff
    clients — every query lands, the admitted cost never passes the
    budget, and the shed count shows the gate actually worked."""
    import threading

    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.query.engine import QueryConfig, QueryEngine
    from filodb_tpu.query.plancache import plan_cache, warmup
    from filodb_tpu.query.scheduler import AdmissionRejected

    n_series = 8192 if full else 2048
    n_samples = 90                       # 15 minutes @ 10s
    rng = np.random.default_rng(13)
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ms.setup("serve", PROM_COUNTER, 0, cfg)
    for s in range(n_series):
        b = RecordBuilder(PROM_COUNTER)
        vals = np.cumsum(rng.exponential(5.0, n_samples))
        for t in range(n_samples):
            b.add({"_metric_": "request_total", "job": f"J{s % 4}",
                   "instance": f"i{s}"}, BASE + t * IV, float(vals[t]))
        ms.ingest("serve", 0, b.build())
    ms.flush_all()
    start, end, step = BASE + 300_000, BASE + (n_samples - 1) * IV, 60_000
    q = 'sum(rate(request_total[1m]))'

    # -- (a) cold vs warm compile ------------------------------------------
    eng = QueryEngine(ms, "serve")

    def one(engine=eng, query=q):
        return engine.query_range(query, start, end, step)

    plan_cache.clear()                   # a cold process, reproduced
    t0 = time.perf_counter()
    one()
    cold_ms = (time.perf_counter() - t0) * 1000
    dt, it = timed(one, max_iters=40)
    warm_ms = dt / it * 1000
    emit("serving", "cold_first_query_ms", cold_ms, "ms")
    emit("serving", "warm_p50_ms", warm_ms, "ms")
    emit("serving", "cold_vs_warm_speedup", cold_ms / warm_ms, "x")
    # config-driven warmup absorbs the cold cost before the first query
    plan_cache.clear()
    winfo = warmup([{"fn": "rate", "op": "sum", "series": n_series,
                     "samples": 128, "steps": (end - start) // step + 1,
                     "step_ms": step, "window_ms": 60_000,
                     "interval_ms": IV}])
    tr0 = plan_cache.traces
    t0 = time.perf_counter()
    one()
    emit("serving", "warmed_first_query_ms",
         (time.perf_counter() - t0) * 1000, "ms")
    emit("serving", "warmup_ms", winfo["ms"], "ms")
    emit("serving", "warmup_programs", winfo["programs"], "count")
    emit("serving", "first_query_compiles_after_warmup",
         plan_cache.traces - tr0, "count")

    # -- (b) result cache on vs off ----------------------------------------
    ceng = QueryEngine(ms, "serve",
                       config=QueryConfig(result_cache_size=64))
    r_off = one()                        # warm, uncached engine
    r_hit = ceng.query_range(q, start, end, step)   # populate
    dt, it = timed(lambda: ceng.query_range(q, start, end, step),
                   max_iters=200)
    hit_ms = dt / it * 1000
    dt, it = timed(one, max_iters=40)
    exec_ms = dt / it * 1000
    r_hit = ceng.query_range(q, start, end, step)
    assert (r_hit.exec_path or "").startswith("result-cache")
    parity = float(np.array_equal(np.asarray(r_off.matrix.to_host().values),
                                  np.asarray(r_hit.matrix.to_host().values)))
    emit("serving", "result_hit_p50_ms", hit_ms, "ms")
    emit("serving", "reexec_p50_ms", exec_ms, "ms")
    emit("serving", "result_cache_speedup", exec_ms / hit_ms, "x")
    emit("serving", "result_cache_bit_parity", parity, "bool")
    # repeated-dashboard qps, cache on vs off
    dt, it = timed(lambda: ceng.query_range(q, start, end, step),
                   max_iters=200)
    emit("serving", "dashboard_qps_cache_on", it / dt, "queries/s")
    dt, it = timed(one, max_iters=40)
    emit("serving", "dashboard_qps_cache_off", it / dt, "queries/s")

    # -- (c) overload: admission gate + honored-backoff clients ------------
    per_cost = eng.estimate_cost(
        __import__("filodb_tpu.promql.parser", fromlist=["x"])
        .query_to_logical_plan(q, start, end, step))
    budget = per_cost * 2.5              # ~2 queries execute at a time
    aeng = QueryEngine(ms, "serve", config=QueryConfig(
        max_concurrent_cost=budget, shed_retry_after_s=0.005))
    n_clients, per_client = 8, 6
    sheds = [0]
    landed = [0]
    peak = [0.0]
    lock = threading.Lock()

    def client():
        done = 0
        while done < per_client:
            try:
                r = aeng.query_range(q, start, end, step)
                assert r.matrix.num_series == 1
                done += 1
            except AdmissionRejected as e:
                with lock:
                    sheds[0] += 1
                time.sleep(e.retry_after_s)      # honor the hint
            with lock:
                peak[0] = max(peak[0], aeng.admission.stats()["in_use"])
        with lock:
            landed[0] += done

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    emit("serving", "overload_budget_cost", budget, "cost")
    emit("serving", "overload_queries_landed", landed[0], "count")
    emit("serving", "overload_sheds", sheds[0], "count")
    emit("serving", "overload_peak_cost_in_use", peak[0], "cost")
    emit("serving", "overload_budget_respected",
         float(peak[0] <= budget), "bool")
    emit("serving", "overload_wall_s", wall, "s")
    assert landed[0] == n_clients * per_client, \
        "every honored-backoff client must land every query"
    assert peak[0] <= budget, "admitted cost exceeded the budget"


def bench_fused_resident(full: bool) -> None:
    """ISSUE 9: the fused compressed-resident kernel tier. Per-shape A/B of
    the fused path (query.fused_kernels = xla / pallas) against the composed
    (PR 8-cached) two-step chain (mode off) at MATCHED fixtures; plus the
    flush-path row proving the donated scatter stops copying the store. All
    paths run warm (plan cache populated) — the delta is execution, not
    compilation.

    Fixtures are the shapes the tier exists for: high-cardinality
    dashboards (many series, fine step grid, T steps >> C stored samples)
    where the composed chain materializes the [S, Tp]/[S, Tp*B] windowed
    intermediate in HBM and re-reads it for the segment reduce — the
    traffic the one-pass program deletes.

    Parity semantics (same rules the tests assert, tests/
    test_fused_resident.py): the two fused backends share one tiling plan
    and tile math, so pallas vs xla is BIT-IDENTICAL (asserted). Against
    the composed oracle, single-tile shapes (S <= 512) are exact; at the
    multi-tile scale benchmarked here the per-tile f32 fold sums in a
    different order than the oracle's one-shot contraction, so the oracle
    rows document max relative delta instead (asserted <= 2e-5, f32
    epsilon-order)."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_COUNTER, PROM_HISTOGRAM
    from filodb_tpu.ops import fusedresident
    from filodb_tpu.query.engine import QueryEngine

    n_series = 32768 if full else 16384
    n_samp = 48          # 30s scrape over a 23-minute retention window
    siv = 30_000
    n_hist = 8192 if full else 4096
    nh_samp = 32         # 10s scrape, 32-bucket latency histograms
    nb = 32
    les = np.concatenate([2.0 ** np.arange(nb - 1), [np.inf]])

    def scalar_store():
        ms = TimeSeriesMemStore()
        cfg = StoreConfig(max_series_per_shard=n_series,
                          samples_per_series=n_samp,
                          flush_batch_size=10**9, dtype="float32")
        ms.setup("fr", PROM_COUNTER, 0, cfg)
        rng = np.random.default_rng(3)
        for s0 in range(0, n_series, 512):
            b = RecordBuilder(PROM_COUNTER)
            vals = np.cumsum(rng.exponential(5.0, (512, n_samp)), axis=1)
            for t in range(n_samp):
                for s in range(s0, s0 + 512):
                    b.add({"_metric_": "rt", "job": f"J{s % 8}",
                           "inst": f"i{s}"}, BASE + t * siv,
                          float(vals[s - s0, t]))
            ms.ingest("fr", 0, b.build())
        ms.flush_all()
        return ms

    def hist_store():
        ms = TimeSeriesMemStore()
        sh = ms.setup("frh", PROM_HISTOGRAM, 0,
                      StoreConfig(max_series_per_shard=n_hist,
                                  samples_per_series=nh_samp,
                                  flush_batch_size=10**9, dtype="float32",
                                  compressed_residency="all"))
        rng = np.random.default_rng(5)
        for s0 in range(0, n_hist, 256):
            b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
            c = np.cumsum(np.cumsum(
                rng.poisson(0.4, (256, nh_samp, nb)), axis=1),
                axis=2).astype(np.float64)
            for t in range(nh_samp):
                for s in range(256):
                    b.add({"_metric_": "h", "host": f"x{s0 + s}"},
                          BASE + t * IV, c[s, t])
            ms.ingest("frh", 0, b.build())
        sh.flush()
        assert sh.store.is_narrow_resident
        return ms

    # dashboard step grids: T steps >> C stored cells (step finer than the
    # scrape interval — Grafana auto-intervals on a zoomed panel)
    sc_range = (BASE + 240_000, BASE + (n_samp - 2) * siv, 2_500)
    h_range = (BASE + 120_000, BASE + (nh_samp - 2) * IV, 2_500)
    old_mode = fusedresident.mode()
    sstore = scalar_store()          # shared: both scalar shapes, one build
    shapes = [
        ("rate_sum", sstore, "fr", "sum(rate(rt[2m]))", sc_range),
        ("window_reduce", sstore, "fr", "sum(avg_over_time(rt[2m]))",
         sc_range),
        ("hist_quantile", hist_store(), "frh",
         "histogram_quantile(0.9, sum(rate(h[1m])))", h_range),
    ]
    try:
        for shape, ms, ds, q, (start, end, step) in shapes:
            eng = QueryEngine(ms, ds)
            res = {}
            for mode in ("off", "xla", "pallas"):
                fusedresident.set_mode(mode)
                r0 = eng.query_range(q, start, end, step)   # warm compile
                dt, iters = timed(
                    lambda: eng.query_range(q, start, end, step))
                ms_q = dt / iters * 1000
                res[mode] = (ms_q, np.asarray(r0.matrix.values))
                emit("fused_resident", f"{shape}_{mode}_ms", ms_q, "ms")
            # pallas vs xla: one tiling plan, one tile math — bit parity
            # by construction, asserted
            vparity = np.array_equal(res["xla"][1], res["pallas"][1],
                                     equal_nan=True)
            emit("fused_resident", f"{shape}_variant_bit_parity",
                 float(vparity), "bool")
            assert vparity, f"{shape}: pallas and xla variants must be " \
                            "bit-identical"
            # vs the composed oracle: exact at single-tile, f32 fold-order
            # delta at this scale (see docstring)
            with np.errstate(all="ignore"):
                o = res["off"][1]
                maxrel = float(max(
                    np.nanmax(np.abs(res[m][1] - o)
                              / np.maximum(np.abs(o), 1e-12), initial=0.0)
                    for m in ("xla", "pallas")))
            emit("fused_resident", f"{shape}_oracle_exact",
                 float(all(np.array_equal(res[m][1], o, equal_nan=True)
                           for m in ("xla", "pallas"))), "bool")
            emit("fused_resident", f"{shape}_oracle_maxrel_ppm",
                 maxrel * 1e6, "ppm")
            assert maxrel <= 2e-5, (shape, maxrel)
            emit("fused_resident", f"{shape}_speedup_xla_x",
                 res["off"][0] / res["xla"][0], "x")
            emit("fused_resident", f"{shape}_speedup_pallas_x",
                 res["off"][0] / res["pallas"][0], "x")
    finally:
        fusedresident.set_mode(old_mode)

    # -- flush-path donation: the donated scatter updates the store arrays
    # in place; the undonated twin allocates (and writes) a full copy of
    # the [S, C] ts+val blocks per staged-row commit
    from filodb_tpu.core.chunkstore import _scatter_append

    @functools.partial(jax.jit)   # undonated twin of the SAME body
    def _scatter_copy(ts, val, n, rows, cols, new_ts, new_val, counts_add):
        ts = ts.at[rows, cols].set(new_ts, mode="drop")
        val = val.at[rows, cols].set(new_val, mode="drop")
        return ts, val, n + counts_add

    S, C = (65536, 512) if full else (32768, 512)
    m = 4096
    ts = jnp.full((S, C), 1 << 62, jnp.int64)
    val = jnp.zeros((S, C), jnp.float32)
    n = jnp.zeros(S, jnp.int32)
    rows = jnp.asarray(np.arange(m, dtype=np.int32) % S)
    cols = jnp.zeros(m, jnp.int32)
    new_ts = jnp.asarray(np.full(m, BASE, np.int64))
    new_val = jnp.ones(m, jnp.float32)
    counts = jnp.zeros(S, jnp.int32)

    def donated():
        nonlocal ts, val, n
        ts, val, n = _scatter_append(ts, val, n, rows, cols, new_ts,
                                     new_val, counts)
        n.block_until_ready()

    def copied():
        out = _scatter_copy(ts, val, n, rows, cols, new_ts, new_val, counts)
        out[2].block_until_ready()

    dt_c, it_c = timed(copied, min_s=0.5)
    dt_d, it_d = timed(donated, min_s=0.5)
    ms_d, ms_c = dt_d / it_d * 1000, dt_c / it_c * 1000
    bytes_saved = S * C * (8 + 4)      # the ts+val copy that no longer exists
    emit("fused_resident", "flush_scatter_donated_ms", ms_d, "ms")
    emit("fused_resident", "flush_scatter_copy_ms", ms_c, "ms")
    emit("fused_resident", "flush_scatter_speedup_x", ms_c / ms_d, "x")
    emit("fused_resident", "flush_alloc_saved_mb", bytes_saved / 2**20, "MB")


def bench_rules(full: bool) -> None:
    """ISSUE 11: streaming recording rules & alerting. Four phases:
    (a) isolated rule throughput — grid ticks of a 4-group / 16-rule set
    evaluated through the full engine, derived series published back into
    the store; (b) the same rule load sustained WHILE a dashboard pool
    hammers query_range (both rates + dashboard p50 under load reported);
    (c) derived-series bit-parity vs one-shot oracle evaluation at every
    tick; (d) exactly-once soak — derived ticks published through a REAL
    two-broker replica set with a FaultPlan leader kill mid-stream, then
    crash-replayed; the survivor's pub-id journal must show zero lost and
    zero duplicated frames."""
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.parallel.shardmapper import ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.rules import (DerivedSeriesPublisher, RULE_LABEL,
                                  RulesManager, derive_pub_id, load_groups)

    n_series = 2048 if full else 512
    n_samples = 120
    rng = np.random.default_rng(29)
    ms = TimeSeriesMemStore()
    ms.setup("rb", GAUGE, 0, StoreConfig(
        max_series_per_shard=n_series + 256, samples_per_series=1024,
        flush_batch_size=10**9, dtype="float64"))
    ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV
    b = RecordBuilder(GAUGE)
    for s in range(n_series):
        b.add_batch({"_metric_": "m", "host": f"h{s}", "dc": f"dc{s % 4}",
                     "job": f"J{s % 8}"}, ts_arr,
                    100.0 + np.cumsum(rng.exponential(2.0, n_samples)))
    ms.ingest("rb", 0, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "rb")

    def pub(shard, container, pub_id):
        ms.ingest("rb", shard, container)

    publisher = DerivedSeriesPublisher(GAUGE, ShardMapper(1), pub,
                                       dataset="rb")
    fns = ["sum", "avg", "max", "min"]
    spec = [{"name": f"g{gi}", "interval": "30s", "rules":
             [{"record": f"g{gi}:m:{fn}",
               "expr": f"{fn} by (dc) (rate(m[1m]))"} for fn in fns]}
            for gi in range(4)]
    groups = load_groups(spec)
    mgr = RulesManager(groups, eng, publisher=publisher, sink=None,
                       dataset="rb")
    n_rules = sum(len(g.rules) for g in groups)
    tick0 = BASE + 600_000

    # -- (a) isolated throughput -------------------------------------------
    def run_tick(k: int) -> None:
        # 1s tick spacing keeps every eval inside the fixture's 20-minute
        # data range (pub-id determinism is spacing-agnostic); production
        # intervals are grid-aligned the same way at 15-60s
        for g in groups:
            mgr.scheduler.run_group_once(g, tick0 + k * 1_000,
                                         advance_watermark=False)

    run_tick(0)                          # warmup (compiles the rule shapes)
    t0 = time.perf_counter()
    ticks = 0
    while time.perf_counter() - t0 < 0.4 and ticks < 150:
        ticks += 1
        run_tick(ticks)
    dt = time.perf_counter() - t0
    emit("rules", "rules_per_sec_isolated", ticks * n_rules / dt, "rules/s")

    # -- (b) rules sustained under dashboard traffic -----------------------
    start, end, step = BASE + 600_000, BASE + (n_samples - 1) * IV, 30_000
    dash_q = "sum by (job) (rate(m[1m]))"
    eng.query_range(dash_q, start, end, step)          # warm the shape
    stop = threading.Event()
    lat: list[float] = []

    def dashboard():
        while not stop.is_set():
            q0 = time.perf_counter()
            eng.query_range(dash_q, start, end, step)
            lat.append((time.perf_counter() - q0) * 1000)

    pool = ThreadPoolExecutor(max_workers=4)
    for _ in range(4):
        pool.submit(dashboard)
    t0 = time.perf_counter()
    cticks = 0
    while time.perf_counter() - t0 < 0.6 and cticks < 150:
        cticks += 1
        run_tick(200 + cticks)
    cdt = time.perf_counter() - t0
    stop.set()
    pool.shutdown(wait=True)
    emit("rules", "rules_per_sec_concurrent", cticks * n_rules / cdt,
         "rules/s")
    emit("rules", "dashboard_qps_during_rules", len(lat) / cdt, "q/s")
    if lat:
        emit("rules", "dashboard_p50_ms_during_rules",
             float(np.percentile(lat, 50)), "ms")

    # -- (c) derived bit-parity vs one-shot oracle -------------------------
    # the oracle runs IMMEDIATELY BEFORE each tick, against the exact store
    # state the rule itself evaluates (publishing derived rows grows the
    # store and can shift padded-reduce accumulation shapes by 1 ulp — the
    # honest comparison holds the state fixed, like a crash-replay would)
    ms.flush_all()
    mismatches = checked = 0
    for k in range(3):
        ets = tick0 + (360 + k) * 1_000      # fresh ticks, in-range
        for rule in groups[0].rules:
            oracle = eng.query_instant(rule.expr, ets)
            want = {dict(kk.labels).get("dc"): float(v[-1])
                    for kk, _t, v in oracle.matrix.iter_series()}
            mgr.evaluator.evaluate_rule(rule, ets)
            ms.flush_all()
            got_res = eng.query_instant(
                f'{rule.name}{{{RULE_LABEL}="{rule.uid}"}}', ets)
            got_n = 0
            for kk, _t, v in got_res.matrix.iter_series():
                got_n += 1
                checked += 1
                if want.get(dict(kk.labels).get("dc")) != float(v[-1]):
                    mismatches += 1
            if got_n != len(want):
                mismatches += abs(got_n - len(want))
    emit("rules", "derived_parity_cells_checked", checked, "cells")
    emit("rules", "derived_parity_mismatches", mismatches, "cells")

    # -- (d) exactly-once under a broker leader kill -----------------------
    import socket

    from filodb_tpu.ingest.broker import BrokerBus, BrokerServer
    from filodb_tpu.ingest.faults import FaultPlan, FaultRule

    def reserve_port() -> int:
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    n_ticks = 64 if full else 24
    with tempfile.TemporaryDirectory() as tmp:
        pa, pb = reserve_port(), reserve_port()
        peers = [f"127.0.0.1:{pa}", f"127.0.0.1:{pb}"]
        plan = FaultPlan([FaultRule("append", "kill_server", partition=0,
                                    at_offset=n_ticks // 2)])
        a = BrokerServer(f"{tmp}/a", 1, port=pa, peers=peers, node_index=0,
                         replication=2, fault_plan=plan).start()
        srv_b = BrokerServer(f"{tmp}/b", 1, port=pb, peers=peers,
                             node_index=1, replication=2).start()
        bus = BrokerBus(peers, 0, retry_backoff_ms=0, seed=11)
        bus._sleep = lambda _s: None
        cont_b = RecordBuilder(GAUGE)
        cont_b.add({"_metric_": "r", RULE_LABEL: "g/r", "dc": "dc0"},
                   BASE, 1.0)
        frame = cont_b.build()
        expected = set()
        t0 = time.perf_counter()
        for k in range(n_ticks):
            pid = derive_pub_id("g/r", tick0 + k * 30_000, 0)
            expected.add(pid)
            bus.publish_with_id(frame, pid)
        # crash recovery: re-drive EVERY tick under the same ids
        for k in range(n_ticks):
            bus.publish_with_id(frame,
                                derive_pub_id("g/r", tick0 + k * 30_000, 0))
        soak_s = time.perf_counter() - t0
        logged = [pid for _off, pid in srv_b._journals[0].items()]
        bus.close()
        try:
            a.stop()
        except Exception:
            pass
        srv_b.stop()
    emit("rules", "soak_frames_published", 2 * n_ticks, "frames")
    emit("rules", "soak_leader_kills", len(plan.fired), "kills")
    emit("rules", "soak_lost", len(expected - set(logged)), "frames")
    emit("rules", "soak_duplicated", len(logged) - len(set(logged)),
         "frames")
    emit("rules", "soak_wall_s", soak_s, "s")


def bench_elastic(full: bool) -> None:
    """Elastic cluster (ISSUE 12 acceptance): (a) kill-a-node soak —
    ingest and queries continue with a bounded gap while the survivor
    warms the dead node's shard from the durable ring at bit parity with
    the pre-kill oracle; (b) live shard rebalance under publish load at
    bit parity with the arithmetic oracle; (c) split-brain zero-duplicate
    audit — an epoch-fenced leader killed mid-window, the failed-over
    client claims a new epoch, and the acked-id ledger reconciles against
    the survivor's journal with zero lost / zero duplicated."""
    import contextlib
    import tempfile
    import threading
    import urllib.request

    from filodb_tpu.config import Config
    from filodb_tpu.core.diststore import StoreServer
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.ingest.broker import BrokerBus, BrokerServer
    from filodb_tpu.ingest.faults import FaultPlan, FaultRule
    from filodb_tpu.standalone import FiloServer

    # ---- (a)+(b): two standalone nodes over a shared ring + broker -----
    tmp = tempfile.mkdtemp(prefix="filodb-elastic-")
    store = StoreServer(tmp + "/ring").start()
    broker = BrokerServer(tmp + "/broker", 2).start()
    reg = tmp + "/members"

    def node(name):
        return FiloServer(Config({
            "num_shards": 2, "bus_addr": f"127.0.0.1:{broker.port}",
            "http": {"port": 0},
            "store_nodes": [f"127.0.0.1:{store.port}"],
            "store_replication": 1,
            "cluster": {"registrar": reg, "self_addr": name,
                        # stale_after must clear scheduling hiccups under
                        # load: a survivor that misses its OWN beat past it
                        # self-quarantines (the double-ownership guard)
                        "heartbeat_interval": "200ms", "stale_after": "5s",
                        "min_members": 2, "join_timeout": "20s",
                        "shard_fencing": True},
            "store": {"max_series_per_shard": 64, "samples_per_series": 512,
                      "flush_batch_size": 10**9},
        }))

    servers: dict = {}
    threads = {n: threading.Thread(
        target=lambda n=n: servers.update({n: node(n).start()}))
        for n in ("elastic-a:1", "elastic-b:1")}
    for t in threads.values():
        t.start()
    for t in threads.values():
        t.join(timeout=40)
    a, b = servers["elastic-a:1"], servers["elastic-b:1"]
    n_rows = 4000 if full else 800
    stop_pub = threading.Event()
    published = {"n": 0}
    query_errors = {"n": 0, "ok": 0}
    b_shard = a.manager.shards_of_node("prometheus", "elastic-b:1")[0]
    try:
        prod = BrokerBus(f"127.0.0.1:{broker.port}", b_shard,
                         publish_window=8)

        def load():
            i = 0
            while not stop_pub.is_set() and i < n_rows:
                bld = RecordBuilder(GAUGE)
                bld.add({"_metric_": "m", "host": f"h{i % 4}"},
                        BASE + i * 1000, float(i))
                prod.publish(bld.build())
                published["n"] += 1
                i += 1
                time.sleep(0.002)

        loader = threading.Thread(target=load)
        loader.start()
        deadline = time.time() + 60
        while published["n"] < 50 and loader.is_alive() \
                and time.time() < deadline:
            time.sleep(0.05)
        if published["n"] < 50:
            raise RuntimeError("elastic: publish load never ramped")
        # pre-kill oracle on the owner (node b)
        eng_b = b.engines["prometheus"]
        deadline = time.time() + 20
        oracle_n = 0
        while time.time() < deadline:
            r = eng_b.query_instant("count(m)", BASE + n_rows * 1000)
            if r.matrix.num_series:
                oracle_n = float(np.asarray(r.matrix.values)[0, -1])
                if oracle_n == 4.0:
                    break
            time.sleep(0.1)
        # KILL node b; survivor must take over its shard and keep serving
        t_kill = time.perf_counter()
        b.shutdown()
        eng_a = a.engines["prometheus"]

        def probe_queries():
            while not stop_pub.is_set():
                try:
                    eng_a.query_instant("count(m)", BASE + n_rows * 1000)
                    query_errors["ok"] += 1
                except Exception:  # noqa: BLE001 — continuity accounting
                    query_errors["n"] += 1
                time.sleep(0.05)

        prober = threading.Thread(target=probe_queries)
        prober.start()
        deadline = time.time() + 30
        while time.time() < deadline:
            if a.manager.node_of("prometheus", b_shard) == "elastic-a:1" \
                    and b_shard in a._running:
                break
            time.sleep(0.1)
        takeover_s = time.perf_counter() - t_kill
        loader.join(timeout=60)
        stop_pub.set()
        prober.join(timeout=10)
        prod.close()
        total = published["n"]
        # continuity + parity: every published row served by the survivor
        want = float(sum(range(total)))
        got = -1.0
        deadline = time.time() + 30
        while time.time() < deadline:
            r = eng_a.query_instant("sum(sum_over_time(m[2h]))",
                                    BASE + n_rows * 1000)
            if r.matrix.num_series:
                got = float(np.asarray(r.matrix.values)[0, -1])
                if got == want:
                    break
            time.sleep(0.2)
        emit("elastic", "kill_node_takeover_s", takeover_s, "s")
        emit("elastic", "kill_node_rows_published", total, "rows")
        emit("elastic", "kill_node_rows_lost",
             0 if got == want else abs(want - got), "rows")
        emit("elastic", "kill_node_query_errors_during_takeover",
             query_errors["n"], "queries")
        emit("elastic", "kill_node_queries_served", query_errors["ok"],
             "queries")
        emit("elastic", "kill_node_warm_parity", float(got == want), "bool")

        # ---- (b) live rebalance back to a fresh node under load --------
        c = node("elastic-c:1")         # joins the established cluster
        # (min_members=2 already satisfied; it adopts incumbent claims)
        c.start()
        servers["elastic-c:1"] = c
        stop_pub.clear()
        published2 = {"n": 0}
        prod2 = BrokerBus(f"127.0.0.1:{broker.port}", b_shard,
                          publish_window=8)

        def load2():
            i = 0
            while not stop_pub.is_set() and i < (n_rows // 2):
                bld = RecordBuilder(GAUGE)
                bld.add({"_metric_": "reb", "host": f"h{i % 4}"},
                        BASE + i * 1000, float(i))
                prod2.publish(bld.build())
                published2["n"] += 1
                i += 1
                time.sleep(0.002)

        loader2 = threading.Thread(target=load2)
        loader2.start()
        deadline = time.time() + 60
        while published2["n"] < 25 and loader2.is_alive() \
                and time.time() < deadline:
            time.sleep(0.05)
        if published2["n"] < 25:
            raise RuntimeError("elastic: rebalance load never ramped")
        t_move = time.perf_counter()
        req = urllib.request.Request(
            f"http://127.0.0.1:{a.http.port}/api/v1/cluster/rebalance"
            f"?dataset=prometheus&shard={b_shard}&to=elastic-c:1",
            method="POST", data=b"")
        with urllib.request.urlopen(req, timeout=90.0) as r:
            r.read()
        move_s = time.perf_counter() - t_move
        loader2.join(timeout=60)
        stop_pub.set()
        prod2.close()
        total2 = published2["n"]
        want2 = float(sum(range(total2)))
        got2 = -1.0
        eng_c = c.engines["prometheus"]
        deadline = time.time() + 30
        while time.time() < deadline:
            r = eng_c.query_instant("sum(sum_over_time(reb[2h]))",
                                    BASE + n_rows * 1000)
            if r.matrix.num_series:
                got2 = float(np.asarray(r.matrix.values)[0, -1])
                if got2 == want2:
                    break
            time.sleep(0.2)
        emit("elastic", "rebalance_cutover_s", move_s, "s")
        emit("elastic", "rebalance_rows_under_load", total2, "rows")
        emit("elastic", "rebalance_parity", float(got2 == want2), "bool")
    finally:
        stop_pub.set()
        for srv in servers.values():
            with contextlib.suppress(Exception):
                srv.shutdown()
        broker.stop()
        store.stop()

    # ---- (c) split-brain zero-duplicate audit (epoch-fenced brokers) ---
    import socket as _socket

    def _port():
        with _socket.socket() as s:
            s.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    n_frames = 12000 if full else 3000
    kill_at = n_frames // 3
    tmp2 = tempfile.mkdtemp(prefix="filodb-splitbrain-")
    pa, pb = _port(), _port()
    peers = [f"127.0.0.1:{pa}", f"127.0.0.1:{pb}"]
    plan = FaultPlan([FaultRule("append", "kill_server", partition=0,
                                at_offset=kill_at)])
    ba = BrokerServer(tmp2 + "/a", 1, port=pa, peers=peers, node_index=0,
                      replication=2, fault_plan=plan,
                      epoch_fencing=True).start()
    bb = BrokerServer(tmp2 + "/b", 1, port=pb, peers=peers, node_index=1,
                      replication=2, epoch_fencing=True).start()
    bus = BrokerBus(peers, 0, publish_window=32, retry_backoff_ms=1,
                    seed=12, track_acks=True, epoch_fencing=True)
    t0 = time.perf_counter()
    bld = RecordBuilder(GAUGE)
    bld.add({"_metric_": "sb", "host": "h"}, BASE, 1.0)
    frame = bld.build()
    for _ in range(n_frames):
        bus.publish_async(frame)
    bus.flush_publishes()
    soak_s = time.perf_counter() - t0
    logged = [pid for _off, pid in bb._journals[0].items() if pid]
    acked = set(bus.acked_ids)
    end = bb._parts[0].end_offset
    epoch, owner = bb.epochs.get(0)
    bus.close()
    with contextlib.suppress(Exception):
        ba.stop()
    bb.stop()
    emit("elastic", "splitbrain_frames", n_frames, "frames")
    emit("elastic", "splitbrain_leader_kills", len(plan.fired), "kills")
    emit("elastic", "splitbrain_survivor_epoch", epoch, "epoch")
    emit("elastic", "splitbrain_lost", len(acked - set(logged)), "frames")
    emit("elastic", "splitbrain_duplicated",
         len(logged) - len(set(logged)), "frames")
    emit("elastic", "splitbrain_log_dense", float(end == len(set(logged))),
         "bool")
    emit("elastic", "splitbrain_rate", n_frames / soak_s, "frames/s")


def bench_dashboard_soak(full: bool) -> None:
    """ISSUE 14: incremental serving at a realistic 15s refresh mix. A
    4h/2m-step dashboard re-asks its sliding window every 15 s while the
    scrape stream lands one new sample per series between ANY two
    refreshes — so some shard epoch moves every refresh and PR 8's
    all-or-nothing result cache never hits (emitted as
    baseline_result_cache_hits). With the fragment cache, 5 of 6
    refreshes are pure per-step cache hits (the appended samples are
    provably newer than every cached step — the epoch log proves it) and
    only the step-completing refresh computes ONE new step. Measured: effective qps of the
    delta path vs the PR 8 serving stack re-executing the full range, at
    bit parity of the rendered series on every refresh — on the FUSED
    serving tier and, since PR 16, the composed two-step path too: its
    segment reduce is segment_sum-stable and the cross-shard fold runs
    on host in f64 shard order, so the [G,R]x[R,T] reduce no longer
    shifts in the last ulp across T pad buckets (the caveat PR 9's
    suite documented; closed by the bit-stability sweeps in
    tests/test_distributed.py). Acceptance bar: >= 10x effective qps
    (ISSUE 14)."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.ops import fusedresident
    from filodb_tpu.query.engine import QueryConfig, QueryEngine

    n_series = 4096
    iv = 15_000                              # scrape interval == refresh
    step = 120_000                           # Grafana-style 4h/120-point
    steps_per_panel = 120
    # 24 refreshes = 3 step completions; more would slide the active-
    # column window across a 128-cell block boundary mid-run and charge a
    # one-off (c0,Ck) variant retrace (~every 32 min of wall time; covered
    # by query.warmup_shapes in production) to one unlucky refresh
    refreshes = 24
    per_step = step // iv
    rng = np.random.default_rng(14)
    cfg = StoreConfig(max_series_per_shard=n_series, samples_per_series=1024,
                      flush_batch_size=10**9, dtype="float32")
    ms = TimeSeriesMemStore()
    ms.setup("soak", PROM_COUNTER, 0, cfg)
    state = np.zeros(n_series)
    t_cells = steps_per_panel * per_step + 24

    def ingest_cells(c0, n_cells):
        nonlocal state
        for s in range(n_series):
            b = RecordBuilder(PROM_COUNTER)
            inc = np.cumsum(rng.exponential(5.0, n_cells))
            for i in range(n_cells):
                b.add({"_metric_": "request_total", "job": f"J{s % 4}",
                       "instance": f"i{s}"},
                      BASE + (c0 + i) * iv, float(state[s] + inc[i]))
            state[s] += inc[-1]
            ms.ingest("soak", 0, b.build())
        ms.flush_all()

    ingest_cells(0, t_cells)
    # the xla fused variant: the serving mode a CPU deployment would run
    # (pallas-interpret emulation overhead would tax BOTH paths; on TPU
    # the compiled Mosaic kernels serve) — restored after the suite
    mode0 = fusedresident.mode()
    fusedresident.set_mode("xla")
    panels = ['sum(rate(request_total[2m]))',
              'sum by (job) (rate(request_total[2m]))']
    delta = QueryEngine(ms, "soak",
                        config=QueryConfig(fragment_cache_size=64))
    # the baseline is the PR 8 serving stack: full re-execution behind the
    # watermark-equality result cache (which this mix voids every refresh)
    base = QueryEngine(ms, "soak", config=QueryConfig(result_cache_size=64))

    def window_of(lead_cell: int):
        end = (BASE + lead_cell * iv) // step * step
        return end - (steps_per_panel - 1) * step, end

    # prime: compile the full shapes, seed the fragments, and compile the
    # extension shapes — the measured mix is the warmed steady state PR 8's
    # startup warmup already establishes for the full path
    cursor = t_cells
    s0, e0 = window_of(cursor - 1)
    for q in panels:
        base.query_range(q, s0, e0, step)
        delta.query_range(q, s0, e0, step)
    ingest_cells(cursor, per_step)
    cursor += per_step
    s0, e0 = window_of(cursor - 1)
    for q in panels:
        delta.query_range(q, s0, e0, step)

    # the refresh mix: ONE scrape lands before every refresh (the ordered
    # stream means data for a completed step has fully arrived — later
    # cells carry timestamps past it), a new step completes every 8th
    # refresh. Both engines serve EVERY refresh back-to-back against the
    # same store state, with the ingest between refreshes — so the
    # baseline's result cache faces the real cadence (an epoch bump
    # before every refresh; the emitted hit count proves it never hits)
    # and every refresh must render bit-identically across the engines.
    t_delta = t_base = 0.0
    delta_out, base_out = [], []
    for _ in range(refreshes):
        ingest_cells(cursor, 1)
        cursor += 1
        start, end = window_of(cursor - 1)
        for q in panels:
            for eng, out in ((delta, delta_out), (base, base_out)):
                t0 = time.perf_counter()
                r = eng.query_range(q, start, end, step)
                dt = time.perf_counter() - t0
                if eng is delta:
                    t_delta += dt
                else:
                    t_base += dt
                m = r.matrix.to_host()
                # f64 cast before compare: the delta path serves stitched
                # f64 columns, the full path native f32 — the cast is exact
                out.append(sorted(
                    (k_.labels, ts.tobytes(),
                     np.asarray(v, np.float64).tobytes())
                    for k_, ts, v in m.iter_series()))
    fusedresident.set_mode(mode0)
    parity = float(delta_out == base_out)
    n_q = refreshes * len(panels)
    st = delta.fragment_cache.stats()
    emit("dashboard_soak", "panels", len(panels), "count")
    emit("dashboard_soak", "refreshes", refreshes, "count")
    emit("dashboard_soak", "steps_per_panel", steps_per_panel, "steps")
    emit("dashboard_soak", "series", n_series, "count")
    emit("dashboard_soak", "effective_qps_delta", n_q / t_delta, "queries/s")
    emit("dashboard_soak", "effective_qps_full", n_q / t_base, "queries/s")
    emit("dashboard_soak", "delta_speedup", t_base / t_delta, "x")
    emit("dashboard_soak", "bit_parity", parity, "bool")
    emit("dashboard_soak", "baseline_result_cache_hits",
         base.result_cache.stats()["hits"], "count")
    emit("dashboard_soak", "fragment_extensions", st["extensions"], "count")
    emit("dashboard_soak", "fragment_hits", st["hits"], "count")
    emit("dashboard_soak", "fragment_bytes", st["bytes"], "bytes")


def bench_mesh_query(full: bool) -> None:
    """ISSUE 16: per-query dispatch floor of the one-program mesh path vs
    the host shard loop, at the hicard fixture sharded 8 ways (full:
    8 shards x 2048 series x 48 samples f32 counter = 16384x48). Two
    engines over bit-identical ingests — one mesh-configured (shards
    device-placed on the mesh), one plain (the scatter-gather host loop
    dispatches 8 per-shard programs and merges partials on host) — serve
    the same sum(rate) dashboard query. Emitted: p50 ms per query for the
    host loop, the shard_map mesh program, and the forced-pjit global-view
    program; the pjit/host ratio (acceptance bar: <= 0.7); bit_parity
    (EXACT equality of all three rendered matrices — the host-order f64
    fold contract, not allclose); and warm_compile_count — the traces a
    first mesh query costs AFTER ``plancache.warmup`` with a ``mesh: true``
    spec of this shape, proving warmup covers the mesh variants (bar: 0).
    Skips (one row) on a single-device process, where make_mesh has no
    second device to program."""
    import jax

    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.parallel import distributed
    from filodb_tpu.parallel.distributed import make_mesh
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.plancache import plan_cache, warmup

    if len(jax.devices()) < 2:
        emit("mesh_query", "skipped_single_device", 1.0, "bool")
        return
    n_shards = 8
    per_shard = 2048 if full else 256
    n_samples = 48
    rng = np.random.default_rng(16)
    cfg = StoreConfig(max_series_per_shard=per_shard, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    mesh = make_mesh()
    devs = mesh.devices.ravel()
    mesh_ms, host_ms = TimeSeriesMemStore(), TimeSeriesMemStore()
    for s in range(n_shards):
        mesh_ms.setup("meshq", PROM_COUNTER, s, cfg,
                      device=devs[s % len(devs)])
        host_ms.setup("meshq", PROM_COUNTER, s, cfg)
    ts_arr = BASE + np.arange(n_samples, dtype=np.int64) * IV
    for s in range(n_shards * per_shard):
        vals = np.cumsum(rng.exponential(5.0, n_samples))
        for ms in (mesh_ms, host_ms):
            b = RecordBuilder(PROM_COUNTER)
            b.add_batch({"_metric_": "request_total", "instance": f"i{s}"},
                        ts_arr, vals)
            ms.ingest("meshq", s % n_shards, b.build())
    mesh_ms.flush_all()
    host_ms.flush_all()
    mesh_eng = QueryEngine(mesh_ms, "meshq", mesh=mesh)
    host_eng = QueryEngine(host_ms, "meshq")
    query = 'sum(rate(request_total[1m]))'
    start, end, step = BASE + 120_000, BASE + 460_000, 20_000
    steps = (end - start) // step + 1

    # warmup FIRST, then the very first mesh query: its trace delta is the
    # falsifiable form of "query.warmup_shapes covers the mesh variants"
    warmup([{"fn": "rate", "op": "sum", "series": per_shard, "samples": 64,
             "steps": steps, "step_ms": step, "window_ms": 60_000,
             "interval_ms": IV, "groups": 1, "mesh": True}])
    t0 = plan_cache.traces
    r_mesh = mesh_eng.query_range(query, start, end, step)
    emit("mesh_query", "warm_compile_count", plan_cache.traces - t0,
         "programs")
    assert r_mesh.exec_path.startswith("mesh"), r_mesh.exec_path

    def render(r):
        return sorted((k.labels, ts.tobytes(),
                       np.asarray(v, np.float64).tobytes())
                      for k, ts, v in r.matrix.iter_series())

    out = {}

    def run(eng, tag):
        def q():
            r = eng.query_range(query, start, end, step)
            np.asarray(r.matrix.values)   # force the fold/fetch: the mesh
            out[tag] = r                  # result is lazy until rendered
        dt, it = timed(q, max_iters=30)
        return dt / it * 1000

    host_ms_q = run(host_eng, "host")
    results = {"host_loop_p50": host_ms_q}
    try:
        for mode, tag in (("shard_map", "mesh_shard_map_p50"),
                          ("pjit", "mesh_pjit_p50")):
            distributed.set_mesh_mode(mode)
            results[tag] = run(mesh_eng, mode)
    finally:
        distributed.set_mesh_mode("auto")

    # the leaf compute EVERY orchestration must execute: the same fused
    # kernel over each shard's resident block, dispatched back-to-back with
    # no per-shard fetch, blocked once. Subtracting it isolates per-query
    # ORCHESTRATION overhead — the dispatch floor the one-program path
    # attacks. (On 1-core CI the serialized kernel compute dominates the
    # total identically in both paths; on a rig it overlaps across chips.)
    from filodb_tpu.ops import fusedgrid, fusedresident
    out_ts_arr = np.arange(start, end + 1, step, dtype=np.int64)
    leaf_shards = [host_ms.shard("meshq", s) for s in range(n_shards)]

    def floor_q():
        pps = []
        for sh in leaf_shards:
            st = sh.store
            pps.append(fusedresident.scalar_aggregate(
                "sum", "rate", st.value_block(), st.n,
                fusedgrid.zero_gids(st.S), 1, out_ts_arr, 60_000, BASE, IV,
                fetch=False))
        jax.block_until_ready([p._outs for p in pps])

    dt, it = timed(floor_q, max_iters=30)
    floor = dt / it * 1000
    emit("mesh_query", "shards", n_shards, "count")
    emit("mesh_query", "series", n_shards * per_shard, "count")
    emit("mesh_query", "samples", n_samples, "count")
    for tag, v in results.items():
        emit("mesh_query", tag, v, "ms")
    emit("mesh_query", "leaf_compute_floor_p50", floor, "ms")
    over = {t: max(v - floor, 0.0) for t, v in results.items()}
    emit("mesh_query", "host_loop_overhead_p50", over["host_loop_p50"], "ms")
    emit("mesh_query", "mesh_pjit_overhead_p50", over["mesh_pjit_p50"], "ms")
    emit("mesh_query", "mesh_vs_host_total_ratio",
         results["mesh_pjit_p50"] / results["host_loop_p50"], "x")
    emit("mesh_query", "mesh_vs_host_ratio",
         over["mesh_pjit_p50"] / max(over["host_loop_p50"], 1e-9), "x")
    emit("mesh_query", "bit_parity",
         float(render(out["host"]) == render(out["pjit"])
               == render(out["shard_map"])), "bool")


SUITES = {
    "mesh_query": bench_mesh_query,
    "dashboard_soak": bench_dashboard_soak,
    "elastic": bench_elastic,
    "rules": bench_rules,
    "fused_resident": bench_fused_resident,
    "ingestion": bench_ingestion,
    "serving": bench_serving,
    "observability": bench_observability,
    "ingest": bench_ingest,
    "ingest_soak": bench_ingest_soak,
    "odp": bench_odp,
    "retention": bench_retention,
    "count_values": bench_count_values,
    "narrow_resident": bench_narrow_resident,
    "scalar_residency": bench_scalar_residency,
    "hist_retention": bench_hist_retention,
    "encoding": bench_encoding,
    "partkey_index": bench_partkey_index,
    "hist_ingest": bench_hist_ingest,
    "hist_query": bench_hist_query,
    "query_hicard": bench_query_hicard,
    "query_ingest": bench_query_ingest,
    "gateway": bench_gateway,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--suite", choices=sorted(SUITES), action="append",
                    help="run only these suites (default: all)")
    ap.add_argument("--full", action="store_true",
                    help="reference-scale sizes (1M index keys, 8000 series, ...)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (for dev boxes without a TPU)")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_enable_x64", True)
    # per-run floors, ONE shared definition with bench.py (BASELINE.md
    # "Floor accounting"): every latency-shaped metric below rides them
    #   sync_rt_floor_ms         = trivial jitted dispatch + HOST FETCH p50
    #                              (the request round-trip every blocking
    #                              query pays at least once)
    #   device_dispatch_floor_ms = empty-kernel dispatch + completion p50,
    #                              NO host fetch (the enqueue cost pipelined
    #                              queries pay per dispatch)
    import jax
    import jax.numpy as jnp
    z = jnp.zeros(8)
    z.block_until_ready()
    np.asarray(z + 1)
    rt, disp = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(z + 1)
        rt.append((time.perf_counter() - t0) * 1000)
        t0 = time.perf_counter()
        (z + 1).block_until_ready()
        disp.append((time.perf_counter() - t0) * 1000)
    emit("session", "rt_floor_ms", sorted(rt)[len(rt) // 2], "ms")
    emit("session", "device_dispatch_floor_ms",
         sorted(disp)[len(disp) // 2], "ms")
    emit("session", "backend", float(jax.default_backend() == "tpu"), "is_tpu")
    import gc
    for name in (args.suite or sorted(SUITES)):
        SUITES[name](args.full)
        gc.collect()     # release the suite's device stores before the next


if __name__ == "__main__":
    main()
