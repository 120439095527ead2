"""North-star benchmark: PromQL ``sum(rate(metric[5m]))`` over 1M series,
executed through the FULL query engine (parse -> planner -> leaf ->
PeriodicSamplesMapper -> AggregateMapReduce -> present).

Mirrors the reference's jmh QueryInMemoryBenchmark workload
(jmh/src/main/scala/filodb.jmh/QueryInMemoryBenchmark.scala: 720 samples/series
@ 10s spacing = 2h of data, query_range step 150s over the window; it too goes
through QueryEngine.materialize, :44-51) scaled to the BASELINE.json north
star: 2^20 in-memory series on one chip.

Status: this file predates the attached chip and is only kept honest here —
it refuses to run off-TPU (it used to time the Pallas interpreter) and fails
when its baseline cannot be built (it used to divide by an estimate). The
benchmark proper — cells, served path, percentiles — is the next PR's.

METHODOLOGY (matches the reference benchmark's own): the headline
number is per-query wall time with NUM_QUERIES=500 queries in flight,
exactly how the jmh benchmark measures — ``Mode.Throughput`` +
``OperationsPerInvocation(500)``, firing 500 concurrent ``asyncAsk``s and
awaiting ``Future.sequence`` (QueryInMemoryBenchmark.scala:136-151). Each
query here runs the full engine path on its own thread and blocks on its own
result fetch, like each jmh future.

Beside it: single blocking query p50 (``single_query_p50_ms``), the host
sync floor (``sync_rt_floor_ms``: a trivial dispatch + host fetch, the round
trip every blocking query pays at least once) and the marginal device time
per query (``device_marginal_ms``, from K pipelined queries). None of them
has been measured on the attached chip.

Setup registers every series through the real ingest path (RecordContainer ->
partition resolution -> part-key index), then installs the bulk sample data
directly into the device store (data-volume shortcut only — 720M samples
through the host staging path is pre-ingest work the reference benchmark also
does outside measurement).

The measured query takes the engine's fused single-pass path
(ops/fusedgrid.py): window rate + cross-series sum partials in one streaming
read of the [S, C] f32 value store.

Baseline: the reference publishes no absolute numbers and this image has no
JVM (BASELINE.md "Methodology"), so the baseline is MEASURED at bench time:
scripts/baseline_proxy.cpp, a tuned C++ implementation of the reference's
ChunkedRateFunction algorithm on this host, deliberately more favorable than
the JVM path (no chunk decompression, O(1) precomputed window edges, no
iterator/boxing overhead). The proxy is compute-bound; this host has
``nproc`` core(s), so its per-query time under concurrency is
proxy_p50 / nproc (reported as such). vs_baseline =
proxy_per_query_ms / measured_per_query_ms at matched 500-query methodology.
The proxy is built into a fixed, git-ignored path in the checkout; if it
cannot be built or run, the benchmark fails — there is no estimate to divide by.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def measure_baseline_proxy():
    """Compile + run the C++ chunked-path proxy; (p50_ms, how). Built anew
    each run (-march=native binds it to this host) at a fixed, git-ignored
    path; a failed build or run fails the benchmark."""
    src = os.path.join(HERE, "scripts", "baseline_proxy.cpp")
    exe = os.path.join(HERE, "scripts", "baseline_proxy.bin")
    subprocess.run(["g++", "-O3", "-march=native", "-funroll-loops",
                    "-o", exe, src], check=True, capture_output=True,
                   timeout=120)
    out = subprocess.run([exe], check=True, capture_output=True,
                         timeout=600).stdout
    return float(json.loads(out)["proxy_p50_ms"]), "measured_cpp_proxy"

NUM_SERIES = 1 << 20       # 1,048,576
NUM_SAMPLES = 720          # 2h @ 10s
CAPACITY = 768             # padded row capacity
INTERVAL_MS = 10_000
WINDOW_MS = 300_000        # [5m]
STEP_MS = 150_000          # 150s, ref benchmark step
REG_BATCH = 1 << 19    # registration container size
DATA_BATCH = 1 << 17   # device data-synthesis chunk (bounds transient HBM)
BASE_TS = 1_700_000_000_000
NUM_QUERIES = 500          # jmh OperationsPerInvocation(500)
POOL_WORKERS = 64          # bounded worker pool draining the 500 queries


def build_engine():
    """Shard with 2^20 registered series + synthesized device store."""
    import jax
    import jax.numpy as jnp

    from filodb_tpu.core.chunkstore import TS_PAD
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.query.engine import QueryEngine

    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=NUM_SERIES,
                      samples_per_series=CAPACITY,
                      flush_batch_size=10**9, dtype="float32")
    shard = ms.setup("prometheus", GAUGE, 0, cfg)

    # register every series through the real ingest path (partition
    # resolution + index); samples stay staged and are discarded — the bulk
    # data lands below, and a flush of the full-size store would transiently
    # double its HBM footprint
    t_reg = time.perf_counter()
    for start in range(0, NUM_SERIES, REG_BATCH):
        b = RecordBuilder(GAUGE)
        # bulk registration API (core/record.py add_series_batch): columnar
        # label values -> vectorized key derivation + the index's columnar
        # bulk add; same real path (RecordContainer -> partition resolution
        # -> part-key index) the per-record loop took
        b.add_series_batch(
            {"_metric_": "m",
             "host": [f"h{i}" for i in range(start, start + REG_BATCH)]},
            BASE_TS, 0.0)
        shard.ingest(b.build())
    with shard.lock:
        shard._stage_pid.clear(); shard._stage_ts.clear()
        shard._stage_val.clear(); shard._staged = 0
    reg_s = time.perf_counter() - t_reg

    # bulk data: synthesized on device (pre-ingest volume shortcut)
    st = shard.store
    st.ts = st.val = st.n = None   # release before allocating replacements

    @jax.jit
    def make_vals(key):
        inc = jax.random.exponential(key, (DATA_BATCH, NUM_SAMPLES), jnp.float32) * 5.0
        v = jnp.cumsum(inc, axis=1)
        return jnp.zeros((DATA_BATCH, CAPACITY), jnp.float32).at[:, :NUM_SAMPLES].set(v)

    keys = jax.random.split(jax.random.PRNGKey(7), NUM_SERIES // DATA_BATCH)
    st.val = jnp.concatenate([make_vals(k) for k in keys])
    ts_row = np.full(CAPACITY, TS_PAD, np.int64)
    ts_row[:NUM_SAMPLES] = BASE_TS + np.arange(NUM_SAMPLES, dtype=np.int64) * INTERVAL_MS

    @jax.jit
    def make_ts():
        return jnp.tile(jnp.asarray(ts_row), (NUM_SERIES, 1))

    st.ts = make_ts()
    st.n = jnp.full(NUM_SERIES, NUM_SAMPLES, jnp.int32)
    st.val.block_until_ready()
    st.n_host = np.full(NUM_SERIES, NUM_SAMPLES, np.int32)
    st.first_ts = np.full(NUM_SERIES, BASE_TS, np.int64)
    st.last_ts = np.full(NUM_SERIES, BASE_TS + (NUM_SAMPLES - 1) * INTERVAL_MS,
                         np.int64)
    st.grid_base = BASE_TS
    st.grid_interval = INTERVAL_MS
    st.grid_ok = True
    return QueryEngine(ms, "prometheus"), shard, reg_s


def stream_probe(val):
    """Roofline: one pure streaming pass over the value store (Pallas)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, C = val.shape
    Sb = 512

    def body(v_ref, out_ref):
        i = pl.program_id(0)
        s = jnp.sum(v_ref[:], axis=0, keepdims=True)[:, :128]

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)
        out_ref[:] += jnp.broadcast_to(s, (8, 128))

    call = pl.pallas_call(
        body, grid=(S // Sb,),
        in_specs=[pl.BlockSpec((Sb, C), lambda i: (i, 0), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))
    with jax.enable_x64(False):
        f = jax.jit(call)
        np.asarray(f(val))
        lat = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(f(val))
            lat.append((time.perf_counter() - t0) * 1000)
    return float(np.percentile(lat, 50))


def sync_floor_ms():
    """``sync_rt_floor_ms`` (shared definition with bench_suite.py, see
    BASELINE.md "Floor accounting"): p50 of a trivial (4KB in/out) jitted
    dispatch + HOST FETCH — the round trip every blocking query pays at
    least once."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def triv(x):
        return x + 1.0

    x = jnp.zeros((8, 128), jnp.float32)
    np.asarray(triv(x))
    lat = []
    for _ in range(7):
        t0 = time.perf_counter()
        np.asarray(triv(x))
        lat.append((time.perf_counter() - t0) * 1000)
    return float(np.percentile(lat, 50))


def device_dispatch_floor_ms():
    """``device_dispatch_floor_ms`` (shared definition with bench_suite.py):
    p50 of an empty-kernel dispatch + completion with NO host fetch — the
    enqueue cost pipelined queries pay per dispatch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def triv(x):
        return x + 1.0

    x = jnp.zeros((8, 128), jnp.float32)
    triv(x).block_until_ready()
    lat = []
    for _ in range(7):
        t0 = time.perf_counter()
        triv(x).block_until_ready()
        lat.append((time.perf_counter() - t0) * 1000)
    return float(np.percentile(lat, 50))


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a CPU run would time XLA's CPU backend and the Pallas interpreter,
        # which nobody deploys
        raise SystemExit(f"bench.py: no TPU (jax.devices()[0].platform = "
                         f"{dev.platform!r}); it measures nothing off the chip")
    from filodb_tpu.utils import compilecache
    compilecache.configure()            # before the first compile
    engine, shard, reg_s = build_engine()
    start = BASE_TS + WINDOW_MS
    end = BASE_TS + NUM_SAMPLES * INTERVAL_MS
    q = "sum(rate(m[5m]))"

    # 8 distinct time ranges cycled across the concurrent load — the jmh
    # benchmark likewise round-robins distinct queries (:119-123); identical
    # repeats would also understate work on any caching/speculative layer
    variants = [(start + k * INTERVAL_MS, end - k * INTERVAL_MS)
                for k in range(8)]

    def run_query(i=0):
        s, e = variants[i % len(variants)]
        r = engine.query_range(q, s, e, STEP_MS)
        # the host fetch forces completion
        (_k, _t, v), = list(r.matrix.iter_series())
        return np.asarray(v)

    expect = [run_query(k) for k in range(len(variants))]  # warmup/compile
    res = expect[0]
    T = len(res)
    assert all(np.isfinite(r).all() for r in expect), "non-finite rate sum"

    # single blocking query p50
    lat = []
    for _ in range(10):
        t0 = time.perf_counter()
        run_query()
        lat.append((time.perf_counter() - t0) * 1000)
    single_p50 = float(np.percentile(lat, 50))

    # HEADLINE: jmh-parity — 500 concurrent queries, per-query wall time
    # (QueryInMemoryBenchmark.scala:136-151: 500 asyncAsk + Future.sequence,
    # Mode.Throughput, OperationsPerInvocation(500))
    pool = ThreadPoolExecutor(max_workers=POOL_WORKERS)
    warm = list(pool.map(run_query, range(POOL_WORKERS)))   # thread warm
    rounds = []
    outs = None
    for _ in range(5):
        t0 = time.perf_counter()
        outs = list(pool.map(run_query, range(NUM_QUERIES)))
        rounds.append((time.perf_counter() - t0) * 1000 / NUM_QUERIES)
    pool.shutdown()
    # both the best round and the p50 of the rounds are reported
    per_query = float(np.min(rounds))
    per_query_p50 = float(np.percentile(rounds, 50))
    # result parity: every concurrent query matches its variant's answer
    for i, o in enumerate(warm + outs):
        assert np.array_equal(o, expect[i % len(variants)], equal_nan=True), \
            "concurrent query results diverge"

    # marginal device time per query: K pipelined dispatches (cycling the
    # variant ranges so no layer can dedupe identical executions), one sync
    from filodb_tpu.ops import fusedgrid
    gids = fusedgrid.zero_gids(NUM_SERIES)
    var_out_ts = [np.arange(s, e + 1, STEP_MS, dtype=np.int64)
                  for s, e in variants]

    def submit(i):
        return fusedgrid.fused_grid_aggregate(
            "sum", "rate", shard.store.val, shard.store.n, gids, 8,
            var_out_ts[i % len(var_out_ts)], WINDOW_MS, BASE_TS, INTERVAL_MS,
            fetch=False)

    def pipelined_marginal(submit_fn, reps: int = 3) -> float:
        """Median of (K=34 minus K=2)/32 pipelined-dispatch differences —
        long pipelines + medians survive host latency spikes, which can
        exceed the whole signal for single (1, 16) pairs."""
        out = []
        for _ in range(reps):
            marg = []
            for K in (2, 34):
                t0 = time.perf_counter()
                ps = [submit_fn(i) for i in range(K)]
                jax.device_get([p._outs for p in ps])
                marg.append((time.perf_counter() - t0) * 1000)
            out.append((marg[1] - marg[0]) / 32.0)
        return float(np.percentile(out, 50))

    for i in range(len(variants)):
        submit(i).resolve()   # warm/compile
    device_marginal = pipelined_marginal(submit)

    # sub-range marginal: a "last 30m" dashboard panel over the 2h retention
    # — the active-column kernel streams/matmuls only the panel's store
    # tiles. Ranges cycle (shifted by one cell) for the same reason the main
    # marginal cycles variants: identical repeats could be deduped
    sub_ts_vars = [np.arange(end - 1_800_000 - k * INTERVAL_MS,
                             end - k * INTERVAL_MS + 1, STEP_MS,
                             dtype=np.int64) for k in range(8)]

    def submit_sub(i):
        return fusedgrid.fused_grid_aggregate(
            "sum", "rate", shard.store.val, shard.store.n, gids, 8,
            sub_ts_vars[i % len(sub_ts_vars)], WINDOW_MS, BASE_TS,
            INTERVAL_MS, fetch=False)

    for i in range(len(sub_ts_vars)):
        submit_sub(i).resolve()
    device_marginal_sub = pipelined_marginal(submit_sub)

    floor_ms = sync_floor_ms()
    roofline_ms = stream_probe(shard.store.val)
    baseline_ms, baseline_how = measure_baseline_proxy()
    ncores = os.cpu_count() or 1
    # the C++ proxy is compute-bound: under the same 500-query methodology it
    # amortizes across host cores, no further
    baseline_per_query = baseline_ms / ncores

    result = {
        "metric": "promql_sum_rate_5m_per_query_ms_1M_series_500concurrent",
        "value": round(per_query, 2),
        "unit": "ms/query",
        "vs_baseline": round(baseline_per_query / per_query, 2),
        "detail": {
            "series": NUM_SERIES,
            "samples_per_series": NUM_SAMPLES,
            "steps": T,
            "methodology": "jmh QueryInMemoryBenchmark parity: 500 concurrent "
                           "queries (64-thread pool), per-query wall time, "
                           "BEST of 5 rounds (p50 also reported); "
                           "every query runs the full engine path and blocks "
                           "on its own result",
            "per_query_ms_p50": round(per_query_p50, 2),
            "queries_per_sec": round(1000.0 / per_query, 1),
            "series_per_sec": round(NUM_SERIES / (per_query / 1000.0)),
            "per_query_ms_rounds": [round(x, 2) for x in rounds],
            "single_query_p50_ms": round(single_p50, 2),
            "sync_rt_floor_ms": round(floor_ms, 2),
            "device_dispatch_floor_ms": round(device_dispatch_floor_ms(), 2),
            "single_query_minus_floor_ms": round(single_p50 - floor_ms, 2),
            "device_marginal_ms_per_query": round(device_marginal, 2),
            "device_marginal_ms_subrange_30m": round(device_marginal_sub, 2),
            "hbm_stream_pass_ms": round(roofline_ms, 2),
            "baseline_p50_ms": round(baseline_ms, 2),
            "baseline_method": baseline_how,
            "baseline_host_cores": ncores,
            "baseline_per_query_ms_at_methodology": round(baseline_per_query, 2),
            "vs_baseline_single_query": round(baseline_ms / single_p50, 2),
            "setup_register_1M_series_s": round(reg_s, 1),
            "device": str(dev),
            "single_latencies_ms": [round(x, 1) for x in lat],
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
