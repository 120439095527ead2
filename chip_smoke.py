#!/usr/bin/env python3
"""chip_smoke.py — the served ingest -> query path, once, on the attached TPU.

One process, one chip (``--chips 4``: one host's four). It starts a
``filodb_tpu.standalone.FiloServer`` with shipped defaults for
``query.fused_kernels`` and ``store.compressed_residency``, writes seeded
counters for every series through the real write path (RecordContainers
published to the shard's bus, consumed, resolved, indexed, staged, flushed),
answers PromQL over HTTP, compares every answer with a plain f64 reference of
the same semantics, and fails unless every aggregate ran through the compiled
fused Pallas route. Then the two other store shapes the query path ships
kernels for (delta8-resident counters, i8-resident native histograms), each in
a server of its own. Sizes are options; their defaults are the real sizes.

Everything worth reading goes to stdout on earlier lines; the LAST line of
stdout is the contract's JSON object and nothing follows it. Any failure in
any phase is a non-zero exit and no such line. Without a TPU nothing runs.

    python chip_smoke.py [--seed N] [--series N] [--capacity N] [--scrapes N]
    python chip_smoke.py --chips 4        # the four-shard mesh path, alone
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import threading
import time
import urllib.parse
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASE_TS = 1_700_000_000_000      # ms; data time, not wall clock
IV = 10_000                      # 10 s scrape interval
WINDOW = 300_000                 # [5m]
GROUPS = 8
BUCKETS = 64                     # BASELINE.json configs[2]: 64-bucket histograms
CHUNK = 1 << 17                  # series per RecordContainer
RTOL, ATOL = 2e-4, 1e-4          # the tier-1 fused-vs-reference bound
# the name ops/fusedgrid.kernel_tag gives a Mosaic-COMPILED kernel; the
# interpreted form reads "pallas-interpret" and fails every route check
EXPECT_TAG = "pallas"
# the mesh serves one pjit program per route (parallel/distributed.py)
MESH_PREFIX = "mesh[pjit]-"

_t0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _t0:7.1f}s] {msg}", flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# device, compile accounting
# --------------------------------------------------------------------------

def find_device(chips: int) -> dict:
    """The device as JAX reports it — a TPU, ``chips`` of them, or nothing
    runs: no CPU run passes for a chip run."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax.devices()[0].platform = "
            f"{devs[0].platform!r}); this script only runs on the chip")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileClock:
    """Seconds XLA spent compiling, and persistent-cache hits, from JAX's own
    monitoring events (a cache hit skips the backend compile)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs

    def _ev(self, name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


def device_memory(label: str) -> None:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    live = sum(a.nbytes for a in jax.live_arrays())
    say(f"memory[{label}]: live_arrays={live / 2**30:.2f} GiB "
        f"bytes_in_use={st.get('bytes_in_use', 'n/a')} "
        f"peak_bytes_in_use={st.get('peak_bytes_in_use', 'n/a')} "
        f"bytes_limit={st.get('bytes_limit', 'n/a')}")


# --------------------------------------------------------------------------
# seeded data + the plain reference (numpy, f64, window by window)
# --------------------------------------------------------------------------

def make_counters(seed: int, S: int, n: int) -> np.ndarray:
    """[S, n] integer-valued counters: a seeded start below 1e5 plus seeded
    per-scrape increments in [0, 100) — exact in f32, and within the delta8
    ladder's i8 range for the compressed-residency phase."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, 100_000, S)
    inc = rng.integers(0, 100, (S, n))
    inc[:, 0] = 0
    return (start[:, None] + np.cumsum(inc, axis=1)).astype(np.float64)


def make_hists(seed: int, S: int, n: int) -> np.ndarray:
    """[S, n, BUCKETS] cumulative bucket counts (cumulative over buckets AND
    time), small Poisson increments: integer, monotone, i8-sized deltas."""
    rng = np.random.default_rng(seed + 1)
    inc = rng.poisson(0.6, (S, n, BUCKETS))
    return np.cumsum(np.cumsum(inc, axis=1), axis=2).astype(np.float64)


def window_cells(t: int, n: int) -> tuple[int, int]:
    """Sample indices k with t - WINDOW <= BASE_TS + k*IV <= t (closed, as
    tests/prom_reference.window_samples), clipped to the n scrapes written."""
    lo = -(-(t - WINDOW - BASE_TS) // IV)
    hi = (t - BASE_TS) // IV
    return max(lo, 0), min(hi, n - 1)


def ref_window(fn: str, vals: np.ndarray, out_ts) -> np.ndarray:
    """fn(m[5m]) for every series at every step: [S, T(, B)] f64. The rate
    algebra is Prometheus' extrapolatedRate spelled out per window
    (tests/prom_reference.extrapolated_rate is the same, one series at a
    time; check_against_repo_reference ties the two on a sample)."""
    n = vals.shape[1]
    out = []
    for t in out_ts:
        lo, hi = window_cells(int(t), n)
        cnt = hi - lo + 1
        if fn in ("sum_over_time", "avg_over_time"):
            if cnt < 1:
                out.append(np.full(vals[:, 0].shape, np.nan))
                continue
            s = vals[:, lo:hi + 1].sum(axis=1)
            out.append(s / cnt if fn == "avg_over_time" else s)
            continue
        if cnt < 2:
            out.append(np.full(vals[:, 0].shape, np.nan))
            continue
        v0, v1 = vals[:, lo], vals[:, hi]           # monotone: no resets
        delta = v1 - v0
        t0, t1 = BASE_TS + lo * IV, BASE_TS + hi * IV
        sampled = (t1 - t0) / 1000.0
        avg = sampled / (cnt - 1)
        dur_start = np.full(delta.shape, (t0 - (t - WINDOW)) / 1000.0)
        dur_end = (t - t1) / 1000.0
        with np.errstate(divide="ignore", invalid="ignore"):
            dur_zero = sampled * (v0 / delta)
        clamp = (delta > 0) & (v0 >= 0) & (dur_zero < dur_start)
        dur_start = np.where(clamp, dur_zero, dur_start)
        thresh = avg * 1.1
        extrap = sampled + np.where(dur_start < thresh, dur_start, avg / 2) \
            + (dur_end if dur_end < thresh else avg / 2)
        out.append(delta * (extrap / sampled) / (WINDOW / 1000.0))
    return np.stack(out, axis=1)


def check_against_repo_reference(vals: np.ndarray, out_ts, seed: int) -> None:
    """The vectorized reference above vs the repo's own golden model
    (tests/prom_reference.py), series by series, on a seeded sample."""
    from tests import prom_reference as pr
    ts = BASE_TS + np.arange(vals.shape[1], dtype=np.int64) * IV
    rows = np.random.default_rng(seed).choice(len(vals), 16, replace=False)
    for fn in ("rate", "sum_over_time", "avg_over_time"):
        mine = ref_window(fn, vals[rows], out_ts)
        for i, r in enumerate(rows):
            gold = pr.eval_range_fn(fn, ts, vals[r], np.asarray(out_ts), WINDOW)
            np.testing.assert_allclose(mine[i], gold, rtol=1e-12,
                                       err_msg=f"{fn} row {r}")


def ref_hist_quantile(q: float, les: np.ndarray, counts: np.ndarray):
    """Prometheus bucketQuantile over cumulative counts [T, B] -> [T]."""
    out = np.full(len(counts), np.nan)
    for i, c in enumerate(counts):
        total = c[-1]
        if not total > 0:
            continue
        rank = q * total
        b = int(np.searchsorted(c, rank, side="left"))
        if b == len(c) - 1:
            out[i] = les[-2]
            continue
        lower = les[b - 1] if b > 0 else 0.0
        prev = c[b - 1] if b > 0 else 0.0
        out[i] = lower + (les[b] - lower) * (rank - prev) / (c[b] - prev)
    return out


# --------------------------------------------------------------------------
# the server, the write path, HTTP
# --------------------------------------------------------------------------

def start_server(tmp: str, name: str, **over):
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    cfg = {"num_shards": 1, "bus_dir": os.path.join(tmp, name, "bus"),
           "http": {"port": 0}}
    for k, v in over.items():
        if isinstance(v, dict):
            cfg.setdefault(k, {}).update(v)
        else:
            cfg[k] = v
    os.makedirs(cfg["bus_dir"], exist_ok=True)
    srv = FiloServer(Config(cfg)).start()
    c = srv.config
    say(f"server[{name}] up on :{srv.http.port}  dataset={c['dataset']} "
        f"schema={c['schema']} shards={c['num_shards']} "
        f"query.fused_kernels={c['query.fused_kernels']} "
        f"store.compressed_residency={c['store.compressed_residency']} "
        f"max_series_per_shard={c['store.max_series_per_shard']} "
        f"samples_per_series={c['store.samples_per_series']}")
    return srv


def stop_server(srv) -> None:
    """Stop every thread the server started and drop its device arrays."""
    import jax
    from filodb_tpu.ops import fusedgrid, fusedresident
    from filodb_tpu.query.plancache import plan_cache
    consumers = list(srv.consumers)
    srv.shutdown()
    for c in consumers:
        c.join(timeout=30)
        check(not c.is_alive(), f"consumer thread {c.name} did not stop")
    srv.memstore._shards.clear()
    srv.engines.clear()
    plan_cache.clear()
    fusedgrid._device_operands.cache_clear()
    fusedgrid.zero_gids.cache_clear()
    fusedresident._hist_device_operands.cache_clear()
    del srv, consumers
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


def series_labels(ids, metric: str) -> dict:
    return {"_metric_": metric,
            "host": [f"h{i}" for i in ids],
            "g": [f"g{i % GROUPS}" for i in ids],
            "rack": [f"r{i // 4}" for i in ids]}


class Writer:
    """A scraper: one RecordContainer per CHUNK of series, built once with
    ``RecordBuilder.add_series_batch`` (its key bytes and hashes are what a
    producer memoizes), re-sent per scrape with that scrape's stamp and
    values, published to the shard's bus like any producer's."""

    def __init__(self, srv, schema, shard: int, ids: np.ndarray, metric: str,
                 bucket_les=None):
        from filodb_tpu.core.record import RecordBuilder
        from filodb_tpu.ingest.bus import FileBus
        self.shard = srv.memstore.shard(srv.config["dataset"], shard)
        self.bus = FileBus(os.path.join(srv.config["bus_dir"],
                                        f"shard{shard}.log"))
        self.ids = ids
        self.rows = 0
        self.seconds = 0.0
        self.templates = []
        b = RecordBuilder(schema, bucket_les=bucket_les)
        zero = 0.0 if bucket_les is None else np.zeros(len(bucket_les))
        for lo in range(0, len(ids), CHUNK):
            sel = ids[lo:lo + CHUNK]
            b.add_series_batch(series_labels(sel, metric), BASE_TS, zero)
            self.templates.append((lo, lo + len(sel), b.build()))

    def scrape(self, k: int, values: np.ndarray) -> None:
        """Publish scrape ``k``: values [len(ids)] or [len(ids), W]."""
        t = time.perf_counter()
        for lo, hi, rc in self.templates:
            self.bus.publish(dataclasses.replace(
                rc, ts=np.full(hi - lo, BASE_TS + k * IV, np.int64),
                values=np.ascontiguousarray(values[lo:hi], np.float64)))
            self.rows += hi - lo
        self.seconds += time.perf_counter() - t

    def drain(self, timeout_s: float = 900.0) -> None:
        """Wait until the consumer has ingested every published row, then
        flush what is staged and wait for the device to retire it."""
        t = time.perf_counter()
        deadline = t + timeout_s
        while self.shard.stats.rows_ingested < self.rows:
            check(time.perf_counter() < deadline,
                  f"consumer stalled at {self.shard.stats.rows_ingested} of "
                  f"{self.rows} rows")
            time.sleep(0.05)
        self.shard.flush()
        import jax
        jax.block_until_ready(self.shard.store.n)
        self.seconds += time.perf_counter() - t

    def close(self) -> None:
        self.bus.close()


def http_get(port: int, path: str, **params) -> dict:
    url = f"http://127.0.0.1:{port}{path}?{urllib.parse.urlencode(params)}"
    t = time.perf_counter()
    with urllib.request.urlopen(url, timeout=600) as r:
        body = json.loads(r.read())
    body["_ms"] = (time.perf_counter() - t) * 1000.0
    check(body.get("status") == "success", f"{url}: {body}")
    return body


def query_range(srv, promql: str, k0: int, k1: int):
    """query_range over scrape stamps k0..k1 (step = one scrape): (out_ts,
    {label-tuple: values[T]}, exec_path, ms)."""
    ds = srv.config["dataset"]
    body = http_get(srv.http.port, f"/promql/{ds}/api/v1/query_range",
                    query=promql, start=(BASE_TS + k0 * IV) / 1000,
                    end=(BASE_TS + k1 * IV) / 1000, step=IV // 1000)
    out_ts = BASE_TS + np.arange(k0, k1 + 1, dtype=np.int64) * IV
    got = {}
    for s in body["data"]["result"]:
        row = np.full(len(out_ts), np.nan)
        for ts, v in s["values"]:
            row[int(round((ts * 1000 - out_ts[0]) / IV))] = float(v)
        got[tuple(sorted(s["metric"].items()))] = row
    return out_ts, got, body["stats"]["exec_path"], body["_ms"]


def require_route(what: str, path: str, want: str) -> None:
    check(path == want,
          f"{what}: route {path!r}, wanted {want!r} — the compiled fused "
          f"Pallas route and no other (not the composed two-step path, the "
          f"xla twin, an interpreted kernel or a cache)")


def compare(what: str, got: np.ndarray, want: np.ndarray, rtol=RTOL,
            atol=ATOL) -> None:
    check(got.shape == want.shape, f"{what}: shape {got.shape} vs {want.shape}")
    check(bool(np.isfinite(want).all()), f"{what}: reference not finite")
    check(bool(np.isfinite(got).all()), f"{what}: answer not finite: {got}")
    err = np.abs(got - want) / (atol + rtol * np.abs(want))
    check(bool((err <= 1.0).all()),
          f"{what}: differs from the reference (worst {err.max():.2f}x the "
          f"bound rtol={rtol} atol={atol})\n got  {got}\n want {want}")


def require_compiled_kernels(label: str) -> None:
    """Every Pallas program in the plan cache carries the compiled tag."""
    from filodb_tpu.query.plancache import plan_cache
    tags = {x for k in plan_cache.keys() for x in k
            if isinstance(x, str) and x.startswith("pallas")}
    say(f"{label}: Pallas programs in the plan cache: {sorted(tags)}")
    check(tags == {EXPECT_TAG}, f"{label}: plan cache holds {sorted(tags)}, "
          f"wanted only {EXPECT_TAG!r}")


def by_group(per_series: np.ndarray, ids: np.ndarray, op) -> dict:
    return {(("g", f"g{g}"),): op(per_series[ids % GROUPS == g])
            for g in range(GROUPS)}


def aggregate_queries(srv, vals, ids, k0: int, k1: int, route: str,
                      label: str) -> dict:
    """The four fused aggregates of phase 4 over distinct ranges (so no
    result cache can answer), each against the reference; returns the
    answers keyed by query for cross-phase parity."""
    answers = {}
    plan = [
        ("sum(rate(m[5m]))", "rate", 0, lambda x: {(): x.sum(axis=0)}),
        ("sum by (g)(rate(m[5m]))", "rate", 1,
         lambda x: by_group(x, ids, lambda v: v.sum(axis=0))),
        ("avg(avg_over_time(m[5m]))", "avg_over_time", 2,
         lambda x: {(): x.mean(axis=0)}),
        ("stddev(sum_over_time(m[5m]))", "sum_over_time", 3,
         lambda x: {(): x.std(axis=0)}),
    ]
    for q, fn, shift, reduce_ in plan:
        out_ts, got, path, ms = query_range(srv, q, k0 - shift, k1 - shift)
        want = reduce_(ref_window(fn, vals, out_ts))
        check(set(got) == set(want), f"{label} {q}: series {sorted(got)}")
        for key in want:
            compare(f"{label} {q} {dict(key)}", got[key], want[key])
        require_route(f"{label} {q}", path, route)
        say(f"{label}: {q}  steps={len(out_ts)} series_out={len(got)} "
            f"route={path}  {ms:.1f} ms  ok")
        answers[q] = got
    return answers


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_raw(tmp: str, args, clock: CompileClock) -> None:
    """Phases 2-5: the default server at the north-star shape."""
    from filodb_tpu.core.schemas import GAUGE
    S, n1, n2 = args.series, args.scrapes, args.scrapes + args.more_scrapes
    ids = np.arange(S)
    vals = make_counters(args.seed, S, n2)
    check_against_repo_reference(vals[:, :n1],
                                 BASE_TS + np.arange(n1 - 6, n1) * IV,
                                 args.seed)
    srv = start_server(tmp, "raw", store={
        "max_series_per_shard": S, "samples_per_series": args.capacity})
    try:
        shard = srv.memstore.shard("prometheus", 0)
        w = Writer(srv, GAUGE, 0, ids, "m")
        say(f"raw: {len(w.templates)} containers of <= {CHUNK} series built")
        for k in range(n1):
            w.scrape(k, vals[:, k])
        w.drain()
        st = shard.store
        check(shard.num_series == S, f"registered {shard.num_series} of {S}")
        check(int(st.n_host.sum()) == S * n1,
              f"store holds {int(st.n_host.sum())} of {S * n1} samples")
        check(st.grid_ok, "store fell off the scrape grid")
        say(f"raw: wrote {w.rows} rows ({n1} scrapes x {S} series) through "
            f"bus -> consumer -> index -> staging -> flush in "
            f"{w.seconds:.1f} s = {w.rows / w.seconds:,.0f} rows/s; resident "
            f"ts={st.ts.nbytes / 1e9:.2f} GB ({st.ts.dtype}) "
            f"val={st.val.nbytes / 1e9:.2f} GB ({st.val.dtype}); "
            f"compile so far {clock.seconds:.1f} s")
        device_memory("raw after first flush")

        k1 = n1 - 1
        route = f"local-fused[{EXPECT_TAG}]"
        aggregate_queries(srv, vals[:, :n1], ids, k1 - 5, k1, route, "raw")

        # one instant query
        body = http_get(srv.http.port, "/promql/prometheus/api/v1/query",
                        query="sum(rate(m[5m]))",
                        time=(BASE_TS + (k1 - 4) * IV) / 1000)
        res = body["data"]["result"]
        check(len(res) == 1, f"instant: {res}")
        want = ref_window("rate", vals[:, :n1],
                          [BASE_TS + (k1 - 4) * IV]).sum(axis=0)
        compare("raw instant sum(rate(m[5m]))",
                np.array([float(res[0]["value"][1])]), want)
        require_route("raw instant", body["stats"]["exec_path"], route)
        say(f"raw: instant sum(rate(m[5m])) route="
            f"{body['stats']['exec_path']}  {body['_ms']:.1f} ms  ok")

        # an equality-filtered raw selector of a few series
        rack = S // 8 + 3
        out_ts, got, path, ms = query_range(srv, f'm{{rack="r{rack}"}}',
                                            k1 - 3, k1)
        check(len(got) == 4, f"raw selector: {len(got)} series")
        for key, row in got.items():
            i = int(dict(key)["host"][1:])
            check(i // 4 == rack, f"raw selector returned {dict(key)}")
            check(bool((row == vals[i, k1 - 3:k1 + 1]).all()),
                  f"raw selector {dict(key)}: {row} vs {vals[i, k1-3:k1+1]}")
        say(f'raw: m{{rack="r{rack}"}}  4 series x {len(out_ts)} steps exact '
            f"route={path}  {ms:.1f} ms  ok")

        # phase 5: a second batch, flushed (a donated append on a store that
        # has been read), then read again
        for k in range(n1, n2):
            w.scrape(k, vals[:, k])
        w.drain()
        check(int(st.n_host.sum()) == S * n2, "second batch not all landed")
        # (a query text of its own: a repeat of sum(rate(...)) would be
        # stitched from the fragment cache, route "incremental[...]")
        out_ts, got, path, ms = query_range(srv, "sum(increase(m[5m]))",
                                            n2 - 4, n2 - 1)
        compare("raw after 2nd flush sum(increase(m[5m]))", got[()],
                ref_window("rate", vals, out_ts).sum(axis=0) * WINDOW / 1000)
        require_route("raw after 2nd flush", path, route)
        say(f"raw: +{n2 - n1} scrapes flushed, re-query route={path} "
            f"{ms:.1f} ms  ok; total {w.rows} rows at "
            f"{w.rows / w.seconds:,.0f} rows/s")
        require_compiled_kernels("raw")
        device_memory("raw after second flush + queries")
        w.close()
    finally:
        stop_server(srv)


def wait_resident(shard, label: str, timeout_s: float = 300.0):
    """Residency lands at flush cadence (two-phase, epoch-checked)."""
    deadline = time.perf_counter() + timeout_s
    while not shard.store.is_narrow_resident:
        check(time.perf_counter() < deadline,
              f"{label}: store never went compressed-resident "
              f"(declined: {shard.store.residency_decline})")
        shard.flush()
        time.sleep(0.3)
    return shard.store


def phase_gauge(tmp: str, args) -> None:
    """Phase 6a: compressed_residency=gauge on the same seeded counters —
    the delta8 ladder, answers against the same reference."""
    from filodb_tpu.core.schemas import GAUGE
    S, n = args.gauge_series, args.scrapes
    ids = np.arange(S)
    vals = make_counters(args.seed, args.series, n)[:S]
    srv = start_server(tmp, "gauge", store={
        "max_series_per_shard": S, "samples_per_series": args.capacity,
        "compressed_residency": "gauge"})
    try:
        shard = srv.memstore.shard("prometheus", 0)
        w = Writer(srv, GAUGE, 0, ids, "m")
        for k in range(n):
            w.scrape(k, vals[:, k])
        w.drain()
        st = wait_resident(shard, "gauge")
        kind, ops, ok = st.narrow_operands()
        check(kind == "delta8", f"gauge: residency kind {kind}, not delta8")
        check(bool(ok[:S].all()), "gauge: rows fell to the cohort pool")
        check(st.val is None and st.ts is None, "gauge: raw blocks still held")
        say(f"gauge: {w.rows} rows at {w.rows / w.seconds:,.0f} rows/s; "
            f"resident kind={kind} block={ops[0].dtype}{list(ops[0].shape)} "
            f"= {st.resident_sample_bytes() / 1e9:.3f} GB (raw would be "
            f"{S * args.capacity * 12 / 1e9:.2f} GB)")
        aggregate_queries(srv, vals, ids, n - 6, n - 1,
                          f"local-fused-narrow[delta8,{EXPECT_TAG}]", "gauge")
        require_compiled_kernels("gauge")
        device_memory("gauge")
        w.close()
    finally:
        stop_server(srv)


def phase_hist(tmp: str, args) -> None:
    """Phase 6b: a native-histogram store, 64 buckets, compressed_residency
    =all, histogram_quantile over the i8-resident block."""
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    S, n = args.hist_series, args.scrapes
    ids = np.arange(S)
    les = np.concatenate([10.0 * np.arange(1, BUCKETS), [np.inf]])
    h = make_hists(args.seed, S, n)
    srv = start_server(tmp, "hist", dataset="hists", schema="prom-histogram",
                       store={"max_series_per_shard": S,
                              "samples_per_series": args.capacity,
                              "compressed_residency": "all"})
    try:
        shard = srv.memstore.shard("hists", 0)
        w = Writer(srv, PROM_HISTOGRAM, 0, ids, "h", bucket_les=les)
        layout = PROM_HISTOGRAM.col_layout(BUCKETS)
        for k in range(n):
            flat = np.zeros((S, PROM_HISTOGRAM.flat_width(BUCKETS)))
            for name, off, width, is_hist in layout:
                if is_hist:
                    flat[:, off:off + width] = h[:, k]
                elif name == "count":
                    flat[:, off] = h[:, k, -1]
            w.scrape(k, flat)
        w.drain()
        st = wait_resident(shard, "hist")
        dd, first_d, ok = st.hist_operands()
        check(str(dd.dtype) == "int8", f"hist: dd tier {dd.dtype}, not int8")
        check(bool(ok[:S].all()), "hist: rows fell to the cohort pool")
        say(f"hist: {w.rows} rows x {BUCKETS} buckets at "
            f"{w.rows / w.seconds:,.0f} rows/s; resident dd={dd.dtype}"
            f"{list(dd.shape)} = {dd.nbytes / 1e9:.3f} GB (f32 would be "
            f"{dd.size * 4 / 1e9:.2f} GB)")
        q = "histogram_quantile(0.9, sum(rate(h[5m])))"
        out_ts, got, path, ms = query_range(srv, q, n - 6, n - 1)
        summed = ref_window("rate", h, out_ts).sum(axis=0)      # [T, B]
        compare(f"hist {q}", got[()], ref_hist_quantile(0.9, les, summed))
        require_route(f"hist {q}", path, f"fused-hist-narrow[{EXPECT_TAG}]")
        say(f"hist: {q}  steps={len(out_ts)} route={path}  {ms:.1f} ms  ok")
        require_compiled_kernels("hist")
        device_memory("hist")
        w.close()
    finally:
        stop_server(srv)


def phase_mesh(tmp: str, args) -> None:
    """--chips 4: one server, four shards, one per device; aggregates through
    the pjit ``dist_*`` programs, against the same plain reference."""
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.query.plancache import plan_cache
    nsh, per, n = 4, args.mesh_series, args.scrapes
    S = nsh * per
    vals = make_counters(args.seed, S, n)
    # spread=2: one metric's series fan out over 2^2 shards by part-key hash
    srv = start_server(tmp, "mesh", num_shards=nsh, spread=2, store={
        "max_series_per_shard": per, "samples_per_series": args.capacity})
    try:
        eng = srv.engines["prometheus"]
        check(eng.mesh is not None, "no mesh: the server fell back to the "
              "in-process dispatch path")
        # series -> shard exactly as a producer routes them: by the
        # container's own shard hash through the server's mapper
        from filodb_tpu.core.record import RecordBuilder
        b = RecordBuilder(GAUGE)
        owner = np.empty(S, np.int64)
        for lo in range(0, S, CHUNK):
            hi = min(lo + CHUNK, S)
            b.add_series_batch(series_labels(range(lo, hi), "m"), BASE_TS,
                               0.0)
            rc = b.build()
            owner[lo:hi] = eng.mapper.shards_vector(rc.shard_hash,
                                                    rc.part_hash)
        # hashing spreads 4 * per series a little unevenly; a shard holds
        # ``per`` at most, so the overflow of the fuller shards is not
        # written (nor counted in the reference)
        writers = [Writer(srv, GAUGE, sh, np.flatnonzero(owner == sh)[:per],
                          "m") for sh in range(nsh)]
        written = np.sort(np.concatenate([w.ids for w in writers]))
        say(f"mesh: {len(written)} of {S} series written, per shard "
            f"{[len(w.ids) for w in writers]} (capacity {per})")
        t_write = time.perf_counter()
        for k in range(n):
            for w in writers:
                w.scrape(k, vals[w.ids, k])
        for w in writers:
            w.drain()
        t_write = time.perf_counter() - t_write
        homes = []
        for sh in range(nsh):
            st = srv.memstore.shard("prometheus", sh).store
            (dev,) = st.val.devices()
            check(st.ts.devices() == {dev}, f"shard {sh}: ts/val split")
            homes.append(dev)
            say(f"mesh: shard {sh}: {int(st.n_host.sum())} samples of "
                f"{len(writers[sh].ids)} series on {dev}")
        check(len(set(homes)) == nsh,
              f"the {nsh} shards sit on {len(set(homes))} device(s): {homes}")
        rows = sum(w.rows for w in writers)
        say(f"mesh: {rows} rows over {nsh} shards in {t_write:.1f} s = "
            f"{rows / t_write:,.0f} rows/s")

        k1 = n - 1
        out_ts, got, path, ms = query_range(srv, "sum(rate(m[5m]))",
                                            k1 - 5, k1)
        compare("mesh sum(rate(m[5m]))", got[()],
                ref_window("rate", vals[written], out_ts).sum(axis=0))
        require_route("mesh sum(rate)", path, MESH_PREFIX + "fused")
        say(f"mesh: sum(rate(m[5m])) route={path}  {ms:.1f} ms  ok")

        out_ts, got, path, ms = query_range(srv, "topk(5, rate(m[5m]))",
                                            k1 - 4, k1 - 4)
        rate1 = ref_window("rate", vals[written], out_ts)[:, 0]
        top = np.sort(rate1)[-5:]
        compare("mesh topk(5, rate(m[5m]))",
                np.sort(np.array([v[0] for v in got.values()])), top)
        require_route("mesh topk", path, MESH_PREFIX + "topk")
        say(f"mesh: topk(5, rate(m[5m])) route={path}  {ms:.1f} ms  ok")

        out_ts, got, path, ms = query_range(srv, "quantile(0.9, rate(m[5m]))",
                                            k1 - 3, k1 - 3)
        want = np.quantile(ref_window("rate", vals[written], out_ts)[:, 0],
                           0.9)
        # the mesh quantile is a sketch merged by psum: the repo's own bound
        compare("mesh quantile(0.9, rate(m[5m]))", got[()],
                np.array([want]), rtol=2.5e-2)
        require_route("mesh quantile", path, MESH_PREFIX + "sketch")
        say(f"mesh: quantile(0.9, rate(m[5m])) route={path}  {ms:.1f} ms  ok")
        require_compiled_kernels("mesh")
        device_memory("mesh (device 0)")
        for w in writers:
            w.close()
    finally:
        stop_server(srv)


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--series", type=int, default=1 << 20,
                    help="series of the default-server phases (2^20)")
    ap.add_argument("--capacity", type=int, default=768,
                    help="store.samples_per_series (768: BASELINE configs[0])")
    ap.add_argument("--scrapes", type=int, default=36,
                    help="10 s scrapes written per series before the queries")
    ap.add_argument("--more-scrapes", type=int, default=4,
                    help="scrapes of the second batch (phase 5)")
    ap.add_argument("--gauge-series", type=int, default=1 << 18,
                    help="series of the compressed_residency=gauge phase")
    ap.add_argument("--hist-series", type=int, default=1 << 13,
                    help="series of the 64-bucket histogram phase")
    ap.add_argument("--mesh-series", type=int, default=1 << 18,
                    help="series per shard with --chips 4")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the four-shard mesh phase")
    args = ap.parse_args(argv)

    device = find_device(args.chips)
    import jax

    from filodb_tpu.core import native as partset
    from filodb_tpu.memory import native as codecs
    from filodb_tpu.utils import compilecache
    cache_dir = compilecache.configure()
    clock = CompileClock()
    say(f"device: {device}  jax {jax.__version__}  compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries)")
    libs = {"partset": partset.available(), "codecs": codecs.available()}
    say(f"native libraries (built from the checkout's .cpp): {libs}")
    check(all(libs.values()), f"a native library is missing: {libs} — the "
          "write path measured here is the native one")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            phase_mesh(tmp, args)
        else:
            phase_raw(tmp, args, clock)
            phase_gauge(tmp, args)
            phase_hist(tmp, args)
    say(f"compile: {clock.seconds:.1f} s in XLA/Mosaic, "
        f"{clock.cache_hits} persistent-cache hits")
    extra = [t.name for t in threading.enumerate()
             if t is not threading.main_thread() and not t.daemon]
    check(not extra, f"threads still running: {extra}")
    sys.stdout.write(json.dumps({"ok": True, "device": device}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
