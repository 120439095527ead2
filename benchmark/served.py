"""The system under test, as the benchmark holds it: one in-process
``FiloServer`` (shipped defaults but for the sizes the deployment's file
states), scrapers that publish ``RecordContainer``s to each shard's bus, and
an HTTP client. Copied from ``chip_smoke.py`` (PERF.md, Open questions: the
original can go) so that no later PR can change the yardstick by changing
that script. What the containers carry and what the store then holds is the
deployment's data module's (``benchmark/data/``), passed in as ``data``.
"""

from __future__ import annotations

import dataclasses
import gc
import http.client
import json
import os
import time
import urllib.parse

import numpy as np


def chunk_of(deploy: dict) -> int:
    """Series per RecordContainer: a scrape of the whole deployment is
    ``containers_per_scrape`` containers."""
    return -(-int(deploy["series"]) // int(deploy["containers_per_scrape"]))


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def start_server(deploy: dict, run_dir: str):
    """``deploy``: a configuration file's content. Everything it does not
    state is the program's shipped default."""
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    cfg = json.loads(json.dumps(deploy["server"]))      # a copy
    cfg["bus_dir"] = os.path.join(run_dir, "bus")
    cfg.setdefault("http", {})["port"] = 0
    os.makedirs(cfg["bus_dir"], exist_ok=True)
    srv = FiloServer(Config(cfg)).start()
    c = srv.config
    log(f"server up on :{srv.http.port} dataset={c['dataset']} "
        f"shards={c['num_shards']} spread={c['spread']} "
        f"query.fused_kernels={c['query.fused_kernels']} "
        f"store.compressed_residency={c['store.compressed_residency']} "
        f"max_series_per_shard={c['store.max_series_per_shard']} "
        f"samples_per_series={c['store.samples_per_series']} "
        f"trace.enabled={c['trace.enabled']} "
        f"trace.sample_rate={c['trace.sample_rate']}")
    return srv


def stop_server(srv) -> list[str]:
    """Stop every thread the server started; names of any that would not."""
    consumers = list(srv.consumers)
    srv.shutdown()
    stuck = []
    for c in consumers:
        c.join(timeout=30)
        if c.is_alive():
            stuck.append(c.name)
    srv.memstore._shards.clear()
    srv.engines.clear()
    gc.collect()
    return stuck


class Writer:
    """A scraper of one shard's series: one RecordContainer per chunk of
    series, built once with ``RecordBuilder.add_series_batch`` (its key
    bytes and hashes are what a producer memoizes), re-sent per scrape with
    that scrape's stamp and values, published to the shard's bus."""

    def __init__(self, srv, shard: int, ids: np.ndarray, deploy: dict, data):
        from filodb_tpu.core.record import RecordBuilder
        from filodb_tpu.ingest.bus import FileBus
        self.data, self.deploy = data, deploy
        self.shard_num = shard
        self.shard = srv.memstore.shard(srv.config["dataset"], shard)
        self.bus = FileBus(os.path.join(srv.config["bus_dir"],
                                        f"shard{shard}.log"))
        self.ids = np.asarray(ids, np.int64)
        self.rows = 0
        self.templates = []
        b = RecordBuilder(data.schema())
        chunk = chunk_of(deploy)
        for lo in range(0, len(self.ids), chunk):
            sel = self.ids[lo:lo + chunk]
            b.add_series_batch(data.series_labels(sel, deploy),
                               data.scrape_ms(0, deploy), 0.0)
            self.templates.append((lo, lo + len(sel), b.build()))

    def publish(self, j: int, k: int, seed: int) -> None:
        """Publish container ``j`` of scrape ``k`` (acknowledged on return)."""
        lo, hi, rc = self.templates[j]
        self.bus.publish(dataclasses.replace(
            rc, **self.data.scrape(seed, self.ids[lo:hi], k, self.deploy)))
        self.rows += hi - lo

    def drain(self, timeout_s: float = 300.0) -> None:
        """Wait until the consumer has ingested every published row, then
        flush what is staged and wait for the device to retire it."""
        import jax
        deadline = time.perf_counter() + timeout_s
        while self.shard.stats.rows_ingested < self.rows:
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"shard {self.shard_num}: consumer stalled at "
                    f"{self.shard.stats.rows_ingested} of {self.rows} rows")
            time.sleep(0.02)
        self.shard.flush()
        jax.block_until_ready(self.shard.store.n)

    def close(self) -> None:
        self.bus.close()


def owners(srv, n_series: int, deploy: dict, data) -> np.ndarray:
    """series -> shard exactly as a producer routes them: by the
    container's own shard hash through the server's mapper."""
    from filodb_tpu.core.record import RecordBuilder
    eng = srv.engines[srv.config["dataset"]]
    b = RecordBuilder(data.schema())
    owner = np.empty(n_series, np.int64)
    chunk = chunk_of(deploy)
    for lo in range(0, n_series, chunk):
        hi = min(lo + chunk, n_series)
        b.add_series_batch(data.series_labels(range(lo, hi), deploy),
                           data.scrape_ms(0, deploy), 0.0)
        rc = b.build()
        owner[lo:hi] = eng.mapper.shards_vector(rc.shard_hash, rc.part_hash)
    return owner


def pid_series(shard, ids: np.ndarray, seed: int, deploy: dict,
               data) -> np.ndarray:
    """[S] series id held by each store row, -1 for unused rows. Scrape 0
    registers a shard's series in the order published; a seeded sample of
    rows is checked against the index's own labels."""
    st = shard.store
    if shard.num_series != len(ids):
        raise RuntimeError(f"shard {shard.shard_num}: registered "
                           f"{shard.num_series} of {len(ids)} series")
    sid = np.full(st.S, -1, np.int64)
    sid[:len(ids)] = ids
    rng = np.random.default_rng(seed)
    rows = np.unique(np.concatenate(
        [[0, len(ids) - 1], rng.integers(0, len(ids), 254)]))
    want = {k: v for k, v in data.series_labels(sid[rows], deploy).items()
            if not isinstance(v, str)}
    for i, p in enumerate(rows):
        held = shard.index.labels_of(int(p))
        mine = {k: v[i] for k, v in want.items()}
        if {k: held.get(k) for k in mine} != mine:
            raise RuntimeError(f"shard {shard.shard_num}: row {p} holds "
                               f"{held}, expected {mine}")
    return sid


def build(srv, deploy: dict, seed: int, data) -> dict:
    """Register every series through the write path (scrape 0), then have
    the data module fill and check every shard's history on the device.
    Returns {"writers", "sids" (sorted, the series written), "sid_of"
    {shard: [S]}, "seconds" {...}}."""
    nsh = int(deploy["server"]["num_shards"])
    per = int(deploy["server"]["store"]["max_series_per_shard"])
    n_series = int(deploy["series"])
    dataset = srv.config["dataset"]
    t0 = time.perf_counter()
    if nsh == 1:
        ids_of = [np.arange(n_series)]
    else:
        # hashing spreads the series a little unevenly; a shard holds
        # ``per`` at most, so the overflow of the fuller shards is not
        # written (nor counted in the reference)
        owner = owners(srv, n_series, deploy, data)
        ids_of = [np.flatnonzero(owner == sh)[:per] for sh in range(nsh)]
    writers = [Writer(srv, sh, ids_of[sh], deploy, data) for sh in range(nsh)]
    t1 = time.perf_counter()
    for w in writers:
        for j in range(len(w.templates)):
            w.publish(j, 0, seed)
    for w in writers:
        w.drain()
    t2 = time.perf_counter()
    sid_of = {}
    homes = set()
    for w in writers:
        sid = pid_series(w.shard, w.ids, seed, deploy, data)
        data.fill(w.shard, sid, seed, deploy)
        homes |= data.check_filled(w.shard, sid, deploy)
        sid_of[w.shard_num] = sid
        # drain() waits for the rows this writer published; a module may
        # have filled through the shard's own ingest: count on from here
        w.rows = w.shard.stats.rows_ingested
    if len(homes) != nsh:
        raise RuntimeError(f"{nsh} shards sit on {len(homes)} device(s)")
    t3 = time.perf_counter()
    written = np.sort(np.concatenate([w.ids for w in writers]))
    log(f"fill: {len(written)} of {n_series} series over {nsh} "
        f"shard(s) {[len(w.ids) for w in writers]}; templates "
        f"{t1 - t0:.1f} s, registration (scrape 0 through the write "
        f"path) {t2 - t1:.1f} s, the history on the device "
        f"{t3 - t2:.1f} s; dataset {dataset}")
    return {"writers": writers, "sids": written, "sid_of": sid_of,
            "seconds": {"templates": t1 - t0, "registration": t2 - t1,
                        "device_fill": t3 - t2}}


def query_range(port: int, dataset: str, promql: str, start_ms: int,
                end_ms: int, step_ms: int, tenant: str | None = None,
                timeout_s: float = 120.0) -> dict:
    """One HTTP ``query_range``, body read and parsed. Returns {"code",
    "body" (parsed, or None), "ms"}; never raises for an HTTP status."""
    q = urllib.parse.urlencode({"query": promql, "start": start_ms / 1000,
                                "end": end_ms / 1000, "step": step_ms / 1000})
    t = time.perf_counter()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("GET", f"/promql/{dataset}/api/v1/query_range?{q}",
                     headers={"X-Filo-Tenant": tenant} if tenant else {})
        r = conn.getresponse()
        raw = r.read()
        code = r.status
        body = json.loads(raw) if code == 200 else None
    except (OSError, http.client.HTTPException, ValueError) as e:
        code, body = -1, None
        log(f"query failed: {type(e).__name__}: {e}")
    finally:
        conn.close()
    return {"code": code, "body": body,
            "ms": (time.perf_counter() - t) * 1000.0}


def answer_rows(body: dict, out_ts: np.ndarray, step_ms: int) -> dict:
    """A matrix answer as {label-tuple: f64[T]}, NaN where a step is absent."""
    got = {}
    for s in body["data"]["result"]:
        row = np.full(len(out_ts), np.nan)
        for ts, v in s["values"]:
            j = int(round((ts * 1000 - int(out_ts[0])) / step_ms))
            if 0 <= j < len(row):
                row[j] = float(v)
        got[tuple(sorted(s["metric"].items()))] = row
    return got
