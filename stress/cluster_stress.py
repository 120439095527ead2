"""Cluster stress: a live two-node cluster under sustained ingest + spanning
queries, then a node kill with takeover, then continued serving.

Reference: stress/src/main/scala/filodb.stress/BatchIngestion + the multi-jvm
ClusterRecoverySpec arc — this app runs it as one long soak: two FiloServers
share a broker + registrar; producers push a fixed scrape rate into both
partitions while query threads issue spanning sum(rate)/topk/count to BOTH
nodes (each answers the peer's shard via cross-node /exec dispatch); then one
node dies, the survivor takes over, and queries must keep answering (with at
most a bounded takeover gap).

Run: python stress/cluster_stress.py [seconds] [records_per_sec]

One process, no children: both FiloServers, the broker, the producers and the
query threads are threads of this process, which is the only one that touches
JAX. It asks for two virtual host devices (XLA_FLAGS below) and is meant for
the CPU; on a chip host run it with ``JAX_PLATFORMS=cpu`` — it starts nothing
that could contend for the chip.
"""

import os
import sys
import threading
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import numpy as np


def main(duration_s: int = 30, target_rps: int = 5_000) -> int:
    import tempfile

    from filodb_tpu.config import Config
    from filodb_tpu.core.record import RecordBuilder, RecordContainer
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.ingest.broker import BrokerBus, BrokerServer
    from filodb_tpu.standalone import FiloServer

    BASE = 1_700_000_000_000
    tmp = tempfile.mkdtemp(prefix="cluster_stress_")
    broker = BrokerServer(f"{tmp}/broker", num_partitions=2).start()
    reg = f"{tmp}/members"

    def server(name):
        return FiloServer(Config({
            "num_shards": 2, "bus_addr": f"127.0.0.1:{broker.port}",
            "http": {"port": 0},
            "cluster": {"registrar": reg, "self_addr": name,
                        "heartbeat_interval": "250ms", "stale_after": "2s",
                        "min_members": 2, "join_timeout": "30s"},
            "store": {"max_series_per_shard": 1024, "samples_per_series": 1024,
                      "flush_batch_size": 10**9},
        }))

    servers = {}
    ths = [threading.Thread(target=lambda n=n: servers.update({n: server(n).start()}))
           for n in ("node-a:1", "node-b:1")]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=60)
    assert len(servers) == 2, f"cluster never formed: {sorted(servers)}"
    a, b = servers["node-a:1"], servers["node-b:1"]
    print(f"cluster up: a={a.http.port} b={b.http.port}")

    stop = threading.Event()
    stats = {"ingested": 0, "queries": 0, "errors": 0, "gap_errors": 0}
    n_series = 256

    def producer(shard: int):
        bus = BrokerBus(f"127.0.0.1:{broker.port}", shard)
        b_ = RecordBuilder(GAUGE)
        for i in range(n_series):
            b_.add({"_metric_": "cm", "host": f"s{shard}h{i}"}, 0, 0.0)
        tpl = b_.build()
        period = n_series / (target_rps / 2)
        k = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            # k+2: the warmup already published ticks 0 and 1
            ts = np.full(len(tpl.ts), BASE + (k + 2) * 10_000, np.int64)
            vals = np.full(len(tpl.ts), float(k), np.float64)
            c = RecordContainer(tpl.schema, ts, vals, tpl.part_hash,
                                tpl.shard_hash, tpl.part_idx, tpl.label_sets,
                                tpl.bucket_les, tpl.part_keys, tpl.set_hashes)
            try:
                bus.publish(c)
                stats["ingested"] += n_series
            except Exception:  # noqa: BLE001 — broker gone at shutdown
                break
            k += 1
            wait = period - (time.perf_counter() - t0)
            if wait > 0:
                stop.wait(wait)
        bus.close()

    phase = {"takeover": False}

    def querier(which: str):
        import json
        import urllib.parse
        import urllib.request
        k = 0
        while not stop.is_set():
            # after the kill, the dead node's querier redirects to the
            # survivor (a real LB would stop routing to it)
            which_srv = (servers["node-a:1"]
                         if which == "node-b:1" and phase["takeover"]
                         else servers[which])
            # per-thread rotation: a persistently failing shape must not
            # stall coverage of the others
            q = ["sum(rate(cm[1m]))", "count(cm)", "topk(3, cm)"][k % 3]
            k += 1
            lead = BASE + (stats["ingested"] // n_series // 2) * 10_000
            params = urllib.parse.urlencode({
                "query": q, "start": max(BASE, lead - 300_000) / 1000.0,
                "end": lead / 1000.0, "step": "30s"})
            url = (f"http://127.0.0.1:{which_srv.http.port}"
                   f"/promql/prometheus/api/v1/query_range?{params}")
            try:
                with urllib.request.urlopen(url, timeout=30) as r:
                    json.load(r)
                stats["queries"] += 1
            except Exception:  # noqa: BLE001
                if phase["takeover"]:
                    stats["gap_errors"] += 1
                else:
                    stats["errors"] += 1
                stop.wait(0.2)

    # warm the query path BEFORE the producers start: the first spanning
    # query compiles kernels on both nodes, and on a 1-core host that
    # compile must not race a full-rate ingest stream
    import json
    import urllib.parse
    import urllib.request
    for shard in (0, 1):
        bus = BrokerBus(f"127.0.0.1:{broker.port}", shard)
        wb = RecordBuilder(GAUGE)
        for t in (0, 1):     # two ticks: rate() needs >= 2 samples
            for i in range(n_series):
                wb.add({"_metric_": "cm", "host": f"s{shard}h{i}"},
                       BASE + t * 10_000, float(t))
        bus.publish(wb.build())
        bus.close()
    for srv in (a, b):
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                # compile EVERY query shape the stress issues, through the
                # same query_range path (an instant count alone would leave
                # rate/topk compiling mid-stress)
                ok = 0
                for q in ("count(cm)", "sum(rate(cm[1m]))", "topk(3, cm)"):
                    params = urllib.parse.urlencode({
                        "query": q, "start": (BASE + 10_000) / 1000.0,
                        "end": (BASE + 60_000) / 1000.0, "step": "30s"})
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{srv.http.port}"
                            f"/promql/prometheus/api/v1/query_range?{params}",
                            timeout=120) as r:
                        res = json.load(r)["data"]["result"]
                    if res:
                        ok += 1
                if ok == 3:
                    break
            except Exception:  # noqa: BLE001 — still warming
                pass
            time.sleep(0.5)
        else:
            raise AssertionError(f"warmup query never succeeded on {srv.node}")
    print("[warmup] spanning queries compiled on both nodes")

    threads = [threading.Thread(target=producer, args=(s,), daemon=True)
               for s in (0, 1)]
    threads += [threading.Thread(target=querier, args=(n,), daemon=True)
                for n in ("node-a:1", "node-b:1")]
    for t in threads:
        t.start()

    half = duration_s / 2
    time.sleep(half)
    steady_q, steady_err = stats["queries"], stats["errors"]
    print(f"[steady] ingested={stats['ingested']} queries={steady_q} "
          f"errors={steady_err}")
    assert steady_q > 0, "no successful spanning queries in steady state"
    assert steady_err <= steady_q * 0.05, "steady-state error rate > 5%"

    # kill node-b: its shard must move to a and queries must keep answering
    phase["takeover"] = True
    b.shutdown()
    print("[kill] node-b down; waiting for takeover")
    deadline = time.time() + 30
    while time.time() < deadline:
        if all(a.manager.node_of("prometheus", s) == "node-a:1"
               for s in (0, 1)) and len(a._running) == 2:
            break
        time.sleep(0.25)
    else:
        raise AssertionError("survivor never took over")
    time.sleep(half)
    post_q = stats["queries"] - steady_q
    print(f"[takeover] queries_after={post_q} gap_errors={stats['gap_errors']} "
          f"ingested={stats['ingested']}")
    assert post_q > 0, "no queries succeeded after takeover"
    # the takeover gap must be BOUNDED: after the reassignment window,
    # serving recovers — not a trickle of successes amid steady failures
    assert stats["gap_errors"] <= post_q + 5, \
        f"post-takeover outage: {stats['gap_errors']} errors vs {post_q} successes"

    stop.set()
    for t in threads:
        t.join(timeout=5)
    a.shutdown()
    broker.stop()
    print(f"OK: {stats['ingested']} records, {stats['queries']} spanning "
          f"queries, {stats['errors']} steady errors, "
          f"{stats['gap_errors']} takeover-window errors")
    return 0


if __name__ == "__main__":
    sys.exit(main(*(int(x) for x in sys.argv[1:3])))
