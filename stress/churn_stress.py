"""Partition churn stress: continuous series creation, purge, eviction and
slot reuse — the index arena, bloom filter, free-list, and eviction paths
under sustained pressure.

Reference analogs: stress/src/main/scala/filodb.stress/MemStoreStress.scala +
RowReplaceStress.scala (this framework has no row replacement; slot reuse
under churn is the matching hazard).
Run: python stress/churn_stress.py [rounds] [series_per_round]
"""

import sys
import time

from filodb_tpu.core.filters import Equals
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE


def main(rounds=50, series_per_round=2_000):
    ms = TimeSeriesMemStore()
    cap = series_per_round * 2          # forces live eviction every few rounds
    cfg = StoreConfig(max_series_per_shard=cap, samples_per_series=64,
                      flush_batch_size=10**9)
    shard = ms.setup("churn", GAUGE, 0, cfg)
    base = 1_700_000_000_000
    t0 = time.perf_counter()
    for r in range(rounds):
        b = RecordBuilder(GAUGE)
        for i in range(series_per_round):
            b.add({"_metric_": "pod_cpu", "pod": f"pod-{r}-{i}"},
                  base + r * 600_000, float(i))
        shard.ingest(b.build())
        shard.flush()
        if r % 5 == 4:    # purge series quiet for > 20 minutes of data time
            shard.purge_expired_partitions(base + (r - 2) * 600_000)
        assert shard.num_series <= cap, (shard.num_series, cap)
        shard.index.maybe_compact_arena()
    dt = time.perf_counter() - t0
    created = shard.stats.series_created
    print(f"{rounds} rounds x {series_per_round:,} new series in {dt:.1f}s: "
          f"created={created:,} evicted={shard.stats.partitions_evicted:,} "
          f"purged={shard.stats.partitions_purged:,} "
          f"live={shard.num_series:,} arena={shard.index.arena_bytes():,}B")
    assert created == rounds * series_per_round
    # arena stays bounded by LIVE cardinality, not total churn
    assert shard.index.arena_bytes() < 200 * cap, "index arena leaked churn"
    print("OK: capacity bounded, arena bounded, no crashes under churn")
    # no pod above is scraped twice, so that store never learns an interval
    # and every row sits in column 0: one cohort, trivially
    assert shard.store.grid_cohorts()[0] == "uniform"
    grid_churn(max(rounds // 5, 4), max(series_per_round // 20, 16))
    return 0


def grid_churn(rounds: int, pods: int):
    """The same churn on a scrape GRID: every pod scraped every 10 s, a
    tenth of them replaced every twenty scrapes, ended ones purged, the
    store aged out by its retention — the store keeps its time-aligned
    cells throughout: ONE start cohort, every new series and every REUSED
    slot written from its birth cell on."""
    import numpy as np
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=pods * 2, samples_per_series=64,
                      flush_batch_size=10**9, retention_ms=300_000)
    shard = ms.setup("gridchurn", GAUGE, 0, cfg)
    base, iv = 1_700_000_000_000, 10_000
    gen = {i: 0 for i in range(pods)}
    born_at = {(i, 0): 0 for i in range(pods)}
    for k in range(rounds * 20):
        if k and k % 20 == 0:
            for i in range((k // 20) % 10, pods, 10):
                gen[i] += 1
                born_at[(i, gen[i])] = k
            shard.purge_expired_partitions(base + (k - 5) * iv)
        b = RecordBuilder(GAUGE)
        for i, g in gen.items():
            b.add({"_metric_": "pod_cpu", "pod": f"pod-{i}", "gen": str(g)},
                  base + k * iv, float(k))
        shard.ingest(b.build())
        shard.flush()
        st = shard.store
        assert st.grid_cohorts()[0] == "uniform" and st.res is None, k
    st = shard.store
    cell0 = st.grid_cohorts()[1]
    live = np.flatnonzero(st.n_host > 0)
    # every row's first cell is its birth cell: its first stamp lies there
    assert (st.first_ts[live]
            == base + (cell0 + st.born[live].astype(np.int64)) * iv).all()
    reused = shard.stats.partitions_purged
    assert reused > 0 and st.births["aligned"] > 0 and not st.births["minority"]
    for (i, g), k in born_at.items():
        if g == gen[i] and k > cell0:           # alive, born after cell 0
            pid = int(shard.part_ids_from_filters(
                [Equals("pod", f"pod-{i}"), Equals("gen", str(g))],
                0, 1 << 60)[0])
            assert st.born[pid] == k - cell0, (i, g, k, st.born[pid], cell0)
    print(f"OK: {rounds * 20} scrapes on a grid, {len(born_at) - pods} "
          f"births ({st.births['aligned']} past cell 0), {reused} slots "
          f"purged and reused, {st.stats.compactions} compactions: one "
          f"cohort, every row from its birth cell")


if __name__ == "__main__":
    args = [int(a) for a in sys.argv[1:]]
    sys.exit(main(*args))
