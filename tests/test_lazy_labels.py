"""Label sets on the wire are read when someone needs one (PR 41).

A ``RecordContainer`` carries its label sets twice: as canonical key bytes
with their hashes (what resolves a series the shard knows) and as dicts
(what registers one it does not). Encoding and decoding the dicts of a
125,000-row scrape of eleven-label series held the interpreter for a
quarter of a second each, every scrape; now a batch container encodes them
once and a decoded one parses them on first access.
"""

import dataclasses

import numpy as np

from filodb_tpu.core import record
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder, RecordContainer
from filodb_tpu.core.schemas import GAUGE, Schemas

BASE = 1_700_000_000_000


def batch(n=600, ts=BASE, v=1.0):
    b = RecordBuilder(GAUGE)
    b.add_series_batch({"_metric_": [f"cpu_f{i % 3}" for i in range(n)],
                        "hostname": [f"host_{i // 3}" for i in range(n)],
                        "os": "linux"}, ts, v)
    return b.build()


def test_a_decoded_container_builds_no_dict_until_one_is_read():
    c = batch()
    got = RecordContainer.from_bytes(c.to_bytes(), Schemas())
    ls = got.label_sets
    assert isinstance(ls, record._LazyJsonLabels) and ls._real is None
    assert len(ls) == 600 == len(got.part_keys)
    assert ls._real is None                      # len() parsed nothing
    assert ls[4] == {"_metric_": "cpu_f1", "hostname": "host_1",
                     "os": "linux"}
    assert ls._real is not None
    assert ls == c.label_sets and list(ls) == list(c.label_sets)
    assert [d["hostname"] for d in ls][:4] == ["host_0"] * 3 + ["host_1"]


def test_encoding_is_made_once_a_batch_and_kept_by_a_decoded_container(
        monkeypatch):
    calls = []
    real = record._labels_json
    monkeypatch.setattr(record, "_labels_json",
                        lambda ls: calls.append(len(ls)) or real(ls))
    c = batch()
    first = c.to_bytes()
    later = dataclasses.replace(c, ts=c.ts + 10_000, values=c.values + 1)
    assert later.label_sets is c.label_sets
    second = later.to_bytes()
    assert calls == [600] and len(first) == len(second) and first != second
    back = RecordContainer.from_bytes(second, Schemas())
    assert back.to_bytes() == second and calls == [600]
    assert back.label_sets._real is None         # re-sent as it came
    np.testing.assert_array_equal(back.ts, later.ts)
    # a container built record by record has plain dicts and encodes them
    b = RecordBuilder(GAUGE)
    b.add({"_metric_": "m", "h": "a"}, BASE, 1.0)
    plain = b.build()
    assert isinstance(plain.label_sets, list)
    assert RecordContainer.from_bytes(plain.to_bytes(), Schemas()
                                      ).label_sets == [{"_metric_": "m",
                                                        "h": "a"}]
    assert calls == [600, 1]


def test_ingest_of_known_series_reads_no_label_set_and_a_new_one_does():
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=1024, samples_per_series=16,
        flush_batch_size=10**9))
    sch = Schemas()
    first = RecordContainer.from_bytes(batch().to_bytes(), sch)
    sh.ingest(first)
    sh.flush()
    assert sh.num_series == 600 and first.label_sets._real is not None
    again = RecordContainer.from_bytes(batch(ts=BASE + 10_000, v=2.0
                                             ).to_bytes(), sch)
    sh.ingest(again)
    sh.flush()
    assert again.label_sets._real is None        # resolved by key and hash
    assert sh.num_series == 600
    assert (sh.store.n_host[:600] == 2).all()
    more = RecordContainer.from_bytes(batch(n=603, ts=BASE + 20_000, v=3.0
                                            ).to_bytes(), sch)
    sh.ingest(more)
    sh.flush()
    assert more.label_sets._real is not None and sh.num_series == 603
    assert sh.index.labels_of(602) == {"_metric_": "cpu_f2",
                                       "hostname": "host_200", "os": "linux"}
