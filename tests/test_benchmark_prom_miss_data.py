"""``benchmark/tests/test_prom_miss_data.py`` under tier-1, in a namespace of its
own (see ``tests/test_benchmark_data.py``), with the case
that waits on a `benchmark` PR marked and what it says held by membership."""

import json
import os

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_prom_miss_data")

from benchmark.tests.test_prom_miss_data import *     # noqa: E402,F401,F403
from benchmark.tests import test_prom_miss_data as _cases     # noqa: E402


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_prom_miss_data.py pins per_layer's last two names "
    "to demoted_rows_pct and hole_cells_pct; PR 39 appended its five "
    "readers' entries, as ISSUE 39 asks, and may edit no file the benchmark "
    "has. A `benchmark` PR has to make that case test membership, not the "
    "tail (ROADMAP.md queue 2 item 0 (12)); everything else it says of "
    "promdev_prom_miss_1m and promdev_prom_1m is held by "
    "test_the_prom_cells_are_as_named_whatever_follows_them"))
def test_prom_miss_configuration_and_cell_are_as_named():
    _cases.test_prom_miss_configuration_and_cell_are_as_named()


def test_the_prom_cells_are_as_named_whatever_follows_them():
    """What the two pinned cases above say of ``promdev_prom_1m`` x
    ``adhoc`` and ``promdev_prom_miss_1m`` x ``adhoc``, by membership and
    order, not by the tail: entries appended after them change nothing."""
    ROOT, BENCH = _cases.ROOT, _cases.BENCH
    traffic, BASE, IV = _cases.traffic, _cases.BASE, _cases.IV
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    conf, cell = confs["promdev_prom_miss_1m"], cells["adhoc_prom_miss"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "promdev_prom_miss_1m", "adhoc", 1)
    for entries, first, then in (
            (bench["configs"], confs["promdev_prom_1m"], conf),
            (bench["workloads"], cells["adhoc_prom"], cell)):
        assert entries.index(first) < entries.index(then)
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    with open(os.path.join(ROOT, confs["promdev_prom_1m"]["file"])) as f:
        prom = json.load(f)
    assert d["source"] == conf["source"] and len(d["source"]) <= 200
    assert "scrape.go" in d["source"] and "StaleNaN" in d["source"] \
        and "timeseries-dev-source.conf" in d["source"]
    assert d["source"] != prom["source"]
    assert d["reduced"] == conf["reduced"] == [] and d["architecture"] is None
    for key in ("server", "series", "metric", "labels", "scrape_interval_ms",
                "fill_columns", "containers_per_scrape"):
        assert d[key] == prom[key], key
    assert d["data"] == "prom_miss" and "GB" in conf["why"]
    stated = dict(d["guarantees"])
    assert stated.pop("holes").startswith("a missed scrape is not a sample")
    assert stated == prom["guarantees"]
    assumed = dict(d["assumed"])
    for key in ("stream", "markers", "hole_runs"):
        assert key in assumed
    assert "0 mod 128" in assumed["stream"] and "k = 0" in assumed["stream"]
    assert "departure" in assumed["markers"]
    for key in ("stamp_law", "samples_per_series", "targets", "values",
                "scrape_ms", "fill_columns"):
        assert assumed[key] == prom["assumed"][key], key
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("query_p50_ms", "kernel_roofline_pct", "leaf_ms",
                 "demoted_rows_pct"):
        lists = metrics[name]["workloads"]
        assert lists.index("adhoc_prom") + 1 == lists.index(
            "adhoc_prom_miss"), name
    # (the two cells lead the list; a later cell appended after them, as
    # ISSUE 49 asks of ``adhoc_churn``, changes nothing they report)
    assert metrics["demoted_rows_pct"]["workloads"][:2] == [
        "adhoc_prom", "adhoc_prom_miss"]
    assert metrics["hole_cells_pct"] == {
        "name": "hole_cells_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fused kernel",
        "moves": "query_rate", "workloads": ["adhoc_prom_miss"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("demoted_rows_pct") + 1 == names.index(
        "hole_cells_pct")
    # promdev_prom_1m's own, as its file's case has them
    pc, pw = confs["promdev_prom_1m"], cells["adhoc_prom"]
    assert (pw["config"], pw["traffic"], pw["chips"]) == (
        "promdev_prom_1m", "adhoc", 1)
    assert prom["source"] == pc["source"] and prom["data"] == "prom"
    assert prom["reduced"] == pc["reduced"] == []
    assert "no missed scrape" in prom["assumed"]["stream"]
    with open(os.path.join(BENCH, "configs", "promdev_raw_1m.json")) as f:
        raw = json.load(f)
    assert len(prom["source"]) <= 200 and "scrape.go" in prom["source"] \
        and "2 ms" in prom["source"] \
        and "timeseries-dev-source.conf" in prom["source"]
    assert prom["architecture"] is None
    for key in ("server", "series", "metric", "labels", "scrape_interval_ms",
                "fill_columns", "containers_per_scrape"):
        assert prom[key] == raw[key], key
    stated = dict(prom["guarantees"])
    assert stated.pop("stamps") == ("a sample is stored under the stamp it "
                                    "came with; a raw selector returns that "
                                    "stamp")
    assert stated == raw["guarantees"]
    assert {"stamp_law", "samples_per_series", "targets", "stream", "values",
            "scrape_ms"} <= set(prom["assumed"])
    assert {k: v for k, v in metrics["demoted_rows_pct"].items()
            if k != "workloads"} == {
        "name": "demoted_rows_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fused kernel",
        "moves": "query_rate"}
    mix = traffic.load("adhoc")
    assert mix["expect_routes"] == ["fused"]
    gen = traffic.Generator(mix, 5, BASE + 720 * IV)
    assert all(r.end_ms <= BASE + 720 * IV for r in gen.warmup())
    for f in ("data/prom_miss/__init__.py", "data/prom_miss/datagen.py",
              "data/prom_miss/fill.py", "data/prom_miss/reference.py",
              "data/prom/__init__.py", "layers/hole_cells_pct.py",
              "layers/demoted_rows_pct.py", "control_holes.py",
              "control_stamps.py", "configs/promdev_prom_miss_1m.json",
              "configs/promdev_prom_1m.json"):
        assert os.path.isfile(os.path.join(BENCH, f)), f
