"""The benchmark's data modules, guarded by tier-1.

``benchmark/tests/`` holds the data modules' own cases — ``counter``'s golden
parity, the loader, the open harness (``test_data.py``) and ``hist``'s
golden parity, generator, reference, fill and files (``test_hist_data.py``)
and ``prom``'s — the stamp law, the true-stamp reference, the fill of a
line store, probes, reader, control and files (``test_prom_data.py``) —
and ``prom_miss``'s: the miss law, the reference over the samples that
exist, the fill of a hole store, probes, reader, control and files
(``test_prom_miss_data.py``), and the cases of the five readers of what a
worker waits for (``test_wait_layers.py``, PR 39) and of ``fall_tiles_pct``'s
reader (``test_fall_layer.py``, PR 40), and ``tsbs_cpu``'s: the walk and the
tags, the reference against its brute-force twin, the fill, the served path
for the twelve text kinds, probes, the generated traffic file, the files
(``test_tsbs_data.py``) with the four readers of what a narrow leaf does
(``test_tsbs_layers.py``, PR 41) and the reader of how often a gathered
leaf ran as one program (``test_gather_fused_layer.py``, PR 42), and
``tsbs_cpu_d8``'s: the fill's deltas, the checks of a narrow store, the
reference at 12 h against the twin, the served path over the 12 h mix,
probes over the whole depth, the files, the three readers of the flush's
form and the cell dry-added (``test_tsbs_d8_data.py``, PR 44), every case
under a name of its own.
They run in seconds on the CPU, and what they pin is the yardstick: tier-1
collects them here, under their own names, so that the floor counts them.
"""

import pytest

for _mod in ("benchmark.tests.test_data", "benchmark.tests.test_hist_data",
             "benchmark.tests.test_prom_data",
             "benchmark.tests.test_prom_miss_data",
             "benchmark.tests.test_wait_layers",
             "benchmark.tests.test_fall_layer",
             "benchmark.tests.test_tsbs_data",
             "benchmark.tests.test_tsbs_layers",
             "benchmark.tests.test_gather_fused_layer",
             "benchmark.tests.test_tsbs_d8_data"):
    pytest.register_assert_rewrite(_mod)

from benchmark.tests.test_data import *        # noqa: E402,F401,F403
from benchmark.tests.test_hist_data import *   # noqa: E402,F401,F403
from benchmark.tests.test_prom_data import *   # noqa: E402,F401,F403
from benchmark.tests.test_prom_miss_data import *   # noqa: E402,F401,F403
from benchmark.tests.test_wait_layers import *      # noqa: E402,F401,F403
from benchmark.tests.test_fall_layer import *       # noqa: E402,F401,F403
from benchmark.tests.test_tsbs_data import *        # noqa: E402,F401,F403
from benchmark.tests.test_tsbs_layers import *      # noqa: E402,F401,F403
from benchmark.tests.test_gather_fused_layer import *   # noqa: E402,F401,F403
from benchmark.tests.test_tsbs_d8_data import *         # noqa: E402,F401,F403


# Cases of those files that a star import alone does not give tier-1:

import json                                                 # noqa: E402
import os                                                   # noqa: E402

from benchmark.tests import test_hist_data as _hist_cases   # noqa: E402
from benchmark.tests import test_prom_data as _prom_cases   # noqa: E402
from benchmark.tests import test_prom_miss_data as _miss_cases  # noqa: E402
from benchmark.tests import test_wait_layers as _wait_cases     # noqa: E402
from benchmark.tests import test_gather_fused_layer as _fused_cases  # noqa: E402
from benchmark.tests import test_tsbs_data as _tsbs_cases       # noqa: E402

# ``hist``'s fill case bears the name of ``prom``'s, which the later import
# shadows: collected here under a name of its own
test_hist_fill_leaves_the_store_the_write_path_would = \
    _hist_cases.test_fill_leaves_the_store_the_write_path_would


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_prom_data.py pins adhoc_prom to the END of the "
    "workloads lists of query_p50_ms, kernel_roofline_pct and leaf_ms, "
    "demoted_rows_pct's list to adhoc_prom alone and demoted_rows_pct to "
    "the end of per_layer; PR 35 appended adhoc_prom_miss and "
    "hole_cells_pct, as ISSUE 35 asks, and may edit no file the benchmark "
    "has. A `benchmark` PR has to make that case test membership, not the "
    "tail (ROADMAP.md queue 2 item 0 (12)); the rest of what it says of "
    "promdev_prom_1m is held by test_the_prom_cells_are_as_named_whatever_"
    "follows_them"))
def test_the_configuration_and_the_cell_are_as_named():
    _prom_cases.test_the_configuration_and_the_cell_are_as_named()


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_prom_miss_data.py pins per_layer's last two names "
    "to demoted_rows_pct and hole_cells_pct; PR 39 appended its five "
    "readers' entries, as ISSUE 39 asks, and may edit no file the benchmark "
    "has. A `benchmark` PR has to make that case test membership, not the "
    "tail (ROADMAP.md queue 2 item 0 (12)); everything else it says of "
    "promdev_prom_miss_1m and promdev_prom_1m is held by "
    "test_the_prom_cells_are_as_named_whatever_follows_them"))
def test_prom_miss_configuration_and_cell_are_as_named():
    _miss_cases.test_prom_miss_configuration_and_cell_are_as_named()


@pytest.mark.xfail(strict=True, reason=(
    "benchmark/tests/test_wait_layers.py pins device_ahead_mean's entry to "
    "exactly six keys, none of them `workloads`. PR 41's cell tsbs_single "
    "reports query_p50_ms, which that metric moves, and runs no fused "
    "program, so the reader finds nothing there: the entry gained the list "
    "of the cells that do report it, as the contract asks, and PR 41 may "
    "edit no file the benchmark has. A `benchmark` PR has to make that case "
    "compare the six keys and leave `workloads` to its own (ROADMAP.md queue "
    "2 item 0 (12)); everything else it says of the five entries is held by "
    "test_the_five_wait_entries_are_as_named_whatever_cells_they_list"))
def test_benchmark_json_lists_the_five_with_their_layers():     # noqa: F811
    _wait_cases.test_benchmark_json_lists_the_five_with_their_layers()


def test_the_five_wait_entries_are_as_named_whatever_cells_they_list():
    """What the pinned case above says of PR 39's five entries, key by key,
    with ``workloads`` — where an entry has one — held to the cells that
    report the metric it moves and whose leaves run what it reads."""
    with open(os.path.join(_miss_cases.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    want = {"lock_hold_pct": ("%", "leaf under the shard lock", "query_rate"),
            "lock_hold_mean_ms": ("ms", "leaf under the shard lock",
                                  "query_rate"),
            "device_ahead_mean": ("programs", "fused kernel", "query_p50_ms"),
            "wakeup_mean_ms": ("ms", "runtime", "query_rate"),
            "stall_max_ms": ("ms", "runtime", "query_rate")}
    assert tuple(want) == _wait_cases.WAIT_LAYERS
    for name, (unit, layer, moves) in want.items():
        entry = dict(per_layer[name])
        cells = entry.pop("workloads", None)
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": "program_span", "layer": layer,
                         "moves": moves}, name
        assert os.path.isfile(os.path.join(_miss_cases.BENCH, "layers",
                                           f"{name}.py"))
        assert (cells is None) == (name != "device_ahead_mean"), name
    p50 = next(m for m in bench["end_to_end"] if m["name"] == "query_p50_ms")
    assert per_layer["device_ahead_mean"]["workloads"] == [
        c for c in p50["workloads"] if not c.startswith("tsbs_single")]


def test_the_prom_cells_are_as_named_whatever_follows_them():
    """What the two pinned cases above say of ``promdev_prom_1m`` x
    ``adhoc`` and ``promdev_prom_miss_1m`` x ``adhoc``, by membership and
    order, not by the tail: entries appended after them change nothing."""
    ROOT, BENCH = _miss_cases.ROOT, _miss_cases.BENCH
    traffic, BASE, IV = _miss_cases.traffic, _miss_cases.BASE, _miss_cases.IV
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
    assert metrics["demoted_rows_pct"]["workloads"] == [
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


_PR44 = (
    "{file} pins the `workloads` of {what} to [\"tsbs_single\"] alone; PR 44 "
    "appended its cell tsbs_single_12h to those lists, as ISSUE 44 asks (the "
    "cell runs the same narrow leaf and reports what they read), and may "
    "edit no file the benchmark has. A `benchmark` PR has to make that case "
    "test membership, not equality (ROADMAP.md queue 2 item 0 (12)); "
    "everything else it says is held by {held}")


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="benchmark/tests/test_tsbs_data.py",
    what="gather_mean_ms, selected_series_mean, matcher_miss_pct and "
         "leaf_device_ms",
    held="test_the_tsbs_cells_are_as_named_whatever_follows_them"))
def test_tsbs_configuration_cell_and_layers_are_as_named():     # noqa: F811
    _tsbs_cases.test_tsbs_configuration_cell_and_layers_are_as_named()


@pytest.mark.xfail(strict=True, reason=_PR44.format(
    file="benchmark/tests/test_gather_fused_layer.py",
    what="gather_fused_pct",
    held="test_the_tsbs_cells_are_as_named_whatever_follows_them"))
def test_the_entry_is_as_the_issue_names_it():                  # noqa: F811
    _fused_cases.test_the_entry_is_as_the_issue_names_it()


def test_the_tsbs_cells_are_as_named_whatever_follows_them():
    """What the two pinned cases above say of PR 41's four entries and PR
    42's one, key by key, with ``workloads`` held to the cells whose leaves
    gather — ``tsbs_single`` first — and of ``tsbs_cpu_100k`` x
    ``tsbs_single`` themselves, by membership."""
    ROOT, BENCH = _tsbs_cases.ROOT, _tsbs_cases.BENCH
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    gathering = [w["name"] for w in bench["workloads"]
                 if w["traffic"].startswith("tsbs_single")]
    assert gathering[0] == "tsbs_single"
    for name, unit, better, source in (
            ("gather_mean_ms", "ms", "lower", "program_span"),
            ("selected_series_mean", "series", "lower", "program_span"),
            ("matcher_miss_pct", "%", "lower", "program_span"),
            ("leaf_device_ms", "ms", "lower", "device_trace"),
            ("gather_fused_pct", "%", "higher", "program_span")):
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "leaf under the shard lock", "moves": "query_rate",
            "workloads": gathering}, name
        assert os.path.isfile(os.path.join(BENCH, "layers", f"{name}.py"))
    confs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    conf, cell = confs["tsbs_cpu_100k"], cells["tsbs_single"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tsbs_cpu_100k", "tsbs_single", 1)
    assert "9.66 GB" in cell["why"] and "8 rows of 2^20" in cell["why"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    assert d["source"] == conf["source"] and d["data"] == "tsbs_cpu"
    assert d["reduced"] == conf["reduced"] == ["history"]
    assert (d["series"], d["hosts"], d["fill_columns"]) == (
        1_000_000, 100_000, 720)
    # accepted metrics whose readers find nothing where no fused program
    # and no grouping runs: listed for the cells that do report them
    fused = [w["name"] for w in bench["workloads"]
             if w["name"] not in gathering]
    for name in ("groupids_mean_ms", "kernel_host_mean_ms"):
        assert per_layer[name]["workloads"] == fused, name
    assert per_layer["device_ahead_mean"]["workloads"] == [
        c for c in fused if c != "dash_live"]
