"""The data modules (``benchmark/data/``) and the harness's hold on them.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

- golden parity: ``counter`` gives, through the module's interface, the
  numbers that ``datagen.py``, ``reference.py``, ``kernelbytes.py`` and
  ``correct.readback`` gave on the parent tree before they moved
  (``golden_counter.json``): moved, not changed;
- the loader: a name with no module, a module that lacks a function and a
  configuration without ``"data"`` each stop with the names that are there,
  before JAX is touched;
- every module under ``benchmark/data/``, and the test double, is complete;
- the harness is open: the double's cell runs from a scratch copy to which
  it was added as new files only (``rehearse.dry_add``), and none of the
  general files knows a module by name;
- the readers this PR repaired, and the trace's lost stretches.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import data, run, tracedata  # noqa: E402

with open(os.path.join(HERE, "golden_counter.json")) as f:
    GOLD = json.load(f)
DEPLOY = {"metric": "m", "labels": {"groups": 8, "per_rack": 4},
          "scrape_interval_ms": 10000, "fill_columns": 720}
DOUBLE = os.path.join(HERE, "double")
GENERAL = ("run.py", "served.py", "load.py", "correct.py", "traffic.py")


@pytest.fixture(scope="module")
def counter():
    return data.load("counter")


@pytest.mark.parametrize("seed", sorted(GOLD["values"], key=int))
def test_counter_values_are_the_parents(counter, seed):
    sids, cols = GOLD["sids"], GOLD["cols"]
    want = np.asarray(GOLD["values"][seed], np.float64)
    got = counter.raw_values(int(seed), sids, cols, DEPLOY)
    assert got.dtype == np.float64 and (got == want).all()
    for j, k in enumerate(cols):
        sc = counter.scrape(int(seed), np.asarray(sids), k, DEPLOY)
        assert (sc["values"] == want[:, j]).all()
        assert (sc["ts"] == 1_700_000_000_000 + k * 10000).all()
        assert counter.scrape_ms(k, DEPLOY) == sc["ts"][0]


@pytest.mark.parametrize("i", range(len(GOLD["evaluate"]["answers"])))
def test_counter_answers_are_the_parents(counter, i):
    ev = GOLD["evaluate"]
    a = ev["answers"][i]
    from benchmark import traffic
    mix = traffic.load("adhoc")
    assert mix["queries"][a["qi"]]["promql"] == a["promql"]
    ref = mix["queries"][a["qi"]]["ref"]
    out_ts = np.arange(a["start_ms"], a["end_ms"] + 1, a["step_ms"])
    sids = np.arange(ev["sid_lo"], ev["sid_lo"] + ev["sid_n"])
    got = counter.evaluate(ev["seed"], sids, ref, out_ts, DEPLOY,
                           ev["head_col"])
    want = {tuple(map(tuple, k)): np.array(
        [np.nan if x is None else x for x in v]) for k, v in a["rows"]}
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], v)
    assert counter.query_bytes(1048576, ref, out_ts, DEPLOY, ev["head_col"],
                               768) == a["query_bytes"]


@pytest.mark.parametrize("p", GOLD["probes"], ids=lambda p: str(p["seed"]))
def test_counter_probes_are_the_parents(counter, p):
    got = counter.probes(p["seed"], np.arange(p["lo"], p["hi"]), p["col"],
                         DEPLOY, 2)
    assert [g["promql"] for g in got] == [f'm{{rack="r{r}"}}'
                                          for r in p["racks"]]
    for g, rack, want in zip(got, p["racks"], p["want"]):
        assert [lb for lb, _ in g["want"]] == [{"host": f"h{rack * 4 + j}"}
                                               for j in range(4)]
        assert (np.stack([v for _, v in g["want"]]) == np.asarray(want)).all()
        assert (g["start_ms"], g["end_ms"], g["step_ms"]) == (
            1_700_000_000_000 + (p["col"] - 3) * 10000,
            1_700_000_000_000 + p["col"] * 10000, 10000)


def test_fold_seed_is_the_parents(counter):
    for seed, word in GOLD["fold_seed"].items():
        assert counter.datagen.fold_seed(int(seed)) == word


def test_a_name_with_no_module_stops_with_the_names_that_are_there():
    with pytest.raises(SystemExit) as e:
        data.load("histogram")
    assert "histogram" in str(e.value) and "counter" in str(e.value)


def test_a_module_that_lacks_a_function_is_named_with_what_it_lacks(tmp_path):
    (tmp_path / "half.py").write_text(
        "def schema(): pass\ndef scrape(seed, ids, k, deploy): pass\n")
    with pytest.raises(SystemExit) as e:
        data.load("half", str(tmp_path))
    msg = str(e.value)
    assert "half" in msg and "evaluate" in msg and "probes" in msg
    assert "'scrape'" not in msg.split("exposes")[0]


def _scratch(tmp_path, deploy_edit):
    """A root whose one configuration is promdev_raw_1m's, edited."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs", "promdev_raw_1m.json")) as f:
        deploy = json.load(f)
    deploy_edit(deploy)
    home = tmp_path / "benchmark"
    (home / "configs").mkdir(parents=True)
    (home / "configs" / "promdev_raw_1m.json").write_text(json.dumps(deploy))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(BENCH, "traffic"), home / "traffic")
    os.symlink(os.path.join(BENCH, "data"), home / "data")
    return str(tmp_path)


@pytest.mark.parametrize("edit, said", [
    (lambda d: d.pop("data"), "names no data module"),
    (lambda d: d.update(data="gauge_delta8"), "no data module 'gauge_delta8'"),
])
def test_a_configuration_without_its_module_stops_before_jax(tmp_path, edit,
                                                             said):
    root = _scratch(tmp_path, edit)
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n"
            f"from benchmark import run\n"
            f"try:\n    run.chips_of('adhoc_cold', {root!r})\n"
            f"except SystemExit as e:\n    print(e)\n"
            f"print('jax' in sys.modules)\n")
    import subprocess
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120).stdout
    assert said in out and "counter" in out, out
    assert out.strip().endswith("False"), out        # JAX never imported


def _modules():
    return ([(n, data.HERE) for n in data.names()] +
            [(n, os.path.join(DOUBLE, "data"))
             for n in data.names(os.path.join(DOUBLE, "data"))])


@pytest.mark.parametrize("name, home", _modules())
def test_every_module_is_complete_and_documented(name, home):
    mod = data.load(name, home)
    for fn in data.INTERFACE:
        assert callable(getattr(mod, fn)), fn
        assert fn in data.__doc__, f"{fn} is not in the interface's text"
    assert mod.__doc__ and len(mod.__doc__) > 200
    # the reference side imports nothing of the program at import time
    src_dir = os.path.dirname(mod.__file__) if mod.__file__.endswith(
        "__init__.py") else None
    files = ([os.path.join(src_dir, f) for f in os.listdir(src_dir)
              if f.endswith(".py")] if src_dir else [mod.__file__])
    for path in files:
        with open(path) as fh:
            top = [ln for ln in fh if re.match(r"(from|import) ", ln)]
        assert not any("filodb_tpu" in ln for ln in top), (path, top)


def test_every_configuration_names_a_module_that_is_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["data"] in data.names(), c["file"]


@pytest.mark.parametrize("fname", GENERAL)
def test_the_general_files_know_no_kind_of_data(fname):
    """ISSUE 27's grep, kept: what a deployment's data is lives under
    ``benchmark/data/`` alone (a comment may still say what moved)."""
    with open(os.path.join(BENCH, fname)) as f:
        src = f.read()
    code = "\n".join(ln.split("#")[0] for ln in src.splitlines())
    code = re.sub(r'"""(.|\n)*?"""', "", code)
    for word in ("GAUGE", "counter_np", "n_host", '"labels"', "rack",
                 "datagen", "kernelbytes", "sine"):
        assert word not in code, (fname, word)
    assert not re.search(r"benchmark\.data\.\w", code), fname


def test_the_double_differs_from_counter_in_every_point(counter):
    sine = data.load("sine", os.path.join(DOUBLE, "data"))
    with open(os.path.join(DOUBLE, "configs", "sine_tiny.json")) as f:
        deploy = json.load(f)
    ids = np.arange(64)
    a, b = counter.series_labels(ids, DEPLOY), sine.series_labels(ids, deploy)
    assert not set(a) & set(b) - {"_metric_"} and a["_metric_"] != b["_metric_"]
    v = sine.raw_values(3, ids, np.arange(48), deploy)
    assert (np.diff(v, axis=1) < 0).any() and (np.diff(v, axis=1) > 0).any()
    assert sine.scrape_ms(5, deploy) % deploy["scrape_interval_ms"] != 0
    with open(os.path.join(DOUBLE, "traffic", "wave.json")) as f:
        mix = json.load(f)
    for q in mix["queries"]:
        assert not set(q["ref"]) & {"agg", "fn", "window_s", "by"}
        with pytest.raises(KeyError):
            counter.evaluate(3, ids, q["ref"], [counter.scrape_ms(40, DEPLOY)],
                             DEPLOY, 40)
    p = sine.probes(3, ids, 40, deploy, 1)[0]
    assert "floor=" in p["promql"] and len(p["want"][0][1]) == 3


def test_the_doubles_cell_runs_from_files_added_and_none_edited(tmp_path):
    from benchmark import rehearse
    root = str(tmp_path)
    (name,) = rehearse.dry_add(DOUBLE, root)
    res = rehearse.rehearse_cell(name, 3.0, 2**31 + 7, 0, root)
    assert res["failed"] == 0 and "query_rate" in res["metrics_reported"]
    # the scratch copy holds every file of the benchmark's unchanged
    for d in rehearse.BY_NAME:
        for f in os.listdir(os.path.join(BENCH, d)):
            src = os.path.join(BENCH, d, f)
            if os.path.isfile(src):
                with open(src, "rb") as x, open(
                        os.path.join(root, "benchmark", d, f), "rb") as y:
                    assert x.read() == y.read(), (d, f)


def test_a_deployment_that_would_edit_a_file_is_refused(tmp_path):
    from benchmark import rehearse
    fake = tmp_path / "double"
    (fake / "configs").mkdir(parents=True)
    (fake / "configs" / "promdev_raw_1m.json").write_text("{}")
    (fake / "entries.json").write_text('{"configs": [], "workloads": []}')
    with pytest.raises(RuntimeError, match="edits none"):
        rehearse.dry_add(str(fake), str(tmp_path / "root"))


class _Narrow:
    """A store of 1-byte values: a quarter of counter's bytes a query."""
    @staticmethod
    def query_bytes(rows, ref, out_ts, deploy, head_col, capacity):
        return float(rows * 100)


def _kernel_ctx(data_mod):
    class Req:
        qi = 0

        @staticmethod
        def out_ts():
            return np.arange(1_700_000_000_000 + 3_600_000,
                             1_700_000_000_000 + 7_200_001, 60_000)
    rec = {"path": "local-fused[pallas]", "req": Req, "t1": 1.0}
    ev = ["%x = f32[8] custom-call(), custom_call_target=\"tpu_custom_call\"",
          1e9, 2e6]
    return {"trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": [ev]}]}]},
            "tw0_ns": 0.0, "w1_ns": 10e9, "done_traced": [rec],
            "peaks": {"kernel_names": ["tpu_custom_call"]},
            "peak": {"hbm_bytes_per_s": 819e9}, "deploy": DEPLOY,
            "mix": {"queries": [{"ref": {"agg": "sum", "fn": "rate",
                                         "window_s": 300, "by": []}}]},
            "data": data_mod, "head_col": 720, "rows_per_shard": [1 << 20],
            "capacity": 768}


def test_the_roofline_takes_its_bytes_from_the_module(counter):
    read = run.load_layer("kernel_roofline_pct").read
    full = read(_kernel_ctx(counter))
    need = counter.query_bytes(1 << 20, {"window_s": 300},
                               _kernel_ctx(counter)["done_traced"][0]["req"].out_ts(),
                               DEPLOY, 720, 768)
    assert full == pytest.approx(100 * need / 819e9 / 2e-3)
    narrow = read(_kernel_ctx(_Narrow))
    assert narrow == pytest.approx(100 * (1 << 20) * 100 / 819e9 / 2e-3)
    assert narrow < full
    assert run.load_layer("kernel_ms").read(_kernel_ctx(counter)) == 2.0


def test_gc_pause_reads_zero_in_a_window_without_a_collection():
    read = run.load_layer("gc_pause_pct").read
    spans = [{"name": "query", "trace_id": "a", "t0": 1.0, "dur_s": 0.1,
              "tags": {}}]
    assert read({"spans": spans, "w0_ns": 0.0, "w1_ns": 10e9}) == 0.0
    assert read({"spans": [], "w0_ns": 0.0, "w1_ns": 10e9}) is None


def _plane(name, events):
    return {"name": name, "lines": [{"name": "XLA Ops", "events": [
        ["op", float(s), float(d)] for s, d in events]}]}


def test_a_stretch_with_queries_and_no_events_is_lost_not_idle():
    ms = 1e6
    # chip 0 goes dark from 2000 to 8000 ms while chip 1 keeps working;
    # both are idle together from 9000 to 9400 ms (nobody asked)
    busy = [(t * ms, 50 * ms) for t in range(0, 10000, 100)]
    c0 = [e for e in busy if not 2000 * ms <= e[0] < 8000 * ms
          and not 9000 * ms <= e[0] < 9400 * ms]
    c1 = [e for e in busy if not 9000 * ms <= e[0] < 9400 * ms]
    tr = {"planes": [_plane("/device:TPU:0", c0), _plane("/device:TPU:1", c1)]}
    proofs = [(t * ms, (t + 80) * ms) for t in range(0, 9000, 100)]
    assert tracedata.event_counts(tr) == [len(c0), len(c1)]
    lost = tracedata.holes(tr, 0.0, 10000 * ms, proofs)
    assert len(lost) == 1
    a, b = lost[0]
    assert a == pytest.approx(1950 * ms) and b == pytest.approx(8000 * ms)
    cut = tracedata.without(tr, lost)
    # chip 1's events of the lost stretch are not read either
    assert tracedata.event_counts(cut)[1] < len(c1)
    whole = tracedata.busy_seconds(cut, 0.0, 10000 * ms)
    traced_s = (10000 * ms - (b - a)) / 1e9
    assert whole / traced_s == pytest.approx(0.5, abs=0.06)
    # read as idle, the hole would have made chip 0 look two thirds idle
    assert tracedata.busy_seconds(tr, 0.0, 10000 * ms) / 10.0 < 0.40
    gaps = tracedata.idle_gaps(cut, 0.0, 10000 * ms, [], cuts=lost)
    assert gaps[0][1] == pytest.approx(0.45)       # the true idle stretch
    # no proof, no hole: an idle device is idle
    assert tracedata.holes(tr, 0.0, 10000 * ms, []) == []
