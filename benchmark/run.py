#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, on the attached TPU.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s): it starts a ``FiloServer`` in-process,
registers every series through the write path, fills the history on the
device from the seed, warms the cell's shapes, then measures for
``--seconds`` with live ingest underneath, checks what the window produced
against the plain reference, and prints the contract's JSON object as the
last line of stdout. Without a TPU (or with fewer chips than the cell asks
for) it prints no such line and exits non-zero.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one kind of data is a file of its own, found by name:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``layers/<metric>.py`` by the names in BENCHMARK.json, and
``data/<name>.py`` by the ``"data"`` key of the configuration's file: the
module that says what the deployment's series, scrapes, device-resident
history, reference answers, read-back probe and needed bytes are
(``benchmark/data/__init__.py`` documents its interface). This file and
``served.py``, ``load.py``, ``correct.py``, ``traffic.py`` know none of
that themselves, so a deployment with another kind of store is added as
new files only.
"""

from __future__ import annotations

import time

_T_PROC = time.perf_counter()     # set-up is counted from here

import argparse                   # noqa: E402
import importlib.util             # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import shutil                     # noqa: E402
import sys                        # noqa: E402
import tempfile                   # noqa: E402
import threading                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np                # noqa: E402

DRAIN_S = 120.0                   # in-flight queries and containers may take this long
# The profiler's trace of ONE chip over a 51 s window is whole (12-13 MB);
# of four chips over 51 s (37 MB) it has lost seconds of a chip's events
# (PERF.md §5, PR 26). Its volume grows with chips x seconds, so a cell on
# n chips is traced for the last 51 / n seconds of its window.
TRACE_CHIP_SECONDS = 51.0


def find_device(chips: int) -> dict:
    """The device as JAX reports it — a TPU, ``chips`` of them — or no run."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (jax.devices()[0].platform = "
                         f"{devs[0].platform!r}); a cell only runs on the chip")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileClock:
    """Backend compiles (count, seconds) and persistent-cache hits, from
    JAX's own monitoring events (a cache hit skips the backend compile)."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs
            self.compiles += 1

    def _ev(self, name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.cache_hits, self.seconds


def home_of(root: str) -> str:
    """The benchmark's directory under ``root``: where files are found by name."""
    return os.path.join(root, os.path.basename(HERE))


def load_cell(name: str, root: str = ROOT) -> tuple:
    """(BENCHMARK.json, the cell's entry, its configuration's file, its
    mix, its data module), each found by name under ``root`` — the checkout,
    or the scratch copy in which the rehearsal dry-adds a deployment. No JAX
    is touched: a name with no file stops the run before the device check."""
    home = home_of(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        deploy = json.load(f)
    from benchmark import data, traffic
    data_home = os.path.join(home, "data")
    if "data" not in deploy:
        raise SystemExit(f"benchmark: {conf['file']} names no data module "
                         f"under \"data\"; {data_home} has "
                         f"{data.names(data_home)}")
    return (bench, cell, deploy, traffic.load(cell["traffic"], home),
            data.load(deploy["data"], data_home))


def chips_of(workload: str, root: str = ROOT) -> int:
    """Chips the cell asks for (no JAX touched: the device check needs it)."""
    return int(load_cell(workload, root)[1]["chips"])


def metrics_of(bench: dict, cell: dict, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def load_layer(name: str, home: str = HERE):
    path = os.path.join(home, "layers", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark.layers.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class SpanDrain(threading.Thread):
    """Empties the program's span ring (4096 spans) as the window goes."""

    def __init__(self):
        super().__init__(name="bench-spans", daemon=True)
        from filodb_tpu.utils.tracing import tracer
        self.tracer, self.spans, self._halt = tracer, [], threading.Event()

    def take(self) -> None:
        self.spans += self.tracer.drain()

    def run(self) -> None:
        while not self._halt.wait(0.25):
            self.take()

    def halt(self) -> None:
        self._halt.set()
        self.join(5)
        self.take()


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def host_memory(run_dir: str) -> str:
    """Peak resident memory of this process and what the run has written
    under its temporary directory (the bus logs), for the earlier lines."""
    import resource
    written = sum(os.path.getsize(os.path.join(d, f))
                  for d, _dirs, files in os.walk(run_dir) for f in files)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return (f"host: peak RSS {rss / 2**30:.2f} GiB, {written / 2**30:.2f} "
            f"GiB under the run's temporary directory")


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Refused(Exception):
    """Set-up found the system not as the cell needs it: no result line."""


def set_up(s: dict, deploy: dict, mix: dict, seed: int, run_dir: str,
           clock, strict: bool = True) -> None:
    """Server, registration, device fill, the first live scrape through the
    write path, the exact read-back, the warm-up. Everything the window
    needs goes into ``s`` as it comes to be, the server first, so that the
    caller can stop it whatever happens after."""
    from benchmark import correct, load, served, traffic
    log = served.log
    data = s["data"]
    fill_cols = int(deploy["fill_columns"])
    t = time.perf_counter()
    srv = s["srv"] = served.start_server(deploy, run_dir)
    s.update(dataset=srv.config["dataset"], port=srv.http.port)
    t_server = time.perf_counter() - t
    built = served.build(srv, deploy, seed, data)
    s.update(writers=built["writers"], sids=built["sids"])

    # the first live scrape goes through the write path, unpaced, and must
    # land in column fill_cols of every series
    t = time.perf_counter()
    for w in s["writers"]:
        for j in range(len(w.templates)):
            w.publish(j, fill_cols, seed)
    for w in s["writers"]:
        w.drain()
        sid = built["sid_of"][w.shard_num]
        if not data.landed(w.shard, np.flatnonzero(sid >= 0),
                           fill_cols).all():
            raise Refused(f"shard {w.shard_num}: the first live scrape did "
                          f"not land in column {fill_cols}")
    s["head_col"] = fill_cols
    s["head_ms"] = data.scrape_ms(fill_cols, deploy)
    last = s["writers"][-1]
    probe = {"writer": last, "row": 0, "col": fill_cols,
             "rows": last.templates[0][1]}
    e, lines = correct.readback(s["port"], s["dataset"], deploy, seed, probe, 1)
    for ln in lines:
        log(f"set-up read-back: {ln}")
    if e != 0 and strict:
        raise Refused("the filled store does not return the generator's "
                      "values exactly")
    t_head = time.perf_counter() - t

    s["gen"] = traffic.Generator(mix, seed, s["head_ms"])
    s["clients"] = load.Clients(s["gen"], s["port"], s["dataset"])
    t = time.perf_counter()
    warm = s["gen"].warmup()
    for req in warm:
        rec = s["clients"].issue(req)
        if not rec["ok"]:
            raise Refused(f"warm-up query failed ({rec['code']}): {req}")
    t_warm = time.perf_counter() - t
    slowest = max(r["t1"] - r["t0"] for r in s["clients"].records)
    s["clients"].records = []
    log(f"set-up: server {t_server:.1f} s; fill "
        f"{ {k: round(v, 1) for k, v in built['seconds'].items()} }; first "
        f"live scrape + exact read-back {t_head:.1f} s; {len(warm)} warm-up "
        f"queries {t_warm:.1f} s (slowest {slowest:.1f} s); compiles so far "
        f"{clock.compiles} ({clock.seconds:.1f} s), persistent-cache hits "
        f"{clock.cache_hits}")


class GcWatch:
    """Full (generation 2) collections of this process, the server's too:
    with ~10^7 objects of index and part keys alive one of them stops every
    thread for seconds. Counted, not prevented: the program runs as shipped."""

    def __init__(self):
        import gc
        self.pauses: list[tuple[float, float]] = []     # (start, seconds)
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t))
            self._t = None

    def close(self) -> None:
        import gc
        gc.callbacks.remove(self._cb)

    def between(self, t0: float, t1: float) -> str:
        inside = [d for t, d in self.pauses if t0 <= t <= t1]
        return (f"full garbage collections: {len(inside)} inside the window "
                f"({sum(inside):.2f} s, longest {max(inside, default=0):.2f} "
                f"s), {len(self.pauses)} in the run "
                f"({sum(d for _, d in self.pauses):.2f} s, longest "
                f"{max((d for _, d in self.pauses), default=0):.2f} s)")


def measure(s: dict, deploy: dict, mix: dict, seed: int, seconds: float,
            trace_dir: str | None, clock, chips: int = 1) -> dict:
    """The window: closed-loop clients over live ingest, for ``seconds``;
    then no new queries, and those in flight and the containers on their
    way get DRAIN_S to arrive. With ``trace_dir``: the program's spans of
    the whole window, and the profiler's trace of its last
    ``TRACE_CHIP_SECONDS / chips`` seconds (all of it on one chip)."""
    import jax
    from benchmark import load, served, tracedata
    log = served.log
    scraper = load.Scraper(s["writers"], deploy, seed, s["head_col"] + 1)
    poller = load.LagPoller(scraper)
    spans = SpanDrain() if trace_dir else None
    w = {"sync_perf": None, "spans": spans}
    untraced_s = max(0.0, seconds - TRACE_CHIP_SECONDS / chips)

    def start_trace() -> None:
        po = jax.profiler.ProfileOptions()
        po.host_tracer_level, po.python_tracer_level = 1, 0
        jax.profiler.start_trace(trace_dir, profiler_options=po)
        w["sync_perf"] = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracedata.SYNC):
            time.sleep(0.002)

    if trace_dir:
        if not untraced_s:
            start_trace()
        spans.tracer.drain()
        spans.start()
    scraper.start()
    poller.start()
    before = clock.snapshot()
    w["wall0"] = time.time()
    t0 = w["t0"] = time.perf_counter()
    w["setup_s"] = t0 - _T_PROC
    clients = s["clients"]
    clients.start()
    if trace_dir and untraced_s:
        time.sleep(max(0.0, untraced_s - (time.perf_counter() - t0)))
        start_trace()
    time.sleep(max(0.0, seconds - (time.perf_counter() - t0)))
    t1 = w["t1"] = time.perf_counter()
    in_flight = clients.stop(DRAIN_S)
    scraper.halt()
    scraper.join(30)
    t_drained = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
        spans.halt()
    deadline = time.perf_counter() + DRAIN_S
    while scraper.sent and poller.poll() and time.perf_counter() < deadline:
        time.sleep(0.01)
    poller.halt()
    after = clock.snapshot()
    if scraper.error is not None:
        raise Refused(f"the scraper died: {scraper.error!r}")

    recs = clients.records
    for r in recs:
        r["head_ms"] = s["head_ms"]
    ok = [r for r in recs if r["ok"]]
    sent = [c for c in scraper.sent if c["t"] <= t1]
    landed = [c for c in sent if c["landed"] is not None]
    w.update(recs=recs, ok=ok, done_in=[r for r in ok if r["t1"] <= t1],
             sent=sent, landed=landed, in_flight=in_flight,
             lag_ms=[(c["landed"] - c["t"]) * 1000.0 for c in landed])
    codes: dict = {}
    for r in recs:
        codes[r["code"]] = codes.get(r["code"], 0) + 1
    log(f"window {t1 - t0:.2f} s: {len(recs)} queries issued, "
        f"{len(w['done_in'])} answered inside it, {len(ok)} answered in all "
        f"(drain {t_drained - t1:.1f} s), HTTP codes {codes}, {in_flight} "
        f"still in flight; {len(sent)} containers "
        f"({sum(c['rows'] for c in sent)} rows) acknowledged, {len(landed)} "
        f"in the store; scraper at most {scraper.late_s * 1000:.0f} ms late")
    log(s["gc"].between(t0, t1))
    log(f"compiles inside the window: {after[0] - before[0]} "
        f"({after[2] - before[2]:.2f} s), persistent-cache hits inside it: "
        f"{after[1] - before[1]} (both should be 0); run total {after[0]} "
        f"compiles {after[2]:.1f} s, {after[1]} cache hits")
    return w


def end_to_end(w: dict) -> dict:
    from benchmark.served import log
    t0, t1 = w["t0"], w["t1"]
    e2e = {"setup_s": w["setup_s"],
           "query_rate": len(w["done_in"]) / (t1 - t0)}
    lat = [(r["t1"] - r["t0"]) * 1000.0 for r in w["ok"]]
    if lat:
        e2e["query_p50_ms"] = percentile(lat, 50)
        e2e["query_p95_ms"] = percentile(lat, 95)
        log(f"latency over {len(lat)} answers: p50 "
            f"{e2e['query_p50_ms']:.1f} ms p95 {e2e['query_p95_ms']:.1f} "
            f"ms max {max(lat):.1f} ms; deciles "
            f"{[round(percentile(lat, q)) for q in range(10, 100, 10)]}")
    rows = sum(c["rows"] for c in w["sent"])
    in_rows = sum(c["rows"] for c in w["landed"] if c["landed"] <= t1)
    log(f"ingest: {rows} rows acknowledged in the window "
        f"({rows / (t1 - t0):.0f} rows/s offered), {in_rows} of them in the "
        f"store before it closed ({in_rows / (t1 - t0):.0f} rows/s)")
    if w["lag_ms"]:
        took = max(c["landed"] for c in w["landed"]) - t0
        log(f"ingest lag over {len(w['lag_ms'])} of {len(w['sent'])} "
            f"containers: mean {float(np.mean(w['lag_ms'])):.1f} ms max "
            f"{max(w['lag_ms']):.1f} ms; the last landed {took:.2f} s after "
            f"the window opened")
    return e2e


def decide_correct(s: dict, w: dict, deploy: dict, mix: dict, seed: int,
                   allow_interpret: bool) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): each number compared, printed
    beside its limit (benchmark/correct.py)."""
    from benchmark import correct
    from benchmark.served import log
    t = time.perf_counter()
    g = deploy["guarantees"]
    picked = correct.sample_answers(w["ok"], seed)
    err, lines = correct.check_answers(picked, mix, deploy, seed, s["sids"],
                                       s["head_col"], s["data"])
    for ln in lines:
        log(f"compared: {ln}")
    if w["landed"]:
        rb, rb_lines = correct.readback(s["port"], s["dataset"], deploy, seed,
                                        w["landed"][-1])
    else:
        rb, rb_lines = float("inf"), ["no container landed"]
    for ln in rb_lines:
        log(f"read back: {ln}")
    off, seen = correct.routes_off(w["ok"], mix["expect_routes"],
                                   allow_interpret)
    log(f"routes: {seen}")
    good, numbers = bool(picked), {}
    for name, val, lim, what in (
            ("answers_err", err, 1.0, f"{len(picked)} answers of the window "
             f"against the f64 reference, in units of atol {g['atol']} + "
             f"rtol {g['rtol']}"),
            ("readback_abs", rb, 0.0, "last landed scrape through a raw "
             "selector"),
            ("routes_off", off, 0.0, "answers on a route the mix does not "
             "expect")):
        log(f"correct: {name} = {val:.6g} (limit {lim:g}) — {what}")
        good &= bool(val <= lim)
        numbers[name] = {"value": min(float(val), 1e308), "limit": lim}
    log(f"reference and comparison took {time.perf_counter() - t:.1f} s")
    return good, numbers


def per_layer(bench: dict, cell: dict, s: dict, w: dict, deploy: dict,
              mix: dict, device: dict,
              trace_dir: str) -> tuple[dict, dict, dict]:
    """(metrics, device fields, breakdown) of the traced run: each metric
    from its own reader, ``benchmark/layers/<name>.py``."""
    from benchmark import tracedata
    from benchmark.layers import _kernels
    from benchmark.served import log
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device["kind"] not in peaks["devices"]:
        raise Refused(f"no peaks for device kind {device['kind']!r} in "
                      f"benchmark/peaks.json")
    tr = tracedata.extract(trace_dir)
    if tr["sync_ns"] is None:
        log("trace: the sync annotation is missing; the host clock is tied "
            "to the start of the trace instead")
        tr["sync_ns"] = 0.0
    t0, t1 = w["t0"], w["t1"]

    def to_ns(perf_t: float) -> float:
        return tr["sync_ns"] + (perf_t - w["sync_perf"]) * 1e9

    w0, w1 = to_ns(t0), to_ns(t1)
    tw0 = max(w0, tr["sync_ns"])      # the traced part of the window
    wall_to_perf = t0 - w["wall0"]
    spans = [{"name": x.name, "trace_id": x.trace_id,
              "t0": x.start_us / 1e6 + wall_to_perf,
              "dur_s": x.duration_us / 1e6, "tags": dict(x.tags)}
             for x in w["spans"].spans]
    spans = [x for x in spans if t0 <= x["t0"] <= t1]
    # a query that executed ran a device operation between its span's ends:
    # a chip that shows none while such queries came and went has lost them
    proofs = [(to_ns(x["t0"]), to_ns(x["t0"] + x["dur_s"])) for x in spans
              if x["name"] == "query" and x["tags"].get("status") == "ok"
              and not str(x["tags"].get("exec_path", "")).startswith(
                  _kernels.CACHE_ANSWERS)]
    counts = tracedata.event_counts(tr)
    lost = tracedata.holes(tr, tw0, w1, proofs)
    log(f"trace: {tr['bytes']} bytes over the window's last "
        f"{(w1 - tw0) / 1e9:.2f} s; operation events a chip {counts}; "
        f"{len(proofs)} executed queries prove the device at work")
    for a, b in lost:
        log(f"trace: a chip shows no event for {(b - a) / 1e9:.2f} s from "
            f"{(a - w0) / 1e9:.2f} s into the window while queries executed: "
            f"its events are LOST there; that stretch is cut out of every "
            f"chip's trace and of window_s, not read as idle")
    tr = tracedata.without(tr, lost)
    traced_ns = (w1 - tw0) - sum(b - a for a, b in lost)

    def in_trace(perf_t: float) -> bool:
        t = to_ns(perf_t)
        return tw0 <= t <= w1 and not any(a <= t <= b for a, b in lost)

    writers = s["writers"]
    ctx = {"records": w["ok"], "done_in": w["done_in"], "spans": spans,
           "trace": tr, "w0_ns": w0, "w1_ns": w1, "tw0_ns": tw0,
           "done_traced": [r for r in w["done_in"] if in_trace(r["t1"])],
           "peaks": peaks, "peak": peaks["devices"][device["kind"]],
           "deploy": deploy, "mix": mix, "data": s["data"],
           "head_col": s["head_col"],
           "rows_per_shard": [int(x.shard.store.S) for x in writers],
           "capacity": int(writers[0].shard.store.C)}
    out = {}
    for m in metrics_of(bench, cell, "per_layer"):
        v = load_layer(m["name"], s["home"]).read(ctx)
        if v is None:
            log(f"layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        log(f"layer metric {m['name']} = {v:.6g} {m['unit']} [{m['layer']}]")
    busy = tracedata.busy_seconds(tr, tw0, w1)
    dev = {"busy_s": busy, "window_s": traced_ns / 1e9}
    host = [[x["name"], to_ns(x["t0"]), to_ns(x["t0"] + x["dur_s"])]
            for x in spans]
    breakdown = {"device_ops": tracedata.top_ops(tr, tw0, w1),
                 "idle_gaps": tracedata.idle_gaps(tr, tw0, w1, host,
                                                  cuts=lost)}
    log(f"trace: {len(spans)} program spans in the window; device busy "
        f"{busy:.3f} s of {dev['window_s']:.2f} s traced (idle "
        f"{100 * (1 - busy / dev['window_s']):.1f} %)")
    return out, dev, breakdown


def run(args, device: dict, allow_interpret: bool = False,
        shrink: dict | None = None, strict_setup: bool = True,
        root: str = ROOT) -> dict | None:
    """Everything after the device check. Returns the result object, or
    None when set-up found the system not as the cell needs it.
    ``allow_interpret`` and ``shrink`` are the CPU rehearsal's
    (benchmark/rehearse.py): interpreted kernels pass the route check, and
    the deployment's sizes are replaced by tiny ones. ``strict_setup``
    False is the control's (benchmark/control.py): set-up's exact read-back
    is printed and not enforced, so that the window's own answers get
    compared under the lower precision. ``root`` is where the cell's
    files are found by name (``load_cell``)."""
    import jax
    from benchmark import served
    from filodb_tpu.core import native as partset
    from filodb_tpu.memory import native as codecs
    from filodb_tpu.utils import compilecache
    log = served.log
    bench, cell, deploy, mix, data = load_cell(args.workload, root)
    chips = int(cell["chips"])
    if shrink:
        deploy["series"] = shrink["series"]
        deploy["server"]["store"]["max_series_per_shard"] = \
            shrink["series"] // int(deploy["server"]["num_shards"])
    if args.trace:
        # run options, not program changes (both are the shipped defaults
        # today; stated so that the traced run does not depend on them)
        deploy["server"].setdefault("trace", {}).update(
            {"enabled": True, "sample_rate": 1.0})
    cache_dir = compilecache.configure()
    # keep every program, however quick its compile, so that only a
    # checkout's first run of a cell compiles (run options of this process;
    # JAX's defaults skip programs that compile in under a second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = CompileClock()
    log(f"cell {cell['name']}: config {cell['config']} x traffic "
        f"{cell['traffic']} on {chips} chip(s); device {device}; jax "
        f"{jax.__version__}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries); seed {args.seed}; window {args.seconds} s; trace "
        f"{args.trace}")
    libs = {"partset": partset.available(), "codecs": codecs.available()}
    if not all(libs.values()):
        log(f"a native library is missing: {libs} — the write path measured "
            f"here is the native one")
        return None
    seed = int(args.seed)
    run_dir = tempfile.mkdtemp(prefix="filobench_")
    trace_dir = os.path.join(run_dir, "trace") if args.trace else None
    s: dict = {"gc": GcWatch(), "data": data, "home": home_of(root)}
    try:
        set_up(s, deploy, mix, seed, run_dir, clock, strict_setup)
        w = measure(s, deploy, mix, seed, args.seconds, trace_dir, clock,
                    chips)
        e2e = end_to_end(w)
        peak = memory_peak(chips)
        log(f"peak HBM on the fullest chip: {peak} bytes; "
            f"{host_memory(run_dir)}")
        good, numbers = decide_correct(s, w, deploy, mix, seed,
                                       allow_interpret)
        log(f"after the reference: {host_memory(run_dir)}")
        result = {"correct": good,
                  "attempted": len(w["recs"]) + len(w["sent"]),
                  "failed": (len(w["recs"]) - len(w["ok"]) + w["in_flight"]
                             + len(w["sent"]) - len(w["landed"]))}
        dev = dict(device, memory_peak_bytes=peak)
        breakdown = None
        if args.trace:
            metrics, traced, breakdown = per_layer(
                bench, cell, s, w, deploy, mix, device, trace_dir)
            dev.update(traced)
        else:
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            metrics = {
                m["name"]: {"value": e2e[m["name"]], "unit": units[m["name"]]}
                for m in metrics_of(bench, cell, "end_to_end")
                if m["name"] in e2e}
        result.update(metrics=metrics, device=dev)
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compared"] = numbers      # the contract: it comes last
        return result
    except Refused as e:
        log(f"refused: {e}")
        return None
    finally:
        s["gc"].close()
        if s.get("srv") is not None:
            stuck = served.stop_server(s["srv"])
            if stuck:
                log(f"threads that would not stop: {stuck}")
        shutil.rmtree(run_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    device = find_device(chips_of(args.workload))
    result = run(args, device)
    if result is None:
        return 1
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared: {name} = {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
