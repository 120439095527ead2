"""What a deployment's data IS, one module per kind, found by name.

A configuration's file (``benchmark/configs/<config>.json``) names its data
module under ``"data"``; the harness loads ``benchmark/data/<name>.py`` (or
the package ``benchmark/data/<name>/``) and takes from it everything that
depends on the kind of data a store holds: schema and labels, what a scrape
carries, how the history gets onto the device and how one sees that a
sample has landed, the plain reference, the read-back probe and the bytes a
query needs. ``run.py``, ``served.py``, ``load.py``, ``correct.py`` and
``traffic.py`` know no module by name and none of these things themselves.
A later PR gives a second kind of store (compressed-resident gauges,
histograms, jittered stamps) a cell by ADDING a module, a configuration and
a mix; it edits nothing that is here.

``deploy`` below is the configuration file's content, passed whole: a
module reads its own keys from it (``counter``: ``metric``, ``labels``,
``scrape_interval_ms``, ``fill_columns``) and the harness reads none of
them. ``ref`` is a query's ``"ref"`` object from the traffic file, passed
through UNREAD by the harness: its keys are the module's. ``shard`` is the
program's ``TimeSeriesShard``; ``sid`` is ``int64[S]``, the series id each
store row holds, -1 for an unused row. A module exposes exactly:

1. series
   ``schema()`` -> the program's schema object for ``RecordBuilder``.
   ``series_labels(ids, deploy)`` -> the ``labels`` argument of
   ``RecordBuilder.add_series_batch`` for the series ``ids`` (the metric
   name under ``_metric_`` included). ``served.owners`` routes them; the
   module never does.
2. a scrape
   ``scrape_ms(k, deploy)`` -> the nominal stamp of scrape ``k`` (the head
   of the history is ``scrape_ms(fill_columns)``; no query reaches past it).
   ``scrape(seed, ids, k, deploy)`` -> the fields of scrape ``k``'s
   container for the series ``ids``, as keywords of
   ``dataclasses.replace(template, **fields)``: ``{"ts": int64[n],
   "values": float64[n] or [n, buckets], ...}``. The stamps are the
   module's: a later one may jitter them. Vectorised: it is called once a
   container inside the window and inside set-up.
3. the history on the device
   ``fill(shard, sid, seed, deploy)`` writes scrapes ``1..fill_columns-1``
   of every registered row into the module's store layout (scrape 0 went
   through the write path) and leaves the host's mirrors as the write path
   would have. ``check_filled(shard, sid, deploy)`` raises unless that is
   so and returns the set of devices the shard's history lives on.
   ``landed(shard, row, col)`` -> whether scrape ``col`` of store row(s)
   ``row`` (an int or an int array) is in the store: the lag poller's and
   set-up's only look at it.
4. the plain reference (imports nothing of the program)
   ``evaluate(seed, sids, ref, out_ts, deploy, head_col, values=None)`` ->
   the answer as ``{label-tuple: float64[T]}``, NaN where a step has none;
   ``values(sids, cols) -> float64[n, len(cols)]`` replaces the module's
   generator (``control.py`` computes it in the precision below).
   ``raw_values(seed, sids, cols, deploy)`` -> ``float64[n, len(cols)]``,
   what a raw selector returns for those scrapes.
5. the read-back probe
   ``probes(seed, ids, col, deploy, n)`` -> ``n`` seeded probes of a
   container (series ``ids``) whose newest scrape is ``col``: ``[{"promql",
   "start_ms", "end_ms", "step_ms", "want": [(labels, float64[T])]}]``; a
   wanted series is the answer's ONE series whose labels include
   ``labels`` (the answer may hold others).
6. the kernel's needed bytes
   ``query_bytes(rows, ref, out_ts, deploy, head_col, capacity)`` -> bytes
   one answered query has to read from a shard of ``rows`` store rows, at
   the store's own value width, for ``kernel_roofline_pct``.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
INTERFACE = ("schema", "series_labels", "scrape_ms", "scrape", "fill",
             "check_filled", "landed", "evaluate", "raw_values", "probes",
             "query_bytes")


def names(home: str = HERE) -> list[str]:
    """The data modules under ``home``: ``<name>.py`` and ``<name>/``."""
    out = set()
    for f in os.listdir(home) if os.path.isdir(home) else ():
        path = os.path.join(home, f)
        if f.endswith(".py") and not f.startswith("_"):
            out.add(f[:-3])
        elif os.path.isfile(os.path.join(path, "__init__.py")):
            out.add(f)
    return sorted(out)


def load(name: str, home: str = HERE):
    """The module ``name`` of ``home``, held to INTERFACE. Touches no JAX:
    a configuration that names no module here stops before the device
    check, with the names that are there."""
    if name not in names(home):
        raise SystemExit(f"benchmark: no data module {name!r}; {home} has "
                         f"{names(home)}")
    path, search = os.path.join(home, f"{name}.py"), None
    if not os.path.isfile(path):
        search = [os.path.join(home, name)]
        path = os.path.join(search[0], "__init__.py")
    modname = f"benchmark.data.{name}"
    mod = sys.modules.get(modname)
    if mod is None or os.path.abspath(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(
            modname, path, submodule_search_locations=search)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod       # a package's relative imports need it
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"benchmark: data module {name!r} ({path}) lacks "
                         f"{missing}; a module exposes {list(INTERFACE)}")
    return mod
