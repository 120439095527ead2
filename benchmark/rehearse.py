#!/usr/bin/env python3
"""The CPU rehearsal: every part of a cell at a tiny size, no chip.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--cell NAME ...] [--seconds S]
                                                    [--no-double]

It checks, and prints as counts only (never the contract's last line, never
a time or a rate under a metric's name):

1. generator parity: ``counter``'s ``datagen.counter`` in numpy and in
   ``jax.numpy`` give the same integers;
2. ``counter``'s plain reference against the repo's golden model
   (``tests/prom_reference.py``), series by series on a sample;
3. the trace reduction against the small recorded trace in ``fixtures/``;
4. for each cell: the whole of ``run.run`` with the device check stubbed
   here — server, registration, device fill and its invariants (on 4
   virtual devices for a mesh cell), warm-up, load generators, live
   ingest, window, reference comparison, layer readers — at a few thousand
   series with interpreted kernels;
5. the same pass for a deployment that is not the benchmark's: the test
   double of ``benchmark/tests/double/`` (another data module, configuration
   and mix) is dry-added, as new files and entries only, to a scratch copy
   of the by-name files, and its cell runs from there.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, ROOT)

import numpy as np                # noqa: E402


def generator_parity() -> int:
    import filodb_tpu  # noqa: F401 — turns x64 on, as the server does
    import jax
    import jax.numpy as jnp
    from benchmark.data.counter import datagen
    n = 0
    for seed in (0, 7, 2**31 + 12345, 2**33 + 1):
        s, c = np.arange(2048), np.arange(768)
        host = datagen.counter_np(seed, s, c)
        dev = np.asarray(jax.jit(
            lambda s, c, w: datagen.counter(jnp, w, s[:, None], c[None, :]))(
                jnp.asarray(s, jnp.uint32), jnp.asarray(c, jnp.uint32),
                jnp.uint32(datagen.fold_seed(seed)))).astype(np.int64)
        assert (host == dev).all(), f"numpy and jnp differ for seed {seed}"
        inc = np.diff(host, axis=1)
        assert inc.min() >= 1 and inc.max() <= 127 and host.max() < 2**24
        n += host.size
    return n


def reference_tie(seed: int = 5) -> int:
    from benchmark.data.counter import datagen, reference
    from tests import prom_reference as pr
    iv, head, S = 10_000, 719, 64
    sids = np.arange(100, 100 + S)
    ts = datagen.BASE_TS + np.arange(head + 1) * iv
    V = datagen.counter_np(seed, sids, np.arange(head + 1)).astype(float)
    n = 0
    for start, step in ((ts[0] - 7000, 120_000), (ts[300] + 3000, 15_000),
                        (ts[-1] - 3_600_000, 60_000)):
        out_ts = start + np.arange(61) * step
        out_ts = out_ts[out_ts <= ts[-1]]
        for fn in ("rate", "sum_over_time", "avg_over_time"):
            cols = reference.needed_columns(fn, out_ts, 300_000, iv, head)
            mine = reference.per_series(fn, V[:, cols], cols, out_ts, 300_000,
                                        iv, head)
            for i in range(S):
                gold = pr.eval_range_fn(fn, ts, V[i], out_ts, 300_000)
                np.testing.assert_allclose(mine[i], gold, rtol=1e-12,
                                           err_msg=f"{fn} series {sids[i]}")
                n += len(gold)
    return n


def trace_fixture() -> dict:
    from benchmark import tracedata
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        fx = json.load(f)
    tr, want = fx["trace"], fx["expect"]
    w0, w1 = fx["w0_ns"], fx["w1_ns"]
    kernels = tracedata.named_events(tr, w0, w1, fx["kernel_names"])
    got = {"busy_s": tracedata.busy_seconds(tr, w0, w1),
           "kernel_events": len(kernels),
           "kernel_s": sum(e[3] for e in kernels) / 1e9,
           "top_op": tracedata.top_ops(tr, w0, w1)[0][0],
           "longest_gap": tracedata.idle_gaps(tr, w0, w1, fx["host_spans"])[0]}
    for k, v in want.items():
        if isinstance(v, float):
            assert abs(got[k] - v) <= 1e-9 * max(1.0, abs(v)), (k, got[k], v)
        else:
            assert got[k] == v, (k, got[k], v)
    return got


DOUBLE = os.path.join(HERE, "tests", "double")
BY_NAME = ("configs", "traffic", "data", "layers")


def dry_add(double: str, root: str) -> list[str]:
    """What a ``model_config`` PR does, in the scratch directory ``root``:
    the benchmark's by-name files and BENCHMARK.json as they are, plus the
    files under ``double`` and its ``entries.json`` — nothing that exists
    is edited. Returns the names of the cells added."""
    home = os.path.join(root, os.path.basename(HERE))
    for d in BY_NAME:
        shutil.copytree(os.path.join(HERE, d), os.path.join(home, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
        if os.path.isdir(os.path.join(double, d)):
            for f in os.listdir(os.path.join(double, d)):
                if os.path.exists(os.path.join(home, d, f)):
                    raise RuntimeError(f"{d}/{f} exists: a deployment adds "
                                       f"files, it edits none")
                shutil.copy(os.path.join(double, d, f),
                            os.path.join(home, d, f))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(double, "entries.json")) as f:
        add = json.load(f)
    for kind in ("configs", "workloads"):
        bench[kind] += add[kind]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return [w["name"] for w in add["workloads"]]


def rehearse_cell(name: str, seconds: float, seed: int, trace: int,
                  root: str = ROOT) -> dict:
    from benchmark import run as runmod
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace)
    stub = {"platform": "cpu-rehearsal", "kind": "TPU v5 lite",
            "count": runmod.chips_of(name, root)}
    res = runmod.run(args, stub, allow_interpret=True,
                     shrink={"series": 4096}, root=root)
    assert res is not None, f"{name}: set-up refused the system"
    assert res["correct"] is True, f"{name}: correct came out false"
    return {"attempted": res["attempted"], "failed": res["failed"],
            "metrics_reported": sorted(res["metrics"])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="append")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=2**31 + 99)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--skip-units", action="store_true")
    ap.add_argument("--no-double", action="store_true")
    a = ap.parse_args()
    if not a.skip_units:
        print(f"generator parity: {generator_parity()} values equal")
        print(f"reference vs tests/prom_reference.py: {reference_tie()} "
              f"steps equal")
        print(f"trace reduction vs fixture: {sorted(trace_fixture())} match")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in a.cell or names:
        print(f"cell {name}: {rehearse_cell(name, a.seconds, a.seed, a.trace)}")
    if not a.no_double and not a.cell:
        root = tempfile.mkdtemp(prefix="filobench_double_")
        try:
            for name in dry_add(DOUBLE, root):
                print(f"dry-added cell {name}: "
                      f"{rehearse_cell(name, a.seconds, a.seed, a.trace, root)}")
        finally:
            shutil.rmtree(root, ignore_errors=True)
    print("rehearsal passed (counts only; nothing here is a device number)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
