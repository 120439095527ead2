#!/usr/bin/env python3
"""The control of ``correct``: the step below the precision the
deployments state, which has to come out as NOT correct.

The deployments state "exact to the f32 store" and the program states
``jax_default_matmul_precision = highest`` for it. ``high`` (three bf16
passes) is exact for this data (integer values below 2**24 against 0/1
band and one-hot operands), so the nearest precision that changes any
answer is ONE bf16 pass — the TPU's default, the fault PR 22 met (a raw
selector returned 92160 for 92181), and the step that would tempt a later
PR. Two forms:

    python3 benchmark/control.py --workload W --seed N --seconds S
        on the chip: the program itself with one-pass bf16 matmuls, through
        the whole of run.run (set-up's exact read-back printed, not
        enforced, so that the window runs and its answers are compared);
        prints the numbers compared and whether correct came out false.
        Never prints a result line.
    python3 benchmark/control.py --numpy --workload W --seed N [--requests K]
        anywhere: the plain reference in the program's place, its values
        rounded to bf16, at the cell's own size; numpy only.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np                # noqa: E402


def to_bf16(v) -> np.ndarray:
    import ml_dtypes
    return (np.asarray(v).astype(np.float32).astype(ml_dtypes.bfloat16)
            .astype(np.float64))


def bf16_values(seed: int, data, deploy: dict):
    """The data module's samples as one bf16 pass would read them."""
    def values(sids, cols):
        return to_bf16(data.raw_values(seed, sids, cols, deploy))
    return values


def numpy_control(workload: str, seed: int, n_requests: int,
                  series: int | None = None) -> dict:
    """answers_err and readback_abs of the bf16 reference against the f64
    reference, over the first ``n_requests`` requests the cell's generator
    deals (one of every query text among them)."""
    from benchmark import correct, run, traffic
    _bench, _cell, deploy, mix, data = run.load_cell(workload)
    if series:
        deploy["series"] = series
    head_col = int(deploy["fill_columns"])
    sids = np.arange(int(deploy["series"]))
    gen = traffic.Generator(mix, seed, data.scrape_ms(head_col, deploy))
    g = deploy["guarantees"]
    low = bf16_values(seed, data, deploy)
    worst, seen = 0.0, set()
    reqs = []
    while len(reqs) < n_requests:
        r = gen.next(len(reqs) % gen.clients)
        if r.qi not in seen or len(seen) == len(mix["queries"]):
            seen.add(r.qi)
            reqs.append(r)
    for r in reqs:
        ref = mix["queries"][r.qi]["ref"]
        want = data.evaluate(seed, sids, ref, r.out_ts(), deploy, head_col)
        got = data.evaluate(seed, sids, ref, r.out_ts(), deploy, head_col,
                            values=low)
        e = correct.err_ratio(got, want, g["rtol"], g["atol"])
        print(f"control(numpy bf16): {r.promql} "
              f"[{(r.end_ms - r.start_ms) // 1000}s/{r.step_ms // 1000}s] "
              f"err={e:.4g} (limit 1)", flush=True)
        worst = max(worst, e)
    rb = 0.0
    for p in data.probes(seed, sids, head_col, deploy, 2):
        for _labels, want in p["want"]:
            rb = max(rb, float(np.abs(to_bf16(want) - want).max()))
    print(f"control(numpy bf16): answers_err = {worst:.6g} (limit 1), "
          f"readback_abs = {rb:g} (limit 0) -> correct = "
          f"{bool(worst <= 1 and rb <= 0)}", flush=True)
    return {"answers_err": worst, "readback_abs": rb}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--numpy", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    a = ap.parse_args(argv)
    if a.numpy:
        n = numpy_control(a.workload, a.seed, a.requests)
        return 0 if (n["answers_err"] > 1 or n["readback_abs"] > 0) else 1
    from benchmark import run
    device = run.find_device(run.chips_of(a.workload))
    import filodb_tpu  # noqa: F401 — sets the package-wide precision ...
    import jax
    jax.config.update("jax_default_matmul_precision", "default")   # ... undone
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=0)
    res = run.run(args, device, strict_setup=False)
    verdict = "set-up refused it" if res is None else f"correct = {res['correct']}"
    print(f"control(program, one bf16 pass): {verdict} (has to be not "
          f"correct)", flush=True)
    return 0 if (res is None or res["correct"] is False) else 1


if __name__ == "__main__":
    sys.exit(main())
