#!/usr/bin/env python3
"""The controls of a store held as deltas: one stored delta flipped, and an
append whose last-value update is dropped — each has to come out as NOT
correct.

``tsbs_cpu_100k_12h`` states "the compressed store returns every stored
sample and stamp bit-exactly". A delta store has two ways of its own to
break that, and neither shows in the newest samples alone:

- ``--fault delta``: after the fill has been checked, the delta of ONE
  early scrape (column 30: the history's first hour) is raised by one in
  every row. Every sample of a row from that scrape on reads one too high —
  the decode is a running sum — while the row's anchor, its count and its
  newest deltas are what they were.
- ``--fault last``: the store's in-place append runs with the update of the
  host's last-value mirror left out: every delta after the first is taken
  against the value the fill ended on, so the decoded head drifts from the
  scrape that was sent.

Through the whole of ``run.run``; prints the numbers compared and whether
``correct`` came out false. Never prints a result line.

    python3 benchmark/control_narrow.py --workload tsbs_single_12h \\
        --fault delta|last --seed N --seconds S
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

FLIP_COL = 30


def flip_a_delta(data) -> None:
    """``data.check_filled`` passes on the sound store, then column
    FLIP_COL of the delta block is raised by one in every live row."""
    import jax
    import jax.numpy as jnp
    check = data.check_filled

    @jax.jit
    def raised(dv, n):
        col = jax.lax.broadcasted_iota(jnp.int32, dv.shape, 1)
        hit = (col == FLIP_COL) & (n[:, None] > FLIP_COL)
        return jnp.where(hit, dv + 1, dv).astype(dv.dtype)

    def check_then_flip(shard, sid, deploy):
        homes = check(shard, sid, deploy)
        st = shard.store
        with shard.lock:
            st._pre_donate("control.flip")
            kind, (dv, anchor), *rest = st._narrow
            st._narrow = (kind, (raised(dv, st.n), anchor), *rest)
        return homes

    data.check_filled = check_then_flip


def drop_last_value() -> None:
    """``SeriesStore._append_delta`` with the host's last-value mirror put
    back to what it was before the append."""
    from filodb_tpu.core.chunkstore import SeriesStore
    append = SeriesStore._append_delta

    def forgetful(self, *a, **k):
        kept = self.last_val.copy()
        append(self, *a, **k)
        if self.last_val is not None:
            self.last_val[:] = kept

    SeriesStore._append_delta = forgetful


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=("delta", "last"), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmark import run
    device = run.find_device(run.chips_of(a.workload))
    if a.fault == "delta":
        flip_a_delta(run.load_cell(a.workload)[4])
    else:
        drop_last_value()
    args = argparse.Namespace(workload=a.workload, seed=a.seed,
                              seconds=a.seconds, trace=a.trace)
    res = run.run(args, device, strict_setup=False)
    verdict = ("set-up refused it" if res is None
               else f"correct = {res['correct']} {res['compared']}")
    print(f"control(narrow store, fault {a.fault}): {verdict} (has to be "
          f"not correct)", flush=True)
    return 0 if (res is not None and res["correct"] is False) else 1


if __name__ == "__main__":
    sys.exit(main())
