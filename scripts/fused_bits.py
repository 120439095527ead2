"""The scalar fused program alone: the bits of its answers and the wall clock
of a dispatch, on whatever device JAX has.

The witness that two trees answer to the bit ON THE CHIP, where the tier-1
tests (interpret mode, XLA:CPU) cannot look: run it on both in ONE chip call
and compare the last line's checksum (PR 38: parent and change,
``f66a4ebe79e1f9dd`` at the default size, ``--full`` ``a39bd4b1db98ece5``
since PR 40; both again when the line form's picks went int8, PR 47;
PERF.md §6).

Usage:
    python scripts/fused_bits.py [--root <tree>] [--tag <name>] [--rows N]
                                 [--full]

``--root``: the tree whose ``filodb_tpu`` is run (default: this one), e.g.
a ``git archive`` of the parent commit. Nine shapes at ``rows`` x 768 (2^20
by default: a chip's store; 1024 fits the CPU's interpret mode), 61 steps:
grid, line and hole stores x rate / ``avg_over_time`` by (g) / the squares x
15 m and 2 h cards. Each line: ms a dispatch (24 pipelined, best of three;
a tree before PR 38 has its two ``[S] -> [S, 1]`` relayouts inside), a
checksum of the partial state and, for a line or hole rate shape on a tree
that telescopes its delta (PR 40), the tiles that fell of the tiles it has;
the last: one checksum over all nine. One row in 97 ends 40 cells early, so
by default EVERY tile of a rate shape holds a row that ends under a window
and runs the band form: the parent's work plus the test. ``--full`` fills
every row (no tile falls but where the hole shape's random holes run past
the fills' reach): the telescoped tile's own time, and a checksum of its
own.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import sys
import time

C, IV, WINDOW, STEPS = 768, 10_000, 300_000, 61
BASE = 1_700_000_000_000


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--full", action="store_true")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(a.root))
    import jax
    import jax.numpy as jnp
    import numpy as np

    import filodb_tpu  # noqa: F401 — turns x64 on, as the server does
    from filodb_tpu.ops import fusedgrid

    S, K = a.rows, 24 if a.rows > 4096 else 2

    @jax.jit
    def chunk(key):
        # an eighth of the store at a time: counters, int8 residuals, and
        # the same residuals with one cell in 128 a hole
        k1, k2, k3 = jax.random.split(key, 3)
        v = jnp.cumsum(jax.random.randint(k1, (S // 8, C), 0, 50)
                       .astype(jnp.float32), axis=1)
        r = jax.random.randint(k2, (S // 8, C), -60, 61).astype(jnp.int8)
        rh = jnp.where(jax.random.uniform(k3, (S // 8, C)) < 1 / 128,
                       jnp.int8(-128), r)
        return v, r, rh

    parts = [chunk(k) for k in jax.random.split(jax.random.PRNGKey(7), 8)]
    val, res, res_h = (jnp.concatenate([p[i] for p in parts])
                       for i in range(3))
    del parts
    rows = jnp.arange(S, dtype=jnp.int32)
    n = jnp.full((S,), C, jnp.int32) - (rows % 97 == 0) * (0 if a.full
                                                            else 40)
    gids8 = (rows * 7) % 8
    start = jax.random.randint(jax.random.PRNGKey(9), (S,), 0, IV
                               ).astype(jnp.int32)
    zero = fusedgrid.zero_gids(S)
    jax.block_until_ready((val, res, res_h))
    end = BASE + (C - 1) * IV
    every, times = hashlib.sha256(), []

    def bench(name, op, fn, gids, G, step, line=None, holes=False):
        out_ts = end - np.arange(STEPS)[::-1].astype(np.int64) * step

        def go():
            return fusedgrid.fused_grid_aggregate(
                op, fn, val, n, gids, G, out_ts, WINDOW, BASE, IV,
                fetch=False, line=line, holes=holes)
        first = go()
        r = first.resolve()
        falls = getattr(first, "fall_tags", {})     # a tree before PR 40: none
        bits = b"".join(np.asarray(r[k]).tobytes() for k in sorted(r))
        every.update(bits)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            ps = [go() for _ in range(K)]
            # a tree before PR 48 keeps them in ``_outs``
            jax.block_until_ready([getattr(p, "outs", None) or p._outs
                                   for p in ps])
            best = min(best, (time.perf_counter() - t0) / K * 1e3)
        times.append(best)
        print(a.tag, name, round(best, 3), "ms",
              hashlib.sha256(bits).hexdigest()[:12],
              *([f"fell {falls['fall_tiles']}/{falls['tiles']}"]
                if falls else []), flush=True)

    for card, step in (("15m", 15_000), ("2h", 120_000)):
        bench(f"grid rate {card}", "sum", "rate", zero, 1, step)
        bench(f"line rate {card}", "sum", "rate", zero, 1, step,
              line=(start, res))
        bench(f"line avg by g {card}", "sum", "avg_over_time", gids8, 8,
              step, line=(start, res))
    bench("grid avg 15m", "sum", "avg_over_time", zero, 1, 15_000)
    bench("line sumsq 2h", "stddev", "sum_over_time", zero, 1, 120_000,
          line=(start, res))
    bench("hole rate 15m", "sum", "rate", zero, 1, 15_000,
          line=(start, res_h), holes=True)
    print(a.tag, jax.devices()[0].platform, "ALL", every.hexdigest()[:16],
          "mean", round(sum(times) / len(times), 3), "ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
