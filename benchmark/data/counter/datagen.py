"""Seeded counters as a closed form of (seed, series, column).

``counter(xp, seed, s, c)`` is evaluated by numpy on the host (the plain
reference, the live scrapes) and by ``jax.numpy`` on the device (the fill
of the history) to the SAME integers, so the reference never reads the
store and the store never reads the reference:

    v(s, c) = start(s) + 64 * c + h(s, c)        h in [0, 64)
    start(s) in [0, 100000)

Monotone (an increment is 64 + h(c) - h(c-1), in [1, 127]: i8-sized, so a
delta8-resident deployment can reuse the data), integer, and below 2**24
for every column a store holds, hence exact in f32. All arithmetic is
uint32 with wrap-around, which numpy arrays and XLA integers share.
"""

from __future__ import annotations

import numpy as np

BASE_TS = 1_700_000_000_000      # ms; data time, not the wall clock
START_RANGE = 100_000
STEP = 64                        # mean increment per scrape

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35
_START_COL = 0xFFFF_FFFF         # no store has this column


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's pass 2**31) -> one uint32 word."""
    seed = int(seed)
    x = (seed ^ (seed >> 32) ^ 0xA511E9B3) & 0xFFFF_FFFF
    x = (x * _M2) & 0xFFFF_FFFF
    x ^= x >> 15
    return x


def _mix(xp, word, s, c):
    """Two multiply rounds over (word, s, c): uint32 in, uint32 out. The
    per-row and per-column products are [S, 1] and [1, C]; only the xor and
    what follows touch the whole block. ``word`` is ``fold_seed(seed)``, a
    Python int on the host or a traced uint32 scalar on the device (so one
    compiled fill serves every seed)."""
    u = xp.uint32
    word = xp.asarray(word, dtype=u)
    x = ((s.astype(u) * u(_M1)) ^ word) ^ (c.astype(u) * u(_M2) + u(_M3))
    x = x * u(_M2)
    x = x ^ (x >> u(15))
    return x * u(_M3)


def start_of(xp, word, s):
    """start(s), as uint32."""
    c = xp.full(s.shape, _START_COL, dtype=xp.uint32)
    return _mix(xp, word, s, c) % xp.uint32(START_RANGE)


def counter(xp, word, s, c):
    """v(s, c) as uint32; ``s`` and ``c`` broadcast against each other."""
    u = xp.uint32
    h = _mix(xp, word, s, c) >> u(26)
    return start_of(xp, word, s) + c.astype(u) * u(STEP) + h


def counter_np(seed: int, sids, cols, dtype=np.int64) -> np.ndarray:
    """[len(sids), len(cols)] on the host, as ``dtype``."""
    with np.errstate(over="ignore"):
        v = counter(np, fold_seed(seed), np.asarray(sids, np.uint32)[:, None],
                    np.asarray(cols, np.uint32)[None, :])
    return v.astype(dtype)
