"""Missed scrapes on ``prom``'s stream, a closed form of (seed, series,
scrape).

Prometheus's scrape loop (``scrape/scrape.go``): a scrape that fails
appends a staleness marker (``value.StaleNaN``) at the scrape's stamp for
every series the target exposed before, and the samples resume with the
next scrape that succeeds. One target a series here (as ``prom`` assumes),
so a series misses on its own:

    raw(s, k)  = a seeded hash of (seed, s, k) is 0 mod MISS_ONE_IN
    miss(s, k) = raw(s, k) and not all of raw(s, k-1), raw(s, k-2),
                 raw(s, k-3); never at k = 0 (the registration scrape)

0.78 % of scrapes are missed and a run is at most RUN_MAX = 3 cells (30 s:
a restart). A missed scrape's row carries ``STALE_NAN`` at its SCHEDULED
stamp, ``BASE_TS + interval * k + phase(s)`` (no lateness: nothing was
scraped). Every other scrape is ``prom``'s: its stamps and ``counter``'s
values, imported, not copied. No counter reset, no out-of-order sample. The
same booleans from numpy (the reference, the live scrapes) and from
``jax.numpy`` (the fill): uint32 arithmetic with wrap-around through
``counter``'s mixer.
"""

from __future__ import annotations

import numpy as np

from benchmark.data.prom import datagen as prom_gen

BASE_TS = prom_gen.BASE_TS
LATE_MAX = prom_gen.LATE_MAX
fold_seed = prom_gen.fold_seed
counter = prom_gen.counter
counter_np = prom_gen.counter_np
late = prom_gen.late
phase = prom_gen.phase

MISS_ONE_IN = 128
RUN_MAX = 3
_MISS_SALT = 0x0D15EA5E
# Prometheus's value.StaleNaN: this bit pattern, not any NaN
STALE_NAN = np.array([0x7FF0000000000002], np.uint64).view(np.float64)[0]


def raw(xp, word, s, k):
    """raw(s, k), bool; ``s`` and ``k`` (uint32) broadcast."""
    u = xp.uint32
    x = prom_gen.counter_gen._mix(
        xp, xp.asarray(word, dtype=u) ^ u(_MISS_SALT), s, k)
    return (x >> u(16)) % u(MISS_ONE_IN) == u(0)    # a product's high bits


def miss(xp, word, s, k):
    """miss(s, k), bool: scrape ``k`` of series ``s`` failed."""
    u = xp.uint32
    run = k >= u(RUN_MAX)           # three scrapes before it exist (k - j
    for j in range(1, RUN_MAX + 1):     # wraps below: masked by ``run``)
        run = run & raw(xp, word, s, k - u(j))
    return raw(xp, word, s, k) & ~run & (k > u(0))


def miss_np(seed: int, sids, cols) -> np.ndarray:
    """miss(s, k), bool [len(sids), len(cols)]; False for a negative k. A
    run of consecutive scrapes (what the reference asks for) takes one
    hash a cell, not four: raw over the run and the three scrapes before
    it, shifted against itself."""
    cols = np.asarray(cols, np.int64)
    s = np.asarray(sids, np.uint32)[:, None]
    word = fold_seed(seed)
    n = len(cols)
    if n > RUN_MAX + 1 and cols[0] >= 0 and (np.diff(cols) == 1).all():
        k = np.arange(cols[0] - RUN_MAX, cols[-1] + 1)
        with np.errstate(over="ignore"):
            r = raw(np, word, s, np.maximum(k, 0).astype(np.uint32)[None, :])
        r &= (k >= 0)[None, :]
        run = r[:, 0:n] & r[:, 1:n + 1] & r[:, 2:n + 2]
        return r[:, RUN_MAX:] & ~run & (cols > 0)[None, :]
    with np.errstate(over="ignore"):
        m = miss(np, word, s, np.maximum(cols, 0).astype(np.uint32)[None, :])
    return m & (cols >= 0)[None, :]


def stamps_np(seed: int, sids, cols, interval_ms: int,
              missed=None) -> np.ndarray:
    """The stamp scrape k's row carries, int64 [len(sids), len(cols)]:
    ``prom``'s where the scrape came, the scheduled one where it failed
    (``missed``: ``miss_np`` of the same cells, where the caller has it)."""
    late_too = prom_gen.stamps_np(seed, sids, cols, interval_ms)
    word = fold_seed(seed)
    with np.errstate(over="ignore"):
        ph = phase(np, word, np.asarray(sids, np.uint32)[:, None],
                   interval_ms).astype(np.int64)
    on_time = BASE_TS + np.asarray(cols, np.int64)[None, :] * interval_ms + ph
    if missed is None:
        missed = miss_np(seed, sids, cols)
    return np.where(missed, on_time, late_too)
