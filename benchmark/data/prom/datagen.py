"""Stamps as a Prometheus scraper makes them, closed forms of (seed, series,
scrape); values by ``counter``'s law.

Prometheus scrapes every target at its own offset inside the interval
(``scrape/scrape.go``: ``offset(interval, hash(target))``) and a sample
carries the time its scrape began; with ``AlignScrapeTimestamps`` a scrape
that began within the 2 ms tolerance of its schedule is stamped ON the
schedule, a later one as it came. One target a series here, so

    stamp(s, k) = BASE_TS + interval * k + phase(s) + late(s, k)
    phase(s)   in [0, interval)   uniform, the target's offset
    late(s, k) = 0 for 15 scrapes in 16 (within tolerance: on schedule)
                 in [3, 63] ms for 1 in 16 (timers fire late, never early)

No scrape is missed, there is no staleness marker and no reset. Evaluated by
numpy on the host (the reference, the live scrapes) and by ``jax.numpy`` on
the device (the fill) to the SAME integers: all of it is uint32 arithmetic
with wrap-around, through ``counter``'s mixer. The values are ``counter``'s
own (``start(s) + 64 c + h(seed, s, c)``: exact in f32), imported, not
copied.
"""

from __future__ import annotations

import numpy as np

from benchmark.data.counter import datagen as counter_gen

BASE_TS = counter_gen.BASE_TS
fold_seed = counter_gen.fold_seed
counter = counter_gen.counter
counter_np = counter_gen.counter_np

LATE_MIN, LATE_MAX = 3, 63       # ms, when a scrape is late at all
LATE_ONE_IN = 16
_PHASE_COL = 0xFFFF_FFFE         # no store has this column
_LATE_SALT = 0x5CA1AB1E


def phase(xp, word, s, interval_ms: int):
    """phase(s), uint32 in [0, interval_ms)."""
    c = xp.full(s.shape, _PHASE_COL, dtype=xp.uint32)
    return counter_gen._mix(xp, word, s, c) % xp.uint32(interval_ms)


def late(xp, word, s, k):
    """late(s, k), uint32: 0, or LATE_MIN..LATE_MAX for one scrape in
    LATE_ONE_IN; ``s`` and ``k`` broadcast against each other."""
    u = xp.uint32
    x = counter_gen._mix(xp, xp.asarray(word, dtype=u) ^ u(_LATE_SALT), s, k)
    span = u(LATE_MAX - LATE_MIN + 1)
    return xp.where(x >> u(28) == u(0), u(LATE_MIN) + (x >> u(8)) % span, u(0))


def offset_np(seed: int, sids, cols, interval_ms: int) -> np.ndarray:
    """phase(s) + late(s, k), int64 [len(sids), len(cols)]: a stamp less its
    scrape's nominal one."""
    word = fold_seed(seed)
    s = np.asarray(sids, np.uint32)[:, None]
    k = np.asarray(cols, np.uint32)[None, :]
    with np.errstate(over="ignore"):
        return (phase(np, word, s, interval_ms).astype(np.int64)
                + late(np, word, s, k).astype(np.int64))


def stamps_np(seed: int, sids, cols, interval_ms: int) -> np.ndarray:
    """stamp(s, k), int64 [len(sids), len(cols)]."""
    cols = np.asarray(cols, np.int64)
    return (BASE_TS + cols[None, :] * interval_ms
            + offset_np(seed, sids, cols, interval_ms))
