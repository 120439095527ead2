"""Seeded native histograms as a closed form of (seed, series, scrape, bucket).

``buckets(xp, word, s, k, nb)`` is evaluated by numpy on the host (the
plain reference, the live scrapes) and by ``jax.numpy`` on the device (the
fill of the history) to the SAME integers, so the reference never reads the
store and the store never reads the reference. Bucket ``b`` of series ``s``
holds a counter of its own

    c(s, b, k) = start(s, b) + ((r(s, b) k + q(s, b) busy(s, k)) >> 1) + j(s, b, k)

- ``r`` in [0, 21], the bucket's rate in half-counts a scrape: a bump 13
  buckets wide round a centre that moves with the group (``s % 8``) and a
  seeded offset of the series, plus a seeded floor of one half-count in a
  quarter of the buckets, so that every quantile has a tail to land in;
- ``q`` in [0, 9], what the bucket gains more in a BUSY scrape: a bump 12
  buckets further up (latency gets worse under load). ``busy(s, k)`` counts
  the busy scrapes up to ``k``: the first 48 of every 96 (16 minutes), the
  phase set by the group and a seeded offset, so the quantile of a group
  moves between buckets as its busy stretches come and go;
- ``j`` in [0, r >> 1], a seeded jitter no larger than the smallest step of
  the term before it: the counter never falls, and grows by 0..31 a scrape;
- ``start`` in [0, 1000).

What a scrape carries, in the schema's ``col_layout(64)`` order, is
``sum``, ``count`` and the buckets CUMULATED over ``b`` (``le`` semantics):
``count`` is the top bucket, ``sum = 2 h[63] + h[31] + start'(s)`` (a law
of its own: no column can stand in for another). The top bucket stays
below 64 (1000 + 16 x 768 + 16) < 2**20, ``sum`` below 2**22: every stored
number is an integer below 2**24, exact in f32. All arithmetic is uint32
(int32 where a difference is signed), which numpy arrays and XLA integers
share. No counter ever resets.
"""

from __future__ import annotations

import numpy as np

BASE_TS = 1_700_000_000_000      # ms; data time, not the wall clock
GROUPS = 8                       # the bump's centre and the busy phase move with s % 8
PERIOD, BUSY = 96, 48            # scrapes: 16 min, half of them busy
START_RANGE = 1000

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35
_CTR, _PHASE, _START, _SUM = 0xFFFF_FFF1, 0xFFFF_FFF2, 0xFFFF_FFF3, 0xFFFF_FFF4


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's pass 2**31) -> one uint32 word."""
    seed = int(seed)
    x = (seed ^ (seed >> 32) ^ 0x51D7A9E3) & 0xFFFF_FFFF
    x = (x * _M2) & 0xFFFF_FFFF
    x ^= x >> 15
    return x


def bucket_les(nb: int) -> np.ndarray:
    """``nb - 1`` finite upper bounds and +Inf: seconds from 1 ms up by a
    fifth a bucket (81 s at 64 buckets), each cut to six digits so that a
    PromQL text can name it exactly."""
    fin = [float(f"{0.001 * 1.2 ** b:.6g}") for b in range(nb - 1)]
    return np.asarray(fin + [np.inf], np.float64)


def _mix(xp, word, a, b):
    """Two multiply rounds over (word, a, b): uint32 in, uint32 out.
    ``word`` is ``fold_seed(seed)``, a Python int on the host or a traced
    uint32 scalar on the device (one compiled fill serves every seed)."""
    u = xp.uint32
    word = xp.asarray(word, dtype=u)
    x = ((a.astype(u) * u(_M1)) ^ word) ^ (b.astype(u) * u(_M2) + u(_M3))
    x = x * u(_M2)
    x = x ^ (x >> u(15))
    return x * u(_M3)


def _const(xp, like, value):
    return xp.full(like.shape, value, dtype=xp.uint32)


def _bump(xp, b, centre, top: int, slope: int):
    """max(0, top - slope |b - centre|) as uint32; ``b``, ``centre`` int32."""
    d = xp.abs(b - centre)
    return xp.maximum(top - slope * d, 0).astype(xp.uint32)


def bucket_counts(xp, word, s, k, b):
    """c(s, b, k) as uint32, NOT cumulated; ``s``, ``k``, ``b`` broadcast
    against each other (``s`` and ``k`` never vary along ``b``'s axis)."""
    u, i32 = xp.uint32, xp.int32
    s, k, b = s.astype(u), k.astype(u), b.astype(u)
    grp = (s % u(GROUPS)).astype(i32)
    centre = i32(6) + i32(5) * grp + (_mix(xp, word, s, _const(xp, s, _CTR))
                                      % u(6)).astype(i32)
    bi = b.astype(i32)
    sb = s * u(64) + b
    r = _bump(xp, bi, centre, 20, 3) \
        + (_mix(xp, word, sb, _const(xp, sb, _CTR)) % u(4) == 0).astype(u)
    q = _bump(xp, bi, centre + i32(12), 9, 2)
    phase = (s % u(GROUPS)) * u(12) \
        + _mix(xp, word, s, _const(xp, s, _PHASE)) % u(8)
    kp = k + phase
    busy = (kp // u(PERIOD)) * u(BUSY) + xp.minimum(kp % u(PERIOD), u(BUSY))
    # a 16-bit draw scaled into [0, r >> 1]: multiply and shift, no division
    j = ((_mix(xp, word, sb, k) >> u(16)) * ((r >> u(1)) + u(1))) >> u(16)
    start = _mix(xp, word, sb, _const(xp, sb, _START)) % u(START_RANGE)
    return start + ((r * k + q * busy) >> u(1)) + j


def columns(xp, word, s, k, nb: int):
    """(sum, count, h): ``h`` the buckets cumulated over the last axis,
    uint32 of shape ``broadcast(s, k) + (nb,)``; ``sum`` and ``count`` of
    shape ``broadcast(s, k)``. ``s`` and ``k`` broadcast against each other."""
    u = xp.uint32
    b = xp.arange(nb, dtype=u)
    h = xp.cumsum(bucket_counts(xp, word, s[..., None], k[..., None], b),
                  axis=-1, dtype=u)
    count = h[..., nb - 1]
    su = s.astype(u) + u(0) * k.astype(u)
    extra = _mix(xp, word, su, _const(xp, su, _SUM)) % u(START_RANGE)
    return u(2) * count + h[..., nb // 2 - 1] + extra, count, h


def columns_np(seed: int, sids, cols, nb: int, dtype=np.float64):
    """(sum [n, m], count [n, m], h [n, m, nb]) on the host, as ``dtype``."""
    with np.errstate(over="ignore"):
        out = columns(np, fold_seed(seed),
                      np.asarray(sids, np.uint32)[:, None],
                      np.asarray(cols, np.uint32)[None, :], nb)
    return tuple(a.astype(dtype) for a in out)
