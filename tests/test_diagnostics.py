"""Concurrency diagnostics (ref analogs: FiloSchedulers.assertThreadName,
ChunkMap lock-leak counters, BlockDetective use-after-reclaim reports)."""

import threading
import time

import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.utils import diagnostics

BASE = 1_700_000_000_000


@pytest.fixture
def diag():
    diagnostics.enable()
    yield
    diagnostics.enable(False)


def test_assert_owned_detects_unlocked_mutation(diag):
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=4, samples_per_series=16,
                      flush_batch_size=10**9)
    shard = ms.setup("prometheus", GAUGE, 0, cfg)
    b = RecordBuilder(GAUGE)
    b.add({"_metric_": "m"}, BASE, 1.0)
    shard.ingest(b.build())
    shard.flush()          # locked path: fine
    # a direct (unlocked) donating mutation trips the assertion
    with pytest.raises(diagnostics.DiagnosticsError, match="shard lock"):
        shard.store.append(np.array([0], np.int32),
                           np.array([BASE + 10_000], np.int64),
                           np.array([2.0]))
    # same call under the lock passes
    with shard.lock:
        shard.store.append(np.array([0], np.int32),
                           np.array([BASE + 10_000], np.int64),
                           np.array([2.0]))


def test_assertions_off_by_default():
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=4, samples_per_series=16,
                      flush_batch_size=10**9)
    shard = ms.setup("prometheus", GAUGE, 0, cfg)
    shard.store.append(np.array([0], np.int32), np.array([BASE], np.int64),
                       np.array([1.0]))   # no lock, no assertion


def test_timed_rlock_counts_contention(diag):
    lock = diagnostics.TimedRLock("t")
    hold = threading.Event()
    release = threading.Event()

    def holder():
        with lock:
            hold.set()
            release.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    hold.wait(5)
    assert not lock.acquire(blocking=False)
    assert lock.contentions >= 1
    release.set()
    t.join(5)
    with lock:          # reentrancy survives the wrapper
        with lock:
            pass


def test_timed_rlock_times_waits_and_holds():
    """Contended: the waiter's blocked time lands in the lock's ``wait_s``
    and in the waiting thread's own total; the holder's in ``hold_s``.
    Uncontended: the hold is added, the wait totals are untouched."""
    lock = diagnostics.TimedRLock("t")
    mine = diagnostics.lock_wait_ns()
    t0 = time.perf_counter()
    with lock:
        with lock:                  # a re-entry is not a second hold
            time.sleep(0.05)
    quiet = time.perf_counter() - t0
    assert lock.wait_s == 0 and lock.contentions == 0
    assert diagnostics.lock_wait_ns() == mine
    assert 0.05 <= lock.hold_s <= quiet

    held, got = threading.Event(), {}

    def holder():
        with lock:
            held.set()
            time.sleep(0.3)

    def waiter():
        before = diagnostics.lock_wait_ns()
        with lock:
            pass
        got["ns"] = diagnostics.lock_wait_ns() - before

    th = threading.Thread(target=holder)
    th.start()
    assert held.wait(5)
    tw = threading.Thread(target=waiter)
    tw.start()
    th.join(5)
    tw.join(5)
    assert not th.is_alive() and not tw.is_alive()
    assert lock.contentions == 1
    assert 0.2 <= lock.wait_s <= 0.35, lock.wait_s
    assert abs(got["ns"] / 1e9 - lock.wait_s) < 1e-6   # the waiter's alone
    assert diagnostics.lock_wait_ns() == mine           # not this thread's
    assert lock.hold_s >= 0.05 + 0.3


def test_timed_rlock_counts_a_wait_that_timed_out():
    lock = diagnostics.TimedRLock("t")
    held, release = threading.Event(), threading.Event()

    def holder():
        with lock:
            held.set()
            release.wait(5)

    th = threading.Thread(target=holder)
    th.start()
    assert held.wait(5)
    before = diagnostics.lock_wait_ns()
    assert lock.acquire(timeout=0.1) is False
    release.set()
    th.join(5)
    assert not th.is_alive()
    assert lock.wait_s >= 0.1
    assert diagnostics.lock_wait_ns() - before >= 100_000_000


def test_donation_detective_explains(diag):
    det = diagnostics.DonationDetective()
    det.record("flush")
    msg = det.explain()
    assert "donation #1" in msg
    with pytest.raises(RuntimeError, match="use-after-donation"):
        diagnostics.explain_deleted_buffer(
            RuntimeError("Array has been deleted with shape=int32[16]"), det)
    assert diagnostics.explain_deleted_buffer(RuntimeError("other"), det) is False


# ------------------------------------------------- lock-hold watchdog (PR 20)

def test_lock_hold_watchdog_flags_wedged_holder(monkeypatch):
    """The watchdog counts a long hold WHILE the lock is still held — the
    release-time check alone never fires for a wedged holder whose release
    never comes (the runtime twin of live-block-under-lock)."""
    monkeypatch.setattr(diagnostics, "HOLD_WARN_S", 0.2)
    was = diagnostics.lock_debug
    diagnostics.enable_lock_debug(True)
    try:
        lk = diagnostics.TimedRLock("wedge-test", order_class="shard")
        with lk:
            deadline = time.monotonic() + 5.0
            while lk.long_holds == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert lk.long_holds >= 1    # flagged before release
    finally:
        diagnostics.enable_lock_debug(was)


def test_lock_hold_ns_counts_first_depth_holds_per_thread_at_release():
    """The holder's twin of ``lock_wait_ns``: what THIS thread held shard
    locks for, added when a first-depth hold is released. A re-entry's
    release adds nothing, another thread's hold is its own, and a lock of
    another class (a group-flush lock around a shard lock) counts nothing."""
    lock = diagnostics.TimedRLock("t", order_class="shard")
    before = diagnostics.lock_hold_ns()
    with lock:
        assert lock.holder == threading.current_thread().name
        with lock:
            time.sleep(0.02)
        assert diagnostics.lock_hold_ns() == before     # inner release
        time.sleep(0.02)
        assert diagnostics.lock_hold_ns() == before     # still held
    mine = diagnostics.lock_hold_ns() - before
    assert mine >= 40_000_000
    assert abs(mine / 1e9 - lock.hold_s) < 1e-6         # the one hold, once

    got = {}

    def other():
        t0 = diagnostics.lock_hold_ns()
        with lock:
            got["holder"] = lock.holder
            time.sleep(0.05)
        got["ns"] = diagnostics.lock_hold_ns() - t0

    th = threading.Thread(target=other, name="the-other")
    th.start()
    th.join(5)
    assert not th.is_alive()
    assert got["holder"] == "the-other" and got["ns"] >= 50_000_000
    assert diagnostics.lock_hold_ns() - before == mine  # not this thread's
    assert abs((mine + got["ns"]) / 1e9 - lock.hold_s) < 1e-6

    outer = diagnostics.TimedRLock("g", order_class="group_flush")
    with outer:
        time.sleep(0.01)
    assert outer.hold_s >= 0.01
    assert diagnostics.lock_hold_ns() - before == mine


def test_inflight_counts_a_dispatch_until_it_is_fetched_or_dropped():
    """``ahead`` is the count as a dispatch entered; a fetch gives its place
    back, once; a handle dropped unfetched (an error between dispatch and
    fetch) gives it back as it is collected; the oldest one's age is kept."""
    q = diagnostics.InflightPrograms()
    assert q.count == 0 and q.oldest_age_s() is None
    a = q.dispatched()
    time.sleep(0.01)
    b = q.dispatched()
    assert (a.ahead, b.ahead, q.count) == (0, 1, 2)
    assert q.oldest_age_s() >= 0.01
    a.fetched()
    a.fetched()                                         # idempotent
    assert q.count == 1 and q.oldest_age_s() < 0.01 + 1.0
    c = q.dispatched()
    assert c.ahead == 1
    del b                                               # dropped unfetched
    assert q.count == 1
    c.fetched()
    assert q.count == 0 and q.oldest_age_s() is None


def test_inflight_count_loses_no_update_under_contending_threads():
    """More threads than cores, a short switch interval: every dispatch is
    counted once and given back once, and no ``ahead`` passes the number of
    other threads."""
    import os
    import sys
    q = diagnostics.InflightPrograms()
    n_threads, rounds = 2 * (os.cpu_count() or 4), 400
    worst, errors = [], []

    def worker():
        top = 0
        try:
            for i in range(rounds):
                h = q.dispatched()
                top = max(top, h.ahead)
                if i % 7:
                    h.fetched()
                else:
                    del h                               # the dropped handle
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)
        worst.append(top)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not any(t.is_alive() for t in threads)
    assert q.count == 0 and q._serial == n_threads * rounds
    assert len(worst) == n_threads and max(worst) <= n_threads - 1
