"""Concurrency diagnostics — config-gated runtime checking of the framework's
locking/donation discipline.

Reference analogs, re-shaped for this design:
  - FiloSchedulers.assertThreadName (core/.../memstore/FiloSchedulers.scala:12-16,
    gated by ``scheduler.enable-assertions``): here the protected resource is
    not a named scheduler thread but the SHARD LOCK — donation-sensitive store
    mutations and query array captures must hold it. ``assert_owned`` checks
    RLock ownership at the hot entry points.
  - ChunkMap's shared-lock deadlock warnings / leaked-lock counters
    (memory/.../data/ChunkMap.scala:22-45): ``TimedRLock`` warns when the
    shard lock is held longer than a threshold and counts contentions.
  - BlockDetective + reclaim event log (memory/.../BlockDetective.scala):
    ``DonationDetective`` records who last donated a store's device buffers,
    and ``explain_deleted_buffer`` turns jax's opaque "Array has been deleted"
    into an actionable report naming the donation site.

All checks are off by default (zero overhead beyond an ``if``); enable with
``filodb_tpu.utils.diagnostics.enable()`` or config ``diagnostics.enabled``.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
import traceback

import jax

log = logging.getLogger(__name__)

enabled = False

HOLD_WARN_S = 5.0      # ChunkMap-style "lock held too long" warning threshold

# Global lock acquisition order (rank increases left to right): a thread may
# only acquire a lock whose rank is STRICTLY greater than every ranked lock
# it already holds (reentrant re-acquisition of the same object excepted).
# Derived statically by filodb_tpu/analysis/lockcheck.py from the nested-with
# graph (group_flush -> {sink, shard}, sink -> shard) and asserted at runtime
# here when FILODB_LOCK_DEBUG=1. The static checker and this constant must
# agree — tests/test_static_analysis.py cross-checks them.
LOCK_ORDER = ("group_flush", "sink", "shard")

_LOCK_RANK = {c: i for i, c in enumerate(LOCK_ORDER)}

# Liveness contract surface, enforced by filodb_tpu/analysis/livecheck.py
# (pure literal — the checker reads it from the AST like EPOCH_SPEC).
#   "locks"      — owner-attribute name -> lock class: the lock shapes the
#                  live-block-under-lock rule tracks (lexical `with`,
#                  enter_context over one or all of them, assert_owned,
#                  and the `_locked`-suffix caller-holds contract on
#                  classes that own one of these attributes).
#   "blocking"   — leaf callee name -> kind: the blocking-call taxonomy.
#                  A call with one of these leaves can park the calling
#                  thread on I/O, a peer, or the clock.
#   "blocking_attr_calls" — the sink protocol's blocking surface:
#                  ``self.sink.*`` resolves to nothing in the call graph
#                  (duck-typed), so its file/network methods are declared
#                  here the way EPOCH_SPEC declares visible_calls.
#   "sites"      — sanctioned block-under-lock sites. Every entry carries
#                  a REQUIRED reason string saying what bounds the block
#                  and who guarantees progress; a reason-less entry is
#                  itself a finding. Sanction extends to helpers reachable
#                  ONLY from declared sites (reverse-call closure).
#   "wait_ok"    — declared shutdown-aware wait wrappers exempt from
#                  live-wait-no-timeout (same shape + reason rule).
#   "retry_ok"   — sanctioned serve loops exempt from live-unbounded-retry
#                  ONLY (same shape + reason rule): a loop whose "retry" is
#                  answering the next request, bounded by connection
#                  lifetime rather than an attempt counter. The sanction
#                  does NOT extend to blocking under locks.
#   "pacing_calls" — leaf callee names that pace a bounded retry loop the
#                  way a sleep would: waits on the device/kernel, not a
#                  hot spin (block_until_ready retires in-flight device
#                  work; a timed select parks in the kernel).
# Undeclared blocking under a lock, unbounded socket I/O, bound-less or
# backoff-less retry loops, and timeout-less waits are tier-1 failures —
# see ANALYSIS.md "Liveness & bounded-wait contracts".
LATENCY_SPEC = {
    "locks": {
        "lock": "shard",
        "owner_lock": "shard",
        "_sink_lock": "sink",
        "_group_flush_locks": "group_flush",
    },
    "blocking": {
        "sleep": "sleep", "_sleep": "sleep",
        "connect": "socket", "accept": "socket",
        "recv": "socket", "recv_into": "socket", "recvfrom": "socket",
        "send": "socket", "sendall": "socket",
        "create_connection": "socket",
        "urlopen": "http",
        "check_call": "subprocess", "check_output": "subprocess",
        "Popen": "subprocess", "communicate": "subprocess",
        "open": "file",
        "join": "thread-join",
    },
    "blocking_attr_calls": {
        "sink": ("age_out", "age_out_prepare", "age_out_commit",
                 "write_chunkset", "write_meta", "write_part_keys",
                 "write_index_bucket", "write_checkpoint",
                 "read_chunksets", "read_part_keys", "read_meta",
                 "read_checkpoints", "read_index_frames"),
    },
    "sites": {
        "partkey_drain": {
            "fn": "TimeSeriesShard._flush_partkey_log",
            "reason": "the sink lock exists to serialize exactly this "
                      "bounded batch write (part-key event order on disk); "
                      "ingest and query threads never take it, so the "
                      "write stalls only a concurrent drain"},
        "group_flush": {
            "fn": "TimeSeriesShard.flush_group",
            "reason": "one group's flush batch written under that group's "
                      "lock; the lock serializes same-group flushes only — "
                      "ingest staging and the query read path never "
                      "take it"},
        "age_out_commit": {
            "fn": "TimeSeriesShard.age_out_durable",
            "reason": "commit half only: the heavy log rewrite ran "
                      "lock-free on a snapshot; under the group locks the "
                      "sink splices the tail appended since (bounded by "
                      "one flush batch per group) and renames. Remote "
                      "sinks run one deadline-bounded RPC instead"},
    },
    "wait_ok": {},
    "retry_ok": {
        "dist_serve_frame_loop": {
            "fn": "StoreServer.__init__.handle",
            "reason": "per-connection serve loop: one request frame per "
                      "iteration, errors are replied to the client and the "
                      "next frame served; bounded by connection lifetime — "
                      "recv raises when the peer closes, and stop() closes "
                      "every tracked connection to unblock it"},
    },
    "pacing_calls": ("block_until_ready", "select"),
}

# opt-in runtime lock-order assertions (cheap thread-local bookkeeping, but
# still off by default on hot ingest paths)
lock_debug = os.environ.get("FILODB_LOCK_DEBUG", "") == "1"

_tls = threading.local()


def enable(on: bool = True) -> None:
    global enabled
    enabled = on


def enable_lock_debug(on: bool = True) -> None:
    global lock_debug
    lock_debug = on


def _held_locks() -> list:
    held = getattr(_tls, "held", None)
    if held is None:
        held = _tls.held = []
    return held


def lock_wait_ns() -> int:
    """Nanoseconds the CALLING thread has spent blocked in contended
    ``TimedRLock.acquire`` calls since it started. A span tags the lock
    wait it contained as the difference of two readings."""
    return getattr(_tls, "wait_ns", 0)


def lock_hold_ns() -> int:
    """Nanoseconds the CALLING thread has HELD shard locks (``order_class``
    "shard") since it started: first-depth holds only, each counted when it
    is released — the holder's twin of :func:`lock_wait_ns`. A span tags
    the holds that ended inside it as the difference of two readings; a
    hold released inside a nested span is in the inner and the outer
    span's tag alike."""
    return int(getattr(_tls, "hold_s", 0.0) * 1e9)


class Dispatched:
    """One counted dispatch (see :class:`InflightPrograms`) and, from
    ``holds()`` on, the handle of its result: the program's outputs on the
    device, not fetched. A leaf dispatches under its shard lock(s); a
    blocking fetch there would stall every ingest and query thread behind
    the lock for the program's whole run, so the fetch (``resolve()``, or a
    caller's own batched ``device_get`` followed by ``parts_of()``) runs at
    present/merge time, after the lock's release.

    ``ahead`` is the in-flight count as this dispatch entered it, ``tags``
    the dispatch span's while :func:`dispatching` has it open. ``fetched()``
    takes the dispatch out of the count, once; a handle dropped unfetched
    (an error, or a route that gives up between dispatch and fetch) takes
    it out as it is collected."""

    __slots__ = ("_owner", "_key", "ahead", "tags", "outs", "_answer",
                 "_falls", "fall_tags")

    def __init__(self, owner: "InflightPrograms", key: int, ahead: int):
        self._owner, self._key, self.ahead = owner, key, ahead
        self.tags: dict = {}
        self.outs = self._answer = self._falls = None
        self.fall_tags: dict = {}

    def fetched(self) -> None:
        self._owner._open.pop(self._key, None)      # atomic; idempotent

    __del__ = fetched

    def holds(self, outs, answer, falls=None) -> "Dispatched":
        """Take the dispatched program's device outputs (a pytree) and
        ``answer``, the route's pure function from the FETCHED outputs to
        what its consumer reads. ``falls`` is given by a program that counts
        its fallen tiles: that count is the LAST of ``outs``, and
        ``falls(fetched count)`` the tags it puts on the fetch span
        (``fall_tiles``, and ``tiles`` for a line rate program), counted in
        ``/metrics`` on the way."""
        self.outs, self._answer, self._falls = outs, answer, falls
        return self

    def parts_of(self, fetched):
        """The answer from ALREADY-FETCHED outputs: for a caller that
        batches many handles into one ``device_get`` (the reduce node) and
        opens the fetch span itself, adding up their ``fall_tags``."""
        self.fetched()
        if self._falls is not None:
            *fetched, falls = fetched
            self.fall_tags = self._falls(falls)
        return self._answer(fetched)

    def resolve(self):
        """The blocking fetch, in its ``query.exec.kernel`` span."""
        from .tracing import SPAN_QUERY_KERNEL, span    # imports this module
        with span(SPAN_QUERY_KERNEL, phase="fetch") as tags:
            answer = self.parts_of(jax.device_get(self.outs))
            tags.update(self.fall_tags)
        return answer


class InflightPrograms:
    """The device queue as the host sees it: fused programs that were
    dispatched and whose result no one has fetched yet, process-wide. A
    dispatch takes ``dispatched()`` BEFORE it hands the program to the
    device (:func:`dispatching`, the one caller) — ``ahead`` of the handle
    it gets is what the device runs before this program — and the handle
    keeps the program's result; its fetch calls ``fetched()``. The flush's
    programs are not counted: no one fetches them."""

    def __init__(self):
        self._lock = threading.Lock()       # serializes count-then-insert
        # serial -> perf_counter_ns at dispatch; insertion order = age
        self._open: dict[int, int] = {}
        self._serial = 0

    def dispatched(self) -> Dispatched:
        now = time.perf_counter_ns()
        with self._lock:
            self._serial = key = self._serial + 1
            ahead = len(self._open)
            self._open[key] = now
        return Dispatched(self, key, ahead)

    @property
    def count(self) -> int:
        return len(self._open)

    def oldest_age_s(self) -> float | None:
        """Seconds since the oldest unfetched dispatch; None with none."""
        stamps = list(self._open.values())  # one C call: atomic
        return ((time.perf_counter_ns() - stamps[0]) / 1e9 if stamps
                else None)


inflight = InflightPrograms()


@contextlib.contextmanager
def dispatching(**tags):
    """The one way a query's program goes to the device: a place in
    ``inflight`` taken BEFORE the hand-over, and ``query.exec.kernel``
    ``phase="dispatch"`` open around it with ``ahead`` and the site's own
    ``tags``. Yields the :class:`Dispatched`; what a site learns only as it
    chooses its program it adds to ``.tags`` inside the block, and the
    program's outputs it gives to ``.holds()``."""
    from .tracing import SPAN_QUERY_KERNEL, span        # imports this module
    d = inflight.dispatched()
    with span(SPAN_QUERY_KERNEL, phase="dispatch", ahead=d.ahead,
              **tags) as d.tags:
        yield d


class DiagnosticsError(AssertionError):
    """A violated concurrency invariant (only raised when diagnostics on)."""


def assert_owned(lock, what: str) -> None:
    """Assert the calling thread holds ``lock`` (an RLock). The donation
    discipline: store mutations (which donate device buffers) and query
    array captures must both happen under the shard lock."""
    if not enabled:
        return
    if not lock._is_owned():
        raise DiagnosticsError(
            f"{what} requires the shard lock: a concurrent flush would donate "
            "(delete) device buffers this thread is using — wrap the call in "
            "`with shard.lock:` (thread "
            f"{threading.current_thread().name})")


class _HoldWatchdog:
    """Background scan catching the long hold the release-time check cannot:
    a WEDGED holder whose release never comes (the exact failure
    live-block-under-lock exists to prevent — a blocking call under the
    lock that never returns). Locks register at first-depth acquire under
    FILODB_LOCK_DEBUG=1; a daemon thread scans the held set every
    HOLD_WARN_S/4 (re-read each cycle so tests can lower the threshold)
    and warns + counts a long hold for any lock still held past
    HOLD_WARN_S — while it is still held, not after the fact."""

    def __init__(self):
        self._lock = threading.Lock()
        self._held: dict[int, "TimedRLock"] = {}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def register(self, lk: "TimedRLock") -> None:
        with self._lock:
            self._held[id(lk)] = lk
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._scan_loop, daemon=True,
                    name="lock-hold-watchdog")
                self._thread.start()

    def unregister(self, lk: "TimedRLock") -> None:
        with self._lock:
            self._held.pop(id(lk), None)

    def _scan_loop(self) -> None:
        while not self._stop.wait(max(0.05, HOLD_WARN_S / 4.0)):
            try:
                now = time.monotonic()
                with self._lock:
                    held = list(self._held.values())
                for lk in held:
                    lk._watchdog_check(now)
            except Exception:   # noqa: BLE001 — watchdog must outlive faults
                log.exception("lock-hold watchdog scan failed; retrying "
                              "next period")


_watchdog = _HoldWatchdog()


class TimedRLock:
    """RLock wrapper counting contentions and warning on long holds.

    Drop-in for ``threading.RLock()`` (context manager + acquire/release +
    _is_owned); stats are cheap enough to keep even when diagnostics are off,
    the long-hold stack capture only happens when on. ``wait_s`` totals the
    time threads blocked in contended acquires (the uncontended path reads
    no clock for it) and ``hold_s`` the first-depth holds, so
    ``rate(hold_s)`` is the lock's utilisation without FILODB_LOCK_DEBUG;
    ``holder`` names the thread of the current (or last) first-depth hold
    (the acquire stores its id; the name is looked up when asked for).

    ``order_class`` names the lock's class in the global acquisition order
    (LOCK_ORDER). Under FILODB_LOCK_DEBUG=1 every acquisition checks the
    calling thread's held-lock set: taking a lock whose class rank is below
    a held (different) lock's rank raises DiagnosticsError BEFORE blocking —
    the would-be deadlock surfaces as a stack trace naming both locks instead
    of a frozen process. WITHIN a class, ``order_index`` (the shard/group
    number) must strictly ascend — the engine's multi-shard ExitStack
    acquisition is deadlock-free precisely because it walks shards in
    ascending shard_num; two indexed same-class locks taken descending are
    the ABBA shape and raise too."""

    def __init__(self, name: str = "lock", order_class: str | None = None,
                 order_index: int | None = None):
        self._lock = threading.RLock()
        self.name = name
        self.order_class = order_class
        self.order_index = order_index
        self.contentions = 0
        self.long_holds = 0
        self.wait_s = 0.0
        self.hold_s = 0.0
        self._acquired_at = 0.0
        self._holder = 0                # thread id of the first-depth holder
        self._depth = 0
        self._registered = False        # in the hold watchdog's held set
        self._warned_hold = 0.0         # _acquired_at already flagged
        # serializes the contention/long-hold counter RMWs: contentions is
        # bumped precisely when the main lock is NOT held, so `+= 1` there
        # races every other contending thread (found by filolint's
        # lock-guard-inconsistent family; diagnostics must not lie)
        self._stats_lock = threading.Lock()

    def _check_order(self) -> None:
        held = _held_locks()
        if self in held:
            return                      # reentrant: always fine
        my_rank = _LOCK_RANK.get(self.order_class)
        if my_rank is None:
            return
        for lk in held:
            r = _LOCK_RANK.get(lk.order_class)
            if r is None:
                continue
            same_rank_ok = (r == my_rank
                            and (lk.order_index is None
                                 or self.order_index is None
                                 or lk.order_index < self.order_index))
            if r > my_rank or (r == my_rank and not same_rank_ok):
                raise DiagnosticsError(
                    f"lock-order violation: acquiring {self.name!r} "
                    f"(class {self.order_class!r}, rank {my_rank}, index "
                    f"{self.order_index}) while holding {lk.name!r} (class "
                    f"{lk.order_class!r}, rank {r}, index {lk.order_index}); "
                    f"the declared order is {LOCK_ORDER}, ascending index "
                    "within a class — see ANALYSIS.md (lock-order) and "
                    "analysis/lockcheck.py "
                    f"(thread {threading.current_thread().name})")

    def acquire(self, blocking: bool = True, timeout: float = -1):
        debug = lock_debug
        if debug:
            self._check_order()
        got = self._lock.acquire(False)
        if not got:
            with self._stats_lock:
                self.contentions += 1
            if not blocking:
                return False
            t0 = time.perf_counter_ns()
            got = self._lock.acquire(True, timeout)
            waited = time.perf_counter_ns() - t0
            _tls.wait_ns = getattr(_tls, "wait_ns", 0) + waited
            with self._stats_lock:
                self.wait_s += waited / 1e9
            if not got:
                return False
        self._depth += 1
        if self._depth == 1:
            self._acquired_at = time.monotonic()
            self._holder = threading.get_ident()
            if debug:
                _watchdog.register(self)
                self._registered = True
        if debug:
            _held_locks().append(self)
        return True

    @property
    def holder(self) -> str:
        for t in threading.enumerate():
            if t.ident == self._holder:
                return t.name
        return f"thread-{self._holder}" if self._holder else ""

    def _watchdog_check(self, now: float) -> None:
        """Called by the hold watchdog's scan thread. Reads are racy by
        design (no lock shared with the hot path); the worst outcome of a
        torn read is one spurious or missed warning."""
        at = self._acquired_at
        if self._depth <= 0 or at == 0.0 or self._warned_hold == at:
            return
        held = now - at
        if held > HOLD_WARN_S:
            self._warned_hold = at
            with self._stats_lock:
                self.long_holds += 1
            log.warning("%s STILL held after %.1fs (> %.1fs) — wedged "
                        "holder? (watchdog; the release-time check cannot "
                        "see a hold that never releases)",
                        self.name, held, HOLD_WARN_S)

    def release(self):
        if self._depth == 1:
            held = time.monotonic() - self._acquired_at
            self.hold_s += held         # serialized by the lock itself
            if self.order_class == "shard":
                # the holder's own total (lock_hold_ns): shard locks only,
                # so a group-flush or sink lock around one counts nothing
                _tls.hold_s = getattr(_tls, "hold_s", 0.0) + held
            if self._registered:
                _watchdog.unregister(self)
                self._registered = False
            if held > HOLD_WARN_S and self._warned_hold != self._acquired_at:
                with self._stats_lock:
                    self.long_holds += 1
                if enabled:
                    log.warning("%s held %.1fs (> %.1fs) — possible lock leak:\n%s",
                                self.name, held, HOLD_WARN_S,
                                "".join(traceback.format_stack(limit=8)))
        self._depth -= 1
        self._lock.release()
        held_list = _held_locks()
        for i in range(len(held_list) - 1, -1, -1):
            if held_list[i] is self:
                del held_list[i]
                break

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def _is_owned(self):
        return self._lock._is_owned()


class DonationDetective:
    """Records the most recent donation of a store's device buffers so a
    use-after-donation (jax: "Array has been deleted") can name its cause."""

    def __init__(self):
        self.count = 0
        self._last_site: str | None = None
        self._last_when = 0.0

    def record(self, what: str) -> None:
        self.count += 1
        if enabled:
            self._last_site = "".join(traceback.format_stack(limit=6)[:-1])
            self._last_when = time.time()
        else:
            self._last_site = what
            self._last_when = time.time()

    def explain(self) -> str:
        if self._last_site is None:
            return "no donation recorded for this store"
        age = time.time() - self._last_when
        return (f"store buffers were last donated {age:.3f}s ago "
                f"(donation #{self.count}) by:\n{self._last_site}")


def explain_deleted_buffer(exc: BaseException,
                           *detectives: DonationDetective):
    """If ``exc`` is jax's use-after-donation error AND diagnostics are on,
    re-raise with the donation provenance attached — of the store that
    donated last, where a leaf held several; otherwise return False (the
    production path re-raises the original exception untouched)."""
    if (not enabled or not detectives
            or "Array has been deleted" not in str(exc)):
        return False
    detective = max(detectives, key=lambda d: d._last_when)
    raise RuntimeError(
        "use-after-donation: a captured device array was invalidated by a "
        "concurrent store mutation. Query code must capture arrays AND "
        "dispatch kernels under the shard lock. " + detective.explain()
    ) from exc
