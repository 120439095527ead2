"""The shard's selection memo: what a selector and a grouping come to, kept
for as long as the index says the same thing.

A query leaf holds the shard lock while it turns label filters into part
ids, snapshots their slot epochs, turns ``by``/``without`` into group ids and
uploads them. All of that is a function of (filters, grouping, the index's
state) and of nothing a sample changes, and dashboards and explorers ask
the same few selectors over and over. ``TimeSeriesShard.selection`` keeps
the answers, as read-only arrays, and serves them while the counters the
shard already keeps say that nothing they depend on has moved:

- ``PartKeyIndex.epoch`` — any postings mutation (series added, removed);
- ``TimeSeriesShard._release_epoch`` — any slot released (purge, eviction),
  so the slot-epoch snapshot below is what a fresh gather would read;
- the store's row count;
- ``PartKeyIndex.time_epoch`` — an end time moved (the purge's marks);
- and, per query, that its range meets every matching series' start and
  end on the side the kept selection's range met them: where the time mask
  is the identity (``PartKeyIndex.all_live_through``: no series has ended
  and none starts after the query's end) any range that says the same;
  where it bites, the SPAN of ranges ``PartKeyIndex.part_ids_in_span``
  gave with the ids — on a fleet that redeploys a selector has one kept
  selection per set of births and ends its queries' ranges see (twelve
  update events in two hours: a handful), each with its own groupings and
  row mask.

Only selections that stay part ids at the leaf are kept (wider than the
caller's ``keep_over``): a narrow one is a few keys and a gather. Nothing
here is configured; what does not fit takes the path it took before, and
``filodb_selection_memo_total{outcome="bypass", reason=...}`` says why.

Bounds: ``SELECTIONS`` kept selections a shard (a selector under one span
of ranges each), ``GROUPINGS`` groupings a selection, both LRU. At 2^20
series a selection holds 8 MB on the host (part ids, slot epochs; 1 MB more
and 1 MB on the device where it is not the whole shard: its row mask) and
a grouping 8 MB on the host (group id per series, the dense row array) and
4 MB on the device: at most 4 x (9 + 4 x 8) = 164 MB of host memory and
68 MB of HBM a shard.

Everything is read and written under the shard lock the caller holds.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from ..utils.diagnostics import assert_owned
from ..utils.metrics import FILODB_SELECTION_MEMO, registry

SELECTIONS = 4      # selections a shard keeps (a selector under one span each)
GROUPINGS = 4       # (by, without) groupings a selection keeps


def count_memo(part: str, outcome: str, reason: str | None = None) -> None:
    labels = {"part": part, "outcome": outcome}
    if reason is not None:
        labels["reason"] = reason
    registry.counter(FILODB_SELECTION_MEMO, labels).increment()


def _frozen(a: np.ndarray) -> np.ndarray:
    """Handed to every query that asks: a caller that writes must copy."""
    a.setflags(write=False)
    return a


class Grouping:
    """One ``by``/``without`` over one selection: the group id of every
    selected series in first-appearance order, the G group keys, and — per
    array height R — the dense row array the kernels take, with its device
    copy."""

    __slots__ = ("gids", "keys", "_dense")

    def __init__(self, gids: np.ndarray, keys):
        self.gids = _frozen(gids)
        self.keys = tuple(keys)
        self._dense = None      # (R, host [R], device [R]) of the last R asked

    def dense(self, pids: np.ndarray, R: int):
        """(host, device) int32 ``[R]``: row ``pids[i]`` holds series i's
        group id, rows outside the selection group 0 (their ``n`` is 0).
        Built on first use, rebuilt when the store's height changes."""
        d = self._dense
        if d is None or d[0] != R:
            import jax.numpy as jnp
            host = np.zeros(R, np.int32)
            host[pids] = self.gids
            d = self._dense = (R, _frozen(host), jnp.asarray(host))
        return d[1], d[2]


class ShardSelection:
    """The part ids one selector matches, with what a leaf derives from
    them. ``stamp`` is the index state it was built in, None for a selection
    the shard does not keep (it then lives as long as its query)."""

    __slots__ = ("shard", "pids", "is_all", "stamp", "why", "_release",
                 "_epochs", "_groupings", "_cells", "_rows", "_late")

    def __init__(self, shard, pids: np.ndarray, stamp=None, why=None):
        self.shard = shard
        # a view: a caller's own array stays the caller's to write
        self.pids = _frozen(np.asarray(pids, np.int32).view())
        self.is_all = len(self.pids) == len(shard.index)
        self.stamp = stamp
        # why the select that built it was no hit — ``recovering``, else
        # ``time_mask`` (it ran the index's pass over every matching
        # entry's start and end time; kept for its span unless narrow),
        # else ``narrow``: the select span's ``memo_why``
        self.why = why
        self._release = shard._release_epoch
        self._epochs = None
        self._groupings: OrderedDict = OrderedDict()
        self._cells = None      # (store state, hole cells, used cells)
        self._rows = None       # (S, host [S], device [S]): the row mask
        self._late = None       # (the store's late mask, selected rows in it)

    def cells(self, store) -> tuple[int, int]:
        """(hole cells, used cells) of the selected rows, from the counts
        the store keeps on the host a row: one pass per state of the store
        (a flush moves both), not one per query."""
        state = store.mutation_epoch()
        kept = self._cells
        if kept is None or kept[0] != state:
            rows = slice(None) if self.is_all else self.pids
            kept = self._cells = (state, int(store.holes_host[rows].sum()),
                                  int(store.n_host[rows].sum()))
        return kept[1], kept[2]

    def row_mask(self, S: int):
        """(host, device) bool ``[S]``, true at the selected rows: what a
        wide leaf zeroes the other rows' counts with. Built on first use,
        rebuilt when the store's height changes."""
        m = self._rows
        if m is None or m[0] != S:
            import jax.numpy as jnp
            host = np.zeros(S, bool)
            host[self.pids] = True
            m = self._rows = (S, _frozen(host), jnp.asarray(host))
        return m[1], m[2]

    def late_rows(self, late: np.ndarray) -> int:
        """How many of the selected rows ``late`` marks (the store's
        ``late_mask()``: bool ``[S]``, another array whenever a birth cell
        moved): one pass per such array, not one per query."""
        kept = self._late
        if kept is None or kept[0] is not late:
            rows = self.row_mask(len(late))[0]
            kept = self._late = (late, int(np.count_nonzero(rows & late)))
        return kept[1]

    def snapshot(self) -> None:
        """Capture the slot epochs of the selected series (once; a kept
        selection is only served while no slot has been released since)."""
        if self._epochs is None:
            self._epochs = _frozen(self.shard.slot_epoch[self.pids])

    def released(self) -> bool:
        """Has a selected series' slot been released since the snapshot?
        No release at all since then answers without a gather."""
        if self.shard._release_epoch == self._release:
            return False
        return bool((self.shard.slot_epoch[self.pids] != self._epochs).any())

    def grouping(self, by, without) -> tuple[Grouping, str]:
        """(the grouping, ``hit`` | ``miss`` | ``bypass``): from the index's
        label columns on first use (``PartKeyIndex.group_ids``)."""
        from ..query.rangevector import RangeVectorKey
        key = (tuple(by), tuple(without))
        g = self._groupings.get(key)
        if g is not None:
            self._groupings.move_to_end(key)
            how = "hit"
        else:
            gids, groups = self.shard.index.group_ids(self.pids, by, without)
            g = self._groupings[key] = Grouping(
                gids, [RangeVectorKey(gk) for gk in groups])
            if len(self._groupings) > GROUPINGS:
                self._groupings.popitem(last=False)
            how = "miss"
        if self.stamp is None:
            how = "bypass"
        count_memo("groupids", how)
        return g, how


class SelectionMemo:
    """A shard's kept selections (see the module's text for the rule)."""

    def __init__(self, shard):
        self._shard = shard
        self._kept: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        return len(self._kept)

    def select(self, filters, start_ms: int, end_ms: int,
               keep_over: int) -> tuple[ShardSelection, str]:
        sh = self._shard
        assert_owned(sh.lock, "selection")
        idx = sh.index
        key = tuple(filters)
        stamp = (idx.epoch, idx.time_epoch, sh._release_epoch,
                 sh.store.S if sh.store is not None else 0)
        if not sh.recovering:
            masked = not idx.all_live_through(end_ms)
            for at, kept in self._kept.items():
                if (at[0] == key and kept.stamp == stamp
                        and _spans(at[1], masked, start_ms, end_ms)):
                    self._kept.move_to_end(at)
                    count_memo("select", "hit")
                    return kept, "hit"
        pids, span = idx.part_ids_in_span(filters, start_ms, end_ms)
        pids = pids.astype(np.int32, copy=False)
        narrow = len(pids) <= keep_over
        why = ("recovering" if sh.recovering
               else "time_mask" if span is not None
               else "narrow" if narrow else None)
        if sh.recovering or narrow:
            count_memo("select", "bypass", why)
            return ShardSelection(sh, pids, why=why), "bypass"
        for at in [at for at, kept in self._kept.items()
                   if kept.stamp != stamp]:
            del self._kept[at]          # of a state that does not come back
        sel = self._kept[key, span] = ShardSelection(sh, pids, stamp, why)
        if len(self._kept) > SELECTIONS:
            self._kept.popitem(last=False)
        count_memo("select", "miss", why)
        return sel, "miss"


def _spans(span, masked: bool, start_ms: int, end_ms: int) -> bool:
    """Does a kept selection's span (``PartKeyIndex.part_ids_in_span``)
    hold the range [start_ms, end_ms]? ``masked``: the time mask bites at
    ``end_ms``."""
    if span is None:
        return not masked
    end_lo, end_hi, start_lo, start_hi = span
    return end_lo <= end_ms < end_hi and start_lo < start_ms <= start_hi
