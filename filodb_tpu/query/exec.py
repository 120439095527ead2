"""ExecPlan tree + RangeVectorTransformers: the physical query execution layer.

Reference: query/.../exec/ExecPlan.scala:36 (execute = doExecute + transformer
chain + limits), SelectRawPartitionsExec.scala (the only data-reading leaf),
DistConcatExec / ReduceAggregateExec / BinaryJoinExec / SetOperatorExec,
RangeVectorTransformer.scala:27 (PeriodicSamplesMapper, ScalarOperationMapper,
InstantVectorFunctionMapper, AggregateMapReduce/Presenter, sort & misc mappers).

TPU-native execution shape:
  - The leaf resolves part ids host-side (index), then hands the *device store
    arrays* to the kernel chain. Narrow selections gather rows; wide selections
    (the 1M-series aggregation case) skip the gather entirely — the range kernel
    runs over the full [S, C] store and rows outside the selection are disabled
    via a zeroed sample count (their outputs are NaN and aggregation ignores
    them). No per-series dispatch anywhere.
  - Aggregation = host-computed dense group ids + one segment reduce on device.
  - Scatter-gather across shards is in-process here; parallel/ runs the same
    plan shape over a jax Mesh with psum (multi-chip) — same partial format.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.filters import Filter
from ..core.selection import ShardSelection
from ..ops import aggregators, binop, instantfns, rangefns
from ..utils.diagnostics import (explain_deleted_buffer, lock_hold_ns,
                                 lock_wait_ns)
from ..utils.metrics import (FILODB_GROUPIDS, FILODB_INDEX_RESOLVE,
                             FILODB_QUERY_REFUSED,
                             FILODB_QUERY_LEAF, FILODB_QUERY_LEAF_GATHER,
                             registry)
from ..utils.tracing import (SPAN_QUERY_GATHER, SPAN_QUERY_GROUPIDS,
                             SPAN_QUERY_KERNEL, SPAN_QUERY_LEAF,
                             SPAN_QUERY_ODP, SPAN_QUERY_REDUCE,
                             SPAN_QUERY_SELECT, span)
from .rangevector import (QueryError, QueryResult, QueryStats,
                          RangeVectorKey, ResultMatrix, fmt_value)

DEFAULT_SAMPLE_LIMIT = 1_000_000
GATHER_THRESHOLD = 8192      # selections narrower than this gather rows up front
ODP_BATCH = 4096             # wide on-demand paging proceeds in pid batches


@dataclass
class QueryContext:
    memstore: object
    dataset: str
    sample_limit: int = DEFAULT_SAMPLE_LIMIT
    stale_ms: int = 5 * 60 * 1000
    # per-query accounting: every leaf/ODP/remote hop feeds this one
    # accumulator (thread-safe; remote legs merge peer stats into it)
    stats: QueryStats = field(default_factory=QueryStats)
    # exec route taken for THIS query ("local"/"mesh-*"/"fused-hist"/...):
    # the engine's last_exec_path is engine-shared and racy under the
    # scheduler's concurrent workers — the slow-query log reads this one
    exec_path: str | None = None
    # fused-tier programs this query's local leaves ran, as (residency kind,
    # fusedgrid.kernel_tag) pairs; the engine folds them into exec_path
    # ("local-fused[pallas]", "local-fused-narrow[delta8,pallas]") so a
    # route through a compiled kernel, its interpreted form, the xla twin
    # and the composed two-step path read differently. Shared by reference
    # across dataclasses.replace copies, like ``stats``
    kernels: set = field(default_factory=set)
    # how this query's local leaves took their rows ("gather" | "wide" |
    # "paged", ``count_leaf``; a remote leaf adds "remote"): a plan whose
    # leaves ALL gathered a narrow selection and ran no fused program reads
    # "local-gather". Shared by reference like ``kernels``
    leaf_routes: set = field(default_factory=set)


@dataclass
class SeriesSelection:
    """Leaf output: device store arrays + which rows are selected.

    Three states, distinguished by ``rows`` and the array row count R:
    - ``rows is None``: arrays are exactly the selection (R == len(keys)).
    - ``rows`` = identity map [0..P): arrays are the gathered selection padded
      to R = pow2(P) rows; pad rows have n=0 and carry no key.
    - ``rows`` = store-row ids: arrays cover the full store [S, C]; ``rows[i]``
      is the array row of key i and ``n`` is zeroed outside the selection.
    Consumers only ever index arrays *by rows* (compaction, group-id scatter),
    which is correct in all three states.
    """
    ts: object                # [R, C] int64
    val: object               # [R, C] float (or [R, C, B] histogram buckets)
    n: object                 # [R] int32 (0 => row disabled)
    keys: list[RangeVectorKey]
    rows: np.ndarray | None   # int32 [P] array-row of each key, or None
    grid: tuple | None = None  # (base_ts, interval_ms) => MXU band-matmul path
    bucket_les: np.ndarray | None = None  # histogram bucket tops [B]
    # array-row indices of live selected series whose start cell differs from
    # the majority cohort grid/base_ts was shifted to (churn): the grid kernel
    # result is wrong for exactly these rows; PSM recomputes them generally
    grid_minority: np.ndarray | None = None
    # narrow operands (kind, operands, bad_rows) of the FULL store value
    # column: ``kind`` names the decode variant (ops/decodereg.py —
    # "quant16" for the mirror/quantized store, "delta16"/"delta8" for
    # delta-resident counters) and ``operands = (block, *row_operands)``;
    # the fused kernel streams them instead of val — 1/4 to 1/2 the HBM
    # bytes. ``bad_rows`` (store rows that are not bit-exact under the
    # encoding) fold into grid_minority. Wide selections only.
    narrow: tuple | None = None
    # hist-resident twin: (dd, first_d, bad_rows) of the FULL [S, C, B]
    # bucket block (ops/narrow.py build_narrow_hist) — the narrow hist grid
    # kernels stream it so the whole-store f32 temp never materializes;
    # ``bad_rows`` (store rows in the cohort pool) recompute via row-wise
    # decode through the general kernels. Wide selections only.
    hist_narrow: tuple | None = None
    # the store keeps stamps as line + residual (core/chunkstore.py
    # ``LineInfo``): ``grid`` is None and the fused scalar tier reads the
    # line — ``base_ts``/``interval_ms`` in the grid's place, each row's
    # start and the residual block beside ``val``; the rows off their line
    # are in ``grid_minority``. Wide selections only.
    line: object | None = None
    # some used cell of the store holds no sample (a missed scrape: its
    # stamp in ``ts`` lies past TS_PAD, core/chunkstore.py): the general
    # kernels are told (ops/rangefns.py ``_open_holes``)
    holes: bool = False
    # the store keeps its grid in time-aligned cells and holds a row born
    # late (core/chunkstore.py ``born_late``): the birth cell of every
    # array row, device i32 [R] — the grid and fused kernels then run in
    # their births mode, where a row's samples are its cells ``born <= c <
    # n`` — and how many of the SELECTED rows were born past the grid's
    # first cell (the dispatch span's ``born_late``). None / 0 otherwise
    born: object | None = None
    born_late: int = 0


@dataclass
class MatrixView:
    """Post-kernel matrix that may still be un-compacted (R >= P rows)."""
    out_ts: np.ndarray
    values: object            # [R, T] (or [R, T, B] for histogram results)
    keys: list[RangeVectorKey]
    rows: np.ndarray | None
    bucket_les: np.ndarray | None = None

    def compact(self) -> ResultMatrix:
        vals = self.values
        if self.rows is not None:
            vals = jnp.take(vals, jnp.asarray(self.rows), axis=0)
        return ResultMatrix(self.out_ts, vals, self.keys, self.bucket_les)


def _pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def _dval(arr):
    """Materialize a compressed-resident store's deferred view (transient
    f32 decode / i64 grid derivation); real arrays pass through. The single
    choke point general query paths funnel through — the fused/grid paths
    plan from shape metadata and never call this."""
    from ..core.chunkstore import DecodeRefused, _Deferred
    if not isinstance(arr, _Deferred):
        return arr
    try:
        return arr.materialize()
    except DecodeRefused as e:
        # a wide read of a store too deep to decode whole: the QUERY fails
        registry.counter(FILODB_QUERY_REFUSED,
                         {"reason": "decode_bytes"}).increment()
        raise QueryError(str(e)) from None


def _gather_rows_padded(ts, val, n, rows: np.ndarray, grid_gather=None):
    """Gather the given array rows padded to a pow2 row count (kernel-shape
    stability). Pad rows are fully disabled: n = 0 AND timestamps forced to
    the pad sentinel — the general kernels derive windows from timestamps, so
    a pad row aliasing row 0's real data would otherwise produce phantom
    (non-NaN) outputs that aggregation counts as present. ``grid_gather``
    (``SeriesStore.grid_row_gather``): the rows taken by the store's own
    one program, their stamps derived from the grid (a delta block's rows
    decoded in it), where the store has one for ``val`` — the s64 block is
    then no operand."""
    from ..core.chunkstore import _Deferred
    M = len(rows)
    P = _pow2(M)
    pad = np.zeros(P, np.int32)
    pad[:M] = rows
    if grid_gather is not None:
        return grid_gather(pad, M, val, n)[:3] + (P,)
    rid = jnp.asarray(pad)

    # deferred (compressed-resident) blocks gather row-wise — a minority fix
    # over a few rows must not materialize the full [S, C] block
    def take(block):
        return (block.gather_rows(rid) if isinstance(block, _Deferred)
                else jnp.take(block, rid, axis=0))

    ts_g, n_g = _disable_pad_rows(take(ts), jnp.take(n, rid), M)
    return ts_g, take(val), n_g, P


def _disable_pad_rows(ts_rows, n_rows, live):
    """(ts, n) of gathered rows with those past the first ``live`` disabled:
    n = 0 and TS_PAD all along (see ``_gather_rows_padded``)."""
    from ..core.chunkstore import TS_PAD
    real = jnp.arange(n_rows.shape[0]) < live
    return (jnp.where(real[:, None], ts_rows, TS_PAD),
            jnp.where(real, n_rows, 0).astype(jnp.int32))


def _take_rows(ts, val, n, rid, live):
    """``_gather_rows_padded`` of resident blocks as a traceable function of
    its operands: the store's ``(ts, val, n)``, then what the host knows —
    the pow2-padded row ids and how many of them are real."""
    ts_g, n_g = _disable_pad_rows(jnp.take(ts, rid, axis=0),
                                  jnp.take(n, rid), live)
    return ts_g, jnp.take(val, rid, axis=0), n_g


# device dispatches of a gathered leaf in its STEPWISE form, as its gather
# span's ``programs`` says them (a tag, not a profiler: the form's own
# constant): the gather's — an upload and the store's one program on a grid;
# ids, mask, three takes, two selects and a cast off it — and the window
# program after it, the least that follows
STEPWISE_PROGRAMS = {True: 3, False: 10}


def _joins_one_program(ts, val, les, minority_sel, on_grid: bool) -> bool:
    """May a narrow selection's gather join the leaf's one program
    (``GatheredRows``)? By what the selection can observe, no knob: scalar
    rows whose stamps are the grid's (``on_grid``: the store has a gather
    for them, ``SeriesStore.grid_gather_operands`` — of a resident value
    block, or of a delta block whose picked rows it decodes inside the
    program) or a resident s64 block beside a resident value block — a
    quant16 block decodes row by row on the host's say
    (``DeferredDecode.gather_rows``), a line store's stamps are laid
    together from host and device state (``SeriesStore._line_ts``) — all of
    one start cohort: a minority's rows are gathered again from the
    gathered ones (``_correct_minority_cohort``)."""
    from ..core.chunkstore import _Deferred
    return (les is None and minority_sel is None
            and (on_grid or not (isinstance(val, _Deferred)
                                 or isinstance(ts, _Deferred))))


def count_gather(tags: dict, programs: int) -> None:
    """How a gathered leaf reached the device, on its gather span
    (``programs``: 1 = gather, window function, step slice and the
    aggregate's map phase dispatched as ONE program; more = the stepwise
    form's dispatches) and in ``/metrics``."""
    tags["programs"] = programs
    registry.counter(FILODB_QUERY_LEAF_GATHER,
                     {"form": "one" if programs == 1 else "steps"}).increment()


@dataclass
class GatheredRows:
    """Leaf output of a NARROW selection whose rows are still in the store:
    what ``SeriesSelection``'s second state carries, less the three gathered
    device arrays. The gather has not run; it is composed into the ONE
    program the leaf dispatches under its shard's lock (``GatheredWindow``):
    every eager dispatch there is a release of the interpreter and a wait to
    get it back while the shard's other clients queue behind the lock.

    What the selection could observe decided this form (``_select``): a
    scalar store on the gather route, its value block resident, its stamps
    the grid's (``on_grid``: derived in the program from each row's first
    stamp, ``SeriesStore.grid_row_picks``) or a resident s64 block, no row
    of a minority cohort. Everything else is gathered in ``_select``, step
    by step, as it was."""
    store_ops: tuple          # on a grid (val, n) or a delta block's (dv,
    #                           anchor, pool, slot, n), else (ts, val, n)
    host_ops: tuple           # (picked s64 [3, P],) | (row ids s32 [P], live)
    on_grid: bool
    body: object              # the grid's gather (``grid_gather_operands``)
    decode: str               # "raw" | "delta8" | "delta16": done in it
    shape: tuple              # (P, C) of the rows once gathered
    dtype: object             # ... and their values' dtype
    keys: list[RangeVectorKey]
    rows: np.ndarray | None   # identity map [0..len(keys)) where P is padded
    grid: tuple | None
    span_tags: dict           # the gather span's: shard, rows, padded, bytes
    born_late: int = 0        # the picked rows born late (picked is [4, P])
    _sel: SeriesSelection | None = None

    # SeriesSelection's fields that are never set on this form
    bucket_les = None
    grid_minority = None
    line = None
    holes = False

    def gathered(self) -> SeriesSelection:
        """The rows gathered on their own, the stepwise form: for a
        consumer that is no window function of this module (the fused
        tier's kernel takes gathered rows; a raw selection leaving the
        leaf)."""
        if self._sel is None:
            with span(SPAN_QUERY_GATHER, **self.span_tags) as tags:
                count_gather(tags, STEPWISE_PROGRAMS[self.on_grid])
                # a store that holds a row born late: ``born`` beside n
                ts, val, n, *born = self.gather_body()(
                    *self.store_ops, *map(jnp.asarray, self.host_ops))
            self._sel = SeriesSelection(ts, val, n, self.keys, self.rows,
                                        self.grid, born=born[0] if born
                                        else None, born_late=self.born_late)
        return self._sel

    def gather_body(self):
        """``(*store_ops, *host_ops) -> (ts, val, n)``, traceable: the
        bodies the stepwise gather runs."""
        return self.body if self.on_grid else _take_rows


def check_sample_limit(num_series: int, steps: int, limit: int) -> None:
    """Shared result-size guard (ref: QueryConfig sample limits) — one
    definition for the ExecPlan, mesh, and fused-hist result paths."""
    if num_series * steps > limit:
        raise QueryError(
            f"result too large: {num_series} series x {steps} steps "
            f"> sample limit {limit}")


def _pad_steps(out_ts: np.ndarray) -> tuple[np.ndarray, int]:
    """(padded out_ts to a multiple of 32 by repeating the last step, true T).
    Window kernels jit-compile per output shape; padding buckets the compile
    space for ad-hoc query shapes (duplicate steps are sliced off after)."""
    T = len(out_ts)
    Tpad = -(-T // 32) * 32 if T else 0
    if Tpad == T:
        return out_ts, T
    return np.concatenate([out_ts, np.full(Tpad - T, out_ts[-1], np.int64)]), T


@dataclass
class FusedWindowData:
    """Lazy PeriodicSamplesMapper output on a grid-aligned f32 selection: the
    window function has NOT run yet. AggregateMapReduce recognizes this and
    fuses window evaluation + aggregation into one single-pass Pallas kernel
    (ops/fusedgrid.py) — the [S, T] rate matrix never hits HBM. Any other
    consumer materializes through the standard grid kernel first."""
    sel: "SeriesSelection | GatheredRows"
    out_ts: np.ndarray
    window: int
    fn: str
    stale_ms: int

    def unfused(self) -> "GatheredWindow":
        """Over rows not gathered yet, with no fused aggregate to run: the
        grid kernel composed after their gather."""
        return GatheredWindow(self.sel, self.out_ts, "grid", self.fn,
                              self.window, (0.0, 0.0), self.stale_ms)

    def materialize(self) -> MatrixView:
        from ..ops import gridfns
        if isinstance(self.sel, GatheredRows):
            return self.unfused().materialize()
        # same T-bucketing as PSM.apply: this fallback otherwise re-opens the
        # per-dashboard-shape compile cost on the hot f32 path
        out_eval, T = _pad_steps(self.out_ts)
        if self.sel.grid is None:
            # a line store with no aggregate over the window function to
            # fuse with: the general kernels, from the stamps themselves
            vals = rangefns.periodic_samples(
                _dval(self.sel.ts), _dval(self.sel.val), self.sel.n,
                out_eval, self.window, self.fn, holes=self.sel.holes)
            return MatrixView(self.out_ts, vals[:, :T], self.sel.keys,
                              self.sel.rows)
        base_ts, interval_ms = self.sel.grid
        vals = gridfns.periodic_samples_grid(
            _dval(self.sel.val), self.sel.n, out_eval, self.window, self.fn,
            base_ts, interval_ms, stale_ms=self.stale_ms, born=self.sel.born)
        minority = self.sel.grid_minority
        if minority is not None and len(minority):
            vals = _correct_minority_cohort(self.sel, vals, out_eval,
                                            self.window, self.fn, 0.0, 0.0)
        if vals.shape[1] != T:
            vals = vals[:, :T]
        return MatrixView(self.out_ts, vals, self.sel.keys, self.sel.rows)


@dataclass
class GatheredWindow:
    """Lazy PeriodicSamplesMapper output over ``GatheredRows``: neither the
    gather nor the window function has run. ``AggregateMapReduce`` composes
    its map phase onto them (``aggregate``), any other consumer asks for the
    matrix (``materialize``); either way ONE program is dispatched, from the
    plan cache, and what the host knows — the picked rows, the steps, the
    window, the function's arguments, the group ids — goes in as host
    values, the call's own arguments. Must not leave the shard lock
    undispatched (``LeafFrame.locked``), like ``FusedWindowData``."""
    sel: GatheredRows
    out_ts: np.ndarray
    kernel: str               # "grid" | "periodic": PSM.apply's own choice
    fn: str
    window: int
    args: tuple               # (arg0, arg1) of a "periodic" function
    stale_ms: int

    def materialize(self) -> MatrixView:
        return MatrixView(self.out_ts, self._dispatch(), self.sel.keys,
                          self.sel.rows)

    def aggregate(self, op: str, gids: np.ndarray, num_groups: int) -> dict:
        """``_segment_partial`` of the matrix, in the same program."""
        return self._dispatch(op, np.asarray(gids, np.int32), num_groups)

    def _dispatch(self, op=None, gids=None, num_groups=0):
        from ..ops import gridfns
        from .plancache import plan_cache
        sel = self.sel
        out_eval, T = _pad_steps(self.out_ts)
        if self.kernel == "grid":
            win_ops = gridfns.grid_kernel_operands(
                sel.shape[1], sel.dtype, out_eval, self.window, self.fn,
                *sel.grid, self.stale_ms)
        else:
            win_ops = rangefns.periodic_operands(out_eval, self.window,
                                                 *self.args)
        spec, *packed = _pack_operands(
            (*sel.host_ops, *win_ops) + (() if op is None else (gids,)))
        statics = (self.kernel, self.fn, op, num_groups, T, spec,
                   len(sel.host_ops))
        prog = plan_cache.program(
            "leaf",
            statics + (sel.on_grid, sel.decode) + sel.shape + tuple(
                (o.shape, str(o.dtype)) for o in sel.store_ops),
            lambda: functools.partial(_leaf_body, sel.gather_body(),
                                      *statics))
        with span(SPAN_QUERY_GATHER, **sel.span_tags) as tags:
            count_gather(tags, 1)
            return prog(sel.store_ops, *packed)


def _pack_operands(operands) -> tuple:
    """``(spec, ints, floats, device)`` of a program's operands: what the
    host knows laid into ONE s64 and at most one f64 host vector (None
    where there is no float), the device arrays as they are, and ``spec``
    (static, hashable) to take them apart again inside the program
    (``_unpack_operands``). Every host argument of a jitted call is a
    transfer of its own, and on the chip each costs the shard lock's holder
    ~0.4 ms (the interpreter let go and won back: PERF.md §6 PR 42) — seven
    small arguments were most of a narrow leaf's hold."""
    spec, ints, floats, device = [], [], [], []
    for x in operands:
        if isinstance(x, jax.Array):
            spec.append(("device", None, None))
            device.append(x)
            continue
        x = np.asarray(x)
        kind = "float" if x.dtype.kind == "f" else "int"
        (floats if kind == "float" else ints).append(x.ravel())
        spec.append((kind, x.shape, x.dtype.name))
    return (tuple(spec), np.concatenate(ints).astype(np.int64),
            np.concatenate(floats).astype(np.float64) if floats else None,
            tuple(device))


def _unpack_operands(spec, ints, floats, device) -> list:
    """The operands ``_pack_operands`` took, in their order, shapes and
    dtypes (every value exactly: s64 holds the integer types, f64 the
    floats)."""
    out, at = [], {"int": 0, "float": 0, "device": 0}
    for kind, shape, dtype in spec:
        if kind == "device":
            out.append(device[at[kind]])
            at[kind] += 1
            continue
        size = int(np.prod(shape, dtype=np.int64))
        src = ints if kind == "int" else floats
        out.append(src[at[kind]:at[kind] + size].reshape(shape).astype(dtype))
        at[kind] += size
    return out


def _leaf_body(gather, kernel, fn, op, num_groups, T, spec, n_host,
               store_ops, ints, floats, device):
    """THE one program of a gathered leaf: the row gather, the window
    function, the step-pad slice and the aggregate's map phase — the bodies
    the stepwise form dispatches one by one, composed unchanged (so the
    answers are that form's, bit for bit). Its operands: the store's blocks,
    then the gather's ``n_host`` host operands, the window kernel's and, with
    an aggregate, the group ids, packed (``_pack_operands``)."""
    from ..ops import gridfns
    ops = _unpack_operands(spec, ints, floats, device)
    gids = None if op is None else ops.pop()
    # (a fourth value where the picked rows come with birth cells)
    ts, val, n, *born = gather(*store_ops, *ops[:n_host])
    if kernel == "grid":
        vals = gridfns._grid_kernel(fn, val, n, *ops[n_host:], *born)
    else:
        vals = rangefns.periodic_body(fn)(ts, val, n, *ops[n_host:])
    vals = vals[:, :T]
    if op is None:
        return vals

    def reduce(vals, gids):
        return aggregators.partial_aggregate(op, vals, gids,
                                             num_groups=num_groups,
                                             stable=True)

    # The reduce as a computation of its own, as it is a program of its own
    # in the stepwise form: fused with the window function's last
    # arithmetic the compiler contracts other multiplies and adds into one
    # fma (XLA:CPU drops an optimization barrier before it fuses), and
    # quantile_over_time's interpolation comes out another last bit. A group
    # id is never negative; the compiler cannot know, and no fusion crosses
    # a conditional.
    return jax.lax.cond(gids[0] >= 0, reduce, reduce, vals, gids)


def _correct_minority_cohort(data, vals, out_ts, window, fn, a0, a1,
                             hist: bool = False, rows=None):
    """Patch grid-kernel output for churned rows: series whose start cell
    differs from the majority cohort (the band matrices assume the majority
    start) are recomputed through the general searchsorted kernels — an
    [M, C] row gather for a small M, scattered back into the [R, T] result.
    ``rows`` overrides the row set (e.g. churn minority merged with a
    compressed store's cohort-pool rows)."""
    rows = np.asarray(data.grid_minority if rows is None else rows, np.int32)
    M = len(rows)
    sub_ts, sub_val, sub_n, _ = _gather_rows_padded(data.ts, data.val, data.n, rows)
    if hist:
        corr = rangefns.periodic_samples_hist(sub_ts, sub_val, sub_n,
                                              out_ts, window, fn, a0)
    else:
        corr = rangefns.periodic_samples(sub_ts, sub_val, sub_n,
                                         out_ts, window, fn, a0, a1,
                                         holes=data.holes)
    return vals.at[jnp.asarray(rows)].set(corr[:M].astype(vals.dtype))


# ---------------------------------------------------------------------------
# Transformers (ref: RangeVectorTransformer)
# ---------------------------------------------------------------------------

class Transformer:
    def apply(self, data, ctx: QueryContext):  # pragma: no cover - interface
        raise NotImplementedError


@dataclass
class PeriodicSamplesMapper(Transformer):
    """Range/instant function evaluation (ref: PeriodicSamplesMapper.scala:23)."""
    start_ms: int
    step_ms: int
    end_ms: int
    window_ms: int | None     # None => instant selector (staleness lookback)
    function: str | None      # None => last_sample
    args: tuple = ()

    def out_ts(self, ctx) -> np.ndarray:
        step = max(self.step_ms, 1)
        return np.arange(self.start_ms, self.end_ms + 1, step, dtype=np.int64)

    def apply(self, data, ctx: QueryContext):
        assert isinstance(data, (SeriesSelection, GatheredRows)), \
            "PSM must sit directly on a leaf"
        out_ts = self.out_ts(ctx)
        if len(out_ts) == 0:
            return MatrixView(out_ts, np.zeros((len(data.keys), 0)),
                              data.keys, data.rows, data.bucket_les)
        # bucket the step count: the window kernels jit-compile per output
        # shape, and ad-hoc dashboards produce a fresh T per query — pad the
        # evaluation grid to a multiple of 32 (repeating the last step, whose
        # duplicate results are sliced off) so compiles amortize across query
        # shapes (the fused path pads to 128 internally already)
        out_eval, T = _pad_steps(out_ts)
        Tpad = len(out_eval)
        fn = self.function or "last_sample"
        if fn in ("last_sample", "last_sample_age"):
            window = ctx.stale_ms
            args = (float(ctx.stale_ms),)
        else:
            window = self.window_ms
            args = tuple(float(a) for a in self.args)
        a0 = args[0] if len(args) > 0 else 0.0
        a1 = args[1] if len(args) > 1 else 0.0
        from ..ops import gridfns
        grid_usable = (
            data.grid is not None
            and max(abs(int(out_ts[0]) - data.grid[0]),
                    abs(int(out_ts[-1]) - data.grid[0])) + window < 2**31)
        minority = data.grid_minority
        if data.bucket_les is not None:
            if fn not in rangefns.HIST_FNS:
                raise QueryError(f"function {fn} not supported on histogram series")
            if grid_usable and fn in gridfns.HIST_GRID_FNS:
                base_ts, interval_ms = data.grid
                if data.hist_narrow is not None:
                    # hist-resident store: stream the i8/i16 2D-delta block;
                    # cohort-pool rows join the minority set and recompute
                    # through the general kernels (row-wise decode)
                    dd, first_d, bad = data.hist_narrow
                    if len(bad):
                        minority = (bad if minority is None
                                    or not len(minority)
                                    else np.union1d(np.asarray(minority), bad))
                    vals = gridfns.periodic_samples_grid_hist_narrow(
                        dd, first_d, data.n, out_eval, window, fn, base_ts,
                        interval_ms, stale_ms=ctx.stale_ms)
                else:
                    vals = gridfns.periodic_samples_grid_hist(
                        _dval(data.val), data.n, out_eval, window, fn,
                        base_ts, interval_ms, stale_ms=ctx.stale_ms)
                if minority is not None and len(minority):
                    vals = _correct_minority_cohort(data, vals, out_eval, window,
                                                    fn, a0, a1, hist=True,
                                                    rows=minority)
            else:
                # off-grid shard: general searchsorted hist path (ref:
                # HistogramVector read through chunked range functions)
                vals = rangefns.periodic_samples_hist(_dval(data.ts),
                                                      _dval(data.val), data.n,
                                                      out_eval, window, fn, a0)
            if Tpad != T:
                vals = vals[:, :T]
            return MatrixView(out_ts, vals, data.keys, data.rows, data.bucket_les)
        if data.line is not None and self._line_fusable(data, fn, window,
                                                        out_ts):
            return FusedWindowData(data, out_ts, window, fn, ctx.stale_ms)
        on_grid_fn = grid_usable and fn in gridfns.GRID_FNS
        if on_grid_fn and self._fusable(data, fn, out_ts):
            # defer: a following AggregateMapReduce can fuse the window
            # function with the aggregation in one single-pass program
            # (Pallas or the XLA-fused twin per query.fused_kernels)
            return FusedWindowData(data, out_ts, window, fn, ctx.stale_ms)
        if isinstance(data, GatheredRows):
            # rows not gathered yet: the kernel this method would run now,
            # composed with their gather and whatever follows
            return GatheredWindow(data, out_ts,
                                  "grid" if on_grid_fn else "periodic", fn,
                                  window, (a0, a1), ctx.stale_ms)
        if on_grid_fn:
            base_ts, interval_ms = data.grid
            vals = gridfns.periodic_samples_grid(_dval(data.val), data.n,
                                                 out_eval, window,
                                                 fn, base_ts, interval_ms,
                                                 stale_ms=ctx.stale_ms,
                                                 born=data.born)
            if minority is not None and len(minority):
                vals = _correct_minority_cohort(data, vals, out_eval, window,
                                                fn, a0, a1)
        else:
            vals = rangefns.periodic_samples(_dval(data.ts), _dval(data.val),
                                             data.n, out_eval, window, fn,
                                             a0, a1, holes=data.holes)
        if Tpad != T:
            vals = vals[:, :T]
        return MatrixView(out_ts, vals, data.keys, data.rows)


    @staticmethod
    def _fusable(data, fn, out_ts) -> bool:
        """May a following aggregate fuse with this window function?"""
        from ..ops import fusedgrid, fusedresident
        block = data if isinstance(data, GatheredRows) else data.val
        S, C = block.shape
        return (fusedresident.mode() != "off"
                and fusedresident.scalar_shape_of(fn) is not None
                and block.dtype == jnp.float32
                and fusedgrid.fusable(S, C, len(out_ts), 1))

    @classmethod
    def _line_fusable(cls, data, fn, window, out_ts) -> bool:
        """... over a line store's selection: the grid's conditions (the
        relative range fits i32 among them) and the line's own."""
        from ..ops import fusedgrid
        base = data.line.base_ts
        return (cls._fusable(data, fn, out_ts)
                and fusedgrid.line_fusable(window, data.line.interval_ms)
                and max(abs(int(out_ts[0]) - base),
                        abs(int(out_ts[-1]) - base)) + window < 2**31)


@dataclass
class InstantVectorFunctionMapper(Transformer):
    function: str
    args: tuple = ()

    def apply(self, data, ctx):
        m = _as_matrix(data)
        if self.function in ("histogram_quantile", "histogram_bucket",
                             "histogram_max_quantile"):
            from ..ops import gridfns
            if m.bucket_les is None:
                if self.function == "histogram_quantile":
                    # classic le-labeled bucket series (what remote-write and
                    # the Influx gateway ingest): group by labels minus le,
                    # sort buckets, fix monotonicity, same quantile algebra
                    # (ref: HistogramQuantileMapper.scala:23-90)
                    return _classic_le_quantile(m, float(self.args[0]))
                raise QueryError(f"{self.function} requires native histogram series")
            les = np.asarray(m.bucket_les, np.float64)
            if self.function == "histogram_bucket":
                b = int(np.argmin(np.abs(les - self.args[0])))
                return ResultMatrix(m.out_ts, m.values[:, :, b], m.keys)
            q = float(self.args[0])
            vals = gridfns.histogram_quantile(jnp.float64(q), jnp.asarray(les),
                                              jnp.asarray(m.values))
            return ResultMatrix(m.out_ts, vals, m.keys)
        if m.bucket_les is not None:
            raise QueryError(f"{self.function} not supported on histogram series")
        if self.function == "absent":
            vals = np.asarray(m.values)
            empty = np.isnan(vals).all(axis=0) if len(m.keys) else np.ones(len(m.out_ts), bool)
            out = np.where(empty, 1.0, np.nan)[None, :]
            return ResultMatrix(m.out_ts, out, [RangeVectorKey(())])
        return ResultMatrix(m.out_ts, instantfns.apply(self.function, m.values, self.args),
                            m.keys)


def _classic_le_quantile(m, q: float) -> ResultMatrix:
    """histogram_quantile over classic ``le``-labeled scalar bucket series
    (ref: HistogramQuantileMapper.scala:23-90 + Histogram.scala:288).

    Groups input series by labels minus ``le``, sorts each group's buckets by
    ascending le, repairs monotonicity (NaN or decreasing bucket rates take
    the running max — scrapes are not atomic across buckets), and computes
    the Prometheus quantile with the SAME algebra as the native-histogram
    device path (ops/gridfns.histogram_quantile), so both ingestion forms
    answer identically. Host numpy: group counts are dashboard-sized and the
    ragged per-group bucket layouts don't batch."""
    if not len(m.keys):
        return ResultMatrix(m.out_ts, np.zeros((0, len(m.out_ts))), [])
    vals = np.asarray(m.values, np.float64)               # [R, T]
    groups: dict[RangeVectorKey, list[tuple[float, int]]] = {}
    for i, k in enumerate(m.keys):
        d = k.as_dict()
        le_s = d.get("le")
        if le_s is None:
            raise QueryError(
                "cannot calculate histogram quantile: 'le' tag is absent in "
                f"time series {d}")
        try:
            le = np.inf if le_s == "+Inf" else float(le_s)
        except ValueError:
            raise QueryError(
                f"cannot calculate histogram quantile: unparseable le tag "
                f"{le_s!r} in time series {d}") from None
        groups.setdefault(k.without(("le",)), []).append((le, i))
    T = len(m.out_ts)
    out = np.full((len(groups), T), np.nan)
    keys = list(groups)
    for g, gk in enumerate(keys):
        buckets = sorted(groups[gk], key=lambda p: p[0])
        les = np.array([b[0] for b in buckets])
        if not np.isinf(les[-1]):
            continue              # no +Inf bucket: quantile undefined (NaN)
        counts = vals[[b[1] for b in buckets]].T           # [T, B] cumulative
        # makeMonotonic: running max along the bucket axis, floor 0 — NaN and
        # regressions (bucket churn, non-atomic scrapes) take the prior max
        counts = np.maximum.accumulate(
            np.where(np.isnan(counts), -np.inf, counts), axis=1)
        counts = np.maximum(counts, 0.0)
        # the SAME quantile algebra as the native-histogram device path,
        # evaluated host-side: parity by construction, not discipline
        from ..ops import gridfns
        out[g] = gridfns.histogram_quantile_np(q, les, counts)
    return ResultMatrix(m.out_ts, out, keys)


@dataclass
class ScalarOperationMapper(Transformer):
    operator: str
    scalar: float
    scalar_is_lhs: bool = False

    _resolved = None

    def prepare(self, ctx) -> None:
        """Resolve a step-varying scalar subplan (time(), scalar(v)) ONCE per
        query, called by the leaf BEFORE it takes its shard lock: executing
        the subplan inside the lock would nest shard locks across queries
        (ABBA deadlock) and re-run it per ODP batch."""
        if isinstance(self.scalar, ExecPlan) and self._resolved is None:
            sm = _as_matrix(self.scalar.execute(ctx)).to_host()
            self._resolved = np.asarray(sm.values, np.float64)[0]

    def apply(self, data, ctx):
        m = _as_matrix(data)
        s = self.scalar
        if isinstance(s, ExecPlan):
            self.prepare(ctx)     # non-leaf chains have no lock to avoid
            s = self._resolved    # [T] array broadcasts against [P, T]
        vals = binop.apply_scalar_op(self.operator, s, m.values,
                                     self.scalar_is_lhs)
        keys = m.keys
        op = self.operator.removesuffix("_bool")
        if op in binop.MATH_OPS or self.operator.endswith("_bool"):
            keys = [k.without(("_metric_",)) for k in keys]
        return ResultMatrix(m.out_ts, vals, keys)


class LazyKeys:
    """Sequence of RangeVectorKeys materialized on first access per element.
    Wide selections (a 1M-series sum()) must not pay a Python loop over every
    series at the leaf — global aggregation never reads the keys at all.

    Deferred materialization races partition release: an eviction/purge can
    reuse a pid slot after the leaf snapshot, and rv_key_of would then return
    the NEW owner's labels. Per-slot release epochs (captured under the shard
    lock at leaf time) detect that for exactly the selected pids and fail the
    query loudly — a retry is correct; silently mislabeled series are not.
    Releases of unrelated partitions do not invalidate the selection."""

    def __init__(self, shard, pids):
        """``pids``: the shard's ``ShardSelection`` (its arrays are shared
        with every query the selection memo serves it to), or bare part
        ids."""
        self._shard = shard
        self._sel = (pids if isinstance(pids, ShardSelection)
                     else ShardSelection(shard, pids))
        self.pids = self._sel.pids
        self._sel.snapshot()

    def _check(self):
        if self._sel.released():
            raise QueryError("selection invalidated by concurrent partition "
                             "release (eviction/purge); retry the query")

    def __len__(self):
        return len(self.pids)

    def __getitem__(self, i):
        with self._shard.lock:   # label arena mutates during release
            self._check()
            if isinstance(i, slice):
                return [self._shard.rv_key_of(int(p)) for p in self.pids[i]]
            return self._shard.rv_key_of(int(self.pids[i]))

    def __iter__(self):
        with self._shard.lock:
            self._check()
            keys = [self._shard.rv_key_of(int(p)) for p in self.pids]
        return iter(keys)

    def grouping(self, by, without):
        """(the selection's ``Grouping`` for ``by``/``without``, ``hit`` |
        ``miss`` | ``bypass`` of the selection memo): group ids and the G
        group keys straight from the index's label columns
        (PartKeyIndex.group_ids), computed once per index state — no key of
        a selected series is materialized. Same guard as reading the keys."""
        with self._shard.lock:
            self._check()
            return self._sel.grouping(by, without)


def count_groupids(route: str) -> None:
    registry.counter(FILODB_GROUPIDS, {"route": route}).increment()


def count_leaf(ctx, tags: dict, route: str) -> None:
    """How a leaf took its rows, on its select span, in ``/metrics`` and in
    the query's ``leaf_routes``: ``gather`` = a narrow selection (at most
    GATHER_THRESHOLD series and under half the index: keys materialized,
    rows gathered to a power of two, none for an empty selection), ``wide``
    = the store's own blocks with ``n`` zeroed outside the selection,
    ``paged`` = merged with cold chunks from the sink."""
    tags["route"] = route
    ctx.leaf_routes.add(route)
    registry.counter(FILODB_QUERY_LEAF, {"route": route}).increment()


def _group_ids_for(keys, rows, R, by, without):
    """Dense per-array-row group ids for aggregation: (gids [R], group key
    list, G). Rows outside the selection keep group 0 — harmless, their
    values are all-NaN / zero-count. The source follows the input: a
    selection that is still pids (``LazyKeys``) groups by the index's label
    columns, materialized keys (narrow or paged selections, matrices out of
    joins and functions) by a walk over them. The array may be one the
    selection memo shares between queries: read-only, copy before a write."""
    return _grouping_for(keys, rows, R, by, without)[:3]


def _grouping_for(keys, rows, R, by, without):
    """``_group_ids_for`` and, fourth, the device copy of ``gids`` where the
    selection memo keeps one (None: the caller uploads)."""
    if len(keys) and not by and not without:
        # global aggregation: one group, keys never materialized
        return np.zeros(R, np.int32), [RangeVectorKey(())], 1, None
    route = "index" if isinstance(keys, LazyKeys) else "walk"
    with span(SPAN_QUERY_GROUPIDS, keys=len(keys), route=route) as tags:
        if route == "index":
            grouping, tags["memo"] = keys.grouping(by, without)
            gid_of_key, uniq = grouping.gids, list(grouping.keys)
        else:
            seen: dict[RangeVectorKey, int] = {}
            gid_of_key = np.fromiter(
                (seen.setdefault(gk, len(seen))
                 for gk in group_keys_of(keys, by, without)),
                np.int32, count=len(keys))
            uniq = list(seen)
        G = tags["groups"] = max(len(uniq), 1)
    count_groupids(route)
    dev = None
    if not len(keys):
        gids = np.zeros(R, np.int32)
    elif rows is None:
        gids = gid_of_key
    elif route == "index" and rows is keys.pids:
        gids, dev = grouping.dense(rows, R)
    else:
        gids = np.zeros(R, np.int32)
        gids[rows] = gid_of_key
    return gids, uniq, G, dev


def group_keys_of(keys, by, without):
    """Aggregation group key per series (metric label always dropped —
    Prometheus aggregation semantics; ref AggrOverRangeVectors map phase)."""
    out = []
    for k in keys:
        k = k.without(("_metric_",))
        if by:
            out.append(k.only(by))
        elif without:
            out.append(k.without(without))
        else:
            out.append(RangeVectorKey(()))
    return out


@dataclass
class AggregateMapReduce(Transformer):
    """Map phase: matrix -> per-group partial state (ref: AggregateMapReduce)."""
    operator: str
    params: tuple = ()
    by: tuple = ()
    without: tuple = ()

    # order-statistics aggregators with too many groups fall back to full
    # matrices; G is small in practice (topk is usually global)
    ORDER_STAT_MAX_GROUPS = 64

    def apply(self, data, ctx):
        if self.operator in ("topk", "bottomk", "quantile", "count_values"):
            return self._map_order_stat(data, ctx)
        if isinstance(data, FusedWindowData):
            from ..ops import fusedgrid
            if self.operator in fusedgrid.FUSED_OPS:
                fused = self._apply_fused(data, ctx)
                if fused is not None:
                    return fused
            data = (data.unfused() if isinstance(data.sel, GatheredRows)
                    else data.materialize())
        if isinstance(data, GatheredWindow):
            P = data.sel.shape[0]
            gids, uniq, G = _group_ids_for(data.sel.keys, data.sel.rows, P,
                                           self.by, self.without)
            return AggPartial(self.operator, data.out_ts,
                              data.aggregate(self.operator, gids, _pow2(G)),
                              list(uniq), G, None)
        if isinstance(data, MatrixView):
            m = data
        else:
            mm = _as_matrix(data)
            m = MatrixView(mm.out_ts, mm.values, mm.keys, None, mm.bucket_les)
        gids, uniq, G = _group_ids_for(m.keys, m.rows, m.values.shape[0],
                                       self.by, self.without)
        vals = m.values
        les = m.bucket_les
        if les is not None:
            if self.operator not in ("sum", "count", "group"):
                raise QueryError(f"{self.operator} not supported on histograms")
            R_, T_, B_ = vals.shape
            vals = vals.reshape(R_, T_ * B_)   # bucket-wise reduce (hSum)
        parts = _segment_partial(self.operator, vals, jnp.asarray(gids), _pow2(G))
        return AggPartial(self.operator, m.out_ts, parts, list(uniq), G, les)

    def _apply_fused(self, data: FusedWindowData, ctx) -> "AggPartial | None":
        """Single-pass window + aggregation (ops/fusedgrid.py): partial state
        comes straight off the streaming kernel; churned minority-cohort rows
        are excluded there (n forced to 0) and folded in via the general path.
        Returns None when the group count exceeds the kernel's VMEM cap — the
        caller falls back to the two-step path (segment_sum handles large G)."""
        from ..ops import fusedgrid, fusedresident
        sel = data.sel
        lazy = isinstance(sel, GatheredRows)
        R = sel.shape[0] if lazy else sel.val.shape[0]
        gids, uniq, G, gids_dev = _grouping_for(sel.keys, sel.rows, R,
                                                self.by, self.without)
        Gp = _pow2(G)
        if Gp > fusedgrid.MAX_GROUPS:
            fusedresident.count_fallback(
                fusedresident.scalar_shape_of(data.fn) or "rate_sum")
            return None
        if lazy:
            # the fused kernel takes gathered rows: the stepwise gather
            sel = data.sel = sel.gathered()
        line = None
        if sel.grid is not None:
            base_ts, interval_ms = sel.grid
        else:
            base_ts, interval_ms = sel.line.base_ts, sel.line.interval_ms
            line = (sel.line.start, sel.line.res)
        n_eff = sel.n
        minority = sel.grid_minority
        narrow = None
        if sel.narrow is not None:
            # narrow store/mirror: rows that don't round-trip bit-exactly
            # join the minority set — excluded from the kernel and recomputed
            # via the general path below, exactly like churned cohorts
            kind, nops, bad = sel.narrow
            narrow = (kind, nops)
            if len(bad):
                minority = (bad if minority is None or not len(minority)
                            else np.union1d(np.asarray(minority), bad))
        has_minority = minority is not None and len(minority)
        if has_minority:
            n_eff = n_eff.at[jnp.asarray(np.asarray(minority))].set(0)
        if G == 1 and not self.by and not self.without:
            gids_dev = fusedgrid.zero_gids(R)   # cached: no per-query upload
        elif gids_dev is None:      # else the selection memo's device copy
            gids_dev = jnp.asarray(gids)
        # fetch=False: the leaf holds the shard lock through this dispatch —
        # the blocking host fetch happens at present/merge time, outside it.
        # With narrow operands the kernel streams the i16 state and sel.val
        # may stay a deferred decode (shape metadata only). The registry
        # picks the backend (Pallas kernel / XLA-fused twin) per
        # query.fused_kernels and records the per-query fused route
        parts = fusedresident.scalar_aggregate(
            self.operator, data.fn,
            sel.val if narrow is not None else _dval(sel.val),
            n_eff, gids_dev, Gp,
            data.out_ts, data.window, base_ts, interval_ms, fetch=False,
            narrow=narrow, line=line,
            holes=line is not None and sel.line.holes,
            born=sel.born, born_late=sel.born_late)
        ctx.stats.add("fused_kernels")
        ctx.kernels.add((narrow[0] if narrow is not None else "raw",
                         fusedresident.tag()))
        if has_minority:
            rows = np.asarray(minority, np.int32)
            sub_ts, sub_val, sub_n, P = _gather_rows_padded(sel.ts, sel.val,
                                                            sel.n, rows)
            corr = rangefns.periodic_samples(sub_ts, sub_val, sub_n,
                                             data.out_ts, data.window, data.fn,
                                             holes=sel.holes)
            mgids = np.zeros(P, np.int32)
            mgids[:len(rows)] = gids[rows]
            mparts = _segment_partial(self.operator, corr, jnp.asarray(mgids), Gp)
            parts = aggregators.combine_partials(self.operator, parts, mparts)
        return AggPartial(self.operator, data.out_ts, parts, list(uniq), G, None)

    def _map_order_stat(self, data, ctx):
        """Map phase for topk/bottomk/quantile/count_values: per-shard partial
        state instead of shipping the full [P, T] matrix to the reduce node
        (ref: RowAggregator partial state incl. t-digest,
        AggrOverRangeVectors.scala:244-)."""
        if isinstance(data, (FusedWindowData, GatheredWindow)):
            data = data.materialize()
        if isinstance(data, MatrixView):
            m = data
        else:
            mm = _as_matrix(data)
            m = MatrixView(mm.out_ts, mm.values, mm.keys, None, mm.bucket_les)
        return _order_stat_map(m, self.operator, self.params, self.by,
                               self.without, cap=self.ORDER_STAT_MAX_GROUPS)


# quantile partial memory gate: fall back to the exact full matrix when the
# dense sketch would dwarf what it replaces
_SKETCH_BYTES_CAP = 64 << 20


def _order_stat_map(m: MatrixView, op, params, by, without, cap=None):
    """Shared map phase; with ``cap`` set, large group counts (or oversized
    sketches) fall back to the exact full matrix. The reduce node calls this
    WITHOUT a cap to normalize a fallen-back shard into partial form when its
    siblings produced partials."""
    if m.bucket_les is not None:
        raise QueryError(f"{op} not supported on histograms")
    R = m.values.shape[0]
    gids, uniq, G = _group_ids_for(m.keys, m.rows, R, by, without)
    T = len(m.out_ts)
    if cap is not None and G > cap:
        return m.compact()               # exact full-matrix fallback
    if op in ("topk", "bottomk"):
        k = max(int(params[0]), 0)       # topk(0, ...) selects nothing
        return _map_topk(m, gids, uniq, G, k, op == "bottomk")
    if op == "quantile":
        # the bytes gate holds even for reduce-side normalization (cap=None):
        # a dense sketch for a huge group count must never be allocated
        if G * aggregators.SKETCH_WIDTH * T * 4 > _SKETCH_BYTES_CAP:
            return m.compact()
        counts = aggregators.quantile_sketch(np.asarray(m.values), gids, G)
        return SketchPartial(float(params[0]), m.out_ts, list(uniq), counts)
    # count_values: vectorized host histogram of distinct values
    vals_h = np.asarray(m.values)
    label = str(params[0])
    present = ~np.isnan(vals_h)
    p_idx, t_idx = np.nonzero(present)
    v = vals_h[p_idx, t_idx]
    g = gids[p_idx] if len(gids) else np.zeros(0, np.int32)
    uvals, vinv = np.unique(v, return_inverse=True)
    pair = g.astype(np.int64) * max(len(uvals), 1) + vinv
    upairs, pinv = np.unique(pair, return_inverse=True)
    counts = np.zeros((len(upairs), T))
    np.add.at(counts, (pinv, t_idx), 1.0)
    entries: dict = {}
    for i, pr in enumerate(upairs):
        gi, vi = divmod(int(pr), max(len(uvals), 1))
        key = (gi, fmt_value(uvals[vi]))
        # distinct floats could share a truncated rendering: counts accumulate
        if key in entries:
            entries[key] = entries[key] + counts[i]
        else:
            entries[key] = counts[i]
    return CountValuesPartial(label, m.out_ts, list(uniq), entries)


def _map_topk(m: MatrixView, gids, uniq, G: int, k: int, bottom: bool):
    """Per-shard top-k candidates per (group, step): [G, k, T] values + key
    refs — only k series' worth of data crosses the reduce. Presence is
    decided by an exact per-slot mask (selected row AND non-NaN), so real
    +/-Inf samples survive and un-selected pad rows never leak in."""
    T0 = len(m.out_ts)
    R = m.values.shape[0]
    if k == 0 or not len(m.keys):
        return TopKPartial(k, bottom, m.out_ts, list(uniq),
                           np.full((G, 0, T0), np.nan),
                           np.full((G, 0, T0), -1, np.int64), [])
    # array row -> key index (rows may be a non-identity store-row mapping)
    if m.rows is None:
        valid_rows = np.zeros(R, bool)
        valid_rows[:len(m.keys)] = True
        row_to_key = None
    else:
        valid_rows = np.zeros(R, bool)
        valid_rows[m.rows] = True
        row_to_key = {int(r): i for i, r in enumerate(m.rows)}
    vals = m.values if isinstance(m.values, jnp.ndarray) else jnp.asarray(m.values)
    vals = vals.astype(jnp.float64)
    nanmask = jnp.isnan(vals)
    vmask = jnp.asarray(valid_rows)
    garr = jnp.asarray(gids)
    fill = jnp.inf if bottom else -jnp.inf
    fmax = np.finfo(np.float64).max
    # real +/-Inf samples must outrank fill rows at equal sort value: clamp
    # them to +/-DBL_MAX in the SORT domain only (reported values come from
    # the original matrix via the selected indices)
    sortable = jnp.clip(vals, -fmax, fmax)
    out_vals = np.full((G, k, T0), np.nan)
    out_ref = np.full((G, k, T0), -1, np.int64)
    key_rows: list[int] = []
    row_slot: dict[int, int] = {}
    kk = min(k, R)
    for g in range(G):
        presence = (vmask & (garr == g))[:, None] & ~nanmask     # [R, T]
        gv = jnp.where(presence, sortable, fill)
        sv = -gv if bottom else gv
        _, top_i = jax.lax.top_k(sv.T, kk)                       # [T, kk]
        top_ok = jnp.take_along_axis(presence.T, top_i, axis=1)  # exact mask
        # ONE host fetch for all three small arrays (each separate fetch is
        # a host sync of its own: a dispatch round trip)
        top_v, top_i, ok = jax.device_get(
            (jnp.take_along_axis(vals.T, top_i, axis=1), top_i, top_ok))
        for t, s in zip(*np.nonzero(ok)):
            row = int(top_i[t, s])
            slot = row_slot.get(row)
            if slot is None:
                slot = row_slot[row] = len(key_rows)
                key_rows.append(row)
            out_vals[g, s, t] = top_v[t, s]
            out_ref[g, s, t] = slot
    ki = (key_rows if row_to_key is None
          else [row_to_key[r] for r in key_rows])
    key_table = [m.keys[i] for i in ki]
    return TopKPartial(k, bottom, m.out_ts, list(uniq), out_vals, out_ref,
                       key_table)


@dataclass
class TopKPartial:
    """topk/bottomk partial state: per (group, slot, step) candidate values
    and their source-series keys."""
    k: int
    bottom: bool
    out_ts: np.ndarray
    group_keys: list
    values: np.ndarray            # [G, k, T] f64, NaN = empty slot
    key_ref: np.ndarray           # [G, k, T] int64 into key_table, -1 = empty
    key_table: list


@dataclass
class SketchPartial:
    """quantile partial state: DDSketch-style log-bucket counts [G, W, T]."""
    q: float
    out_ts: np.ndarray
    group_keys: list
    counts: np.ndarray


@dataclass
class CountValuesPartial:
    """count_values partial state: (group, value-string) -> [T] counts."""
    label: str
    out_ts: np.ndarray
    group_keys: list
    entries: dict                  # (gid, vstr) -> np[T]


@dataclass
class _WideODP:
    """do_execute marker: the selection needs wide on-demand paging. The
    leaf's execute() converts it via _paged_batches OUTSIDE the long-held
    shard lock; ExecPlan.execute passes it through untransformed."""
    pids: np.ndarray


def _merge_heterogeneous(results, op, params, by, without):
    """Merge a mixed list of aggregation partials (normalizing any member
    that fell back to a full matrix). Returns None when no partials are
    present — the caller concatenates matrices instead."""
    if results and all(isinstance(r, AggPartial) for r in results):
        return _merge_partials(op, results)
    kinds = {TopKPartial: _merge_topk, SketchPartial: _merge_sketch,
             CountValuesPartial: _merge_count_values}
    for kind, merge in kinds.items():
        if not any(isinstance(r, kind) for r in results):
            continue
        norm = [r if isinstance(r, kind)
                else _order_stat_map(_as_mview(r), op, params, by, without)
                for r in results]
        if not all(isinstance(r, kind) for r in norm):
            # normalization refused (e.g. a quantile sketch over the memory
            # gate): partial state cannot be reconstituted into a matrix, so
            # fail loudly rather than merge wrong
            raise QueryError(f"{op} grouping too wide to merge across shards; "
                             "narrow the by() clause")
        return merge(norm)
    return None


def _as_mview(data) -> MatrixView:
    if isinstance(data, MatrixView):
        return data
    m = _as_matrix(data)
    return MatrixView(m.out_ts, m.values, m.keys, None, m.bucket_les)


def _align_groups(parts):
    """Union group-key space across shard partials: (mapping, G)."""
    all_groups: dict[RangeVectorKey, int] = {}
    for p in parts:
        for gk in p.group_keys:
            all_groups.setdefault(gk, len(all_groups))
    return all_groups, max(len(all_groups), 1)


def _merge_sketch(parts: list["SketchPartial"]) -> "SketchPartial":
    first = parts[0]
    all_groups, G = _align_groups(parts)
    W, T = first.counts.shape[1], first.counts.shape[2]
    merged = np.zeros((G, W, T), np.float32)
    for p in parts:
        for gi, gk in enumerate(p.group_keys):
            merged[all_groups[gk]] += p.counts[gi]
    return SketchPartial(first.q, first.out_ts, list(all_groups), merged)


def _merge_count_values(parts: list["CountValuesPartial"]) -> "CountValuesPartial":
    first = parts[0]
    all_groups, _G = _align_groups(parts)
    entries: dict = {}
    for p in parts:
        remap = [all_groups[gk] for gk in p.group_keys]
        for (gi, vstr), row in p.entries.items():
            key = (remap[gi] if remap else 0, vstr)
            if key in entries:
                entries[key] = entries[key] + row
            else:
                entries[key] = row
    return CountValuesPartial(first.label, first.out_ts, list(all_groups),
                              entries)


def _merge_topk(parts: list[TopKPartial]) -> TopKPartial:
    first = parts[0]
    all_groups, G = _align_groups(parts)
    T = len(first.out_ts)
    k = first.k
    key_table: list = []
    cand_v = np.full((G, 0, T), np.nan)
    cand_r = np.full((G, 0, T), -1, np.int64)
    for p in parts:
        off = len(key_table)
        key_table.extend(p.key_table)
        pv = np.full((G, p.values.shape[1], T), np.nan)
        pr = np.full((G, p.values.shape[1], T), -1, np.int64)
        for gi, gk in enumerate(p.group_keys):
            gg = all_groups[gk]
            pv[gg] = p.values[gi]
            pr[gg] = np.where(p.key_ref[gi] >= 0, p.key_ref[gi] + off, -1)
        cand_v = np.concatenate([cand_v, pv], axis=1)
        cand_r = np.concatenate([cand_r, pr], axis=1)
    # re-select top k among the candidates per (group, step); real +/-Inf
    # candidates clamp to +/-DBL_MAX in the sort domain so empty (fill) slots
    # never displace them on ties
    fill = np.inf if first.bottom else -np.inf
    fmax = np.finfo(np.float64).max
    sv = np.where(np.isnan(cand_v), fill, np.clip(cand_v, -fmax, fmax))
    sv = sv if first.bottom else -sv                    # ascending sort picks
    order = np.argsort(sv, axis=1, kind="stable")[:, :k, :]
    out_v = np.take_along_axis(cand_v, order, axis=1)
    out_r = np.take_along_axis(cand_r, order, axis=1)
    return TopKPartial(k, first.bottom, first.out_ts, list(all_groups),
                       out_v, out_r, key_table)


def _present_topk(p: TopKPartial) -> ResultMatrix:
    """Emit the union of selected source series, each with its value at steps
    where it made the top k (Prometheus topk keeps original labels)."""
    T = len(p.out_ts)
    rows: dict[RangeVectorKey, int] = {}
    out: list[np.ndarray] = []
    G, k, _ = p.values.shape
    for g in range(G):
        for s in range(k):
            for t in range(T):
                ref = p.key_ref[g, s, t]
                if ref < 0 or np.isnan(p.values[g, s, t]):
                    continue
                key = p.key_table[ref]
                r = rows.get(key)
                if r is None:
                    r = rows[key] = len(out)
                    out.append(np.full(T, np.nan))
                out[r][t] = p.values[g, s, t]
    if not out:
        return ResultMatrix(p.out_ts, np.zeros((0, T)), [])
    return ResultMatrix(p.out_ts, np.stack(out), list(rows))


@dataclass
class AggPartial:
    op: str
    out_ts: np.ndarray
    parts: dict                     # name -> [Gpad, T] device arrays ([Gpad, T*B] hist)
    group_keys: list[RangeVectorKey]
    num_groups: int
    bucket_les: np.ndarray | None = None


def _segment_partial(op, values, gids, num_groups):
    """Segment reduce via the explicit compiled-plan cache: keyed on
    (op, pow2 group bucket, value shape/dtype) — the in-process map phase's
    half of the compile space (PSM's kernels carry the other half).

    Runs the STABLE reduce (row-order segment_sum, column-independent): the
    composed two-step result is bit-identical across padded-T step buckets
    and row paddings, and matches the mesh program's per-shard partials
    bit-for-bit (the PR 13 fold-order caveat, closed by ISSUE 16)."""
    from .plancache import plan_cache
    prog = plan_cache.program(
        "segment",
        (op, num_groups, tuple(values.shape), str(values.dtype), "stable"),
        lambda: functools.partial(aggregators.partial_aggregate, op,
                                  num_groups=num_groups, stable=True))
    return prog(values, gids)


@dataclass
class AggregatePresenter(Transformer):
    """Present phase (ref: AggregatePresenter in AggrOverRangeVectors.scala)."""
    operator: str
    params: tuple = ()
    by: tuple = ()
    without: tuple = ()

    def apply(self, data, ctx):
        if isinstance(data, AggPartial):
            vals = aggregators.present_partials(data.op, data.parts)[: data.num_groups]
            if data.bucket_les is not None:
                B = len(data.bucket_les)
                vals = vals.reshape(vals.shape[0], -1, B)
            return ResultMatrix(data.out_ts, vals, data.group_keys, data.bucket_les)
        if isinstance(data, TopKPartial):
            return _present_topk(data)
        if isinstance(data, SketchPartial):
            vals = aggregators.present_quantile_sketch(data.counts, data.q)
            return ResultMatrix(data.out_ts, vals, data.group_keys)
        if isinstance(data, CountValuesPartial):
            T = len(data.out_ts)
            keys, rows = [], []
            for (gi, vstr), row in data.entries.items():
                gk = (data.group_keys[gi] if data.group_keys
                      else RangeVectorKey(()))
                keys.append(RangeVectorKey(tuple(sorted(
                    dict(gk.labels, **{data.label: vstr}).items()))))
                rows.append(np.where(row > 0, row, np.nan))
            if not keys:
                return ResultMatrix(data.out_ts, np.zeros((0, T)), [])
            return ResultMatrix(data.out_ts, np.stack(rows), keys)
        # full-matrix aggregators
        m = _as_matrix(data)
        gids, uniq, G = _group_ids_for(m.keys, None, m.num_series,
                                       self.by, self.without)
        if self.operator in ("topk", "bottomk"):
            k = int(self.params[0])
            mask = aggregators.topk_mask(jnp.asarray(m.values), jnp.asarray(gids), _pow2(G),
                                         k, bottom=self.operator == "bottomk")
            vals = jnp.where(mask, m.values, jnp.nan)
            return ResultMatrix(m.out_ts, vals, m.keys)
        if self.operator == "quantile":
            q = float(self.params[0])
            vals = aggregators.group_quantile(jnp.asarray(m.values), jnp.asarray(gids),
                                              _pow2(G), q)
            return ResultMatrix(m.out_ts, vals[:G], uniq)
        if self.operator == "count_values":
            return _count_values(m, [uniq[g] for g in gids],
                                 str(self.params[0]))
        raise QueryError(f"unknown aggregator {self.operator}")


def _count_values(m: ResultMatrix, gkeys, label: str) -> ResultMatrix:
    """count_values aggregation (host path — output cardinality is data-dependent)."""
    vals = np.asarray(m.values)
    T = len(m.out_ts)
    out: dict[RangeVectorKey, np.ndarray] = {}
    for p, gk in enumerate(gkeys):
        for t in range(T):
            v = vals[p, t]
            if np.isnan(v):
                continue
            vstr = fmt_value(v)
            key = RangeVectorKey(tuple(sorted(dict(gk.labels, **{label: vstr}).items())))
            row = out.setdefault(key, np.full(T, np.nan))
            row[t] = (0 if np.isnan(row[t]) else row[t]) + 1
    if not out:
        return ResultMatrix(m.out_ts, np.zeros((0, T)), [])
    return ResultMatrix(m.out_ts, np.stack(list(out.values())), list(out))


@dataclass
class SortFunctionMapper(Transformer):
    function: str                  # sort / sort_desc

    def apply(self, data, ctx):
        m = _as_matrix(data).to_host()
        if not m.keys:
            return m
        with np.errstate(all="ignore"):
            sortkey = np.nanmean(m.values, axis=1)
        sortkey = np.where(np.isnan(sortkey), -np.inf, sortkey)
        order = np.argsort(sortkey, kind="stable")
        if self.function == "sort_desc":
            order = order[::-1]
        return ResultMatrix(m.out_ts, m.values[order], [m.keys[i] for i in order])


@dataclass
class MiscellaneousFunctionMapper(Transformer):
    function: str
    str_args: tuple = ()

    def apply(self, data, ctx):
        import re
        m = _as_matrix(data)
        if self.function == "timestamp":
            # of an instant selector: each sample's own stamp (the leaf
            # evaluated its age, ``str_args == ("age",)``); of anything
            # else, a derived value's stamp is the step
            vals = np.asarray(m.values)
            age = vals if self.str_args == ("age",) else 0
            out = np.where(np.isnan(vals), np.nan,
                           (m.out_ts[None, :] - age) / 1000.0)
            return ResultMatrix(m.out_ts, out,
                                [k.without(("_metric_",)) for k in m.keys])
        if self.function == "label_replace":
            dst, repl, src, regex = self.str_args
            pat = re.compile(regex)
            keys = []
            for k in m.keys:
                d = k.as_dict()
                mo = pat.fullmatch(d.get(src, ""))
                if mo:
                    newval = mo.expand(_go_to_py_template(repl))
                    if newval:
                        d[dst] = newval
                    else:
                        d.pop(dst, None)
                keys.append(RangeVectorKey.of(d))
            return ResultMatrix(m.out_ts, m.values, keys)
        if self.function == "label_join":
            dst, sep, *srcs = self.str_args
            keys = []
            for k in m.keys:
                d = k.as_dict()
                d[dst] = sep.join(d.get(s, "") for s in srcs)
                keys.append(RangeVectorKey.of(d))
            return ResultMatrix(m.out_ts, m.values, keys)
        raise QueryError(f"unknown misc function {self.function}")


def _go_to_py_template(s: str) -> str:
    """Convert Go regexp replacement ($1, ${name}) to Python (\\1, \\g<name>)."""
    import re
    return re.sub(r"\$(\d+)", r"\\\1", re.sub(r"\$\{(\w+)\}", r"\\g<\1>", s))


def _as_matrix(data) -> ResultMatrix:
    if isinstance(data, ResultMatrix):
        return data
    if isinstance(data, (FusedWindowData, GatheredWindow)):
        return data.materialize().compact()
    if isinstance(data, MatrixView):
        return data.compact()
    if isinstance(data, (AggPartial, TopKPartial, SketchPartial,
                         CountValuesPartial)):
        raise QueryError("aggregate partial where matrix expected (missing presenter)")
    if isinstance(data, (SeriesSelection, GatheredRows)):
        raise QueryError("raw series where matrix expected (missing periodic mapper)")
    raise TypeError(type(data))


# ---------------------------------------------------------------------------
# ExecPlans
# ---------------------------------------------------------------------------

@dataclass
class ExecPlan:
    transformers: list = field(default_factory=list)

    def execute(self, ctx: QueryContext):
        data = self.do_execute(ctx)
        if isinstance(data, _WideODP):
            return data        # converted by the leaf's execute wrapper
        for t in self.transformers:
            data = t.apply(data, ctx)
        return data

    def run(self, ctx: QueryContext) -> QueryResult:
        data = self.execute(ctx)
        m = _as_matrix(data).to_host()
        check_sample_limit(m.num_series, len(m.out_ts), ctx.sample_limit)
        return QueryResult(m)

    def do_execute(self, ctx):  # pragma: no cover - interface
        raise NotImplementedError


class LeafFrame:
    """The protocol of a query leaf under its shard lock(s), written once
    for the in-process leaf (``SelectRawPartitionsExec``), the fused-hist
    route and the mesh route (query/engine.py), which keep their own work —
    select, group ids, the choice of program::

        with LeafFrame(shard=...) as leaf:      # query.exec.leaf opens
            ...                                 # what must precede the lock
            got = leaf.locked(shards, body)     # body() under the lock(s)

    The thread's lock counters are read before the span opens, and the span
    opens BEFORE the first lock is asked for: a waiting thread is not what
    the host was doing, so the wait is a tag of the leaf (``lock_wait_ms``),
    not a span; the hold beside it (``lock_hold_ms``) is the lock's time
    this leaf took. Both are differences of the counters, stamped at the
    leaf's END on every route: after the locks' release (a hold is counted
    there), before the span closes, a wait met after the long hold
    (``_paged_batches``' re-locks) included. The mesh route used to stamp
    its wait as it took its last lock and reads the same: nothing waits for
    a shard lock once all are held."""

    def __init__(self, **tags):
        self._span = span(SPAN_QUERY_LEAF, **tags)

    def __enter__(self):
        self._waited, self._held = lock_wait_ns(), lock_hold_ns()
        self.tags = self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self.tags["lock_wait_ms"] = (lock_wait_ns() - self._waited) / 1e6
        self.tags["lock_hold_ms"] = (lock_hold_ns() - self._held) / 1e6
        return self._span.__exit__(*exc)

    def locked(self, shards, body):
        """``body()`` under every one of ``shards``' locks, taken in the
        order given (shard order) and held across array capture AND kernel
        dispatch: a concurrent ingest flush donates (invalidates) the store
        buffers, its compress_commit swaps a store's form (see
        TimeSeriesShard.lock). So nothing lazy leaves them — a window
        view's dispatch or rows' gather after the release would race that
        donation; the blocking FETCH does, in the dispatch's handle
        (``diagnostics.Dispatched``)."""
        try:
            with contextlib.ExitStack() as locks:
                for sh in shards:
                    locks.enter_context(sh.lock)
                result = body()
                if isinstance(result, (FusedWindowData, GatheredWindow)):
                    result = result.materialize()
                elif isinstance(result, GatheredRows):
                    result = result.gathered()
                return result
        except RuntimeError as e:
            # use-after-donation detective (ref: BlockDetective): name the
            # donation site instead of jax's opaque "Array has been deleted"
            if "deleted" in str(e):
                explain_deleted_buffer(e, *(sh.store.detective for sh in shards
                                            if sh.store is not None))
            raise


@dataclass
class SelectRawPartitionsExec(ExecPlan):
    """The only data-reading leaf (ref: SelectRawPartitionsExec.scala)."""
    shard: int = 0
    filters: tuple = ()
    start_ms: int = 0
    end_ms: int = 0
    # __col__ value-column selector: targets an aggregate dataset of a
    # downsample family, e.g. column "dAvg" of family "ds:ds_1m" reads the
    # dataset "ds:ds_1m:dAvg" (ref: the reference's multi-column downsample
    # datasets select with __col__; here each aggregate is its own dataset)
    column: str = ""

    def _shard_of(self, ctx):
        return _shard_of_ctx(ctx, self.shard, self.column)

    def execute(self, ctx: QueryContext):
        with LeafFrame(shard=self.shard) as leaf:
            shard, _col = self._shard_of(ctx)
            if getattr(shard, "recovering", False):
                # partial data: the count crosses the peer wire with the other
                # stats, so the ROOT node knows an empty selection proves
                # nothing (its negative cache must skip this query)
                ctx.stats.add("recovering_shards")
            # step-varying scalar operands resolve BEFORE the lock: their
            # subplans take other shards' locks (nested acquisition would
            # ABBA-deadlock two concurrent mirror-image queries)
            for t in self.transformers:
                if isinstance(t, ScalarOperationMapper):
                    t.prepare(ctx)
            result = leaf.locked([shard],
                                 functools.partial(super().execute, ctx))
            if isinstance(result, _WideODP):
                # batched paging runs OUTSIDE the long-held lock: each batch
                # re-locks only around its store snapshot, so ingest is not
                # stalled for the duration of a wide historical scan
                return self._paged_batches(ctx, shard, result.pids, _col)
            return result

    def _paged_selection(self, shard, pids, keys, cold=None,
                         column=None) -> SeriesSelection:
        # tier tag: a remote sink (StoreServer ring) means the page-in paid
        # the durable tier's network round trips, not just local disk
        tier = ("remote" if getattr(shard.sink, "remote_tier", False)
                else "local")
        with span(SPAN_QUERY_ODP, shard=self.shard, series=len(pids),
                  tier=tier):
            ts_h, val_h, n_h = shard.read_with_paging(pids, self.start_ms,
                                                      self.end_ms, cold=cold,
                                                      column=column)
        return SeriesSelection(jnp.asarray(ts_h), jnp.asarray(val_h),
                               jnp.asarray(n_h), keys, None, None)

    @staticmethod
    def _batch_distributive(t) -> bool:
        """True when applying ``t`` per pid-batch then merging equals applying
        it to the whole selection (row-wise transforms and the aggregation map
        phase are; absent()/sort need the complete result)."""
        if isinstance(t, (PeriodicSamplesMapper, AggregateMapReduce,
                          ScalarOperationMapper)):
            return True
        if isinstance(t, InstantVectorFunctionMapper):
            return t.function != "absent"
        return False

    def _paged_batches(self, ctx, shard, pids, column=None):
        """Wide on-demand paging: bounded memory via pid batches — each batch
        pages its cold chunks, runs the (distributive prefix of the)
        transformer chain, and the per-batch results merge exactly like shard
        results do at a reduce node; the non-distributive suffix applies to
        the merged whole (ref: OnDemandPagingShard.scala:58 pages any width)."""
        n_dist = 0
        while (n_dist < len(self.transformers)
               and self._batch_distributive(self.transformers[n_dist])):
            n_dist += 1
        prefix, suffix = self.transformers[:n_dist], self.transformers[n_dist:]
        agg = next((t for t in prefix if isinstance(t, AggregateMapReduce)), None)
        outs = []
        for i in range(0, len(pids), ODP_BATCH):
            sub = pids[i:i + ODP_BATCH]
            ctx.stats.add("rows_paged_in", len(sub))
            # the sink disk scan runs lock-free (append-only logs); only the
            # resident-store snapshot + key materialization need the lock
            cold = shard.read_cold_for(sub, self.start_ms, self.end_ms)
            with shard.lock:
                keys = [shard.rv_key_of(int(p)) for p in sub]
                data = self._paged_selection(shard, sub, keys, cold=cold,
                                             column=column)
            for t in prefix:
                data = t.apply(data, ctx)
            if isinstance(data, FusedWindowData):
                data = data.materialize()
            outs.append(data)
        merged = None
        if agg is not None:
            merged = _merge_heterogeneous(outs, agg.operator, agg.params,
                                          agg.by, agg.without)
        if merged is None:
            mats = [_as_matrix(o).to_host() for o in outs]
            nonempty = [m for m in mats if m.num_series]
            if nonempty:
                vals = np.concatenate([np.asarray(m.values) for m in nonempty],
                                      axis=0)
                keys = [k for m in nonempty for k in m.keys]
                merged = ResultMatrix(nonempty[0].out_ts, vals, keys,
                                      nonempty[0].bucket_les)
            else:
                merged = mats[0]
        for t in suffix:
            merged = t.apply(merged, ctx)
        return merged

    def do_execute(self, ctx) -> SeriesSelection:
        with span(SPAN_QUERY_SELECT, shard=self.shard) as tags:
            sel = self._select(ctx, tags)
            tags["series"] = len(sel.pids if isinstance(sel, _WideODP)
                                 else sel.keys)
            return sel

    def _select(self, ctx, tags: dict):
        """Index select + array capture (the snapshot the chain runs on)."""
        shard, col = self._shard_of(ctx)
        if shard.store is None:   # histogram shard with no data yet
            z = jnp.zeros((8, 8), jnp.float32)
            return SeriesSelection(jnp.full((8, 8), 1 << 62, jnp.int64), z,
                                   jnp.zeros(8, jnp.int32), [], None, None)
        # the leaf holds the shard lock: nobody else moves the index's
        # count of filter sets it had to resolve (its cache missed)
        resolved = shard.index.filter_misses
        picked, tags["memo"] = shard.selection(
            list(self.filters), self.start_ms, self.end_ms, GATHER_THRESHOLD)
        if picked.why is not None and tags["memo"] != "hit":
            tags["memo_why"] = picked.why   # why this select was no hit
        tags["matchers"] = "+".join(sorted(f.KIND for f in self.filters))
        tags["resolve"] = ("miss" if shard.index.filter_misses != resolved
                           else "hit")
        registry.counter(FILODB_INDEX_RESOLVE,
                         {"outcome": tags["resolve"]}).increment()
        pids = picked.pids      # shared and read-only on a memo hit
        ctx.stats.add("series_matched", len(pids))
        store = shard.store
        # bucket boundaries ride only when the SELECTED column is the
        # histogram one (``{__col__="sum"}`` on prom-histogram is scalar)
        les = getattr(shard, "bucket_les", None)
        if col is not None:
            colobj = shard.schema.column_named(col)
            from ..core.schemas import ColumnType
            if colobj is None or colobj.ctype != ColumnType.HISTOGRAM:
                les = None
        # on-demand paging: query reaches behind resident data -> merge cold
        # chunks from the sink (ref: OnDemandPagingShard.scanPartitions)
        if les is None and shard.needs_paging(pids, self.start_ms):
            count_leaf(ctx, tags, "paged")
            if len(pids) > ODP_BATCH:
                return _WideODP(pids)
            ctx.stats.add("rows_paged_in", len(pids))
            return self._paged_selection(
                shard, pids, [shard.rv_key_of(int(p)) for p in pids],
                column=col)
        if len(pids) > GATHER_THRESHOLD:
            # wide selection: defer key materialization (global aggregates
            # never read them; per-series outputs pay the cost on iteration)
            keys = LazyKeys(shard, picked)
        else:
            keys = [shard.rv_key_of(int(p)) for p in pids]
        ts, val, n = store.arrays(col)
        total = len(shard.index)
        grid = store.grid_info()
        on_grid = grid is not None      # the STORE's form, whatever the cohorts
        if len(pids) == 0:
            # synthetic pad selection (the store-None branch's shape):
            # slicing a compressed-resident store's deferred view here would
            # decode the FULL block — a typo'd metric name must not cost a
            # multi-GB transient. Pad rows have n=0, so every kernel yields
            # the same empty result the real slice would.
            vshape = ((8, 8, store.nbuckets)
                      if getattr(val, "ndim", 2) == 3 else (8, 8))
            count_leaf(ctx, tags, "gather")     # of no row
            return SeriesSelection(
                jnp.full((8, 8), 1 << 62, jnp.int64),
                jnp.zeros(vshape, store.dtype), jnp.zeros(8, jnp.int32),
                [], None, None, les)
        # Churn. A store in time-aligned cells (core/chunkstore.py
        # ``aligned``) is ONE start cohort whenever its rows were born, and
        # there is nothing to do here a query: the kernels take ``born``
        # beside ``n`` while the store holds a row born late. The other
        # grid forms hold mixed start cohorts: the grid base moves to the
        # majority cohort's start cell and the few minority rows are
        # recorded so PSM can recompute them generally. Too much churn =>
        # general path outright.
        minority_sel = None
        if grid is not None:
            base, iv = grid
            kind, coh = store.grid_cohorts()
            if kind == "uniform":     # one scrape cohort — zero per-query work
                grid = (base + coh * iv, iv)
            else:
                goff = coh[pids]
                live = store.n_host[pids] > 0
                if live.any():
                    lv = goff[live]
                    u, cnts = np.unique(lv, return_counts=True)
                    o_maj = int(u[np.argmax(cnts)])
                    mins = live & (goff != o_maj)
                    m = int(mins.sum())
                    if m > 0.25 * int(live.sum()):
                        grid = None
                    else:
                        grid = (base + o_maj * iv, iv)
                        if m:
                            minority_sel = mins
        line = None
        if grid is None and col is None and les is None:
            # stamps kept as line + residual: the fused tier reads the
            # line; the rows off it are this selection's minority, past
            # the gate the general path answers as on any off-grid shard
            line = store.line_info()
            if line is not None and len(line.minority):
                mins = line.off_mask[pids]
                m = int(mins.sum())
                if m > 0.25 * int((store.n_host[pids] > 0).sum()):
                    line = None
                elif m:
                    minority_sel = mins
        tags["demoted"] = (int(minority_sel.sum())
                           if minority_sel is not None else 0)
        holes = store.res is not None and store.hole_cells > 0
        if store.res is not None:
            tags["hole_cells"], tags["used_cells"] = picked.cells(store)
        from ..core.chunkstore import _Deferred
        births = grid is not None and store.born_late > 0
        if len(pids) <= GATHER_THRESHOLD and len(pids) < 0.5 * max(total, 1):
            # narrow selection: gather rows once, padded to a power of two
            ctx.stats.add("blocks_raw")
            count_leaf(ctx, tags, "gather")
            M, P = len(pids), _pow2(len(pids))
            # the grid's own gather of a few rows: stamps derived, a delta
            # block's rows decoded in it (``decode`` says what it read)
            gops = store.grid_gather_operands(val) if on_grid else None
            on_g = gops is not None
            decode = gops[2] if on_g else (
                store.narrow_operands()[0] if store.narrow_operands()
                and isinstance(val, _Deferred) and val.ndim == 2 else "raw")
            width = {"raw": np.dtype(val.dtype).itemsize, "delta8": 1}.get(
                decode, 2)
            gtags = dict(shard=self.shard, rows=M, padded=P, decode=decode,
                         bytes=M * (int(np.prod(val.shape[1:])) * width
                                    + (0 if isinstance(ts, _Deferred)
                                       else ts.shape[1] * 8)))
            # P > len(pids): arrays carry pad rows beyond the keys — expose the
            # identity row map so downstream compaction/group-scatter skips them
            sel_rows = None if P == M else np.arange(M, dtype=np.int32)
            if _joins_one_program(ts, val, les, minority_sel, on_g):
                # the gather joins the window function's program; its span
                # opens where that program is dispatched
                pad = np.zeros(P, np.int32)
                pad[:M] = pids
                return GatheredRows(
                    gops[0] if on_g else (ts, val, n),
                    (store.grid_row_picks(pad, M),) if on_g
                    else (pad, np.int32(M)),
                    on_g, gops[1] if on_g else None, decode,
                    (P, val.shape[1]), val.dtype, keys, sel_rows, grid,
                    gtags, int((store.born[pids] > 0).sum()) if births else 0)
            with span(SPAN_QUERY_GATHER, **gtags) as gtags:
                count_gather(gtags, STEPWISE_PROGRAMS[on_g])
                sel_ts, sel_val, sel_n, _ = _gather_rows_padded(
                    ts, val, n, pids,
                    store.grid_row_gather() if on_g else None)
            g_min = (np.nonzero(minority_sel)[0].astype(np.int32)
                     if minority_sel is not None else None)
            return SeriesSelection(sel_ts, sel_val, sel_n, keys, sel_rows, grid, les,
                                   g_min, holes=holes)
        # wide selection: no gather — disable non-selected rows via n = 0
        # (store.S is the PHYSICAL padded row count; the full-selection test
        # is against the logical series count)
        count_leaf(ctx, tags, "wide")
        born_late = store.born_late if births else 0
        if picked.is_all:
            n_eff = n
        else:
            # the selection's own row mask, kept with it: a kept selection
            # (core/selection.py) uploads it once, not once a query
            n_eff = jnp.where(picked.row_mask(store.S)[1], n, 0)
            if births:      # one pass per change of a birth cell
                born_late = picked.late_rows(store.late_mask())
        g_min = (pids[minority_sel].astype(np.int32)
                 if minority_sel is not None else None)
        narrow = None
        if (grid is not None and col is None and les is None
                and (store.S % 512 == 0 or store.S <= 512)
                and val.ndim == 2):
            # narrow-resident state (the narrow form IS the store)
            nd = store.narrow_operands()
            if nd is not None:
                kind, nops, ok_host = nd
                bad = pids[~ok_host[pids]].astype(np.int32)
                # mostly-inexact data: raw f32 is cheaper than correcting
                if len(bad) <= store.cohort_gate * max(len(pids), 1):
                    narrow = (kind, nops, bad)
        hist_narrow = None
        if (grid is not None and les is not None
                and getattr(val, "ndim", 2) == 3):
            # hist-resident store: ship the 2D-delta operands so PSM/fused
            # paths stream them — the deferred f32 view never materializes;
            # cohort-pool rows recompute via row-wise decode
            hd = store.hist_operands()
            if hd is not None:
                dd, first_d, ok_host = hd
                hist_narrow = (dd, first_d,
                               pids[~ok_host[pids]].astype(np.int32))
        ctx.stats.add("blocks_narrow"
                      if (narrow is not None or hist_narrow is not None)
                      else "blocks_raw")
        if line is not None and (val.ndim != 2 or narrow is not None
                                 or isinstance(val, _Deferred)):
            line = None
        return SeriesSelection(ts, val, n_eff, keys, pids, grid, les,
                               g_min, narrow, hist_narrow, line, holes,
                               store.born_dev if births else None, born_late)


def _execute_children(children, ctx):
    """Execute child plans, fanning remote leaves out concurrently: peer
    round-trips overlap each other AND the local shards' device work (ref:
    NonLeafExecPlan dispatches children as parallel Observables). Local
    children stay on the calling thread — shard locks already serialize
    device-buffer capture. A RemoteBatchExec child (one POST covering a
    peer's K leaves) returns a result LIST; it splices in place so parents
    keep seeing one result per original leaf."""
    remote = [c for c in children if getattr(c, "IS_REMOTE", False)]
    if len(remote) < 1 or len(children) == 1:
        results = [c.execute(ctx) for c in children]
    else:
        from concurrent.futures import ThreadPoolExecutor
        from ..utils.tracing import tracer

        # remote legs run on pool threads: hand them the query's trace
        # context so their dispatch spans join the one trace
        run_remote = tracer.wrap(lambda c: c.execute(ctx))
        with ThreadPoolExecutor(max_workers=min(len(remote), 16)) as pool:
            futs = {id(c): pool.submit(run_remote, c) for c in remote}
            results = [futs[id(c)].result() if id(c) in futs
                       else c.execute(ctx) for c in children]
    batches = [c for c in children if getattr(c, "IS_BATCH", False)]
    if not batches:
        return results
    # splice batch results back into the members' ORIGINAL child positions:
    # reduce/concat merge order (and so float accumulation order — bit-parity
    # with the single-node oracle) must not depend on the batching rewrite
    n_total = (len(children) - len(batches)
               + sum(len(b.members) for b in batches))
    taken = {s for b in batches for s in b.slots}
    free = (i for i in range(n_total) if i not in taken)
    out = [None] * n_total
    for c, r in zip(children, results):
        if getattr(c, "IS_BATCH", False):
            for slot, res in zip(c.slots, r):
                out[slot] = res
        else:
            out[next(free)] = r
    return out


@dataclass
class DistConcatExec(ExecPlan):
    """Concatenate child results (ref: DistConcatExec.scala — shard fan-in)."""
    children: list = field(default_factory=list)

    def do_execute(self, ctx):
        all_mats = [_as_matrix(r).to_host()
                    for r in _execute_children(self.children, ctx)]
        mats = [m for m in all_mats if m.num_series]
        if not mats:
            return all_mats[0]
        out_ts = mats[0].out_ts
        vals = np.concatenate([np.asarray(m.values) for m in mats], axis=0)
        keys = [k for m in mats for k in m.keys]
        return ResultMatrix(out_ts, vals, keys, mats[0].bucket_les)


@dataclass
class SubqueryWindowExec(ExecPlan):
    """Range function over a SUBQUERY's synthetic sample stream
    (``fn(expr[window:sub_step])``): the child plan evaluates the inner
    expression on the absolute sub-step grid, its matrix becomes per-series
    (ts, val) sample arrays (NaN steps = no sample), and the SAME window
    kernels that serve raw selections slide over them — bit-parity with a
    hand-nested evaluation by construction."""
    child: ExecPlan | None = None
    start_ms: int = 0
    step_ms: int = 1
    end_ms: int = 0
    window_ms: int = 0
    function: str = "last_over_time"
    args: tuple = ()
    sub_step_ms: int = 60_000

    def do_execute(self, ctx):
        from ..core.chunkstore import TS_PAD
        inner = _as_matrix(self.child.execute(ctx)).to_host()
        step = max(self.step_ms, 1)
        out_ts = np.arange(self.start_ms, self.end_ms + 1, step,
                           dtype=np.int64)
        S = inner.num_series
        if len(out_ts) == 0 or S == 0:
            return ResultMatrix(out_ts, np.zeros((S, len(out_ts))),
                                list(inner.keys))
        sub_ts = np.asarray(inner.out_ts, np.int64)
        vals = np.asarray(inner.values, np.float64)
        finite = np.isfinite(vals)
        n = finite.sum(axis=1).astype(np.int32)
        C = max(int(n.max(initial=0)), 1)
        ts2d = np.full((S, C), TS_PAD, np.int64)
        val2d = np.zeros((S, C), np.float64)
        for i in range(S):
            m = finite[i]
            k = int(n[i])
            ts2d[i, :k] = sub_ts[m]
            val2d[i, :k] = vals[i, m]
        ctx.stats.add("subquery_inner_cells", int(S * len(sub_ts)))
        out_eval, T = _pad_steps(out_ts)
        a0 = float(self.args[0]) if len(self.args) > 0 else 0.0
        a1 = float(self.args[1]) if len(self.args) > 1 else 0.0
        out = rangefns.periodic_samples(ts2d, val2d, n, out_eval,
                                        self.window_ms, self.function, a0, a1)
        return ResultMatrix(out_ts, np.asarray(out)[:, :T], list(inner.keys))


@dataclass
class RepeatAtExec(ExecPlan):
    """Broadcast an @-pinned evaluation across the query grid: the child
    runs on its own single-step grid at the pinned instant; the result is
    step-invariant by construction, so it tiles to [start_ms, end_ms]."""
    child: ExecPlan | None = None
    start_ms: int = 0
    step_ms: int = 1
    end_ms: int = 0

    def do_execute(self, ctx):
        inner = _as_matrix(self.child.execute(ctx)).to_host()
        step = max(self.step_ms, 1)
        out_ts = np.arange(self.start_ms, self.end_ms + 1, step,
                           dtype=np.int64)
        vals = np.asarray(inner.values, np.float64)
        if vals.shape[1] == 0:
            out = np.full((inner.num_series, len(out_ts)), np.nan)
        else:
            out = np.repeat(vals[:, -1:], len(out_ts), axis=1)
        return ResultMatrix(out_ts, out, list(inner.keys), inner.bucket_les)


@dataclass
class ReduceAggregateExec(ExecPlan):
    """Cross-shard reduce (ref: ReduceAggregateExec in AggrOverRangeVectors.scala).

    Children yield AggPartials (basic ops) or full matrices (order statistics);
    partials merge group-by-group, then the presenter finishes.
    """
    operator: str = "sum"
    params: tuple = ()
    by: tuple = ()
    without: tuple = ()
    children: list = field(default_factory=list)

    def do_execute(self, ctx):
        results = _execute_children(self.children, ctx)
        with span(SPAN_QUERY_REDUCE, op=self.operator,
                  children=len(self.children)), \
                ctx.stats.stage("reduce"):
            # the per-shard group cap is data-dependent, so a sibling shard
            # may have fallen back to a full matrix: normalization happens
            # inside (the matrix has full information; the reverse is
            # impossible)
            merged = _merge_heterogeneous(results, self.operator, self.params,
                                          self.by, self.without)
            if merged is not None:
                return merged
            mats = [_as_matrix(r).to_host() for r in results]
            mats = [m for m in mats if m.num_series]
            if not mats:
                return ResultMatrix(np.zeros(0, np.int64),
                                    np.zeros((0, 0)), [])
            vals = np.concatenate([np.asarray(m.values) for m in mats],
                                  axis=0)
            keys = [k for m in mats for k in m.keys]
            return ResultMatrix(mats[0].out_ts, vals, keys)


def _merge_partials(op: str, partials: list[AggPartial]) -> AggPartial:
    """Align group keys across shards, then combine partial state."""
    if len(partials) == 1:
        # single shard: nothing to align — stay lazy/on-device; the one
        # host fetch happens at matrix materialization (each early fetch
        # of the tiny partial arrays is a host sync: a dispatch round
        # trip)
        return partials[0]
    all_keys: dict[RangeVectorKey, int] = {}
    for p in partials:
        for k in p.group_keys:
            all_keys.setdefault(k, len(all_keys))
    G = max(len(all_keys), 1)
    Gpad = _pow2(G)
    out_ts = partials[0].out_ts
    les = partials[0].bucket_les
    T = len(out_ts) * (len(les) if les is not None else 1)
    # ONE batched host fetch for every shard's (tiny) partial arrays; a
    # fused program's handle (diagnostics.Dispatched) contributes its raw
    # outputs to the same fetch — its resolve() here would round-trip per
    # shard
    raw = [p.parts for p in partials]
    with span(SPAN_QUERY_KERNEL, phase="fetch") as ftags:
        fetched = jax.device_get([r.outs if hasattr(r, "parts_of") else r
                                  for r in raw])
        # parts_of() takes each bundle out of the in-flight count; the line
        # rate programs among them say how many of their tiles fell
        resolved = [r.parts_of(f) if hasattr(r, "parts_of") else f
                    for r, f in zip(raw, fetched)]
        for r in raw:
            for k, n in getattr(r, "fall_tags", {}).items():
                ftags[k] = ftags.get(k, 0) + n
    merged: dict[str, object] = {}
    for p, rparts in zip(partials, resolved):
        # scatter this shard's groups into the global group space
        idx = np.array([all_keys[k] for k in p.group_keys], np.int32)
        for name, arr in rparts.items():
            arr = np.asarray(arr)[: p.num_groups]
            if name == "min":
                base = np.full((Gpad, T), np.inf)
            elif name == "max":
                base = np.full((Gpad, T), -np.inf)
            else:
                base = np.zeros((Gpad, T))
            if len(idx):
                base[idx] = arr
            if name not in merged:
                merged[name] = base
            else:
                if name == "min":
                    merged[name] = np.minimum(merged[name], base)
                elif name == "max":
                    merged[name] = np.maximum(merged[name], base)
                else:
                    merged[name] = merged[name] + base
    return AggPartial(op, out_ts, merged, list(all_keys), G, les)


# ---------------------------------------------------------------------------
# Binary joins and set operators
# ---------------------------------------------------------------------------

def _join_key(k: RangeVectorKey, on, ignoring,
              memo: dict | None = None) -> RangeVectorKey:
    """Join key of a series under on/ignoring. ``memo`` is a per-execution
    dict (both sides of a join share on/ignoring): wide joins reuse keys
    intra-query without retaining label tuples for the process lifetime."""
    if memo is not None:
        jk = memo.get(k)
        if jk is not None:
            return jk
    out = k.without(("_metric_",))
    if on:
        out = out.only(on)
    elif ignoring:
        out = out.without(ignoring)
    if memo is not None:
        memo[k] = out
    return out


@dataclass
class BinaryJoinExec(ExecPlan):
    """Vector-vector binary operation (ref: BinaryJoinExec.scala: one-to-one and
    many-to-one/one-to-many with on/ignoring + group_left/right include)."""
    lhs: ExecPlan = None
    rhs: ExecPlan = None
    operator: str = "+"
    cardinality: str = "OneToOne"
    on: tuple = ()
    ignoring: tuple = ()
    include: tuple = ()

    def do_execute(self, ctx):
        lm = _as_matrix(self.lhs.execute(ctx)).to_host()
        rm = _as_matrix(self.rhs.execute(ctx)).to_host()
        swap = self.cardinality == "OneToMany"   # treat as ManyToOne with sides swapped
        many, one = (rm, lm) if swap else (lm, rm)
        memo: dict = {}           # per-query join-key cache (both sides)
        one_by_key: dict[RangeVectorKey, int] = {}
        for i, k in enumerate(one.keys):
            jk = _join_key(k, self.on, self.ignoring, memo)
            if jk in one_by_key:
                raise QueryError(f"duplicate series on 'one' side of join for {jk}")
            one_by_key[jk] = i
        rows_many, rows_one, keys = [], [], []
        is_filter = (self.operator.removesuffix("_bool") in binop.COMPARISON_OPS
                     and not self.operator.endswith("_bool"))
        seen: set[RangeVectorKey] = set()
        for i, k in enumerate(many.keys):
            jk = _join_key(k, self.on, self.ignoring, memo)
            j = one_by_key.get(jk)
            if j is None:
                continue
            if self.cardinality == "OneToOne":
                if jk in seen:
                    raise QueryError(f"duplicate series on 'many' side of join for {jk}")
                seen.add(jk)
            rows_many.append(i)
            rows_one.append(j)
            if is_filter:
                keys.append(k)               # comparison filter keeps original labels
            else:
                out = k.without(("_metric_",))
                if self.include:
                    d = out.as_dict()
                    od = one.keys[j].as_dict()
                    for lbl in self.include:
                        if od.get(lbl):
                            d[lbl] = od[lbl]
                        else:
                            d.pop(lbl, None)
                    out = RangeVectorKey.of(d)
                elif self.on and self.cardinality == "OneToOne":
                    out = _join_key(k, self.on, self.ignoring, memo)
                keys.append(out)
        if not rows_many:
            return ResultMatrix(lm.out_ts, np.zeros((0, len(lm.out_ts))), [])
        mv = np.asarray(many.values)[rows_many]
        ov = np.asarray(one.values)[rows_one]
        l_vals, r_vals = (ov, mv) if swap else (mv, ov)
        vals = binop.apply_vector_op(self.operator, jnp.asarray(l_vals), jnp.asarray(r_vals))
        return ResultMatrix(lm.out_ts, vals, keys)


@dataclass
class SetOperatorExec(ExecPlan):
    """and/or/unless with per-step presence semantics (ref: SetOperatorExec.scala)."""
    lhs: ExecPlan = None
    rhs: ExecPlan = None
    operator: str = "and"
    on: tuple = ()
    ignoring: tuple = ()

    def do_execute(self, ctx):
        lm = _as_matrix(self.lhs.execute(ctx)).to_host()
        rm = _as_matrix(self.rhs.execute(ctx)).to_host()
        lvals, rvals = np.asarray(lm.values), np.asarray(rm.values)
        memo: dict = {}           # per-query join-key cache (both sides)
        T = len(lm.out_ts)
        # presence of each join key at each step on the rhs / lhs
        def presence(mat, keys):
            pres: dict[RangeVectorKey, np.ndarray] = {}
            for i, k in enumerate(keys):
                jk = _join_key(k, self.on, self.ignoring, memo)
                cur = pres.get(jk)
                here = ~np.isnan(np.asarray(mat)[i])
                pres[jk] = here if cur is None else (cur | here)
            return pres
        if self.operator == "and":
            rp = presence(rvals, rm.keys)
            out = []
            for i, k in enumerate(lm.keys):
                jk = _join_key(k, self.on, self.ignoring, memo)
                mask = rp.get(jk, np.zeros(T, bool))
                out.append(np.where(mask, lvals[i], np.nan))
            vals = np.stack(out) if out else np.zeros((0, T))
            return ResultMatrix(lm.out_ts, vals, list(lm.keys))
        if self.operator == "unless":
            rp = presence(rvals, rm.keys)
            out = []
            for i, k in enumerate(lm.keys):
                jk = _join_key(k, self.on, self.ignoring, memo)
                mask = rp.get(jk, np.zeros(T, bool))
                out.append(np.where(mask, np.nan, lvals[i]))
            vals = np.stack(out) if out else np.zeros((0, T))
            return ResultMatrix(lm.out_ts, vals, list(lm.keys))
        if self.operator == "or":
            lp = presence(lvals, lm.keys)
            rows = [lvals[i] for i in range(len(lm.keys))]
            keys = list(lm.keys)
            for i, k in enumerate(rm.keys):
                jk = _join_key(k, self.on, self.ignoring, memo)
                lmask = lp.get(jk, np.zeros(T, bool))
                rows.append(np.where(lmask, np.nan, rvals[i]))
                keys.append(k)
            vals = np.stack(rows) if rows else np.zeros((0, T))
            return ResultMatrix(lm.out_ts, vals, keys)
        raise QueryError(f"unknown set operator {self.operator}")


@dataclass
class ScalarExec(ExecPlan):
    """Literal scalar evaluated at each step."""
    value: float = 0.0
    start_ms: int = 0
    step_ms: int = 1
    end_ms: int = 0

    def do_execute(self, ctx):
        out_ts = np.arange(self.start_ms, self.end_ms + 1, max(self.step_ms, 1),
                           dtype=np.int64)
        vals = np.full((1, len(out_ts)), self.value)
        return ResultMatrix(out_ts, vals, [RangeVectorKey(())])


@dataclass
class TimeScalarExec(ExecPlan):
    """PromQL ``time()``: evaluation timestamp in seconds per step."""
    start_ms: int = 0
    step_ms: int = 1
    end_ms: int = 0

    def do_execute(self, ctx):
        out_ts = np.arange(self.start_ms, self.end_ms + 1, max(self.step_ms, 1),
                           dtype=np.int64)
        vals = (out_ts / 1000.0)[None, :]
        return ResultMatrix(out_ts, vals, [RangeVectorKey(())])


def _shard_of_ctx(ctx, shard_num: int, column: str = ""):
    """Resolve (shard, store_column) honoring a __col__ value-column selector.

    A column NAMED BY THE SCHEMA selects that column of the dataset's own
    multi-column device store (ref: __col__ in ast/Vectors.scala picking a
    data column — e.g. ``{__col__="sum"}`` on prom-histogram); otherwise the
    selector targets a per-aggregate dataset of a downsample family
    (``ds:ds_1m:dAvg``), the pre-multi-column layout."""
    if column:
        try:
            sh = ctx.memstore.shard(ctx.dataset, shard_num)
        except KeyError:
            sh = None
        if sh is not None and sh.schema.column_named(column) is not None:
            if not sh.schema.is_multi_column:
                # single-column schema: naming its one value column is the
                # default selection (m::value on gauge)
                return sh, None
            return sh, column
    ds = f"{ctx.dataset}:{column}" if column else ctx.dataset
    try:
        return ctx.memstore.shard(ds, shard_num), None
    except KeyError:
        raise QueryError(
            f"unknown {'column ' + column + ' of ' if column else ''}"
            f"dataset {ds}") from None


@dataclass
class SelectChunkInfosExec(ExecPlan):
    """Chunk-metadata debug leaf (ref: SelectChunkInfosExec.scala — id,
    numRows, startTime, endTime, numBytes, readerKlazz per chunk). This
    design keeps ONE resident row per series (no chunk lists), so the row's
    stats come back as labels on a synthetic series, plus the count of
    persisted chunk frames when a sink exists."""
    shard: int = 0
    filters: tuple = ()
    start_ms: int = 0
    end_ms: int = 0
    column: str = ""

    MAX_PARTS = 1000    # debug surface: bound the output

    def do_execute(self, ctx):
        shard, _col = _shard_of_ctx(ctx, self.shard, self.column)
        out_ts = np.array([self.end_ms], np.int64)
        if shard.store is None:
            return ResultMatrix(out_ts, np.zeros((0, 1)), [])
        pids = shard.part_ids_from_filters(list(self.filters), self.start_ms,
                                           self.end_ms, limit=self.MAX_PARTS)
        sink_chunks: dict[int, int] = {}
        if shard.sink is not None and hasattr(shard.sink, "read_chunksets"):
            for _g, recs in shard.sink.read_chunksets(
                    shard.dataset, self.shard, self.start_ms, self.end_ms) or ():
                for r in recs:
                    sink_chunks[r.part_id] = sink_chunks.get(r.part_id, 0) + 1
        st = shard.store
        keys, vals = [], []
        vcol_itemsize = st.column_array().dtype.itemsize   # loop-invariant
        with shard.lock:
            for p in pids:
                p = int(p)
                labels = dict(shard.index.labels_of(p))
                n = int(st.samples_host[p])
                per_sample = 8 + (vcol_itemsize
                                  * max(st.nbuckets, 1))
                labels.update({
                    "_id_": str(p),
                    "_numRows_": str(n),
                    "_startTime_": str(int(st.first_ts[p])),
                    "_endTime_": str(int(st.last_ts[p])) if n else "-1",
                    "_numBytes_": str(n * per_sample),
                    "_readerKlazz_": "SeriesStoreRow",
                    "_sinkChunks_": str(sink_chunks.get(p, 0)),
                })
                keys.append(RangeVectorKey.of(labels))
                vals.append([float(n)])
        if not keys:
            return ResultMatrix(out_ts, np.zeros((0, 1)), [])
        return ResultMatrix(out_ts, np.asarray(vals), keys)


@dataclass
class ScalarOfVectorExec(ExecPlan):
    """PromQL ``scalar(v)``: the single series' values, NaN at steps where
    the vector doesn't have exactly one sample."""
    child: ExecPlan = None

    def do_execute(self, ctx):
        m = _as_matrix(self.child.execute(ctx)).to_host()
        T = len(m.out_ts)
        vals = np.asarray(m.values, np.float64).reshape(-1, T)
        present = (~np.isnan(vals)).sum(axis=0)
        with np.errstate(invalid="ignore"):
            col = np.where(present == 1, np.nansum(vals, axis=0), np.nan)
        return ResultMatrix(m.out_ts, col[None, :], [RangeVectorKey(())])
